"""One run of one cell.

The run builds the engine through the program's own entry point,
``repro.launch.serve.build_server``, with the configuration file's
arguments, and replaces its weights with the benchmark's own
(``bench/weights.py``). Set-up then warms every step shape the cell's
traffic can use, fills the prefix store from a warm-up stream and plays a
lead-in at the cell's rate, so that the window opens on a busy engine
with a full store. The window plays the cell's traffic open-loop on the
wall clock into ``ServeEngine.submit`` / ``ServeEngine.step``, and after
every step reads each emitting request through ``ServeEngine.drain``, as a
streaming front end must. Once the window has closed, the engine's memory
is freed and a sample of the served requests is checked against the
float32 reference (``bench/reference.py``).

Everything that belongs to one configuration, one traffic mix or one
metric lives in a file of its own under ``bench/``; this module only
reads them by name.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"

# the widest gap the check reads at one position is about the spread of
# the logits; a token outside the vocabulary reads this
_NO_LOGIT = float("inf")


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# threads that load step programs in set-up
PREFETCH_THREADS = 8

# step shapes already compiled by this process (calibration and sweeps
# build several engines on one model; the benchmark's runs build one)
_WARMED: Dict[tuple, tuple] = {}


class BenchError(RuntimeError):
    """A run that cannot produce a result: no result line is printed."""


# ---------------------------------------------------------------- the files
def load_json(path: Path) -> Dict:
    return json.loads(path.read_text())


def load_benchmark() -> Dict:
    return load_json(ROOT / "BENCHMARK.json")


def find(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def reported(bench: Dict, cell: str, kind: str) -> List[Dict]:
    """The metrics of ``kind`` ("end_to_end" or "per_layer") that ``cell``
    reports. An end-to-end metric without ``workloads`` is reported by
    every cell; a per-layer one without it by every cell that reports the
    end-to-end metric it moves."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in names
                             else [])]


def load_reader(metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------------ records
@dataclass
class Served:
    """One request as the front end saw it."""
    due: float                      # host clock, seconds
    prompt: List[int]
    max_new: int
    tag: str                        # "fill" | "lead" | "window" | "post"
    handle: object = None           # the engine's Request
    submitted: float = 0.0
    admitted: Optional[float] = None   # start of the step that gave a slot
    pos: int = 0                    # prompt+output positions fed so far
    tokens: List[int] = field(default_factory=list)
    token_times: List[float] = field(default_factory=list)
    skipped: int = 0                # prompt tokens restored from the store
    done: bool = False              # the engine finished it


@dataclass
class Step:
    t0: float                       # host clock before ``step``
    t1: float                       # host clock after the step's drains
    busy: int                       # requests holding a slot in the step
    fed: List[tuple]                # (position before, tokens fed) a slot
    traced: bool = False


@dataclass
class Run:
    """What a run leaves for the metric readers."""
    cell: str
    config: Dict
    mix: Dict
    seconds: float
    slots: int
    t0: float                       # window start, host clock
    setup_s: float
    requests: List[Served]
    steps: List[Step]
    counters: Dict[str, float]      # engine metrics() deltas over the window
    peaks: Dict
    trace: Optional[Dict] = None    # trace_reduce.reduce() of the slice

    @property
    def t_end(self) -> float:
        return self.t0 + self.seconds

    @property
    def window_requests(self) -> List[Served]:
        return [r for r in self.requests if r.tag == "window"]

    @property
    def window_steps(self) -> List[Step]:
        return [s for s in self.steps if self.t0 <= s.t0 < self.t_end]

    @property
    def over(self) -> bool:
        return self.mix["regime"] == "over"


# ------------------------------------------------------------------ device
def device_info(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is {d.platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return d, len(devs)


def peaks_for(kind: str, require_tpu: bool) -> Dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind in table:
        return table[kind]
    if require_tpu:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return {"bf16_flops": float("nan"), "hbm_bytes_per_s": float("nan")}


class CompileCounter:
    """Counts lowerings and compiles (a persistent-cache hit is lowered
    too), so that a window which held one can be told."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring
        self.n = 0
        self.seconds = 0.0

        def on(event, secs, **_):
            if event in self.EVENTS:
                self.n += 1
                self.seconds += secs
        monitoring.register_event_duration_secs_listener(on)


class GcPauses:
    """Python's garbage collections from the moment it is made: how many
    of each generation, and the longest pause, so that a host stall in the
    window can be told from one of the collector."""

    def __init__(self):
        self.counts = [0, 0, 0]
        self.longest = 0.0
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
            return
        self.counts[info["generation"]] += 1
        self.longest = max(self.longest, time.perf_counter() - self._t)

    def close(self) -> None:
        gc.callbacks.remove(self._on)


# ----------------------------------------------------------------- the run
class Harness:
    def __init__(self, cell: Dict, config: Dict, mix: Dict, seed: int,
                 seconds: float, trace: bool, *, require_tpu: bool = True,
                 t_start: Optional[float] = None, step_hook=None):
        self.cell = cell
        self.config = config
        self.mix = mix
        self.seed = seed
        self.seconds = float(seconds)
        self.trace = trace
        self.require_tpu = require_tpu
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.step_hook = step_hook      # tests: break the timed path
        self.lateness: List[float] = []

    # -- set-up ------------------------------------------------------------
    def build(self) -> None:
        import jax
        from repro.launch.serve import build_server
        from .weights import make_weights

        serve = self.config["serve"]
        self.dev, self.count = device_info(self.cell["chips"],
                                           self.require_tpu)
        self.peaks = peaks_for(self.dev.device_kind, self.require_tpu)
        srv = build_server(list(serve["argv"]))
        eng = srv.engine
        self.cfg = srv.cfg
        self.slots = eng.B
        self.max_seq = eng.max_seq
        self.bt = eng.store.block_tokens
        like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                            eng.params)
        # the program's own weights go before the benchmark's are made,
        # so that the chip never holds two copies
        eng.params = None
        del srv
        gc.collect()
        wseed = int(np.random.default_rng([self.seed, 3]).integers(2 ** 31))
        self.weights = make_weights(like, wseed)
        eng.params = self.weights
        jax.block_until_ready(self.weights)
        self.eng = eng
        self.pool_blocks = eng.metrics()["pool_blocks"]
        from .traffic import Traffic
        self.traffic = Traffic(self.mix, self.seed, self.cfg.vocab)
        if self.traffic.longest_request() > self.max_seq:
            raise BenchError(
                f"the mix's longest request needs "
                f"{self.traffic.longest_request()} positions, the engine "
                f"holds {self.max_seq}")

    def warm_shapes(self) -> None:
        """Compile every step shape the traffic can use, through the
        engine's own submit/step/cancel. A step's shape is (S, NW): S the
        widest feed of the step, NW the widest active block table in rows,
        rounded up to a multiple of 4. A request of ``r`` prompt tokens
        whose prompt and output span NW rows gives exactly (r, NW) on its
        first step. The traffic's shortest request spans ``nw_min`` rows,
        so narrower tables never occur. A prompt's last chunk can hold any
        number of tokens up to the prefill chunk, so S takes every width
        from 1 (decode) to the chunk."""
        eng = self.eng
        chunk = eng.prefill_chunk
        rows_max = -(-self.max_seq // self.bt)
        widths = list(range(1, chunk + 1))
        key = (self.cfg, eng.B, self.max_seq, self.bt, chunk, tuple(widths))
        m = self.mix
        shortest = (m["prefix_tokens"]["min"] + m["suffix_tokens"]["min"]
                    + m["output_tokens"]["min"])
        rows_min = -(-shortest // self.bt)
        nw_min = min(-(-rows_min // 4) * 4, rows_max)
        nws = list(range(nw_min, rows_max + 1, 4))
        if nws[-1] != rows_max:
            nws.append(rows_max)
        if key in _WARMED:          # this process has compiled them all
            self.shapes_warmed = _WARMED[key]
            return
        t = time.perf_counter()
        self._prefetch([(r, nw) for nw in nws for r in widths])
        self.phases["prefetch"] = time.perf_counter() - t
        lowered = self.compiles.n
        n = 0
        for nw in nws:
            span = min(nw * self.bt, self.max_seq)
            for r in widths:
                req = eng.submit([1] * r, max_new=span - r)
                eng.step()
                eng.cancel(req)
                n += 1
        # lowerings and compiles the loop had to make itself: 0 where the
        # prefetch loaded every program
        self.phases["loop_compiles"] = self.compiles.n - lowered
        # a fully resident chain copies its last row (copy-on-write)
        block = [2] * self.bt
        for _ in range(2):
            req = eng.submit(block, max_new=1)
            while not req.done:
                eng.step()
        self.shapes_warmed = _WARMED[key] = (n, widths, nw_min, rows_max)

    def _prefetch(self, shapes: List[tuple]) -> None:
        """Load the step programs of ``shapes`` (S, NW) on a few threads,
        so that the loop in ``warm_shapes``, which runs every shape once
        through the engine's own step, finds each one compiled (after a
        cell's first run, from the persistent cache). It lowers the
        engine's jitted step with arguments built as ``ServeEngine.step``
        builds them; where the engine builds them otherwise, it logs why
        and leaves every program to the loop."""
        eng = self.eng
        B = eng.B

        def one(shape):
            S, nw = shape
            eng._step.lower(
                eng.params, eng.pool.buffers,
                eng._put(np.zeros((B, S), np.int32)),
                eng._put(np.zeros((5, B), np.int32)),
                eng._put(np.zeros((B, nw), np.int32)),
                eng._prev_out, eng._done_dev).compile()

        try:
            with ThreadPoolExecutor(PREFETCH_THREADS) as ex:
                list(ex.map(one, shapes))
        except Exception as e:      # noqa: BLE001 - the loop still warms
            log(f"prefetch of the step programs stopped: {e!r}")

    # -- playing traffic -----------------------------------------------------
    def _submit(self, s: Served) -> None:
        now = time.perf_counter()
        s.handle = self.eng.submit(s.prompt, max_new=s.max_new)
        s.submitted = now
        if s.tag == "window":
            self.lateness.append(now - s.due)

    def _annotate(self, name: str):
        if not self.tracing_now:
            return nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def play(self, arrivals: List[Served], stop, on_step=None) -> None:
        """Submit each request when it is due, step the engine, drain
        every emitting request after each step. ``stop(now)`` ends the
        loop (checked before each step)."""
        eng = self.eng
        pending = deque(sorted(arrivals, key=lambda s: s.due))
        queued: List[Served] = self.queued
        live: List[Served] = self.live
        while True:
            now = time.perf_counter()
            if stop(now):
                break
            if pending and pending[0].due <= now:
                with self._annotate("submit"):
                    while pending and pending[0].due <= now:
                        s = pending.popleft()
                        self._submit(s)
                        queued.append(s)
                        self.requests.append(s)
            if not live and not queued:
                if not pending:
                    break
                with self._annotate("wait_arrival"):
                    wait = pending[0].due - time.perf_counter()
                    if wait > 0:
                        time.sleep(min(wait, 0.05))
                continue
            t0 = time.perf_counter()
            with self._annotate("step"):
                if self.step_hook is not None:
                    self.step_hook(eng)
                eng.step()
            fed = []
            for s in queued:
                if s.handle.slot >= 0:
                    s.admitted = t0
                    s.skipped = s.handle.prefill_skipped
                    s.pos = s.skipped
                    live.append(s)
            queued[:] = [s for s in queued if s.admitted is None]
            with self._annotate("drain"):
                for s in live:
                    toks = eng.drain(s.handle)
                    if len(toks) > len(s.tokens):
                        s.tokens.extend(toks[len(s.tokens):])
                t1 = time.perf_counter()
            busy = 0
            for s in live:
                pos = s.handle.pos
                if pos > s.pos:
                    fed.append((s.pos, pos - s.pos))
                    s.pos = pos
                busy += 1
                while len(s.token_times) < len(s.tokens):
                    s.token_times.append(t1)
            for s in live:
                s.done = bool(s.handle.done)
            live[:] = [s for s in live if not s.done]
            step = Step(t0, t1, busy, fed, traced=self.tracing_now)
            self.steps.append(step)
            if on_step is not None:
                on_step(step)

    def prepare(self) -> None:
        """Build, warm every step shape, and fill the store with the
        warm-up stream as fast as the engine takes it."""
        self.requests: List[Served] = []
        self.steps: List[Step] = []
        self.live: List[Served] = []
        self.queued: List[Served] = []
        self.tracing_now = False
        phases = self.phases = {}
        t = time.perf_counter()
        self.build()
        phases["build"] = time.perf_counter() - t
        self.compiles = CompileCounter()
        t = time.perf_counter()
        self.warm_shapes()
        phases["shapes"] = time.perf_counter() - t
        t = time.perf_counter()
        self.play(self.fill_stream(t), stop=lambda now: False)
        phases["fill"] = time.perf_counter() - t

    def fill_stream(self, t: float) -> List[Served]:
        """The warm-up stream that fills the store: requests of the mix,
        one output token each (publishing a prompt needs no more), until
        their prompts hold as many tokens as the store, or
        ``fill_requests`` of them."""
        store = self.eng.store
        room = store.capacity // self.eng.pool.block_nbytes * self.bt
        out: List[Served] = []
        n = self.mix["warmup"]["fill_requests"]
        for r in self.traffic.stream(1, n, max_new=1):
            if room <= 0:
                break
            out.append(Served(t, r.prompt, 1, "fill"))
            room -= len(r.prompt)
        return out

    def _profile(self, directory: Path) -> None:
        import jax
        shutil.rmtree(directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(directory), profiler_options=opts)

    def serve(self) -> Run:
        """Set-up, window, and the tail of the window's requests."""
        self.prepare()
        import jax
        from . import trace_reduce
        tr = self.traffic
        mix = self.mix
        trace_dir = TRACE_DIR / f"{self.cell['name']}-{self.seed}"
        if self.trace:
            # the profiler's first start is slow: take it in set-up, so
            # that the start inside the window is short
            self._profile(trace_dir)
            jax.profiler.stop_trace()
        # what set-up made lives to the end of the run: move it out of the
        # collector's way, so that a collection in the window walks only
        # what the served load makes
        gc.collect()
        gc.freeze()
        self.gc_pauses = GcPauses()
        # lead-in at the cell's rate, then the window, then (below the
        # knee) more of the same load until the window's requests are done
        lead_s = float(mix["warmup"]["lead_in_s"])
        t_lead = time.perf_counter()
        t0 = t_lead + lead_s
        T = self.seconds
        n_lead = max(1, round(tr.rate * lead_s))
        n_win = max(1, round(tr.rate * T))
        arrivals = [Served(t_lead + r.due, r.prompt, r.max_new, "lead")
                    for r in tr.stream(2, n_lead, span=lead_s)]
        arrivals += [Served(t0 + r.due, r.prompt, r.max_new, "window")
                     for r in tr.stream(3, n_win, span=T)]
        over = mix["regime"] == "over"
        tail_s = float(mix.get("tail_limit_s", 120.0))
        if not over:
            n_post = max(1, round(tr.rate * tail_s))
            arrivals += [Served(t0 + T + r.due, r.prompt, r.max_new, "post")
                         for r in tr.stream(4, n_post, span=tail_s)]
        marks: Dict[str, Dict] = {}
        slice_at = t0 + T / 2 - self._slice_s() / 2
        span = {}

        def on_step(step: Step) -> None:
            now = step.t1
            if "start" not in marks and now >= t0:
                marks["start"] = dict(self.eng.metrics())
                marks["compiles"] = self.compiles.n
            if "end" not in marks and now >= t0 + T:
                marks["end"] = dict(self.eng.metrics())
            if not self.trace:
                return
            if not span and now >= slice_at:
                # whole steps only: every device op of the steps marked
                # ``traced`` lies inside the slice, because the harness
                # reads every step's tokens back before the next
                self._profile(trace_dir)
                span["ann"] = jax.profiler.TraceAnnotation(trace_reduce.SLICE)
                span["ann"].__enter__()
                span["t"] = time.perf_counter()
                self.tracing_now = True
            elif self.tracing_now and now >= span["t"] + self._slice_s():
                self._end_slice(span)

        window = [s for s in arrivals if s.tag == "window"]
        if over:
            def stop(now):
                return now >= t0 + T
        else:
            def stop(now):
                return ((now >= t0 + T and all(s.done for s in window))
                        or now >= t0 + T + tail_s)
        self.setup_s = t0 - self.t_start
        self.play(arrivals, stop, on_step)
        self.gc_pauses.close()
        gc.unfreeze()
        if self.tracing_now:
            self._end_slice(span)
        end = marks.get("end") or dict(self.eng.metrics())
        start = marks.get("start") or end
        self.window_compiles = self.compiles.n - marks.get("compiles",
                                                           self.compiles.n)
        self.pool_grew = end["pool_blocks"] != self.pool_blocks
        counters = {k: end[k] - start[k] for k in end
                    if isinstance(end[k], (int, float))
                    and isinstance(start.get(k), (int, float))}
        run = Run(self.cell["name"], self.config, mix, T, self.slots, t0,
                  self.setup_s, self.requests, self.steps, counters,
                  self.peaks)
        if span:
            run.trace = trace_reduce.reduce(trace_reduce.load(trace_dir))
            shutil.rmtree(trace_dir, ignore_errors=True)
        return run

    def _end_slice(self, span: Dict) -> None:
        import jax
        span["ann"].__exit__(None, None, None)
        self.tracing_now = False
        jax.profiler.stop_trace()

    def _slice_s(self) -> float:
        return min(3.0, self.seconds / 3)

    def memory_peak(self) -> int:
        stats = self.dev.memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))

    def release(self) -> None:
        """Free the engine's device state: cancel what is left, drop the
        pool. The benchmark's weights stay for the reference."""
        eng = self.eng
        for s in self.requests:
            if s.handle is not None and not s.handle.done:
                eng.cancel(s.handle)
            s.handle = None
        eng.close()
        self.eng = None
        self.live = []
        self.queued = []
        del eng
        gc.collect()


# ------------------------------------------------------------------- check
def sample_for_check(run: Run, seed: int, max_requests: int,
                     min_tokens: int) -> List[Served]:
    """Finished window requests, drawn from the seed: the longest, one
    whose prefix the store restored (where any was), then others until
    ``min_tokens`` served tokens or ``max_requests``."""
    done = [s for s in run.window_requests
            if len(s.tokens) == s.max_new and s.admitted is not None]
    if run.over:
        done = [s for s in done if s.token_times
                and s.token_times[-1] <= run.t_end]
    if not done:
        return []
    rng = np.random.default_rng([seed, 7])
    order = list(rng.permutation(len(done)))
    longest = max(range(len(done)),
                  key=lambda i: len(done[i].prompt) + len(done[i].tokens))
    pick = [longest]
    hits = [i for i in order if done[i].skipped > 0 and i != longest]
    if hits:
        pick.append(hits[0])
    for i in order:
        if len(pick) >= max_requests or \
                sum(len(done[j].tokens) for j in pick) >= min_tokens:
            break
        if i not in pick:
            pick.append(i)
    return [done[i] for i in pick]


def logit_gaps(ref, s: Served, quant: Optional[str] = None) -> np.ndarray:
    """At every served position: how far the served token's reference
    logit lies below the reference's best. With ``quant`` the served
    token is replaced by the control's own first choice."""
    P, g = len(s.prompt), len(s.tokens)
    seq = s.prompt + s.tokens[:-1]
    logits = ref.logits(seq, P - 1, g)
    if quant is not None:
        ctl = ref.logits(seq, P - 1, g, quant=quant)
        chosen = np.argmax(ctl, axis=-1)
    else:
        chosen = np.asarray(s.tokens)
    best = logits.max(axis=-1)
    gaps = np.full((g,), _NO_LOGIT)
    ok = (chosen >= 0) & (chosen < logits.shape[1])
    gaps[ok] = best[ok] - logits[np.arange(g)[ok], chosen[ok]]
    return gaps


def verdict(gap: float, n_tok: int, short: int, limits: Dict) -> bool:
    """``correct``: the widest logit gap within its limit, enough served
    tokens compared, and every finished request of its full length. A
    configuration whose limit is not set yet is never correct."""
    return (limits["max_logit_gap"] is not None
            and gap <= limits["max_logit_gap"]
            and n_tok >= limits["min_tokens"] and short == 0)


def check(h: Harness, run: Run, controls=()) -> Dict:
    """The comparison that decides ``correct``, on a sample of the
    window's finished requests. Returns the numbers compared, each with
    its limit. Each of ``controls`` puts the reference in the program's
    place at that lower precision and is judged by the same rule: its
    widest gap and its own ``correct``."""
    from .reference import Reference
    cfg = run.config
    limits = cfg["check"]
    sample = sample_for_check(run, h.seed, limits["max_requests"],
                              limits["min_tokens"])
    short = sum(1 for s in run.window_requests
                if s.admitted is not None and s.done
                and len(s.tokens) != s.max_new)
    m = run.mix["output_tokens"]["max"]
    ref = Reference(h.weights, cfg["model"], h.cfg.n_layers, h.max_seq, m)
    gap = 0.0
    n_tok = 0
    ctl = {q: 0.0 for q in controls}
    for s in sample:
        gap = max(gap, float(logit_gaps(ref, s).max()))
        n_tok += len(s.tokens)
        for q in controls:
            ctl[q] = max(ctl[q], float(logit_gaps(ref, s, q).max()))
    out = {
        "max_logit_gap": {"value": gap, "limit": limits["max_logit_gap"]},
        "tokens_compared": {"value": n_tok, "limit": limits["min_tokens"]},
        "wrong_length": {"value": short, "limit": 0},
    }
    return {"correct": verdict(gap, n_tok, short, limits), "checks": out,
            "controls": {q: {"max_logit_gap": v,
                             "correct": verdict(v, n_tok, short, limits)}
                         for q, v in ctl.items()},
            "sample": [(len(s.prompt), len(s.tokens), s.skipped)
                       for s in sample]}


# ------------------------------------------------------------------ result
def cell_files(name: str):
    bench = load_benchmark()
    cell = find(bench["workloads"], name, "workload")
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    from .traffic import load_mix
    mix = load_mix(cell["traffic"])
    return bench, cell, config, mix


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, t_start: Optional[float] = None,
             files=None, step_hook=None,
             controls=(), info: Optional[Dict] = None) -> Dict:
    """One run of cell ``name``: the result line's object. ``files``,
    ``step_hook``, ``controls`` and ``info`` (filled with
    the check's details, the run's records and the harness) are for the
    tests and the calibration, never for the benchmark's own command."""
    bench, cell, config, mix = files or cell_files(name)
    h = Harness(cell, config, mix, seed, seconds, trace,
                require_tpu=require_tpu, t_start=t_start,
                step_hook=step_hook)
    run = h.serve()
    peak = h.memory_peak()
    log(f"set-up {run.setup_s!r} s, phases {h.phases}; shapes warmed "
        f"(count, widths, narrowest, widest) {h.shapes_warmed}; "
        f"compiles in the window {h.window_compiles}; pool grew "
        f"{h.pool_grew}")
    late = np.asarray(h.lateness) if h.lateness else np.zeros(1)
    log(f"generator lateness over {len(h.lateness)} window requests: "
        f"median {float(np.median(late))!r} s, max {float(late.max())!r} s")
    log(f"garbage collections after set-up by generation "
        f"{h.gc_pauses.counts}, longest {h.gc_pauses.longest!r} s")
    attempted = len(run.window_requests)
    unfinished = sum(1 for s in run.window_requests if not s.done)
    h.release()
    t = time.perf_counter()
    result = check(h, run, controls)
    log(f"check took {time.perf_counter() - t!r} s")
    failed = 0 if run.over else unfinished
    if h.window_compiles or h.pool_grew:
        # a compile or a growing pool in the window taints every request
        failed = attempted
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in reported(bench, cell["name"], kind):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": h.dev.platform, "kind": h.dev.device_kind,
              "count": h.count, "memory_peak_bytes": peak}
    out = {"correct": result["correct"], "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["top_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    log(f"checked {result['sample']} (prompt, served, restored)")
    for q, v in result["controls"].items():
        log(f"control {q}: max_logit_gap {v['max_logit_gap']!r} "
            f"correct {v['correct']}")
    for k, v in result["checks"].items():
        log(f"check {k}: {v['value']!r} limit {v['limit']!r}")
    out["checks"] = result["checks"]
    if info is not None:
        info.update(result, run=run, harness=h)
    return out
