#!/usr/bin/env python3
"""Where the device's idle time goes, named by the program's own spans.

  python3 bench/spans.py --workload <cell> --seed <n> --seconds 51 \\
      [--cost 100000] [--save excerpt.json]

Runs one cell as ``bench/run.py --trace 1`` does, and for the profiled
slice attaches a ``repro.obs.TraceRecorder`` to the engine, which then
mirrors each of its spans (``engine.<phase>``, ``store.<phase>``) into
the profiler's trace on the device's clock. One JSON line goes to
standard output:

- ``idle_by_span``: the slice's device idle seconds by the innermost
  program span the host was in (self time), ``harness:<name>`` where only
  one of the harness's own spans covers an idle instant, ``other`` where
  none does; and the share under a program span;
- ``gaps``: the longest idle gaps, each with the harness span
  ``trace_reduce`` names it by and its split by program span;
- ``steps``: the host's phases per engine step (``step_phases``);
- with ``--cost N``: the host time of one mirrored span, N begin/end
  pairs with and without a profiler session running.

``--save`` writes three steps of the slice, in ``trace_reduce.load``'s
form plus the program's spans, for the tests. The benchmark's own runs do
not attach a recorder.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Sequence, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402
from bench.harness import Harness  # noqa: E402

# the program's spans: ``<layer>.<phase>``
PROGRAM = ("engine.", "store.")
# the engine's jitted step, as its whole-program events name it
STEP_MODULE = "_step"
TOP = 10


def load_spans(directory) -> List[list]:
    """The program's spans in the newest trace under ``directory``:
    ``[name, start_ns, duration_ns, stats]``, on the device trace's
    clock."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(str(directory), "**",
                                          "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no trace under {directory}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM):
                    out.append([e.name, int(e.start_ns), int(e.duration_ns),
                                {k: v for k, v in e.stats}])
    return out


def labels(spans: Sequence, host: Sequence, lo: int, hi: int
           ) -> List[Tuple[int, int, str]]:
    """[lo, hi) cut where any span begins or ends, each piece labelled by
    the innermost program span over it (the one begun last), else
    ``harness:<name>`` of the innermost harness span, else ``other``."""
    marks = [(lo, 0, None), (hi, 0, None)]
    for name, s, d, *_ in spans:
        key = (1, s, -(s + d), name)
        marks += [(s, 1, key), (s + d, -1, key)]
    for name, s, d in host:
        key = (0, s, -(s + d), f"harness:{name}")
        marks += [(s, 1, key), (s + d, -1, key)]
    marks.sort(key=lambda m: (m[0], m[1]))
    active: Dict[tuple, int] = {}
    out, t = [], lo
    for at, kind, key in marks:
        at = min(max(at, lo), hi)
        if at > t:
            out.append((t, at, max(active)[3] if active else "other"))
            t = at
        if kind:
            active[key] = active.get(key, 0) + kind
            if not active[key]:
                del active[key]
    return out


def idle_by_span(idle: Sequence[Tuple[int, int]],
                 pieces: Sequence[Tuple[int, int, str]]) -> List[list]:
    """Idle seconds by the label of the piece of ``labels`` over each idle
    instant, largest first."""
    out: Dict[str, float] = defaultdict(float)
    i = 0
    for a, b in sorted(idle):
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < b:
            s, e, label = pieces[j]
            out[label] += (min(b, e) - max(a, s)) / 1e9
            j += 1
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])]


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


def step_phases(spans: Sequence, modules: Sequence) -> Optional[Dict]:
    """The host's side of each engine step, in ms, each a mean over the
    ``engine.step`` spans of ``spans`` so that they add up:

    - ``prelaunch_ms``: from the step's start to the end of its
      ``engine.launch`` (the whole step where it launched nothing);
    - ``store_host_ms``: time inside ``store.*`` spans, submits included;
    - ``launch_wait_ms``: from each launch's end to the start of its step
      program on the device (of ``modules``, ``[name, start, dur, ...]``),
      the first one that starts after the launch does: the harness reads
      every step back before the next, so launches and programs
      alternate. Nothing where the device started before the call
      returned;
    - ``readback_wait_ms``: from the later of a step program's end and the
      start of the first ``engine.readback`` that ends after it, to that
      readback's end; a program that no readback ends after before the
      next program starts adds nothing;
    - ``span_ms``: the time inside each span name, and ``spans``, the
      number of spans a step.

    None where ``spans`` holds no ``engine.step``."""
    steps = [s for s in spans if s[0] == "engine.step"]
    if not steps:
        return None
    n = len(steps)
    launches = sorted((s for s in spans if s[0] == "engine.launch"),
                      key=lambda s: s[1])
    prelaunch = 0
    for st in steps:
        inner = [la for la in launches if _inside(la, st)]
        end = inner[-1][1] + inner[-1][2] if inner else st[1] + st[2]
        prelaunch += end - st[1]
    store = trace_reduce.union((s, s + d) for name, s, d, _ in spans
                               if name.startswith("store."))
    mods = sorted((m[1], m[1] + m[2]) for m in modules)
    wait, k = 0, 0
    for la in launches:
        while k < len(mods) and mods[k][0] < la[1]:
            k += 1
        if k == len(mods):
            break
        wait += max(0, mods[k][0] - (la[1] + la[2]))
        k += 1
    reads = sorted(((s, s + d) for name, s, d, _ in spans
                    if name == "engine.readback"), key=lambda r: r[1])
    rb = 0
    for i, (_, me) in enumerate(mods):
        nxt = mods[i + 1][0] if i + 1 < len(mods) else None
        for s, e in reads:
            if e > me:
                if nxt is None or e <= nxt:
                    rb += e - max(me, s)
                break
    totals: Dict[str, int] = defaultdict(int)
    for name, _, d, _ in spans:
        totals[name] += d
    return {"steps": n, "spans": len(spans) / n,
            "prelaunch_ms": prelaunch / n / 1e6,
            "store_host_ms": sum(e - s for s, e in store) / n / 1e6,
            "launch_wait_ms": wait / n / 1e6,
            "readback_wait_ms": rb / n / 1e6,
            "span_ms": {k: v / n / 1e6 for k, v in sorted(totals.items())}}


def analyse(tr: Dict) -> Dict:
    """``tr`` in ``trace_reduce.load``'s form with the program's ``spans``
    beside: ``trace_reduce.reduce``'s window, busy and idle time, and the
    idle time and engine steps by program span, in the slice."""
    red = trace_reduce.reduce(tr)
    slices = [(s, s + d) for n, s, d in tr["host"]
              if n == trace_reduce.SLICE]
    lo, hi = slices[0]
    host = [h for h in tr["host"] if h[0] != trace_reduce.SLICE]
    devices = sorted({o[3] for o in tr["ops"]})
    first = devices[0] if devices else None
    busy = trace_reduce.union(trace_reduce.clip(
        [(o[1], o[1] + o[2]) for o in tr["ops"] if o[3] == first], lo, hi))
    idle = trace_reduce.gaps(busy, lo, hi)
    pieces = labels(tr["spans"], host, lo, hi)
    by_span = idle_by_span(idle, pieces)
    idle_s = sum(v for _, v in by_span)
    program = sum(v for k, v in by_span
                  if not k.startswith("harness:") and k != "other")
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:TOP]
    modules = [m for m in tr["modules"] if STEP_MODULE in m[0]
               and lo <= m[1] < hi and m[3] == first]
    return {
        "window_s": red["window_s"], "busy_s": red["busy_s"],
        "idle_s": idle_s,
        "programs": len(modules),
        "step_ms": (sum(m[2] for m in modules) / len(modules) / 1e6
                    if modules else None),
        "idle_by_span": by_span,
        "program_share": program / idle_s if idle_s else None,
        "gaps": [[trace_reduce.name_gap(g, host), (g[1] - g[0]) / 1e9,
                  idle_by_span([g], pieces)[:3]] for g in longest],
        "steps": step_phases([s for s in tr["spans"] if lo <= s[1] < hi],
                             modules),
    }


def excerpt(tr: Dict, first: int, n: int = 3) -> Dict:
    """Steps ``first`` to ``first + n - 1`` of the slice in ``tr``: from
    the start of the harness's ``step`` span around the first to the start
    of the one after the last, with a ``slice`` span over that window."""
    steps = sorted(s for s in tr["host"] if s[0] == "step")
    lo, hi = steps[first][1], steps[first + n][1]

    def keep(events):
        return [e for e in events if lo <= e[1] < hi]
    return {"ops": keep(tr["ops"]), "modules": keep(tr["modules"]),
            "host": keep(h for h in tr["host"] if h[0] != "slice")
            + [["slice", lo, hi - lo]],
            "spans": keep(tr["spans"])}


def span_cost(n: int, directory: Path) -> Dict[str, float]:
    """Host microseconds of one begin/end pair of a mirrored span with the
    stats of an ``engine.launch``, with no profiler session and with one
    running (mean of ``n``)."""
    import jax
    from repro.obs import TraceRecorder
    rec = TraceRecorder(limit=1024)
    args = {"step": 1, "S": 8, "NW": 160, "fed": 32, "decoding": 30}

    def timed() -> float:
        t = time.perf_counter()
        for _ in range(n):
            rec.span("engine.launch", "engine", args=args).begin().end()
        return (time.perf_counter() - t) / n * 1e6
    off = timed()
    jax.profiler.start_trace(str(directory))
    try:
        on = timed()
    finally:
        jax.profiler.stop_trace()
    return {"pairs": n, "off_us": off, "on_us": on}


class SpanHarness(Harness):
    """The benchmark's harness with a recorder on the engine for the
    profiled slice, and the slice's trace kept with the program's spans
    before the harness removes it."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, step_hook=self._attach, **kwargs)
        self.slice_trace = None

    def _attach(self, eng) -> None:
        if self.tracing_now and eng.trace is None:
            from repro.obs import TraceRecorder
            eng.attach_trace(TraceRecorder())

    def _profile(self, directory: Path) -> None:
        self._dir = directory
        super()._profile(directory)

    def _end_slice(self, span: Dict) -> None:
        if self.eng.trace is not None:
            self.eng.detach_trace()
        super()._end_slice(span)
        self.slice_trace = dict(trace_reduce.load(self._dir),
                                spans=load_spans(self._dir))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--cost", type=int, default=0)
    ap.add_argument("--save", default=None)
    args = ap.parse_args(argv)
    from bench.jaxenv import use_checkout
    use_checkout()
    from bench import harness
    _, cell, config, mix = harness.cell_files(args.workload)
    h = SpanHarness(cell, config, mix, args.seed % 2 ** 63, args.seconds,
                    True, t_start=T_START)
    h.serve()
    out = {"workload": args.workload, "seed": args.seed,
           "device": h.dev.device_kind, "setup_s": h.setup_s,
           **analyse(h.slice_trace)}
    if args.cost:
        out["cost"] = span_cost(args.cost, harness.TRACE_DIR / "cost")
    if args.save:
        Path(args.save).write_text(json.dumps(excerpt(h.slice_trace, 3)))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
