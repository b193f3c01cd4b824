"""Continuous-batching serve engine over a device-resident paged KV pool,
with a LERC prefix cache underneath.

The serving data plane is built so the hot path is dominated by real
compute, not Python-loop and PCIe overhead — the regime where the paper's
claim (coordinated caching speeds up *jobs*) is measurable:

* **Chunked prefill** — each engine step feeds up to ``prefill_chunk``
  prompt tokens per slot through one batched ``decode_step``, so a P-token
  prompt costs ~ceil(P/chunk) dispatches instead of ~P. Prefill-chunk
  slots and decode slots share the dispatch; decode rows are right-padded
  and masked.
* **Zero-copy paged attention** (``paged=True``, PR 5) — the
  ``KVBlockPool`` is the ONLY KV storage. Each slot owns a *block table*
  (host-side list of pool rows); a prefix hit appends the store's rows to
  the table (zero dispatches, zero copies), new tokens are written by the
  model straight into the slot's tail pool rows, attention streams from
  the rows the table names (``kernels.paged_attention`` on TPU, the same
  ``_sdpa`` numerics via an XLA page gather elsewhere), and publish is an
  ownership transfer of the already-written rows to the store. Rows are
  refcounted: evicting a block another slot is still reading defers the
  actual reclaim to that slot's completion. The per-slot contiguous
  ``(B, max_seq)`` decode cache does not exist in this mode — its bytes
  are free to grow the pool.
* **Gather fallback** (``paged=False``, the PR 2 data plane) — per-slot
  contiguous caches; a hit is a jitted gather pool→slot, publish a jitted
  scatter slot→pool. Retained for rolling/recurrent layer patterns, whose
  KV layout is not absolute-position.
* **Pipelined host readback** — the argmax token of step N is routed into
  step N+1's feed *on device* (decode feeds never round-trip through
  host), so the engine only blocks on a device→host sync when a request
  finishes (or every step when EOS detection is on). ``metrics()`` counts
  the avoided syncs.

Store-visible behavior (the sequence of ``register_request`` / ``lookup``
/ ``insert`` / ``complete_request`` calls and therefore every eviction
decision) is identical across both data planes and the legacy engine on
uniform-length workloads; ``tests/test_engine_equivalence.py`` proves
token-identical generations and bit-identical eviction logs paged vs
gather vs ``LegacyServeEngine`` vs the brute-force
``ReferencePrefixStore``.
"""
from __future__ import annotations

import itertools
import warnings
from collections import deque
from dataclasses import dataclass, field
from functools import lru_cache
from typing import Deque, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.paged_attention import rows_walked
from ..models import decode_step, init_decode_cache
from ..models.common import ModelConfig
from ..models.layers import use_flash_decode
from ..obs.trace import (TID_ENGINE as _TID_ENGINE, TID_REQ as _TID_REQ,
                         TID_SCHED as _TID_SCHED, TID_STORE as _TID_STORE)
from ..sharding import KVShardCtx, serve_tp_context
from .disk_pool import DiskBlockPool
from .host_pool import HostBlockPool
from .kv_pool import KVBlockPool, chain_block_nbytes
from .prefix_store import PrefixStore
from .scheduler import QueueFull, Scheduler, StepCostModel, make_scheduler
from .tiered import TieredKVStore

# pool rows a default-constructed engine starts with when the store's byte
# budget is effectively unbounded (the pool doubles on demand)
_DEFAULT_POOL_BLOCKS = 256


@lru_cache(maxsize=None)
def _step_fn(cfg: ModelConfig, paged: bool, eos_id: int,
             kv_shard: Optional[KVShardCtx] = None):
    """One shared jitted step per (hashable) config, data plane, EOS id,
    and serve-TP mesh: engines spun up on the same model reuse every
    compiled (B, S) specialization instead of retracing behind a fresh
    closure. The KV
    argument (per-slot cache or pool buffers) is donated so XLA updates
    it in place; ``prev``/``use_prev`` route the previous step's argmax
    into decode feeds without a host round-trip.

    ``done`` is the device-side finished mask (PR 6): when EOS detection
    is on, the mask accumulates ``emitted-token == eos_id`` per slot *on
    device*, so the engine only syncs the (B,) mask every
    ``eos_interval`` steps instead of the whole token vector every step —
    EOS mode rides the readback pipeline like everything else."""

    # meta rows: 0 = per-slot position, 1 = real tokens this step,
    # 2 = route the previous argmax into column 0 (decode feed),
    # 3 = this step's output counts as a generated token (EOS-eligible),
    # 4 = clear the slot's done bit (slot re-admitted) — packed into ONE
    # (5, B) host→device upload per step
    def _advance(out_tok, meta, done):
        if eos_id < 0:
            return done
        emit = meta[3].astype(bool)
        reset = meta[4].astype(bool)
        return (done & ~reset) | (emit & (out_tok == eos_id))

    if paged:
        def _step(p, pool, t, meta, tables, prev, done):
            pos, lens, use_prev = meta[0], meta[1], meta[2].astype(bool)
            t = t.at[:, 0].set(jnp.where(use_prev, prev, t[:, 0]))
            logits, new_pool = decode_step(cfg, p, pool, t, pos,
                                           seq_lens=lens,
                                           paged_tables=tables,
                                           kv_shard=kv_shard)
            out = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            return out, new_pool, _advance(out, meta, done)

        return jax.jit(_step, donate_argnums=(1,))

    def _step(p, c, t, meta, prev, done):
        pos, lens, use_prev = meta[0], meta[1], meta[2].astype(bool)
        t = t.at[:, 0].set(jnp.where(use_prev, prev, t[:, 0]))
        logits, new_cache = decode_step(cfg, p, c, t, pos, seq_lens=lens)
        out = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return out, new_cache, _advance(out, meta, done)

    return jax.jit(_step, donate_argnums=(1,))


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    prefix_rid: int = -1            # id inside the PrefixStore
    slot: int = -1
    pos: int = 0                    # next position to fill
    generated: List[int] = field(default_factory=list)
    n_generated: int = 0            # tokens emitted (generated may lag:
                                    # pipelined readback materializes lazily)
    prefill_skipped: int = 0
    done: bool = False
    cancelled: bool = False
    # front-door timing, on the engine's virtual clock (scheduler SLOs)
    arrival: float = 0.0
    deadline: Optional[float] = None    # absolute TTFT deadline, or None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    # failover re-admission (repro.faults): how many times a crash has
    # requeued this request, and the backoff gate before it may re-admit
    retries: int = 0
    not_before: float = 0.0
    # un-synced per-step token vectors (pipelined readback)
    _lazy_out: List = field(default_factory=list, repr=False)


def _kv_leaves(cache) -> List[Tuple[Tuple[str, ...], jax.Array]]:
    out = []

    def walk(t, path=()):
        if isinstance(t, dict):
            for k, v in t.items():
                walk(v, path + (k,))
        else:
            out.append((path, t))

    walk(cache)
    return out


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_slots: int = 4,
                 max_seq: int = 256, store: Optional[PrefixStore] = None,
                 eos_id: int = -1, prefill_chunk: int = 8,
                 pool_blocks: Optional[int] = None,
                 paged: bool = False,
                 scheduler: Union[str, Scheduler, None] = None,
                 max_queue: Optional[int] = None,
                 clock: Optional[StepCostModel] = None,
                 eos_interval: int = 8, tp: int = 1,
                 kv_shard: Optional[KVShardCtx] = None) -> None:
        template = init_decode_cache(cfg, 1, 8)
        for path, _ in _kv_leaves(template):
            assert path[-1] in ("k", "v"), (
                "ServeEngine supports uniform-KV patterns; got leaf "
                f"{'/'.join(path)}")
        absolute_kv = set(cfg.layer_pattern) <= {"G", "M"}
        if prefill_chunk > 1 and not absolute_kv:
            warnings.warn(
                "chunked prefill needs absolute-position KV caches; "
                f"pattern {cfg.layer_pattern!r} has rolling/recurrent "
                "layers — clamping prefill_chunk to 1", stacklevel=2)
            prefill_chunk = 1
        if paged and not absolute_kv:
            warnings.warn(
                "paged attention needs absolute-position KV caches; "
                f"pattern {cfg.layer_pattern!r} has rolling/recurrent "
                "layers — falling back to the gather engine", stacklevel=2)
            paged = False
        # rolling-window (L) KV keeps only the last `window` tokens, so a
        # chain block cannot be restored into it: non-absolute patterns
        # run the full store machinery (lookups, evictions, coordination)
        # but pay prefill recompute instead of a restore. (The PR 2 assert
        # used to reject these configs outright; the restore path it
        # guarded was never valid for them.)
        self.restore_prefix = absolute_kv
        # ----- serve tensor parallelism (PR 7): shard the paged KV pool
        # (and the attention compute reading it) over a 1-D model mesh.
        # Params and per-step host arrays are replicated; block tables,
        # refcounts, and the whole store stay host-global — a pool row
        # index means the same block on every shard.
        if kv_shard is None and tp > 1:
            kv_shard = serve_tp_context(tp)
        if kv_shard is not None:
            if not paged:
                raise ValueError(
                    "tensor parallelism shards the paged data plane; "
                    f"pattern {cfg.layer_pattern!r} (or --no-paged-"
                    "attention) runs the gather engine, which is tp=1 only")
            kv_shard.validate(cfg)
        self.kv_shard = kv_shard
        self.tp = kv_shard.tp if kv_shard is not None else 1
        self._put = (jnp.asarray if kv_shard is None else
                     (lambda x: jax.device_put(jnp.asarray(x),
                                               kv_shard.replicated())))
        if kv_shard is not None:
            # a no-op for params made in place (``init_params(sharding=)``)
            params = jax.device_put(params, kv_shard.replicated())
        self.cfg = cfg
        self.params = params
        self.B = max_slots
        self.max_seq = max_seq
        self.store = store or PrefixStore(capacity_bytes=1 << 62,
                                          policy="lerc")
        self.eos_id = eos_id
        self.prefill_chunk = max(int(prefill_chunk), 1)
        self.paged = bool(paged)

        # ----- paged pool: sized so the store's byte budget, not the pool,
        # is always the binding constraint (bounded budgets evict — and
        # free indices — before alloc; unbounded ones rely on growth). In
        # paged mode the pool additionally carries each slot's private tail
        # rows — the bytes the per-slot contiguous cache used to pin.
        bt = self.store.block_tokens
        self.table_width = -(-max_seq // bt)
        blk_bytes = chain_block_nbytes(template, bt)
        if pool_blocks is None:
            by_capacity = -(-self.store.capacity // max(blk_bytes, 1))
            pool_blocks = int(min(by_capacity, _DEFAULT_POOL_BLOCKS))
            if self.paged:
                pool_blocks += self.B * self.table_width + 1
        self.pool = KVBlockPool(template, bt, pool_blocks,
                                shard_ctx=self.kv_shard)
        if self.paged:
            self.cache = None
            # every right-padded / inactive-slot token is scattered into
            # this reserved row, so real rows only ever see real writes
            self._junk_row = self.pool.alloc()
            assert self._junk_row == 0
            self._tables: List[List[int]] = [[] for _ in range(self.B)]
            # tables only change on admission/completion, not per decode
            # step — keep the device copy and re-upload only when dirty
            self._tables_dev = None
            self._tables_dirty = True
        else:
            self.cache = init_decode_cache(cfg, self.B, max_seq)
        if isinstance(self.store, TieredKVStore):
            # tier 1: host-side pool sized to the store's host byte budget
            # (0 rows when the tier is disabled — the store then behaves
            # op-for-op like a plain PrefixStore). With a quant format the
            # pool stores transcoded rows, so the same budget holds
            # ~itemsize-ratio more blocks. Tier 2, when budgeted, is a
            # memmap pool mirroring the host layout.
            host_pool = HostBlockPool.for_device_pool(
                template, self.pool, self.store.host_capacity,
                quant=self.store.quant)
            disk_pool = None
            if self.store.disk_capacity > 0:
                disk_pool = DiskBlockPool.for_device_pool(
                    template, self.pool, self.store.disk_capacity,
                    quant=self.store.disk_quant,
                    directory=self.store.disk_dir)
            self.store.attach_pools(self.pool, host_pool, disk_pool)
        else:
            self.store.evict_payload = self.pool.free

        self._step = _step_fn(cfg, self.paged, eos_id, self.kv_shard)
        self._prev_out = self._put(jnp.zeros((self.B,), jnp.int32))
        self._done_dev = self._put(jnp.zeros((self.B,), bool))
        self._last_step_avals = None    # shapes of the newest dispatch
        self._rid = itertools.count(1)
        self.queue: Deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * self.B
        # ----- front door (PR 6): step scheduling, admission control, and
        # a deterministic virtual clock for SLO accounting. The default
        # FCFS scheduler reproduces the pre-scheduler step loop exactly.
        self.scheduler = (make_scheduler(scheduler)
                          if isinstance(scheduler, str)
                          else scheduler or Scheduler())
        self.max_queue = max_queue
        self.clock = clock or StepCostModel()
        if getattr(self.scheduler, "clock", False) is None:
            # cost-aware schedulers price chunks on the engine's own clock
            self.scheduler.clock = self.clock
        self.now = 0.0
        self.eos_interval = max(int(eos_interval), 1)
        self._fresh_slots: set = set()  # admitted since the last dispatch
        self.steps = 0
        self.decoded_tokens = 0
        self.prefill_tokens = 0
        self.prefill_tokens_skipped = 0
        self.transfer_dispatches = 0    # gather/scatter/copy-on-write
        self.readback_syncs = 0         # device→host blocking reads
        self.rejected = 0               # backpressure sheds
        self.cancellations = 0
        # obs: an attached ``repro.obs.TraceRecorder`` (None = every
        # instrumentation site is one predicate — bit-identical behavior,
        # see tests/test_obs.py)
        self.trace = None
        self._trace_pid = 0

    # ------------------------------------------------------------------ obs
    def attach_trace(self, recorder, pid: int = 0,
                     name: str = "engine") -> None:
        """Wire a ``TraceRecorder`` through every layer of this engine:
        step phases + scheduler decisions + request lifecycle (this
        class), and store events (the prefix store). ``pid`` namespaces
        the events when several engines (sharded frontend) share one
        recorder."""
        self.trace = recorder
        self._trace_pid = pid
        for tid in (_TID_ENGINE, _TID_SCHED, _TID_STORE, _TID_REQ):
            recorder.label(pid, name, tid=tid)
        self.store.trace = recorder
        self.store.trace_pid = pid
        recorder.vt = self.now

    def detach_trace(self) -> None:
        """Stop recording: the engine and its store drop the recorder and
        run on as if ``attach_trace`` had never been called."""
        self.trace = None
        self._trace_pid = 0
        self.store.trace = None
        self.store.trace_pid = 0

    def _span(self, name: str, args: Optional[dict] = None):
        """A span on this engine's lane of the attached recorder."""
        return self.trace.span(name, "engine", self._trace_pid, _TID_ENGINE,
                               args)

    def _aid(self, req: "Request") -> str:
        """Async-track id for a request: pid-qualified, because rids are
        per-engine counters that collide across shards."""
        return f"{self._trace_pid}:{req.rid}"

    def _trace_req_end(self, r: "Request") -> None:
        """Close a request's lifecycle track with everything
        ``latency_stats`` needs, so reports reconstruct TTFT/TPOT
        percentiles from the trace alone."""
        if self.trace is None:
            return
        self.trace.end_async(
            "req", self._aid(r), "request", self._trace_pid, _TID_REQ,
            args={"rid": r.rid, "arrival": r.arrival, "deadline": r.deadline,
                  "first_token_at": r.first_token_at,
                  "finished_at": r.finished_at,
                  "n_generated": len(r.generated),
                  "cancelled": r.cancelled,
                  "prefill_skipped": r.prefill_skipped})

    # ------------------------------------------------------------- requests
    def submit(self, prompt: Sequence[int], max_new: int = 16, *,
               deadline: Optional[float] = None,
               arrival: Optional[float] = None) -> Request:
        """Enqueue a request. ``deadline`` is an *absolute* TTFT deadline
        on the engine's virtual clock (None = best-effort); ``arrival``
        backdates the request to its true arrival time when a trace loop
        submits it a fraction of a step late. Raises ``QueueFull`` when
        admission control is on and the queue is at ``max_queue``."""
        if self.trace is None:
            return self._submit(prompt, max_new, deadline, arrival)
        with self._span("engine.submit", {"prompt_tokens": len(prompt)}):
            return self._submit(prompt, max_new, deadline, arrival)

    def _submit(self, prompt: Sequence[int], max_new: int,
                deadline: Optional[float],
                arrival: Optional[float]) -> Request:
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            self.rejected += 1
            retry_after = self.retry_after()
            if self.trace is not None:
                self.trace.instant(
                    "rejected", "request", self._trace_pid, _TID_REQ,
                    args={"queued": len(self.queue),
                          "retry_after": retry_after})
            raise QueueFull(f"queue at max_queue={self.max_queue}",
                            depth=len(self.queue), retry_after=retry_after)
        req = Request(next(self._rid), list(prompt), max_new,
                      arrival=self.now if arrival is None else arrival,
                      deadline=deadline)
        req.prefix_rid = self.store.register_request(prompt)
        self.queue.append(req)
        if self.trace is not None:
            self.trace.begin_async(
                "req", self._aid(req), "request", self._trace_pid, _TID_REQ,
                args={"rid": req.rid, "prompt_tokens": len(req.prompt),
                      "max_new": req.max_new, "deadline": req.deadline},
                vt=req.arrival)
        return req

    def retry_after(self) -> float:
        """Backpressure hint stamped on ``QueueFull``: the estimated
        virtual-clock wait until a queue slot frees — the nearest-to-done
        active request's remaining steps priced by the engine's
        ``StepCostModel`` (decode steps at the current batch size)."""
        active = [r for r in self.slots if r is not None]
        per_step = float(self.clock(0, max(len(active), 1), 0))
        if not active:
            return per_step
        steps_left = min(
            -(-max(len(r.prompt) - r.pos, 0) // self.prefill_chunk)
            + max(r.max_new - r.n_generated, 0)
            for r in active)
        return max(steps_left, 1) * per_step

    def cancel(self, req: Request) -> bool:
        """Cancel a request at any point in its lifetime — queued,
        prefilling, or mid-decode. Frees the slot and (paged plane) the
        slot's block-table rows *immediately*: tail rows return to the
        pool, shared store rows drop the slot's reference, and the
        store's pending-chain references retire so eviction stops
        protecting the abandoned chain. Tokens already computed remain
        readable on the returned request. Call between steps."""
        if req.done:
            return False
        req.done = True
        req.cancelled = True
        self.cancellations += 1
        if req.slot >= 0 and self.slots[req.slot] is req:
            self._release_slot(req)
        else:
            try:
                self.queue.remove(req)
            except ValueError:
                pass
        self.store.complete_request(req.prefix_rid)
        self._drain(req)
        req.finished_at = self.now
        self._trace_req_end(req)
        return True

    def drain(self, req: Request) -> List[int]:
        """Streaming read: materialize every token computed so far (one
        blocking device_get) and return the visible generation. Safe at
        any step; with EOS detection on, tokens past the first EOS are
        not shown."""
        self._drain(req)
        gen = req.generated
        if self.eos_id >= 0 and self.eos_id in gen:
            gen = gen[:gen.index(self.eos_id) + 1]
        return list(gen)

    # -------------------------------------------------------- cache plumbing
    def _block_nbytes(self) -> int:
        return self.pool.block_nbytes

    def _publish(self, req: Request) -> None:
        """Prefill complete: publish the prompt's KV chain into the store.

        Paged: the chain's blocks already live in pool rows the slot's
        block table names — the payload factory hands the store a shared
        reference to each fresh block's row. Zero dispatches, zero copies.

        Gather: the store makes room first (freeing pool indices O(1)),
        then the factory allocates one pool row per fresh block and a
        single jitted scatter captures exactly those blocks from the
        slot's contiguous cache."""
        if self.paged:
            table = self._tables[req.slot]
            self.store.insert(req.prompt,
                              lambda i, _node: self.pool.share(table[i]),
                              self.pool.block_nbytes)
            return
        fresh: List[Tuple[int, int]] = []       # (chain position, pool row)

        def alloc(i, _node):
            idx = self.pool.alloc()
            fresh.append((i, idx))
            return idx

        self.store.insert(req.prompt, alloc, self.pool.block_nbytes)
        if fresh:
            self.pool.scatter_from(self.cache, req.slot,
                                   [i for i, _ in fresh],
                                   [idx for _, idx in fresh])
            self.transfer_dispatches += 1

    # ---------------------------------------------------------------- admit
    def _admit(self) -> None:
        bt = self.store.block_tokens
        for i in range(self.B):
            if self.slots[i] is not None or not self.queue:
                continue
            if any(r.not_before > self.now for r in self.queue):
                # failover re-admissions wait out their backoff; everyone
                # else competes normally. This branch is unreachable
                # without a crash (not_before defaults to 0.0).
                eligible = [r for r in self.queue
                            if r.not_before <= self.now]
                if not eligible:
                    break
                pick = self.scheduler.admit_idx(eligible)
                req = eligible[pick]
                self.queue.remove(req)
            else:
                pick = self.scheduler.admit_idx(self.queue)
                if pick == 0:
                    req = self.queue.popleft()
                else:
                    req = self.queue[pick]
                    del self.queue[pick]
            self._fresh_slots.add(i)
            usable = self.store.lookup(req.prompt)
            if not self.restore_prefix:
                usable = []             # hit metrics recorded; no restore
            restored = len(usable) * bt
            # the last prompt token is always recomputed: its logits seed
            # generation and were never cached (vLLM does the same)
            restored = min(restored, len(req.prompt) - 1)
            if self.paged:
                # prefix hit = a host-side block-table write: the slot
                # reads the store's rows in place (refcounted shares)
                table = [self.pool.share(n.payload) for n in usable]
                if table and restored < len(table) * bt:
                    # fully-resident chain: the final block must absorb
                    # the recomputed last prompt token — copy-on-write so
                    # the store's row stays pristine
                    priv = self.pool.alloc()
                    self.pool.copy_row(table[-1], priv)
                    self.pool.free(table[-1])
                    table[-1] = priv
                    self.transfer_dispatches += 1
                # private tail rows for the rest of the prompt + decode
                horizon = min(len(req.prompt) + req.max_new, self.max_seq)
                while len(table) * bt < horizon:
                    table.append(self.pool.alloc())
                self._tables[i] = table
                self._tables_dirty = True
            elif usable:
                # jitted gather pool→slot: the whole resident chain lands
                # in one dispatch, no host round-trip
                self.cache = self.pool.gather_into(
                    self.cache, i, [n.payload for n in usable])
                self.transfer_dispatches += 1
            req.slot = i
            req.pos = restored
            req.prefill_skipped = restored
            self.prefill_tokens_skipped += restored
            self.slots[i] = req
            if self.trace is not None:
                self.trace.async_instant(
                    "req", self._aid(req), "request", self._trace_pid,
                    _TID_REQ, args={"event": "admitted", "slot": i,
                                    "restored_tokens": restored})

    # ----------------------------------------------------------------- step
    def step(self) -> List[Request]:
        """One engine iteration. Decode slots pack first (one pipelined
        token each); the scheduler then divides this step's prefill work —
        up to ``prefill_chunk`` tokens per prefilling slot under FCFS, a
        deadline-ordered token budget under the budgeted scheduler (slots
        it preempts idle for the step) — all in a single batched dispatch.
        Returns requests that finished."""
        trace = self.trace
        if trace is None:
            return self._step_inner(None)
        trace.vt = self.now
        with self._span("engine.step", {"n": self.steps}):
            return self._step_inner(trace)

    def _step_inner(self, trace) -> List[Request]:
        """The step's phases. With a recorder attached each is a span:
        ``engine.admit``, ``engine.plan``, ``engine.feed`` (host arrays,
        uploads, the block-table rebuild), ``engine.avals``,
        ``engine.launch`` (the jitted call), then per request
        ``engine.publish`` and ``engine.finish``, and ``engine.eos_sync``."""
        pid = self._trace_pid
        if trace is None:
            self._admit()
        else:
            with self._span("engine.admit"):
                self._admit()
        active = [r for r in self.slots if r is not None]
        if not active:
            if self.queue and all(r.not_before > self.now
                                  for r in self.queue):
                # everything queued is backing off: jump the virtual clock
                # to the earliest re-admission so the loop can't spin
                self.now = min(r.not_before for r in self.queue)
            return []
        sp = None if trace is None else self._span("engine.plan").begin()
        decoding = [r for r in active if r.pos >= len(r.prompt)]
        prefilling = [r for r in active if r.pos < len(r.prompt)]
        plan = self.scheduler.plan_prefill(prefilling, self.prefill_chunk,
                                           len(decoding))
        plan = {s: n for s, n in plan.items() if n > 0}
        if not decoding and not plan and prefilling:
            # never stall a step that has only prefill work: feed the
            # scheduler's most urgent slot its chunk (a zero budget means
            # "prefill only when decode is idle", not "never prefill")
            r = prefilling[0]
            plan = {r.slot: min(self.prefill_chunk,
                                len(r.prompt) - r.pos)}
        if sp is not None:
            sp.end(args={"prefill_slots": len(plan),
                         "preempted": sum(r.slot not in plan
                                          for r in prefilling),
                         "decoding": len(decoding)})
            sp = self._span("engine.feed").begin()
        feeds: Dict[int, List[int]] = {}
        use_prev = np.zeros((self.B,), bool)
        for r in decoding:
            # the feed is the previous step's argmax for this slot —
            # routed on device, never synced to host
            feeds[r.slot] = [0]
            use_prev[r.slot] = True
            self.decoded_tokens += 1
        for r in prefilling:
            n = plan.get(r.slot, 0)
            if n:                      # preempted slots idle this step
                feeds[r.slot] = r.prompt[r.pos:r.pos + n]
                self.prefill_tokens += n
        fed = [r for r in active if r.slot in feeds]
        S = max(len(f) for f in feeds.values())
        tokens = np.zeros((self.B, S), np.int32)
        # meta rows: pos / lens / use_prev / emits-generated / reset-done
        meta = np.zeros((5, self.B), np.int32)
        meta[2] = use_prev
        for r in fed:
            f = feeds[r.slot]
            tokens[r.slot, :len(f)] = f
            meta[0, r.slot] = r.pos
            meta[1, r.slot] = len(f)
            meta[3, r.slot] = r.pos + len(f) >= len(r.prompt)
        for i in self._fresh_slots:
            meta[4, i] = 1
        self._fresh_slots.clear()
        args = (self.params,
                self.pool.buffers if self.paged else self.cache,
                self._put(tokens), self._put(meta))
        uploaded = self.paged and self._tables_dirty
        if self.paged:
            if self._tables_dirty:
                # attention (and the per-layer page gather on the XLA
                # path) costs scale with the widest ACTIVE table, not
                # max_seq — block granularity's other dividend. Bucketed
                # to multiples of 4 so the jit specializations stay few.
                nw = max((len(t) for t in self._tables), default=1)
                nw = min(self.table_width, max(-(-max(nw, 1) // 4) * 4, 4))
                tables = np.zeros((self.B, nw), np.int32)
                for r in active:
                    tab = self._tables[r.slot]
                    tables[r.slot, :len(tab)] = tab
                self._tables_dev = self._put(tables)
                self._tables_dirty = False
            args += (self._tables_dev,)
        args += (self._prev_out, self._done_dev)
        if sp is not None:
            nw = self._tables_dev.shape[1] if self.paged else 0
            sp.end(args={"S": S, "NW": nw, "tables_uploaded": uploaded})
            sp = self._span("engine.avals").begin()
        # shapes/shardings of this dispatch, captured BEFORE the call
        # (donation invalidates the KV buffers) — step_hlo() re-lowers
        # from these to expose the compiled step, collectives included
        self._last_step_avals = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=getattr(a, "sharding", None)),
            args)
        if sp is not None:
            sp.end()
            launch = {"step": self.steps, "S": S, "NW": nw, "fed": len(fed),
                      "decoding": len(decoding)}
            if self.paged and use_flash_decode(self.cfg):
                # table rows the paged kernel walks (the XLA fallback
                # gathers them all): each slot's through its last query
                launch["kv_rows"] = int(rows_walked(
                    meta[0] + S - 1, self.store.block_tokens, nw).sum())
            sp = self._span("engine.launch", launch).begin()
        out_tok, new_kv, self._done_dev = self._step(*args)
        if sp is not None:
            sp.end()
        if self.paged:
            self.pool.buffers = new_kv
        else:
            self.cache = new_kv
        self._prev_out = out_tok
        self.steps += 1
        # prefill attention reads this step: a prompt chunk of ``lens``
        # tokens attends over a context ending at pos + lens, so late
        # chunks of a long prompt are the expensive ones (decode-side
        # attention is memory-bound and folded into per_token)
        pre = (meta[2] == 0) & (meta[1] > 0)
        attn_pairs = int((meta[1] * (meta[0] + meta[1]) * pre).sum())
        self.now += float(self.clock(int(meta[1].sum()) - len(decoding),
                                     len(decoding), attn_pairs))
        stall = getattr(self.store, "pending_stall", 0.0)
        if stall:
            # slow promotions this step (injected disk stalls) charge the
            # virtual clock once, after the step's compute charge
            self.now += stall
            self.store.pending_stall = 0.0
        if trace is not None:
            trace.vt = self.now
            trace.counter("engine", pid, {
                "queue": len(self.queue),
                "active_slots": sum(s is not None for s in self.slots),
                "pool_blocks_in_use": self.pool.blocks_in_use,
                "store_used_bytes": self.store.used})

        finished: List[Request] = []
        for r in fed:
            r.pos += len(feeds[r.slot])
            in_decode = r.pos >= len(r.prompt)
            if in_decode:
                r.n_generated += 1
                r._lazy_out.append(out_tok)
                if r.n_generated == 1:
                    r.first_token_at = self.now
                    if trace is not None:
                        trace.async_instant(
                            "req", self._aid(r), "request", pid, _TID_REQ,
                            args={"event": "first_token"})
            if r.pos == len(r.prompt):
                if trace is None:
                    self._publish(r)
                else:
                    with self._span("engine.publish", {"rid": r.rid}):
                        self._publish(r)
            if in_decode and r.n_generated >= r.max_new:
                self._finish(r)
                finished.append(r)
        if self.eos_id >= 0 and decoding \
                and self.steps % self.eos_interval == 0:
            # device-side EOS detection: one (B,) bool sync per interval
            # instead of the whole token vector every step. A slot that
            # hit EOS between checks decoded a few garbage tokens past it
            # — _finish truncates them — in exchange for pipelined steps.
            if trace is None:
                done = np.asarray(jax.device_get(self._done_dev))
            else:
                with self._span("engine.eos_sync"):
                    done = np.asarray(jax.device_get(self._done_dev))
            self.readback_syncs += 1
            for r in decoding:
                if not r.done and done[r.slot]:
                    self._finish(r)
                    finished.append(r)
        return finished

    def _finish(self, r: Request) -> None:
        """Complete a request: drain pipelined tokens, truncate at the
        first EOS, retire the store chain, release the slot."""
        if self.trace is None:
            self._finish_request(r)
        else:
            with self._span("engine.finish", {"rid": r.rid}):
                self._finish_request(r)

    def _finish_request(self, r: Request) -> None:
        self._drain(r)
        if self.eos_id >= 0 and self.eos_id in r.generated:
            r.generated = r.generated[:r.generated.index(self.eos_id) + 1]
        r.n_generated = len(r.generated)
        r.done = True
        r.finished_at = self.now
        self.store.complete_request(r.prefix_rid)
        self._release_slot(r)
        self._trace_req_end(r)

    def _release_slot(self, r: Request) -> None:
        """Free a slot's engine-side resources *now* (finish or cancel):
        on the paged plane every block-table row drops the slot's
        reference — private tail rows return to the pool immediately,
        store-shared rows survive on the store's own reference."""
        if self.paged:
            for idx in self._tables[r.slot]:
                self.pool.free(idx)
            self._tables[r.slot] = []
            self._tables_dirty = True
        self.slots[r.slot] = None

    def _drain(self, r: Request) -> None:
        """Drain a request's pipelined token reads into ``generated`` (one
        blocking device_get for all of them — by finish time the pipeline
        has usually already computed every step)."""
        if r._lazy_out:
            if self.trace is None:
                vals = jax.device_get(r._lazy_out)
            else:
                with self._span("engine.readback",
                                {"steps": len(r._lazy_out), "rid": r.rid}):
                    vals = jax.device_get(r._lazy_out)
            r.generated.extend(int(v[r.slot]) for v in vals)
            r._lazy_out = []
            self.readback_syncs += 1

    def run(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if not self.queue and all(s is None for s in self.slots):
                return
            self.step()

    def close(self) -> None:
        """Deterministic teardown of file-backed store resources (the
        disk tier's memmap row files). Idempotent; safe on stores with
        no disk tier."""
        close = getattr(self.store, "close", None)
        if close is not None:
            close()

    def step_hlo(self) -> str:
        """Compiled-HLO text of the most recent step dispatch (re-lowered
        from its captured shapes/shardings — the donated buffers
        themselves are gone). Lets benches count the collectives a TP
        step actually issues. Requires at least one step() call."""
        if self._last_step_avals is None:
            raise RuntimeError("step_hlo() needs a prior step()")
        return self._step.lower(*self._last_step_avals).compile().as_text()

    # -------------------------------------------------------------- metrics
    def metrics(self) -> Dict[str, float]:
        m = dict(self.store.metrics())
        m.update({
            "engine_steps": self.steps,
            "prefill_tokens": self.prefill_tokens,
            "prefill_tokens_skipped": self.prefill_tokens_skipped,
            "decoded_tokens": self.decoded_tokens,
            "pool_blocks": self.pool.num_blocks,
            "pool_blocks_in_use": self.pool.blocks_in_use,
            "pool_high_water": self.pool.high_water,
            "kv_transfer_dispatches": self.transfer_dispatches,
            "readback_syncs": self.readback_syncs,
            "virtual_time": self.now,
            "rejected": self.rejected,
            "cancellations": self.cancellations,
            "host_syncs_avoided": max(self.steps - self.readback_syncs, 0),
            # per-device vs global KV bytes, split EXPLICITLY: once the
            # pool shards (tp>1) the two differ by a factor of tp, and
            # "device_kv_bytes" keeps meaning what it says — bytes ONE
            # device holds. (The gather cache only exists at tp=1.)
            "serve_tp": self.tp,
            "device_kv_bytes": self.pool.nbytes_per_device + (
                0 if self.cache is None else
                sum(leaf.nbytes for leaf in jax.tree.leaves(self.cache))),
            "kv_bytes_global": self.pool.nbytes + (
                0 if self.cache is None else
                sum(leaf.nbytes for leaf in jax.tree.leaves(self.cache))),
            "prefill_saved_frac": (
                self.prefill_tokens_skipped
                / max(self.prefill_tokens + self.prefill_tokens_skipped, 1)),
        })
        if isinstance(self.store, TieredKVStore) \
                and self.store.host_pool is not None:
            hp = self.store.host_pool
            m.update({
                "host_blocks": hp.num_blocks,
                "host_blocks_in_use": hp.blocks_in_use,
                "host_high_water": hp.high_water,
            })
            if self.store.quant is not None:
                # per-tier occupancy in BYTES + the transcode economics:
                # how many blocks one host byte buys vs the lossless tier
                m.update({
                    "kv_quant": self.store.quant.name,
                    "host_block_nbytes": hp.block_nbytes,
                    "host_bytes_in_use": hp.bytes_in_use,
                    "host_compression_ratio": (
                        self.pool.block_nbytes / max(hp.block_nbytes, 1)),
                })
            dp = self.store.disk_pool
            if dp is not None:
                m.update({
                    "disk_blocks": dp.num_blocks,
                    "disk_blocks_in_use": dp.blocks_in_use,
                    "disk_high_water": dp.high_water,
                    "disk_block_nbytes": dp.block_nbytes,
                    "disk_bytes_in_use": dp.bytes_in_use,
                })
        return m
