"""Serving entry point: continuous batching + LERC prefix cache.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --smoke \
      --requests 12 --policy lerc

``--layers N`` keeps the published widths and serves the first N layers,
the depth that fits one chip (qwen2_7b: 14 on a 16 GB v5e).

With ``--arrival`` the run goes through the always-on front door instead
of the batch loop: requests arrive on a timed trace (Poisson / bursty /
diurnal, seeded), the chosen ``--scheduler`` divides each step's prefill
work against decode latency, per-request TTFT deadlines come from
``--deadline-ms``, and the report adds TTFT/TPOT percentiles and
goodput-under-deadline on the deterministic virtual clock:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --smoke \
      --scheduler budgeted --prefill-budget 16 --arrival poisson \
      --arrival-rate 2.0 --deadline-ms 8 --max-queue 64
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from typing import List, Optional, Union

import jax
import numpy as np

from .. import configs
from ..core import POLICIES
from ..faults import FaultInjector, FaultPlan
from ..models import init_params, model_spec
from ..models.common import ModelConfig
from ..obs import TraceRecorder, jsonable
from ..serve import (BudgetedScheduler, PrefixStore, ServeEngine,
                     ShardedFrontend, TieredKVStore, TracedRequest,
                     latency_stats, play_trace)
from ..sharding import serve_tp_context
from ..sim import bursty_arrivals, diurnal_arrivals, poisson_arrivals
from .compile_cache import use_compile_cache

_ARRIVALS = {"poisson": poisson_arrivals, "bursty": bursty_arrivals,
             "diurnal": diurnal_arrivals}


@dataclasses.dataclass
class Server:
    """Everything ``build_server`` makes from a command line: the parsed
    arguments, the (depth-cut) model config, the engine or sharded
    frontend, the seeded prompts, and the fault injector and trace
    recorder when asked for."""
    args: argparse.Namespace
    cfg: ModelConfig
    engine: Union[ServeEngine, ShardedFrontend]
    prompts: List[List[int]]
    injector: Optional[FaultInjector] = None
    recorder: Optional[TraceRecorder] = None


def build_server(argv=None) -> Server:
    """Parse and validate a command line, then build the model and the
    engine it describes, with its prompts. ``serve_main`` runs what this
    returns; ``chip_smoke.py`` drives it step by step."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None, metavar="N",
                    help="serve only the first N layers of the config; "
                         "every width stays as published")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    # belady needs a future-access trace the serve path cannot provide
    ap.add_argument("--policy", default="lerc",
                    choices=sorted(p for p in POLICIES if p != "belady"))
    ap.add_argument("--cache-kb", type=int, default=512)
    ap.add_argument("--block-tokens", type=int, default=8)
    ap.add_argument("--shared-prefix", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="prompt tokens per slot per engine step")
    ap.add_argument("--paged-attention", dest="paged", action="store_true",
                    default=None,
                    help="decode straight out of the KV pool via per-slot "
                         "block tables: hits are host-side table writes, "
                         "publish transfers row ownership, no per-slot "
                         "contiguous KV cache (default: on for uniform "
                         "global-attention patterns)")
    ap.add_argument("--no-paged-attention", dest="paged",
                    action="store_false",
                    help="force the PR 2 gather/scatter data plane")
    ap.add_argument("--pool-blocks", type=int, default=None,
                    help="device KV pool size in blocks "
                         "(default: sized to --cache-kb)")
    ap.add_argument("--host-cache-kb", type=int, default=0,
                    help="host-memory KV tier per engine: device-pressure "
                         "evictions demote blocks here and prefix hits "
                         "promote them back instead of recomputing "
                         "(0 disables the tier; split across --shards)")
    ap.add_argument("--kv-quant", default="none",
                    choices=["none", "int8", "fp8"],
                    help="transcode demoted KV blocks to this format "
                         "(per-layer-per-block f32 scales): the host/disk "
                         "byte budgets then hold ~2-4x more blocks; "
                         "promotion dequantizes on device. 'none' keeps "
                         "every path bit-identical to the lossless tier")
    ap.add_argument("--disk-cache-mb", type=int, default=0,
                    help="disk KV tier per engine (np.memmap row files): "
                         "host-tier evictions demote here instead of "
                         "dying, and lookups promote disk-resident chains "
                         "back to the device pool (0 disables; needs "
                         "--host-cache-kb > 0; split across --shards)")
    ap.add_argument("--disk-dir", default=None,
                    help="directory for the disk tier's memmap files "
                         "(default: a TemporaryDirectory per engine)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor parallelism: shard every KV pool leaf "
                         "(and the paged attention reading it) over a "
                         "1-D model mesh of N devices; block tables and "
                         "the whole store stay host-global. Paged plane "
                         "only. CPU recipe: XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N")
    ap.add_argument("--shards", type=int, default=1,
                    help="cache shards: >1 runs a ShardedFrontend of "
                         "independent engines on the coordination plane, "
                         "splitting --cache-kb across shards")
    ap.add_argument("--scheduler", default="fcfs",
                    choices=["fcfs", "decode-first", "budgeted"],
                    help="step scheduler: fcfs (full-chunk prefill for "
                         "every slot), decode-first (prefill only on "
                         "decode-idle steps), budgeted (earliest-deadline-"
                         "first prefill under --prefill-budget)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="max prompt tokens per step for the budgeted "
                         "scheduler (None = uncapped, 0 = decode-first)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request TTFT deadline on the virtual clock "
                         "(None = best-effort; goodput counts completions)")
    ap.add_argument("--arrival", default=None,
                    choices=sorted(_ARRIVALS),
                    help="drive requests through the timed front door "
                         "with this arrival process instead of the "
                         "batch submit-then-run loop")
    ap.add_argument("--arrival-rate", type=float, default=2.0,
                    help="mean arrivals per virtual time unit")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission-control queue bound (per shard); "
                         "arrivals past it are shed with QueueFull")
    ap.add_argument("--retry-rejected", type=int, default=0,
                    help="re-submit QueueFull-shed arrivals up to N times, "
                         "waiting the engine's advertised retry-after "
                         "between attempts (retries count against goodput)")
    ap.add_argument("--fault-plan", default=None, metavar="PATH",
                    help="JSON repro.faults.FaultPlan: seeded shard "
                         "crashes, bus drop/delay/dup, disk I/O errors, "
                         "slow promotions — the run then exercises "
                         "failover, quarantine and resync deterministically")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="override the fault plan's seed (same plan, "
                         "different draw sequence)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a Chrome/Perfetto trace of the whole run "
                         "(engine step phases, scheduler decisions, "
                         "request lifecycles, store tier moves, bus "
                         "messages) and write trace-event JSON here; "
                         "render reports with benchmarks/trace_report.py")
    ap.add_argument("--trace-limit", type=int, default=200_000,
                    help="trace ring-buffer size in events (oldest drop)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="dump the final metrics dict (plus the run args) "
                         "as JSON")
    args = ap.parse_args(argv)

    # flag cross-validation up front — a bad combination must die with an
    # actionable message before any model weights are initialised, not
    # half-way through a run (or worse, be silently "repaired")
    if args.disk_cache_mb > 0 and args.host_cache_kb <= 0:
        ap.error("--disk-cache-mb needs --host-cache-kb > 0: blocks demote "
                 "device -> host -> disk, so a disk tier without a host "
                 "tier is unreachable. Add --host-cache-kb.")
    if args.disk_dir is not None and args.disk_cache_mb <= 0:
        ap.error("--disk-dir has no effect without --disk-cache-mb > 0 "
                 "(there is no disk tier to place there)")
    if args.kv_quant != "none" and args.host_cache_kb <= 0:
        ap.error(f"--kv-quant {args.kv_quant} transcodes blocks demoted to "
                 "the host/disk tiers, which --host-cache-kb 0 disables. "
                 "Add --host-cache-kb or drop --kv-quant.")
    if args.prefill_budget is not None and args.scheduler != "budgeted":
        ap.error(f"--prefill-budget only applies to --scheduler budgeted "
                 f"(got --scheduler {args.scheduler})")
    if args.tp > 1 and args.paged is False:
        ap.error("--tp > 1 shards the paged KV pool; it cannot run on the "
                 "gather plane forced by --no-paged-attention")
    if args.fault_seed is not None and args.fault_plan is None:
        ap.error("--fault-seed overrides a plan's seed; pass --fault-plan")
    if args.layers is not None and args.layers < 1:
        ap.error(f"--layers must be at least 1 (got {args.layers})")
    injector = None
    if args.fault_plan is not None:
        try:
            plan = FaultPlan.from_json(args.fault_plan)
        except (OSError, ValueError, TypeError) as e:
            ap.error(f"--fault-plan {args.fault_plan}: {e}")
        if args.fault_seed is not None:
            plan = dataclasses.replace(plan, seed=args.fault_seed)
        for _, k in plan.shard_crashes:
            if not 0 <= k < args.shards:
                ap.error(f"fault plan crashes shard {k} but --shards is "
                         f"{args.shards} (valid: 0..{args.shards - 1})")
        injector = plan.injector()

    use_compile_cache()
    cfg = configs.get(args.arch, smoke=args.smoke)
    if args.layers is not None:
        cfg = cfg.replace(n_layers=args.layers)
    # under --tp every device holds the whole model: each leaf is made
    # straight into the replicated placement, never first on device 0
    kv_shard = serve_tp_context(args.tp) if args.tp > 1 else None
    if kv_shard is not None:
        kv_shard.validate(cfg)      # before, not after, the weights exist
    params = init_params(
        jax.random.key(args.seed), model_spec(cfg), dtype=cfg.dtype,
        sharding=None if kv_shard is None else kv_shard.replicated())
    host_bytes = args.host_cache_kb * 1024
    disk_bytes = args.disk_cache_mb * 1024 * 1024
    absolute_kv = set(cfg.layer_pattern) <= {"G", "M"}
    if args.paged is None:
        # zero-copy paged attention is the default wherever the KV layout
        # supports it (absolute positions); the engine itself falls back
        # to the gather plane — with a warning — if asked for more
        args.paged = absolute_kv
    if args.prefill_chunk > 1 and not absolute_kv:
        print(f"warning: pattern {cfg.layer_pattern!r} has rolling/"
              "recurrent layers; clamping --prefill-chunk to 1",
              file=sys.stderr)
        args.prefill_chunk = 1
    # schedulers are stateless policy objects — one instance is safely
    # shared by every shard
    scheduler = (BudgetedScheduler(args.prefill_budget)
                 if args.scheduler == "budgeted" else args.scheduler)
    if args.shards > 1:
        eng = ShardedFrontend(
            cfg, params, args.shards, max_slots=args.slots,
            max_seq=args.max_seq,
            capacity_bytes=max(args.cache_kb * 1024 // args.shards, 1),
            policy=args.policy, block_tokens=args.block_tokens,
            prefill_chunk=args.prefill_chunk, pool_blocks=args.pool_blocks,
            host_capacity_bytes=host_bytes // args.shards,
            kv_quant=args.kv_quant,
            disk_capacity_bytes=disk_bytes // args.shards,
            disk_dir=args.disk_dir,
            paged=args.paged, scheduler=scheduler,
            max_queue=args.max_queue, tp=args.tp, faults=injector)
    else:
        if host_bytes > 0:
            store: PrefixStore = TieredKVStore(
                capacity_bytes=args.cache_kb * 1024, policy=args.policy,
                block_tokens=args.block_tokens,
                host_capacity_bytes=host_bytes,
                kv_quant=args.kv_quant,
                disk_capacity_bytes=disk_bytes,
                disk_dir=args.disk_dir)
            # disk-error / slow-promotion injection: attach before the
            # engine wires the pools so the disk pool inherits the injector
            store.faults = injector
        else:
            store = PrefixStore(capacity_bytes=args.cache_kb * 1024,
                                policy=args.policy,
                                block_tokens=args.block_tokens)
        eng = ServeEngine(cfg, params, max_slots=args.slots,
                          max_seq=args.max_seq, store=store,
                          prefill_chunk=args.prefill_chunk,
                          pool_blocks=args.pool_blocks, paged=args.paged,
                          scheduler=scheduler, max_queue=args.max_queue,
                          kv_shard=kv_shard)

    recorder = None
    if args.trace is not None:
        recorder = TraceRecorder(limit=args.trace_limit)
        eng.attach_trace(recorder)

    if host_bytes > 0:
        # a host budget below one KV block (per shard) sizes the pool to
        # zero rows, silently disabling the tier — say so up front
        engines = eng.shards if args.shards > 1 else [eng]
        if any(getattr(e.store, "host_pool", None) is None
               or e.store.host_pool.num_blocks == 0 for e in engines):
            print(f"warning: --host-cache-kb {args.host_cache_kb} is below "
                  f"one KV block per {'shard' if args.shards > 1 else 'engine'}"
                  f" ({engines[0].pool.block_nbytes} B); host tier disabled",
                  file=sys.stderr)

    rng = np.random.default_rng(args.seed)
    n_families = max(args.requests // 4, 1)
    prefixes = [list(rng.integers(0, cfg.vocab, args.shared_prefix))
                for _ in range(n_families)]
    prompts = [prefixes[i % n_families]
               + list(rng.integers(0, cfg.vocab, 8))
               for i in range(args.requests)]
    return Server(args, cfg, eng, prompts, injector, recorder)


def serve_main(argv=None) -> int:
    srv = build_server(argv)
    args, cfg, eng = srv.args, srv.cfg, srv.engine
    injector, recorder = srv.injector, srv.recorder
    t0 = time.time()
    report = None
    if args.arrival is not None:
        times = _ARRIVALS[args.arrival](args.requests, args.arrival_rate,
                                        args.seed)
        trace = [TracedRequest(t=t, prompt=p, max_new=args.max_new,
                               deadline=args.deadline_ms)
                 for t, p in zip(times, srv.prompts)]
        report = play_trace(eng, trace, retry_rejected=args.retry_rejected)
    else:
        for p in srv.prompts:
            eng.submit(p, max_new=args.max_new)
        eng.run()
    if args.shards > 1:
        if injector is not None:
            # lossy status traffic leaves replicas behind by design; the
            # anti-entropy resync is the documented repair before verify
            eng.resync_replicas()
        eng.verify_replicas()       # smoke doubles as a coherence proof
    m = eng.metrics()
    if report is not None:
        m.update(latency_stats(report))
    if injector is not None:
        for name in sorted(injector.counters):
            m[name] = injector.counters[name]
    paged_on = (all(e.paged for e in eng.shards) if args.shards > 1
                else eng.paged)
    full_layers = configs.get(args.arch, smoke=args.smoke).n_layers
    print(f"arch={cfg.arch}  layers={cfg.n_layers}/{full_layers}  "
          f"policy={args.policy}  shards={args.shards}  tp={args.tp}  "
          f"paged={'on' if paged_on else 'off'}  "
          f"scheduler={args.scheduler}"
          + (f"  arrival={args.arrival}@{args.arrival_rate}"
             if args.arrival else "")
          + f"  host_cache_kb={args.host_cache_kb}  "
          f"kv_quant={args.kv_quant}  disk_cache_mb={args.disk_cache_mb}  "
          f"wall={time.time()-t0:.1f}s")
    for k, v in m.items():
        print(f"  {k:26s} {v:.3f}" if isinstance(v, float)
              else f"  {k:26s} {v}")
    if recorder is not None:
        recorder.export(args.trace)
        print(f"trace: {args.trace}  events={len(recorder.events)}"
              f"  emitted={recorder.n_emitted}"
              f"  dropped={recorder.n_dropped}")
    if args.metrics_json is not None:
        with open(args.metrics_json, "w") as f:
            json.dump(jsonable({"args": vars(args), "metrics": m}),
                      f, indent=2)
        print(f"metrics: {args.metrics_json}")
    close = getattr(eng, "close", None)
    if close is not None:
        close()       # deterministic disk-tier teardown (memmaps + files)
    return 0


if __name__ == "__main__":
    sys.exit(serve_main())
