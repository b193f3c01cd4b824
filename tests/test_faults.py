"""Deterministic fault injection + graceful degradation (robustness PR).

Contracts:

(1) an **empty FaultPlan is bit-identical to no plan at all** — tokens,
eviction logs and the full metrics dict — on every serve configuration
(paged / tiered / sharded / tp=2) and in the simulator; (2) a seeded
shard crash fails over: every admitted request finishes, surviving
requests generate token-identically to the clean run, and the rebuilt
replica reconverges through the anti-entropy resync; (3) a disk tier
failing reads quarantines after ``quarantine_after`` consecutive errors
and the run degrades to eviction + recompute with zero uncaught
exceptions; (4) slow promotions charge the virtual clock exactly, and
promotions stalled past the timeout abandon cleanly (recompute, same
tokens); (5) a sim worker crash recomputes lost blocks through the DAG
lineage with the makespan charged *exactly*; (6) ``on_lost`` /
``on_task_undone`` agree with the from-scratch ``rebuild()`` oracle."""
import os

import jax
import numpy as np
import pytest

from repro import configs
from repro.core import BlockMeta, DagState, JobDAG, TaskSpec
from repro.faults import BusFault, FaultPlan
from repro.models import init_params, model_spec
from repro.models.common import ModelConfig
from repro.serve import (PrefixStore, QueueFull, ServeEngine,
                         ShardedFrontend, TieredKVStore, TracedRequest,
                         latency_stats, play_trace)
from repro.sharding import serve_tp_context
from repro.sim import ClusterSim, HardwareModel, poisson_arrivals

BT = 8          # block_tokens
PROMPT = 40     # uniform prompt length (5 blocks: 4 prefix + 1 suffix)
MAX_NEW = 4
DEADLINE = 60.0


@pytest.fixture(scope="module")
def model():
    cfg = configs.get("qwen2_7b", smoke=True)
    params = init_params(jax.random.key(0), model_spec(cfg),
                         dtype=cfg.dtype)
    return cfg, params


def _blk(cfg, params):
    probe = ServeEngine(cfg, params, max_slots=2, max_seq=64,
                        store=PrefixStore(1 << 30, "lerc", block_tokens=BT),
                        pool_blocks=1)
    return probe._block_nbytes()


def workload(vocab, n_requests=12, n_families=4, seed=3,
             prefix_tokens=PROMPT - BT):
    rng = np.random.default_rng(seed)
    prefixes = [list(rng.integers(0, vocab, prefix_tokens))
                for _ in range(n_families)]
    return [prefixes[i % n_families]
            + list(rng.integers(0, vocab, BT)) for i in range(n_requests)]


def _timed_trace(vocab, n_requests=12, rate=1.5, seed=3):
    reqs = workload(vocab, n_requests)
    times = poisson_arrivals(n_requests, rate=rate, seed=seed)
    return [TracedRequest(t=t, prompt=p, max_new=MAX_NEW,
                          deadline=DEADLINE)
            for t, p in zip(times, reqs)]


def _by_key(requests):
    """Cross-run token comparison key. rids are per-shard counters (they
    collide across shards), so identity is (prompt, arrival)."""
    out = {}
    for r in requests:
        out[(tuple(r.prompt), r.arrival)] = list(r.generated)
    return out


# ---------------------------------------------------------------------------
# (1) empty plan == no plan, bit for bit
# ---------------------------------------------------------------------------

def test_empty_plan_bit_identity_tiered(model):
    """A tiered engine carrying an empty-plan injector is op-for-op the
    healthy engine: tokens, all three eviction logs, full metrics dict."""
    cfg, params = model
    blk = _blk(cfg, params)
    reqs = workload(cfg.vocab)

    def run(injector):
        store = TieredKVStore(6 * blk, "lerc", block_tokens=BT,
                              host_capacity_bytes=3 * blk,
                              disk_capacity_bytes=64 * blk)
        store.faults = injector
        eng = ServeEngine(cfg, params, max_slots=1, max_seq=64,
                          store=store, prefill_chunk=BT)
        rs = [eng.submit(r, max_new=MAX_NEW) for r in reqs]
        eng.run()
        m = eng.metrics()
        eng.close()
        return [r.generated for r in rs], store, m

    base_toks, base_st, base_m = run(None)
    toks, st, m = run(FaultPlan().injector())
    assert base_st.evictions > 0, "workload produced no pressure"
    assert toks == base_toks
    assert st.eviction_log == base_st.eviction_log
    assert st.host_eviction_log == base_st.host_eviction_log
    assert st.disk_eviction_log == base_st.disk_eviction_log
    assert m == base_m


def test_empty_plan_bit_identity_sharded(model):
    """A 2-shard frontend built over FaultPlan() replays a timed trace —
    through the same ``play_trace`` dispatch a faulted run would take the
    door of — bit-identically to faults=None: tokens, latency stats, the
    full metrics dict (all fault counters present and zero)."""
    cfg, params = model
    blk = _blk(cfg, params)
    trace = _timed_trace(cfg.vocab)

    def run(faults):
        fe = ShardedFrontend(cfg, params, 2, max_slots=2, max_seq=64,
                             capacity_bytes=10 * blk, policy="lerc",
                             block_tokens=BT, prefill_chunk=BT,
                             max_queue=64, faults=faults)
        report = play_trace(fe, trace)
        stats = latency_stats(report)
        fe.verify_replicas()
        m = fe.metrics()
        fe.close()
        return _by_key(report.requests), stats, m

    base = run(None)
    empty = run(FaultPlan())
    assert empty == base
    m = empty[2]
    assert m["shard_crashes"] == 0 and m["failover_retries"] == 0
    assert m["msg_dropped"] == 0 and m["msg_resyncs"] == 0


def test_empty_plan_bit_identity_paged(model):
    """Same identity on the paged data plane (batch loop, 2 shards)."""
    cfg, params = model
    blk = _blk(cfg, params)
    reqs = workload(cfg.vocab)

    def run(faults):
        fe = ShardedFrontend(cfg, params, 2, max_slots=1, max_seq=64,
                             capacity_bytes=10 * blk, policy="lerc",
                             block_tokens=BT, paged=True,
                             record_eviction_log=True, faults=faults)
        rs = [fe.submit(r, max_new=MAX_NEW)[1] for r in reqs]
        fe.run()
        fe.verify_replicas()
        logs = [eng.store.eviction_log for eng in fe.shards]
        m = fe.metrics()
        fe.close()
        return [r.generated for r in rs], logs, m

    assert run(FaultPlan()) == run(None)


TP_CFG = ModelConfig(arch="tp_smoke", family="dense", n_layers=2,
                     d_model=32, n_heads=8, n_kv_heads=4, d_head=8,
                     d_ff=64, vocab=256, act="swiglu", layer_pattern="G")


def test_empty_plan_bit_identity_tp2():
    """Same identity on a tp=2 paged engine over a tiered store (the
    injector rides the store). Needs forced host devices — the CI TP leg
    runs with XLA_FLAGS=--xla_force_host_platform_device_count=8."""
    if jax.device_count() < 2:
        pytest.skip("needs >=2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    cfg = TP_CFG
    params = init_params(jax.random.key(0), model_spec(cfg),
                        dtype=cfg.dtype)
    blk = _blk(cfg, params)
    reqs = workload(cfg.vocab, n_requests=10, n_families=2, seed=5)

    def run(injector):
        store = TieredKVStore(6 * blk, "lerc", block_tokens=BT,
                              host_capacity_bytes=64 * blk)
        store.faults = injector
        eng = ServeEngine(cfg, params, max_slots=2, max_seq=64,
                          store=store, prefill_chunk=BT, paged=True,
                          kv_shard=serve_tp_context(2))
        rs = [eng.submit(r, max_new=MAX_NEW) for r in reqs]
        eng.run()
        return ([r.generated for r in rs], store.eviction_log,
                store.host_eviction_log, eng.metrics())

    assert run(FaultPlan().injector()) == run(None)


# ---------------------------------------------------------------------------
# (2) shard-crash failover
# ---------------------------------------------------------------------------

def test_shard_crash_failover(model):
    """Kill shard 0 mid-trace under a lossy status channel: the crash
    fires exactly once, every admitted request still finishes, every
    request generates token-identically to the clean run (failover
    re-prefills, it does not re-sample), and after the anti-entropy
    resync the rebuilt replica passes the bit-identity proof."""
    cfg, params = model
    blk = _blk(cfg, params)
    trace = _timed_trace(cfg.vocab)
    plan = FaultPlan(seed=7, shard_crashes=((5.0, 0),),
                     bus_faults=(BusFault(channel="status", drop_p=0.2),))

    def run(faults):
        fe = ShardedFrontend(cfg, params, 2, max_slots=2, max_seq=64,
                             capacity_bytes=48 * blk, policy="lerc",
                             block_tokens=BT, prefill_chunk=BT,
                             max_queue=64, faults=faults)
        report = play_trace(fe, trace)
        return fe, report

    clean_fe, clean_report = run(None)
    clean_fe.verify_replicas()
    clean_fe.close()

    fe, report = run(plan)
    m = fe.metrics()
    assert m["shard_crashes"] == 1, "scheduled crash did not fire"
    assert fe.faults.counters.get("fault.shard_crash") == 1
    unfinished = [r for r in report.requests
                  if not r.cancelled and r.finished_at is None]
    assert not unfinished, f"failover lost {len(unfinished)} requests"
    # determinism of the surviving work: token identity keyed by
    # (prompt, arrival) — NOT rid, which collides across shards
    assert _by_key(report.requests) == _by_key(clean_report.requests)
    # retries are visible and bounded by the crash's in-flight set
    assert m["failover_retries"] >= 1
    assert m["msg_dropped"] > 0
    fe.resync_replicas()
    fe.verify_replicas()
    assert fe.metrics()["msg_resyncs"] >= 1
    fe.close()


def test_bus_drop_resync_converges(model):
    """A lossy status channel alone (no crash): replicas may diverge
    (counted, not raised), and one anti-entropy round restores the
    bit-identity proof."""
    cfg, params = model
    blk = _blk(cfg, params)
    reqs = workload(cfg.vocab)
    fe = ShardedFrontend(
        cfg, params, 2, max_slots=1, max_seq=64,
        capacity_bytes=10 * blk, policy="lerc", block_tokens=BT,
        faults=FaultPlan(seed=11, bus_faults=(
            BusFault(channel="status", drop_p=0.3),)))
    rs = [fe.submit(r, max_new=MAX_NEW)[1] for r in reqs]
    fe.run()
    assert all(r.done for r in rs)
    assert fe.bus.stats.dropped > 0, "lossy channel dropped nothing"
    fe.resync_replicas()
    fe.verify_replicas()
    assert fe.bus.stats.resyncs >= fe.n_shards
    fe.close()


# ---------------------------------------------------------------------------
# (3) disk quarantine
# ---------------------------------------------------------------------------

def test_disk_quarantine_graceful(model):
    """Every disk read fails: after ``quarantine_after`` consecutive
    errors the tier is fenced (exactly one quarantine), the run completes
    with zero uncaught exceptions, and the store degrades to two-tier
    semantics — no further disk demotions."""
    cfg, params = model
    blk = _blk(cfg, params)
    store = TieredKVStore(8 * blk, "lerc", block_tokens=BT,
                          host_capacity_bytes=3 * blk,
                          disk_capacity_bytes=64 * blk)
    store.faults = FaultPlan(disk_read_error_p=1.0,
                             quarantine_after=2).injector()
    eng = ServeEngine(cfg, params, max_slots=1, max_seq=96,
                      store=store, prefill_chunk=BT)
    rng = np.random.default_rng(5)
    prefixes = [list(rng.integers(0, cfg.vocab, 32)) for _ in range(3)]
    suffix = list(rng.integers(0, cfg.vocab, BT))
    done = 0
    for pfx in prefixes:                     # warm: demote down the rungs
        r = eng.submit(pfx + suffix, max_new=MAX_NEW)
        eng.run()
        done += r.done
    for pfx in prefixes:                     # re-reference: reads fail
        r = eng.submit(list(pfx), max_new=MAX_NEW)
        eng.run()
        done += r.done
    m = eng.metrics()
    eng.close()
    assert done == 2 * len(prefixes), "degraded engine dropped requests"
    assert m["disk_quarantines"] == 1
    assert m["disk_io_errors"] >= 2
    assert store.disk_quarantined and not store.disk_tiered


def test_disk_write_failures_count_but_reads_reset(model):
    """The consecutive-error counter resets ONLY on a successful disk
    read: a disk that still accepts demotion writes but fails every
    promote must quarantine anyway (writes landing doesn't prove the
    bytes come back)."""
    cfg, params = model
    blk = _blk(cfg, params)
    store = TieredKVStore(6 * blk, "lerc", block_tokens=BT,
                          host_capacity_bytes=2 * blk,
                          disk_capacity_bytes=64 * blk)
    store.faults = FaultPlan(disk_read_error_p=1.0,
                             quarantine_after=3).injector()
    eng = ServeEngine(cfg, params, max_slots=1, max_seq=96,
                      store=store, prefill_chunk=BT)
    rng = np.random.default_rng(9)
    prefixes = [list(rng.integers(0, cfg.vocab, 32)) for _ in range(4)]
    # interleave re-references (failed reads) with fresh warms (successful
    # writes) — the writes must NOT rescue the failing tier
    for i in range(2):
        for pfx in prefixes:
            eng.submit(pfx + [i], max_new=MAX_NEW)
            eng.run()
            eng.submit(list(pfx), max_new=MAX_NEW)
            eng.run()
    m = eng.metrics()
    eng.close()
    assert m["disk_quarantines"] == 1
    assert m["disk_demotions"] > 0, "no successful writes interleaved"


# ---------------------------------------------------------------------------
# (4) promotion stalls + timeouts
# ---------------------------------------------------------------------------

def _promotion_workload(cfg, params, blk, plan):
    store = TieredKVStore(6 * blk, "lerc", block_tokens=BT,
                          host_capacity_bytes=64 * blk)
    if plan is not None:
        store.faults = plan.injector()
    eng = ServeEngine(cfg, params, max_slots=1, max_seq=64,
                      store=store, prefill_chunk=BT)
    reqs = workload(cfg.vocab)
    rs = [eng.submit(r, max_new=MAX_NEW) for r in reqs]
    eng.run()
    return eng, store, [r.generated for r in rs]


def test_promotion_stall_charged_to_clock_exactly(model):
    """Every promotion stalls 2.0 virtual-seconds: tokens unchanged, and
    the engine clock lands exactly ``stalls * 2.0`` past the clean run's
    (the stall drains into ``now`` once per step, after compute)."""
    cfg, params = model
    blk = _blk(cfg, params)
    clean_eng, clean_st, clean_toks = _promotion_workload(
        cfg, params, blk, None)
    assert clean_st.metrics_obj.promotions > 0, "no promotion exercised"
    eng, st, toks = _promotion_workload(
        cfg, params, blk,
        FaultPlan(promotion_stall_p=1.0, promotion_stall=2.0))
    stalls = st.metrics_obj.promotion_stalls
    assert stalls > 0
    assert toks == clean_toks
    assert st.metrics_obj.promotions == clean_st.metrics_obj.promotions
    assert eng.now == pytest.approx(clean_eng.now + 2.0 * stalls)


def test_promotion_timeout_abandons_and_recomputes(model):
    """Stall (2.0) past the timeout (1.0): every promotion is abandoned
    *before* any index/payload mutation — the chain recomputes through
    prefill, tokens unchanged, and no stall is charged."""
    cfg, params = model
    blk = _blk(cfg, params)
    _, clean_st, clean_toks = _promotion_workload(cfg, params, blk, None)
    eng, st, toks = _promotion_workload(
        cfg, params, blk,
        FaultPlan(promotion_stall_p=1.0, promotion_stall=2.0,
                  promotion_timeout=1.0))
    m = st.metrics_obj
    assert m.promotion_timeouts > 0
    assert m.promotion_stalls == 0
    assert toks == clean_toks
    assert m.promotions < clean_st.metrics_obj.promotions
    assert eng.prefill_tokens > 0


# ---------------------------------------------------------------------------
# satellite: cancellation racing an in-flight promotion
# ---------------------------------------------------------------------------

def test_cancel_racing_promotion(model):
    """Cancel a request whose chain was just promoted from the host tier,
    mid-prefill: rows return to the pool, the store's pending references
    retire, and the engine keeps serving — token-identically for the
    survivors. Repeats with the promotion *abandoned* by timeout (the
    cancel then races a recompute instead)."""
    cfg, params = model
    blk = _blk(cfg, params)
    for plan in (None,
                 FaultPlan(promotion_stall_p=1.0, promotion_stall=2.0,
                           promotion_timeout=1.0)):
        store = TieredKVStore(6 * blk, "lerc", block_tokens=BT,
                              host_capacity_bytes=64 * blk)
        if plan is not None:
            store.faults = plan.injector()
        eng = ServeEngine(cfg, params, max_slots=2, max_seq=64,
                          store=store, prefill_chunk=BT)
        rng = np.random.default_rng(2)
        fam = list(rng.integers(0, cfg.vocab, PROMPT - BT))
        warm = eng.submit(fam + list(rng.integers(0, cfg.vocab, BT)),
                          max_new=MAX_NEW)
        eng.run()                      # fam's chain demotes under pressure
        for _ in range(8):             # pressure so fam leaves the device
            eng.submit(list(rng.integers(0, cfg.vocab, PROMPT)),
                       max_new=MAX_NEW)
            eng.run()
        base = store.metrics_obj.promotions + store.metrics_obj.promotion_timeouts
        victim = eng.submit(fam + list(rng.integers(0, cfg.vocab, BT)),
                            max_new=MAX_NEW)
        eng.step()                     # promotion (or its timeout) fires
        assert (store.metrics_obj.promotions
                + store.metrics_obj.promotion_timeouts) > base
        assert not victim.done
        assert eng.cancel(victim)
        assert victim.cancelled and not eng.cancel(victim)

        other = eng.submit(fam + list(rng.integers(0, cfg.vocab, BT)),
                           max_new=MAX_NEW)
        eng.run()
        assert other.done and len(other.generated) == MAX_NEW
        # no leaked rows: pool usage bounded by store-resident blocks
        resident = sum(1 for n in store._nodes.values() if n.resident)
        assert eng.pool.blocks_in_use <= resident + 1       # junk row
        assert eng.metrics()["cancellations"] == 1


# ---------------------------------------------------------------------------
# satellite: QueueFull carries depth + retry-after; retries are counted
# ---------------------------------------------------------------------------

def test_queuefull_enriched_and_retries_counted(model):
    cfg, params = model
    eng = ServeEngine(cfg, params, max_slots=1, max_seq=64,
                      store=PrefixStore(1 << 30, "lerc", block_tokens=BT),
                      max_queue=1)
    rng = np.random.default_rng(4)
    prompts = [list(rng.integers(0, cfg.vocab, PROMPT)) for _ in range(3)]
    eng.submit(prompts[0], max_new=MAX_NEW)     # queue now at max_queue=1
    with pytest.raises(QueueFull) as exc:
        eng.submit(prompts[1], max_new=MAX_NEW)
    assert exc.value.depth == 1
    assert exc.value.retry_after is not None and exc.value.retry_after > 0

    from benchmarks.trace_report import latency_from_trace
    from repro.obs import TraceRecorder
    rec = TraceRecorder()
    eng2 = ServeEngine(cfg, params, max_slots=1, max_seq=64,
                       store=PrefixStore(1 << 30, "lerc", block_tokens=BT),
                       max_queue=1)
    eng2.attach_trace(rec)
    trace = [TracedRequest(t=0.0, prompt=p, max_new=MAX_NEW)
             for p in prompts]
    report = play_trace(eng2, trace, retry_rejected=3)
    stats = latency_stats(report)
    assert report.retried > 0
    assert stats["n_retried"] == report.retried
    assert stats["n_rejected"] == 0, "retries should have absorbed the burst"
    assert len(report.requests) == len(trace)
    # trace-side reconstruction splits retried bounces (sched.retry
    # instants) from final rejections — parity with the live stats
    assert latency_from_trace(rec.export()["traceEvents"]) == stats


# ---------------------------------------------------------------------------
# satellite: launch flag validation fails fast with actionable errors
# ---------------------------------------------------------------------------

BAD_FLAG_COMBOS = [
    ["--disk-cache-mb", "16"],                  # disk rung without host tier
    ["--disk-dir", "/tmp/nope"],                # dir without a disk tier
    ["--kv-quant", "int8"],                     # transcode without a tier
    ["--prefill-budget", "16"],                 # budget without the scheduler
    ["--fault-seed", "3"],                      # seed without a plan
    ["--fault-plan", "/nonexistent/plan.json"],  # unreadable plan
    ["--tp", "2", "--no-paged-attention"],      # TP needs the paged plane
    ["--layers", "0"],                          # a model needs a layer
]


@pytest.mark.parametrize("extra", BAD_FLAG_COMBOS,
                         ids=[" ".join(c) for c in BAD_FLAG_COMBOS])
def test_launch_rejects_bad_flag_combos(extra):
    """Validation runs before any model build, so a bad combo exits 2
    in milliseconds instead of failing (or silently no-opting) minutes
    into a run."""
    from repro.launch.serve import serve_main
    argv = ["--arch", "qwen2_7b", "--smoke", "--requests", "2",
            "--slots", "1", "--max-seq", "32", "--cache-kb", "64",
            "--max-new", "2", "--policy", "lerc"] + extra
    with pytest.raises(SystemExit) as exc:
        serve_main(argv)
    assert exc.value.code == 2


def test_fault_plan_json_contract(tmp_path):
    p = tmp_path / "plan.json"
    p.write_text('{"seed": 7, "shard_crashes": [[4.0, 0]], '
                 '"bus_faults": [{"channel": "status", "drop_p": 0.2}], '
                 '"promotion_timeout": null}')
    plan = FaultPlan.from_json(str(p))
    assert plan.seed == 7 and plan.shard_crashes == ((4.0, 0),)
    assert plan.bus_faults[0].drop_p == 0.2
    assert plan.promotion_timeout == float("inf")
    assert not plan.empty
    assert FaultPlan().empty
    p.write_text('{"shard_crashez": []}')
    with pytest.raises(ValueError, match="shard_crashez"):
        FaultPlan.from_json(str(p))
    # capped exponential backoff for failover re-admission
    plan = FaultPlan(retry_backoff=0.5, retry_backoff_cap=4.0)
    assert [plan.backoff(k) for k in (1, 2, 3, 4, 5)] == \
        [0.5, 1.0, 2.0, 4.0, 4.0]


# ---------------------------------------------------------------------------
# satellite: deterministic disk-pool teardown
# ---------------------------------------------------------------------------

def test_disk_pool_close_unlinks_files(model):
    cfg, params = model
    blk = _blk(cfg, params)
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        store = TieredKVStore(6 * blk, "lerc", block_tokens=BT,
                              host_capacity_bytes=2 * blk,
                              disk_capacity_bytes=64 * blk, disk_dir=d)
        eng = ServeEngine(cfg, params, max_slots=1, max_seq=96,
                          store=store, prefill_chunk=BT)
        for r in workload(cfg.vocab, n_requests=6):
            eng.submit(r, max_new=MAX_NEW)
            eng.run()
        pool = store.disk_pool
        assert pool._paths and all(os.path.exists(p) for p in pool._paths)
        paths = list(pool._paths)
        eng.close()                    # cascades store.close -> pool.close
        assert pool.closed
        assert not any(os.path.exists(p) for p in paths)
        eng.close()                    # idempotent


# ---------------------------------------------------------------------------
# (5) simulator: crash-as-restart, lineage recompute, exact makespan
# ---------------------------------------------------------------------------

def _chain_dag(n_tasks, block_size):
    dag = JobDAG()
    dag.add_block(BlockMeta("src", block_size, "src", 0))
    prev = "src"
    for i in range(n_tasks):
        out = f"b{i}"
        dag.add_block(BlockMeta(out, block_size, "chain", i))
        dag.add_task(TaskSpec(id=f"t{i}", inputs=(prev,), output=out,
                              job="chain"))
        prev = out
    return dag


SIZE = 10 * 2 ** 20


def test_sim_empty_plan_identical():
    hw = HardwareModel(cache_bytes=8 * SIZE)
    results = []
    for faults in (None, FaultPlan()):
        sim = ClusterSim(2, hw, faults=faults)
        sim.submit(_chain_dag(6, SIZE))
        results.append(sim.run())
    base, empty = results
    assert empty.makespan == base.makespan
    assert empty.metrics.as_dict() == base.metrics.as_dict()
    assert empty.messages.as_dict() == base.messages.as_dict()
    assert empty.task_runtimes == base.task_runtimes


def test_sim_worker_crash_exact_makespan_delta():
    """One worker, a chain job, a crash at t: the restart recomputes the
    WHOLE chain from scratch (every block was on the lost worker), so the
    faulted makespan is *exactly* ``t + clean_makespan`` — the recompute
    is charged to the clock, not absorbed. Replica coherence is proven
    inside ``run`` (verify_replicas covers the crashed run too)."""
    hw = HardwareModel(cache_bytes=8 * SIZE)
    sim = ClusterSim(1, hw)
    sim.submit(_chain_dag(4, SIZE))
    clean = sim.run()

    crash_t = clean.makespan / 2
    sim_f = ClusterSim(1, hw,
                       faults=FaultPlan(worker_crashes=((crash_t, 0),)))
    sim_f.submit(_chain_dag(4, SIZE))
    fault = sim_f.run()
    assert sim_f.worker_crashes_fired == 1
    assert fault.makespan == pytest.approx(crash_t + clean.makespan)
    # the injector's recovery ledger saw the loss and the recompute
    assert sim_f.faults.counters["fault.worker_crash"] == 1
    assert sim_f.faults.counters["recover.lost_blocks"] > 0


def test_sim_crash_out_of_range_worker_never_fires():
    """A crash scheduled on a worker index outside the cluster is ignored
    (claimed once, fired never) and the run matches the clean one."""
    hw = HardwareModel(cache_bytes=8 * SIZE)
    sim = ClusterSim(1, hw)
    sim.submit(_chain_dag(4, SIZE))
    clean = sim.run()
    sim_f = ClusterSim(1, hw,
                       faults=FaultPlan(worker_crashes=((0.1, 7),)))
    sim_f.submit(_chain_dag(4, SIZE))
    fault = sim_f.run()
    assert sim_f.worker_crashes_fired == 0
    assert fault.makespan == clean.makespan


# ---------------------------------------------------------------------------
# (6) on_lost / on_task_undone vs the rebuild() oracle
# ---------------------------------------------------------------------------

def test_on_lost_matches_rebuild_oracle():
    """Drive a DagState through done/lost/undone transitions and check
    the incremental counters against a from-scratch rebuild at every
    step (the crash path leans on exactly these transitions)."""
    dag = _chain_dag(4, 1)
    state = DagState(dag)

    def check():
        oracle = DagState(dag, materialized=set(state.materialized),
                          cached=set(state.cached),
                          done_tasks=set(state.done_tasks))
        assert state.ref_count == oracle.ref_count
        assert state.eff_ref_count == oracle.eff_ref_count
        assert {t: m for t, m in state.missing.items()
                if oracle.missing.get(t) != m} == {}

    state.on_materialized("src")
    check()
    for i in range(4):
        state.on_materialized(f"b{i}")       # marks t{i} done too
        check()
    # crash loses b1 and b2: producers resurrect, consumers stop counting
    # the unmaterialized inputs as "missing"
    for b in ("b1", "b2"):
        state.on_lost(b)
        check()
    assert "t1" not in state.done_tasks and "t2" not in state.done_tasks
    # recompute them (lineage order) and reconverge
    for b in ("b1", "b2"):
        state.on_materialized(b)
        check()
    assert state.done_tasks == {f"t{i}" for i in range(4)}
