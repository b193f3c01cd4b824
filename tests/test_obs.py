"""Observability PR tests.

The obs contract, each clause with its own test below:

* **zero-overhead-when-off** — an engine (paged / tiered / sharded /
  tensor-parallel) with no recorder attached is *bit-identical* to one
  that was never instrumented: same tokens, same eviction logs, same
  metrics dicts;
* **attribution conservation** — ``sum(ineffective_by_cause.values())
  == hits - effective_hits`` structurally, under any interleaving of
  ``record_access`` and ``merge``, and on real store/sim runs;
* **field-derived aggregation** — ``CacheMetrics``/``MessageStats``
  ``merge``/``as_dict`` cover *every* dataclass field (the
  hand-maintained copies they replaced silently dropped new counters);
* **exact size cache** — the bus's shape-keyed payload size cache
  changes no byte counter vs. pickling every payload from scratch, and
  stats level ``"counts"`` zeroes bytes without touching counts;
* **trace-as-source-of-truth** — ``benchmarks.trace_report``
  reconstructs ``latency_stats`` from the trace file alone, key-for-key.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from benchmarks.trace_report import check as trace_check
from benchmarks.trace_report import (ineffective_causes, latency_from_trace,
                                     tier_flows)
from repro import configs
from repro.core import CacheMetrics, MessageStats, build_cluster
from repro.core.coordination import Message, MessageBus, payload_nbytes
from repro.models import init_params, model_spec
from repro.models.common import ModelConfig
from repro.obs import TraceRecorder
from repro.serve import (BudgetedScheduler, PrefixStore, ServeEngine,
                         ShardedFrontend, TieredKVStore, TracedRequest,
                         latency_stats, play_trace)
from repro.sharding import serve_tp_context

BT = 8          # block_tokens
PROMPT = 32     # uniform prompt length (4 blocks)
MAX_NEW = 4


# ---------------------------------------------------------------------------
# metrics dataclasses: field-derived merge / as_dict (satellite 1)
# ---------------------------------------------------------------------------
def _fill(obj, base, step=7):
    """Distinct value per field so any dropped/crossed field is caught."""
    v = base
    for f in dataclasses.fields(obj):
        if isinstance(getattr(obj, f.name), dict):
            setattr(obj, f.name, {"a": v, "b": v + 1})
        else:
            setattr(obj, f.name, v)
        v += step
    return obj


@pytest.mark.parametrize("cls", [CacheMetrics, MessageStats])
def test_merge_covers_every_field(cls):
    a, b = _fill(cls(), 1), _fill(cls(), 1000, step=13)
    for f in dataclasses.fields(b):        # asymmetric dict keys too
        if isinstance(getattr(b, f.name), dict):
            setattr(b, f.name, {"b": 2, "c": 5})
    snap_a, snap_b = dataclasses.asdict(a), dataclasses.asdict(b)
    m = a.merge(b)
    for f in dataclasses.fields(cls):
        va, vb, vm = getattr(a, f.name), getattr(b, f.name), getattr(m, f.name)
        if isinstance(va, dict):
            assert vm == {k: va.get(k, 0) + vb.get(k, 0)
                          for k in set(va) | set(vb)}, f.name
            vm["mutate"] = 1               # merged dicts are fresh objects
            assert "mutate" not in va and "mutate" not in vb
        else:
            assert vm == va + vb, f.name
    # merge never mutates its operands
    assert dataclasses.asdict(a) == snap_a
    assert dataclasses.asdict(b) == snap_b


@pytest.mark.parametrize("cls", [CacheMetrics, MessageStats])
def test_as_dict_covers_every_field(cls):
    obj = _fill(cls(), 3)
    d = obj.as_dict()
    for f in dataclasses.fields(cls):
        assert d[f.name] == getattr(obj, f.name), f.name
    # dict-valued fields are copied, not aliased
    for f in dataclasses.fields(cls):
        if isinstance(getattr(obj, f.name), dict):
            d[f.name]["mutate"] = 1
            assert "mutate" not in getattr(obj, f.name)
    if cls is CacheMetrics:
        assert d["hit_ratio"] == obj.hit_ratio
        assert d["effective_hit_ratio"] == obj.effective_hit_ratio


# ---------------------------------------------------------------------------
# effective-hit attribution (tentpole analytic)
# ---------------------------------------------------------------------------
def test_record_access_attribution_conserves():
    """Every ineffective hit lands in exactly one bucket — randomized
    interleavings plus a merge cannot break the conservation law."""
    causes = ["evicted", "host", "disk", "never_cached", None]
    rng = np.random.default_rng(0)
    parts = []
    for seed in range(3):
        m = CacheMetrics()
        for _ in range(200):
            hit = bool(rng.integers(2))
            eff = hit and bool(rng.integers(2))
            m.record_access(hit, eff, cause=None if eff or not hit
                            else causes[int(rng.integers(len(causes)))])
        m.check_attribution()
        assert sum(m.ineffective_by_cause.values()) == \
            m.hits - m.effective_hits
        parts.append(m)
    merged = parts[0].merge(parts[1]).merge(parts[2])
    merged.check_attribution()
    assert "unattributed" in merged.ineffective_by_cause


def test_record_access_rejects_impossible_combinations():
    with pytest.raises(ValueError):
        CacheMetrics().record_access(hit=False, effective=True)
    with pytest.raises(ValueError):
        CacheMetrics().record_access(hit=True, effective=True, tier=1)
    # an effective hit never grows a cause bucket, even if one is passed
    m = CacheMetrics()
    m.record_access(hit=True, effective=True, cause="evicted")
    assert m.ineffective_by_cause == {}
    m.check_attribution()


def test_check_attribution_catches_drift():
    m = CacheMetrics()
    m.record_access(hit=True, effective=False, cause="evicted")
    m.check_attribution()
    m.ineffective_by_cause["evicted"] += 1
    with pytest.raises(AssertionError):
        m.check_attribution()


# ---------------------------------------------------------------------------
# bus payload sizing: exact shape cache + stats levels (satellite 2)
# ---------------------------------------------------------------------------
def test_bus_size_cache_is_exact():
    """Byte counters with the shape cache == pickling every payload from
    scratch, across cache hits, magnitude-class edges, and every bail-out
    path (wide ints, long tuples, nesting, identity-duplicate strings)."""
    bus = MessageBus(record_log=True)
    bus.register("sink", lambda m: None)
    dup = "same-object"
    payloads = [
        ("evicted", "b1"), ("evicted", "b2"),        # cached shape, reused
        ("evicted", "a-much-longer-block-name"),     # different byte length
        ("hit", "b1"), ("é", "b1"),                  # utf-8 len != str len
        (0, 255), (256, 65535),                      # BININT1 / BININT2
        (65536, -1), (-2 ** 31, 2 ** 31 - 1),        # BININT edges
        (2 ** 40, 3), (-(2 ** 33),),                 # beyond int32 -> bail
        (1.5, -2.75), (True, False), (None,),
        ("k", 1, 2.0, None),                         # 4-tuple, mixed
        ("k", 1, 2.0, None, True),                   # 5-tuple -> bail
        (("nested",), "x"),                          # nested -> bail
        (dup, dup),                                  # pickle memo -> bail
        ("aa", "ab"),                                # same shape as ("hit",..)?
    ]
    for p in payloads:
        bus.send(Message("status", p, src="t", dst="sink"))
    assert bus._size_cache, "no payload shape ever hit the cache"
    for m in bus.log:
        assert m.nbytes == payload_nbytes(m.payload), m.payload
    assert bus.stats.payload_bytes == \
        sum(payload_nbytes(m.payload) for m in bus.log)


def _drive_cluster(stats_level):
    """Real protocol traffic: a job submit (peer-profile broadcast),
    status relays, and an eviction report/broadcast round-trip."""
    from repro.core import BlockMeta, JobDAG, TaskSpec

    master, workers, bus = build_cluster(2, record_log=False,
                                         stats_level=stats_level)
    job = JobDAG()
    for i in range(4):
        job.add_block(BlockMeta(id=f"b{i}", size=10, dataset="d", index=i))
    job.add_block(BlockMeta(id="out", size=10, dataset="d", index=9))
    job.add_task(TaskSpec(id="t0", inputs=("b0", "b1", "b2", "b3"),
                          output="out", job="j"))
    master.submit_job(job)
    for i in range(4):
        workers[0].report_status("materialized", f"b{i}")
    workers[0].local_eviction("b0")
    return bus.stats


def test_stats_level_counts_zeroes_bytes_only():
    full, counts = _drive_cluster("full"), _drive_cluster("counts")
    assert full.payload_bytes > 0 and full.lerc_bytes > 0
    assert counts.payload_bytes == 0 and counts.lerc_bytes == 0
    for f in dataclasses.fields(MessageStats):
        if f.name not in ("payload_bytes", "lerc_bytes"):
            assert getattr(counts, f.name) == getattr(full, f.name), f.name
    with pytest.raises(ValueError):
        MessageBus(stats_level="verbose")


# ---------------------------------------------------------------------------
# TraceRecorder: ring bound, export shape, timebases
# ---------------------------------------------------------------------------
def test_trace_ring_drops_oldest_and_counts():
    tr = TraceRecorder(limit=10)
    for i in range(50):
        tr.instant(f"e{i}", "t", 0, 0)
    assert len(tr.events) == 10
    assert tr.n_emitted == 50 and tr.n_dropped == 40
    names = [e["name"] for e in tr.export()["traceEvents"]
             if e["ph"] != "M"]
    assert names == [f"e{i}" for i in range(40, 50)]


def test_export_shape_and_timebases():
    tr = TraceRecorder()
    tr.label(0, "proc", tid=2)          # tid 2 -> "store" lane name
    tr.vt = 2.0
    tr.instant("a", "c", 0, 2, args={"k": (1, 2)})
    with tr.span("s", "c", 0, 2):
        pass
    tr.begin_async("req", "0:1", "request", vt=1.5)
    tr.end_async("req", "0:1", "request", args={"rid": 1})
    doc = tr.export()
    json.dumps(doc)                     # strict-JSON-serializable
    evs = doc["traceEvents"]
    # metadata first, with the default lane name
    assert evs[0]["ph"] == "M" and evs[0]["args"]["name"] == "proc"
    assert {"name": "store"} == evs[1]["args"]
    by_name = {e["name"]: e for e in evs if e["ph"] not in ("M", "b", "e")}
    assert by_name["a"]["s"] == "t"               # instants are scoped
    assert by_name["a"]["args"]["k"] == [1, 2]    # jsonable'd tuple
    assert "dur" in by_name["s"]                  # X events carry dur
    asy = [e for e in evs if e["name"] == "req"]
    assert [e["ph"] for e in asy] == ["b", "e"]
    assert all(e["id"] == "0:1" for e in asy)
    # virtual timebase: ts is the embedder clock in ms -> us
    virt = tr.export(timebase="virtual")
    va = [e for e in virt["traceEvents"] if e["name"] == "a"][0]
    assert va["ts"] == pytest.approx(2.0 * 1e3)
    vb = [e for e in virt["traceEvents"] if e["ph"] == "b"][0]
    assert vb["ts"] == pytest.approx(1.5 * 1e3)   # vt= backdating
    assert virt["otherData"]["timebase"] == "virtual"
    with pytest.raises(ValueError):
        tr.export(timebase="cpu")


# ---------------------------------------------------------------------------
# tracing-off bit-identity across the serve substrates (satellite 4)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def model():
    cfg = configs.get("qwen2_7b", smoke=True)
    params = init_params(jax.random.key(0), model_spec(cfg),
                         dtype=cfg.dtype)
    return cfg, params


def workload(vocab, n_requests=8, n_families=3, seed=7):
    rng = np.random.default_rng(seed)
    prefixes = [list(rng.integers(0, vocab, PROMPT - BT))
                for _ in range(n_families)]
    return [prefixes[i % n_families]
            + list(rng.integers(0, vocab, BT)) for i in range(n_requests)]


def _block_nbytes(cfg, params):
    probe = ServeEngine(cfg, params, max_slots=2, max_seq=64,
                        store=PrefixStore(1 << 30, "lerc", block_tokens=BT),
                        pool_blocks=1, paged=True)
    return probe._block_nbytes()


def _run_mode(cfg, params, reqs, mode, recorder=None):
    blk = _block_nbytes(cfg, params)
    if mode == "sharded":
        fe = ShardedFrontend(cfg, params, 2, max_slots=2, max_seq=64,
                             capacity_bytes=blk * 5, policy="lerc",
                             block_tokens=BT, prefill_chunk=8, paged=True)
        if recorder is not None:
            fe.attach_trace(recorder)
        rs = [fe.submit(r, max_new=MAX_NEW)[1] for r in reqs]
        fe.run()
        logs = [e.store.eviction_log for e in fe.shards]
        return [r.generated for r in rs], logs, fe.metrics()
    st = (TieredKVStore(blk * 6, "lerc", block_tokens=BT,
                        host_capacity_bytes=blk * 64)
          if mode == "tiered"
          else PrefixStore(blk * 10, "lerc", block_tokens=BT))
    eng = ServeEngine(cfg, params, max_slots=2, max_seq=64, store=st,
                      prefill_chunk=8, paged=True)
    if recorder is not None:
        eng.attach_trace(recorder)
    rs = [eng.submit(r, max_new=MAX_NEW) for r in reqs]
    eng.run()
    logs = [st.eviction_log]
    if mode == "tiered":
        logs.append(st.host_eviction_log)
    return [r.generated for r in rs], logs, eng.metrics()


# event names every traced run of the mode must produce — doubles as a
# regression net for the instrumentation sites themselves
_EXPECT_EVENTS = {
    "paged": {"engine.submit", "engine.step", "engine.admit", "engine.plan",
              "engine.feed", "engine.avals", "engine.launch",
              "engine.publish", "engine.finish", "engine.readback",
              "store.register", "store.lookup", "store.insert",
              "store.complete", "store.evict", "req"},
    "tiered": {"engine.step", "store.lookup", "store.demote",
               "store.promote", "req"},
    "sharded": {"engine.step", "store.lookup", "req", "bus.status",
                "bus.status_report", "bus.peer_profile"},
}


@pytest.mark.parametrize("mode", ["paged", "tiered", "sharded"])
def test_tracing_off_bit_identity(model, mode):
    """The same workload with and without a recorder attached: token-
    identical generations, bit-identical eviction logs, equal metrics
    dicts. Tracing observes; it never participates."""
    cfg, params = model
    reqs = workload(cfg.vocab, n_requests=10, n_families=2, seed=3)
    base_gens, base_logs, base_m = _run_mode(cfg, params, reqs, mode)
    assert any(base_logs), "workload produced no eviction pressure"
    rec = TraceRecorder()
    gens, logs, m = _run_mode(cfg, params, reqs, mode, recorder=rec)
    assert gens == base_gens
    assert logs == base_logs
    assert m == base_m
    names = {e["name"] for e in rec.events}
    missing = _EXPECT_EVENTS[mode] - names
    assert not missing, f"instrumentation sites went dark: {missing}"


def test_detach_trace_leaves_the_engine_untraced(model):
    """An engine traced for two steps and then detached serves on exactly
    as an engine never traced, and records nothing more."""
    cfg, params = model
    reqs = workload(cfg.vocab, n_requests=10, n_families=2, seed=3)
    base = _run_mode(cfg, params, reqs, "paged")
    blk = _block_nbytes(cfg, params)
    st = PrefixStore(blk * 10, "lerc", block_tokens=BT)
    eng = ServeEngine(cfg, params, max_slots=2, max_seq=64, store=st,
                      prefill_chunk=8, paged=True)
    rec = TraceRecorder()
    eng.attach_trace(rec)
    rs = [eng.submit(r, max_new=MAX_NEW) for r in reqs]
    eng.step()
    eng.step()
    eng.detach_trace()
    assert eng.trace is None and st.trace is None
    assert eng._trace_pid == 0 and st.trace_pid == 0
    emitted = rec.n_emitted
    assert emitted > 0
    eng.run()
    assert rec.n_emitted == emitted
    assert ([r.generated for r in rs], [st.eviction_log], eng.metrics()) \
        == base


def _nested(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


@pytest.mark.parametrize("decode_kernel", ["flash", "xla"])
def test_spans_land_in_the_profiler_trace(model, tmp_path, decode_kernel):
    """With a profiler session running, the recorder's spans are written
    into the profiler's own trace, on its clock: named ``<layer>.<phase>``,
    nested as the calls nest, with the launch's shape as stats. The paged
    kernel's launches also carry the table rows it walks; the XLA
    fallback, which gathers every row, carries none."""
    from bench.spans import load_spans
    cfg, params = model
    cfg = cfg.replace(decode_kernel=decode_kernel)
    reqs = workload(cfg.vocab, n_requests=4, n_families=2, seed=5)
    blk = _block_nbytes(cfg, params)
    eng = ServeEngine(cfg, params, max_slots=2, max_seq=64,
                      store=PrefixStore(blk * 10, "lerc", block_tokens=BT),
                      prefill_chunk=8, paged=True)
    eng.attach_trace(TraceRecorder())
    jax.profiler.start_trace(str(tmp_path))
    try:
        for r in reqs:
            eng.submit(r, max_new=MAX_NEW)
        for _ in range(6):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    spans = load_spans(tmp_path)
    names = {n for n, *_ in spans}
    assert {"engine.submit", "store.register", "engine.step",
            "engine.admit", "store.lookup", "engine.plan", "engine.feed",
            "engine.avals", "engine.launch", "engine.publish",
            "store.insert"} <= names
    assert not names & {"step", "admit", "dispatch", "readback"}
    by = {n: [s for s in spans if s[0] == n] for n in names}
    for lookup in by["store.lookup"]:
        admit = [a for a in by["engine.admit"] if _nested(lookup, a)]
        assert admit, "store.lookup outside every engine.admit"
        assert any(_nested(admit[0], st) for st in by["engine.step"])
    for reg in by["store.register"]:
        assert any(_nested(reg, sub) for sub in by["engine.submit"])
    launches = by["engine.launch"]
    assert len(launches) == 6
    for n, _, _, stats in launches:
        assert stats["S"] >= 1 and stats["NW"] >= 4
        assert {"step", "fed", "decoding"} <= set(stats)
        if decode_kernel == "xla":
            assert "kv_rows" not in stats
        else:
            # at least one table row a slot, at most the whole padded table
            assert 2 <= stats["kv_rows"] <= 2 * stats["NW"]
    assert [st["step"] for *_, st in launches] == list(range(6))
    # the first step prefills a whole chunk of both admitted prompts
    assert launches[0][3]["S"] == 8 and launches[0][3]["fed"] == 2
    if decode_kernel == "flash":
        # from position 0: one table row each
        assert launches[0][3]["kv_rows"] == 2
        assert launches[-1][3]["kv_rows"] > 2


# TP runs on a dedicated config whose 4 KV heads divide the mesh (the
# default smoke config has 1 KV head). Matches the equivalence suite's
# TP_CFG so the jit cache is shared across test files.
TP_CFG = ModelConfig(arch="tp_smoke", family="dense", n_layers=2,
                     d_model=32, n_heads=8, n_kv_heads=4, d_head=8,
                     d_ff=64, vocab=256, act="swiglu", layer_pattern="G")


@pytest.fixture(scope="module")
def tp_model():
    params = init_params(jax.random.key(0), model_spec(TP_CFG),
                         dtype=TP_CFG.dtype)
    return TP_CFG, params


def _run_tp2(cfg, params, reqs, recorder=None):
    blk = _block_nbytes(cfg, params)
    st = PrefixStore(blk * 10, "lerc", block_tokens=BT)
    eng = ServeEngine(cfg, params, max_slots=2, max_seq=64, store=st,
                      prefill_chunk=8, paged=True,
                      kv_shard=serve_tp_context(2))
    if recorder is not None:
        eng.attach_trace(recorder)
    rs = [eng.submit(r, max_new=MAX_NEW) for r in reqs]
    eng.run()
    return [r.generated for r in rs], st.eviction_log, eng.metrics()


def test_tracing_off_bit_identity_tp2(tp_model):
    """Same contract on a tensor-parallel (tp=2) engine. Needs forced
    host devices — the CI TP leg runs with
    XLA_FLAGS=--xla_force_host_platform_device_count=8."""
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")
    cfg, params = tp_model
    reqs = workload(cfg.vocab)
    base = _run_tp2(cfg, params, reqs)
    rec = TraceRecorder()
    traced = _run_tp2(cfg, params, reqs, recorder=rec)
    assert traced == base
    assert rec.n_emitted > 0


# ---------------------------------------------------------------------------
# request lifecycle + trace_report reconstruction (tentpole analytics)
# ---------------------------------------------------------------------------
def test_trace_report_reconstructs_latency_stats(model):
    """The CLI's from-trace latency stats equal the live
    ``latency_stats`` key-for-key — including the shed (rejected) and
    cancelled request paths — on the deterministic virtual clock."""
    cfg, params = model
    reqs = workload(cfg.vocab, n_requests=12, seed=11)
    trace = [TracedRequest(t=0.0 if i < 6 else 0.4 * i, prompt=p,
                           max_new=MAX_NEW,
                           deadline=2.0 + 0.05 * len(p))
             for i, p in enumerate(reqs)]
    rec = TraceRecorder()
    blk = _block_nbytes(cfg, params)
    eng = ServeEngine(cfg, params, max_slots=2, max_seq=64,
                      store=PrefixStore(blk * 10, "lerc", block_tokens=BT),
                      prefill_chunk=8, paged=True, max_queue=3,
                      scheduler=BudgetedScheduler(16))
    eng.attach_trace(rec)
    report = play_trace(eng, trace)
    assert report.rejected > 0, "no arrival was shed; widen the burst"
    doc = rec.export()
    assert trace_check(doc) == []
    assert latency_from_trace(doc["traceEvents"]) == latency_stats(report)


def test_cancel_closes_request_span(model):
    cfg, params = model
    rec = TraceRecorder()
    eng = ServeEngine(cfg, params, max_slots=2, max_seq=64,
                      store=PrefixStore(1 << 30, "lerc", block_tokens=BT),
                      prefill_chunk=8, paged=True)
    eng.attach_trace(rec)
    reqs = workload(cfg.vocab, n_requests=2)
    r0 = eng.submit(reqs[0], max_new=16)
    eng.submit(reqs[1], max_new=MAX_NEW)
    for _ in range(3):
        eng.step()
    assert eng.cancel(r0)
    eng.run()
    ends = [e for e in rec.export()["traceEvents"]
            if e["ph"] == "e" and e["name"] == "req"]
    assert len(ends) == 2
    assert sorted(e["args"]["cancelled"] for e in ends) == [False, True]


def test_traced_tiered_run_attribution_and_flows(model):
    """On a demoting/promoting tiered run: the conservation law holds on
    the live metrics, the per-lookup ``ineffective`` args sum to the
    live ``ineffective_by_cause``, and the tier-flow edges extracted by
    the CLI agree with the store's move counters."""
    cfg, params = model
    reqs = workload(cfg.vocab, n_requests=10, n_families=2, seed=3)
    blk = _block_nbytes(cfg, params)
    st = TieredKVStore(blk * 6, "lerc", block_tokens=BT,
                       host_capacity_bytes=blk * 64)
    eng = ServeEngine(cfg, params, max_slots=2, max_seq=64, store=st,
                      prefill_chunk=8, paged=True)
    rec = TraceRecorder()
    eng.attach_trace(rec)
    for r in reqs:
        eng.submit(r, max_new=MAX_NEW)
    eng.run()
    m = st.metrics_obj                   # metrics() ran check_attribution
    eng.metrics()
    assert m.promotions > 0 and m.demotions > 0
    assert sum(m.ineffective_by_cause.values()) == \
        m.hits - m.effective_hits
    events = rec.export()["traceEvents"]
    assert ineffective_causes(events) == m.ineffective_by_cause
    flows = tier_flows(events)
    assert flows.get(("device", "host"), 0) == m.demotions
    assert sum(n for (s, d), n in flows.items() if d == "device") == \
        m.promotions
    # every store instant carries the policy's eviction key at decision
    # time — the forensic hook for "why did THIS block move"
    moves = [e for e in events
             if e["name"] in ("store.evict", "store.demote",
                              "store.promote")]
    assert moves and all("key" in e["args"] and "uid" in e["args"]
                         for e in moves)


# ---------------------------------------------------------------------------
# cluster sim: task spans on the virtual clock + attribution
# ---------------------------------------------------------------------------
def test_sim_trace_task_spans_and_attribution():
    from repro.sim import ClusterSim, HardwareModel, multi_tenant_zip

    rec = TraceRecorder()
    hw = HardwareModel(cache_bytes=4 * 2 ** 20, disk_bw=25e6)
    sim = ClusterSim(4, hw, policy="lerc", trace=rec)
    for dag, _ in multi_tenant_zip(n_jobs=2, n_blocks=16, file_mb=4,
                                   n_workers=4):
        sim.submit(dag)
    res = sim.run()                      # runs check_attribution
    m = res.metrics
    assert m.evictions > 0, "sim cache never under pressure"
    assert sum(m.ineffective_by_cause.values()) == \
        m.hits - m.effective_hits
    events = rec.export(timebase="virtual")["traceEvents"]
    tasks = [e for e in events if e["ph"] == "X" and e["cat"] == "task"]
    assert tasks
    # virtual-clock spans: ts/dur in us, 1 sim second = 1000 recorder ms
    ends = {e["ts"] + e["dur"] for e in tasks}
    assert max(ends) == pytest.approx(res.makespan * 1e6)
    assert any(e["name"].startswith("bus.") for e in events)
