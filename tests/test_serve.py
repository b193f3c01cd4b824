"""Prefix store (LERC on KV chains) and serve-engine integration tests."""
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import configs
from repro.models import init_params, model_spec
from repro.serve import PrefixStore, ServeEngine


def _payload():
    return {"kv": np.zeros(4, np.float32)}


def test_chain_all_or_nothing():
    """A resident block below a non-resident ancestor yields no effective
    hit (the paper's property, chain form)."""
    st = PrefixStore(capacity_bytes=1 << 20, policy="lerc", block_tokens=4)
    toks = list(range(12))                      # 3 blocks
    st.insert(toks, [_payload()] * 3, nbytes_per_block=100)
    chain = st._walk(toks)
    st._evict(chain[0])                         # break the root block
    usable = st.lookup(toks)
    assert usable == []                         # nothing usable
    m = st.metrics()
    assert m["hit_ratio"] > 0                   # blocks 2,3 are plain hits
    assert m["effective_hit_ratio"] == 0        # ...but effective = 0


def test_lerc_keeps_requested_chain_under_pressure():
    """Cache full of a requested (hot) chain + an unreferenced (cold) one;
    a new insert forces one eviction. LERC sacrifices the cold chain (zero
    effective references); LRU evicts by recency and breaks the hot one."""
    def build(policy):
        st = PrefixStore(capacity_bytes=400, policy=policy, block_tokens=4)
        hot = list(range(8))                    # 2 blocks, queued requests
        cold = list(range(100, 108))            # 2 blocks, no requests
        st.insert(hot, [_payload()] * 2, nbytes_per_block=100)
        for _ in range(3):
            st.register_request(hot + [1, 2, 3, 4])
        st.insert(cold, [_payload()] * 2, nbytes_per_block=100)
        # cold touched last -> under LRU the hot chain is the LRU victim
        st.insert(list(range(200, 204)), [_payload()],
                  nbytes_per_block=100)         # forces one eviction
        return st, hot

    st, hot = build("lerc")
    assert len(st.lookup(hot)) == 2, "LERC must keep the requested chain"
    st, hot = build("lru")
    assert len(st.lookup(hot)) < 2, "LRU breaks the hot chain (recency)"


def test_lru_vs_lerc_effective_ratio():
    rng = np.random.default_rng(0)
    families = [list(rng.integers(0, 1000, 16)) for _ in range(4)]
    out = {}
    for policy in ("lru", "lrc", "lerc"):
        st = PrefixStore(capacity_bytes=900, policy=policy, block_tokens=4)
        # register a queue that reuses family prefixes
        rids = []
        reqs = []
        for i in range(12):
            fam = families[i % 4]
            req = fam + list(rng.integers(0, 1000, 4))
            reqs.append(req)
            rids.append(st.register_request(req))
        for rid, req in zip(rids, reqs):
            st.lookup(req)
            st.insert(req, [_payload()] * (len(req) // 4),
                      nbytes_per_block=60)
            st.complete_request(rid)
        out[policy] = st.metrics()["effective_hit_ratio"]
    assert out["lerc"] >= out["lru"]


def test_engine_prefix_reuse_and_determinism():
    cfg = configs.get("qwen2_7b", smoke=True)
    params = init_params(jax.random.key(0), model_spec(cfg),
                         dtype=cfg.dtype)
    rng = np.random.default_rng(0)
    shared = list(rng.integers(0, cfg.vocab, 24))

    eng = ServeEngine(cfg, params, max_slots=2, max_seq=64,
                      store=PrefixStore(1 << 20, "lerc", block_tokens=8))
    r1 = eng.submit(shared + [1, 2, 3], max_new=4)
    eng.run()
    r2 = eng.submit(shared + [4, 5, 6], max_new=4)
    eng.run()
    assert r1.prefill_skipped == 0
    assert r2.prefill_skipped >= 16             # shared prefix reused
    m = eng.metrics()
    assert m["prefill_saved_frac"] > 0

    # identical prompt must generate identical tokens (cold vs warm)
    e2 = ServeEngine(cfg, params, max_slots=1, max_seq=64)
    a = e2.submit(shared[:16], max_new=5)
    e2.run()
    b = e2.submit(shared[:16], max_new=5)
    e2.run()
    assert a.generated == b.generated


def test_engine_continuous_batching_isolation():
    """Interleaved requests in different slots must not contaminate each
    other: same prompt alone vs alongside another request."""
    cfg = configs.get("qwen2_7b", smoke=True)
    params = init_params(jax.random.key(0), model_spec(cfg),
                         dtype=cfg.dtype)
    rng = np.random.default_rng(1)
    p1 = list(rng.integers(0, cfg.vocab, 10))
    p2 = list(rng.integers(0, cfg.vocab, 7))

    solo = ServeEngine(cfg, params, max_slots=1, max_seq=64)
    rs = solo.submit(p1, max_new=4)
    solo.run()

    duo = ServeEngine(cfg, params, max_slots=2, max_seq=64)
    ra = duo.submit(p1, max_new=4)
    rb = duo.submit(p2, max_new=4)
    duo.run()
    assert ra.generated == rs.generated


# ---------------------------------------------------------------------------
# launch: depth cut, jitted parameter init, compile cache
# ---------------------------------------------------------------------------

def test_launch_layers_cuts_depth_only(capsys, monkeypatch):
    """--layers N serves the first N layers at the config's own widths and
    says so in the summary line."""
    import repro.launch.serve as launch
    from repro.launch.serve import build_server, serve_main
    # the persistent compile cache is process-wide; keep it off in tests
    monkeypatch.setattr(launch, "use_compile_cache", lambda: None)
    argv = ["--arch", "qwen2_7b", "--smoke", "--layers", "2",
            "--requests", "4", "--slots", "2", "--max-seq", "64",
            "--cache-kb", "64", "--max-new", "2"]
    srv = build_server(argv)
    full = configs.get("qwen2_7b", smoke=True)
    assert srv.cfg == full.replace(n_layers=2)
    assert serve_main(argv) == 0
    assert "arch=qwen2_7b_smoke  layers=2/3" in capsys.readouterr().out


@pytest.mark.parametrize("arch", ["qwen2_7b", "moonshot_v1_16b_a3b",
                                  "recurrentgemma_9b"])
def test_init_params_matches_unfused_draw(arch):
    """The jitted per-leaf init is bit-for-bit the unfused formula it
    replaced: a float32 normal draw, times the scale, cast."""
    import zlib
    import jax.numpy as jnp
    from repro.models.common import tree_paths
    cfg = configs.get(arch, smoke=True)
    spec = model_spec(cfg)
    rng = jax.random.key(3)
    got = dict(tree_paths(init_params(rng, spec, dtype=cfg.dtype)))
    for path, s in tree_paths(spec):
        if s.init in ("zeros", "ones"):
            continue
        key = jax.random.fold_in(
            rng, zlib.crc32("/".join(path).encode()) % (2 ** 31))
        scale = (1.0 / np.sqrt(s.shape[-2] if len(s.shape) >= 2
                               else s.shape[-1])
                 if s.init == "scaled" else s.scale)
        want = (jax.random.normal(key, s.shape, jnp.float32)
                * scale).astype(s.dtype or cfg.dtype)
        assert got[path].dtype == want.dtype, path
        np.testing.assert_array_equal(
            np.asarray(got[path].astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)), err_msg=str(path))


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache and is left
    alone; unset, the cache is one fixed directory in the checkout."""
    from repro.launch.compile_cache import CACHE_DIR, use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(CACHE_DIR)
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert use_compile_cache() == want
        if env_dir is None:
            assert jax.config.jax_compilation_cache_dir == want
            assert CACHE_DIR.parent == Path(__file__).resolve().parents[1]
        else:
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
