"""Paged flash-decoding kernel vs oracles, and the decode-kernel flag.

Separate from test_kernels.py so these run without hypothesis installed
(the tier-1 container has no dev extras; CI runs both).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ref import attention_ref


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# paged flash-decoding (block-table split-K over KV pool pages)
# ---------------------------------------------------------------------------

PAGED_CASES = [
    # (B, S, H, KV, D, bt, NW, softcap, pos0, padded)
    # pos0: each row's first query position (None: spread over the table);
    # padded: table entries past a row's last visible block name a pool
    # row of NaN, which the kernel must never read
    (2, 1, 4, 2, 64, 8, 8, None, None, False),     # plain decode, GQA
    (3, 4, 4, 1, 64, 8, 6, None, None, False),     # prefill chunk, MQA
    (1, 8, 8, 2, 32, 4, 16, 50.0, None, False),    # chunk > bt, softcap
    (2, 3, 2, 2, 128, 16, 4, None, None, False),   # chunk not dividing bt
    # mixed lengths on padded tables: a free slot (0..S-1), a row ending
    # mid-tile, a row ending at the table's last block; NW not a multiple
    # of the rows a tile streams (16 // bt)
    (3, 8, 28, 4, 128, 16, 12, None, (0, 150, 184), True),   # G=7
    (3, 1, 7, 1, 128, 8, 21, 30.0, (0, 77, 167), True),      # MQA, G=7
    (4, 8, 8, 2, 64, 8, 36, 50.0, (0, 124, 200, 280), True),  # 18 tiles
    (2, 1, 28, 4, 128, 16, 20, None, (0, 319), True),
]


def _paged_oracle(q, kp, vp, tables, qpos, softcap):
    """Dense causal attention over the logical cache each block table
    describes (pages[tables[b]] flattened), each query token placed at its
    absolute position. Returns (B, S, H, D) float32."""
    B, S, H, D = q.shape
    L = tables.shape[1] * kp.shape[1]
    kc = kp[tables].reshape((B, L) + kp.shape[2:])
    vc = vp[tables].reshape((B, L) + vp.shape[2:])
    bidx = jnp.arange(B)[:, None]
    qf = jnp.zeros((B, L, H, D), jnp.float32).at[bidx, qpos].set(
        q.astype(jnp.float32))
    ref = attention_ref(qf, kc, vc, causal=True, softcap=softcap)
    return ref[bidx, qpos]


@pytest.mark.parametrize("case", PAGED_CASES)
def test_paged_decode_attention_matches_oracle(case):
    """The paged kernel must agree with dense attention over the logical
    cache each block table describes, each query token attending up to
    its absolute position; table entries past the last block a row's
    queries can see must not reach the output."""
    from repro.kernels import paged_decode_attention
    B, S, H, KV, D, bt, NW, softcap, pos0, padded = case
    NB = B * NW + 3                       # pool bigger than any one table
    ks = jax.random.split(jax.random.PRNGKey(sum(case[:6])), 4)
    q = _rand(ks[0], (B, S, H, D))
    kp = _rand(ks[1], (NB, bt, KV, D))
    vp = _rand(ks[2], (NB, bt, KV, D))
    # disjoint, shuffled tables: pool row order is unrelated to position
    perm = jax.random.permutation(ks[3], NB)[:B * NW]
    tables = perm.reshape(B, NW).astype(jnp.int32)
    if pos0 is None:
        pos0 = [(7 * b + 5) % (NW * bt - S) for b in range(B)]
    qpos = jnp.asarray(pos0, jnp.int32)[:, None] + jnp.arange(S)[None, :]
    seen = tables
    if padded:                            # pool row NB: every value NaN
        kp = jnp.concatenate([kp, jnp.full((1, bt, KV, D), jnp.nan)])
        vp = jnp.concatenate([vp, jnp.full((1, bt, KV, D), jnp.nan)])
        reach = qpos[:, -1:] // bt
        seen = jnp.where(jnp.arange(NW)[None, :] > reach, NB, tables)
    out = np.asarray(paged_decode_attention(q, kp, vp, seen, qpos,
                                            softcap=softcap))
    assert np.isfinite(out).all()
    ref = _paged_oracle(q, kp, vp, tables, qpos, softcap)
    np.testing.assert_allclose(out, np.asarray(ref), atol=3e-5, rtol=3e-5)


def test_paged_matches_plain_flash_decoding():
    """With an identity table (row i backs positions [i*bt, (i+1)*bt)) and
    S=1, the paged kernel must reproduce plain flash-decoding over the
    materialized contiguous cache."""
    from repro.kernels import decode_attention, paged_decode_attention
    B, H, KV, D, bt, NW = 2, 4, 2, 64, 8, 8
    S_cache = NW * bt
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = _rand(ks[0], (B, 1, H, D))
    kp = _rand(ks[1], (B * NW, bt, KV, D))
    vp = _rand(ks[2], (B * NW, bt, KV, D))
    tables = jnp.arange(B * NW, dtype=jnp.int32).reshape(B, NW)
    valid = jnp.array([S_cache, S_cache - 13], jnp.int32)
    out_paged = paged_decode_attention(q, kp, vp, tables,
                                       valid[:, None] - 1)
    kc = kp[tables].reshape(B, S_cache, KV, D)
    vc = vp[tables].reshape(B, S_cache, KV, D)
    out_plain = decode_attention(q[:, 0], kc, vc, valid, block_k=bt)
    np.testing.assert_allclose(np.asarray(out_paged[:, 0]),
                               np.asarray(out_plain),
                               atol=3e-5, rtol=3e-5)


def test_flash_decode_flag_matches_xla_decode_path():
    """ModelConfig.decode_kernel="flash" must route the engine's decode
    steps through the flash-decoding kernel (interpret mode here) with
    logits matching the dense-mask XLA path."""
    import jax.numpy as jnp  # noqa: F811
    from repro import configs
    from repro.models import decode_step, init_decode_cache, init_params, \
        model_spec
    cfg = configs.get("qwen2_7b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), model_spec(cfg),
                         dtype=jnp.float32)
    B, S_cache = 2, 32
    tokens = jnp.array([[5], [9]], jnp.int32)
    for pos in (jnp.int32(7),                       # bulk decode
                jnp.array([3, 11], jnp.int32)):     # per-slot decode
        outs = {}
        for impl in ("xla", "flash"):
            cfg_i = cfg.replace(decode_kernel=impl, dtype=jnp.float32)
            cache = init_decode_cache(cfg_i, B, S_cache)
            logits, _ = decode_step(cfg_i, params, cache, tokens, pos)
            outs[impl] = np.asarray(logits, np.float32)
        np.testing.assert_allclose(outs["flash"], outs["xla"],
                                   atol=2e-4, rtol=2e-4)
