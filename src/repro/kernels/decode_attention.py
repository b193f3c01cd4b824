"""Flash-decoding Pallas TPU kernel: single-token attention against a long
KV cache, split over the KV length (split-K).

Decode attention is memory-bound: one query row must stream S·KV·D cache
bytes through the chip. The TPU adaptation of FlashDecoding
[arXiv:2311.01282] splits the KV length across the grid's innermost
dimension and carries the online-softmax state (m, l, acc) in VMEM — one
(1, bk)·(bk, D) matvec pair per step on the VPU/MXU, with the cache tile
streamed HBM→VMEM once. GQA queries of one KV head are processed together
as a (G, D) tile so the streamed K/V block is reused G times.

Grid ``(B, KV, nk)``; valid-length masking supports ragged per-row cache
fills (continuous batching).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, bk: int, nk: int, G: int, scale: float,
                   window: Optional[int], softcap: Optional[float]):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    valid_len = len_ref[pl.program_id(0)]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, bk)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (G, bk), 1)
    mask = kpos < valid_len
    if window is not None:
        mask &= kpos > valid_len - 1 - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.where(mask, jnp.exp(s - safe_m), 0.0)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - safe_m))
    m_scr[...] = m_new
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid_len: jax.Array, *,
                     window: Optional[int] = None,
                     softcap: Optional[float] = None,
                     block_k: int = 256,
                     interpret: bool = False) -> jax.Array:
    """q: (B, H, D) one query per sequence; k, v: (B, S, KV, D) cache;
    valid_len: (B,) number of filled cache slots per row (the query is at
    position valid_len-1). Returns (B, H, D)."""
    B, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    bk = min(block_k, S)
    S_p = -(-S // bk) * bk
    if S_p != S:
        pad = ((0, 0), (0, S_p - S), (0, 0), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    nk = S_p // bk

    qg = q.reshape(B, KV, G, D)
    kt = k.transpose(0, 2, 1, 3)                          # (B, KV, S, D)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _decode_kernel, bk=bk, nk=nk, G=G, scale=1.0 / float(np.sqrt(D)),
        window=window, softcap=softcap)
    # the per-row valid lengths are scalars: they ride in SMEM, ahead of
    # the grid, instead of as a (1,)-blocked vector Mosaic cannot tile
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, ik, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ik, ln: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, ik, ln: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, ik, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        interpret=interpret,
    )(valid_len.astype(jnp.int32), qg, kt, vt)
    return out.reshape(B, H, D)
