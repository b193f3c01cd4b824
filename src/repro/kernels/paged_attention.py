"""Paged flash-decoding Pallas TPU kernel: attention straight out of the
serving tier's KV block pool, indexed by per-sequence block tables.

The serving data plane stores KV in a paged pool — per layer, one buffer
shaped ``(num_blocks, block_tokens, KV, D)`` whose rows belong to prefix
chains, not slots. This kernel extends the flash-decoding split-K scheme
(``kernels/decode_attention.py``): the grid's innermost dimension walks a
sequence's *block table* instead of a contiguous cache, and the table is a
scalar-prefetch operand so each K/V tile's pool row is resolved before the
DMA issues — K/V stream HBM→VMEM directly from their pool rows, with no
gather materializing a contiguous cache view anywhere.

Two generalizations over plain flash-decoding:

* **Chunked queries** — ``S`` query tokens per sequence share the streamed
  K/V tile (they are processed as an ``(S*G, D)`` tile, so GQA packing and
  chunking compose); masking is per query *position* (``kpos <= qpos``),
  which subsumes valid-length masking, per-token causality inside a
  prefill chunk, and right-padded rows whose outputs the caller discards.
* **Logical positions** — block ``i`` of a table covers logical positions
  ``[i*bt, (i+1)*bt)`` regardless of which pool row backs it, so the
  kernel never sees (and the engine never computes) a contiguous layout.

Grid ``(B, num_table_blocks)``: each step DMAs one whole pool row, all
KV heads of it, and updates every head's online-softmax state. On non-TPU
backends the interpret mode runs the identical tiling/masking logic as
traced jnp ops.
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


def _paged_kernel(tbl_ref, qpos_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                  l_scr, acc_scr, *, bt: int, nw: int, KV: int, G: int,
                  S: int, scale: float, softcap: Optional[float]):
    b, ik = pl.program_id(0), pl.program_id(1)

    @pl.when(ik == 0)
    def init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # logical positions of this tile's keys — the table block index, not
    # the pool row, carries position. Query row r of the (S*G, D) tile is
    # chunk token r // G, whose position is read from SMEM.
    kpos = ik * bt + jax.lax.broadcasted_iota(jnp.int32, (S * G, bt), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (S * G, bt), 0)
    qp = jnp.full((S * G, bt), qpos_ref[b, 0], jnp.int32)
    for j in range(1, S):
        qp = jnp.where(row >= j * G, qpos_ref[b, j], qp)
    mask = kpos <= qp

    for h in range(KV):      # one pool row holds every KV head's tile
        q = q_ref[0, h].astype(jnp.float32) * scale      # (S*G, D)
        k = k_ref[0, :, h, :].astype(jnp.float32)         # (bt, D)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[h]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        safe_m = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.where(mask, jnp.exp(s - safe_m), 0.0)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                          jnp.exp(m_prev - safe_m))
        m_scr[h] = m_new
        l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[h] = alpha * acc_scr[h] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nw - 1)
    def finalize():
        for h in range(KV):
            l = jnp.maximum(l_scr[h], 1e-30)
            o_ref[0, h] = (acc_scr[h] / l).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, tables: jax.Array,
                           qpos: jax.Array, *,
                           softcap: Optional[float] = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, S, H, D) query chunk per sequence (S=1 for plain decode);
    k_pages, v_pages: (num_blocks, bt, KV, D) pool pages; tables: (B, NW)
    int32 pool rows in chain order (block i of row b covers logical
    positions [i*bt, (i+1)*bt)); qpos: (B, S) absolute position of each
    query token. Query (b, j) attends to logical positions
    ``kpos <= qpos[b, j]``. Returns (B, S, H, D)."""
    B, S, H, D = q.shape
    bt, KV = k_pages.shape[1], k_pages.shape[2]
    NW = tables.shape[1]
    G = H // KV

    # (B, KV, S*G, D): queries of one KV head share the streamed K/V tile
    qg = q.reshape(B, S, KV, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KV, S * G, D)

    kernel = functools.partial(
        _paged_kernel, bt=bt, nw=NW, KV=KV, G=G, S=S,
        scale=1.0 / float(np.sqrt(D)), softcap=softcap)
    # Mosaic tiles the last two block dims, so every block keeps them
    # whole: a K/V tile is one full pool row (bt, KV, D) and the kernel
    # walks its KV heads; per-token positions ride in SMEM with the table.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # block table, query positions
        grid=(B, NW),
        in_specs=[
            pl.BlockSpec((1, KV, S * G, D),
                         lambda b, ik, tbl, qp: (b, 0, 0, 0)),
            pl.BlockSpec((1, bt, KV, D),
                         lambda b, ik, tbl, qp: (tbl[b, ik], 0, 0, 0)),
            pl.BlockSpec((1, bt, KV, D),
                         lambda b, ik, tbl, qp: (tbl[b, ik], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, S * G, D),
                               lambda b, ik, tbl, qp: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, S * G, 1), jnp.float32),
            pltpu.VMEM((KV, S * G, 1), jnp.float32),
            pltpu.VMEM((KV, S * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, S * G, D), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), qpos.astype(jnp.int32), qg, k_pages,
      v_pages)
    return out.reshape(B, KV, S, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, S, H, D)
