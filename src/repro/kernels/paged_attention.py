"""Paged flash-decoding Pallas TPU kernel: attention straight out of the
serving tier's KV block pool, indexed by per-sequence block tables.

The serving data plane stores KV in a paged pool — per layer, one buffer
shaped ``(num_blocks, block_tokens, KV, D)`` whose rows belong to prefix
chains, not slots. This kernel extends the flash-decoding split-K scheme
(``kernels/decode_attention.py``): it walks a sequence's *block table*
instead of a contiguous cache, and the table is a scalar-prefetch operand
so each pool row is resolved before its DMA issues — K/V stream HBM→VMEM
directly from their pool rows, with no gather materializing a contiguous
cache view anywhere.

Two generalizations over plain flash-decoding:

* **Chunked queries** — ``S`` query tokens per sequence share the streamed
  K/V tile (they are processed as an ``(S*G, D)`` tile, so GQA packing and
  chunking compose); masking is per query *position* (``kpos <= qpos``),
  which subsumes valid-length masking, per-token causality inside a
  prefill chunk, and right-padded rows whose outputs the caller discards.
* **Logical positions** — block ``i`` of a table covers logical positions
  ``[i*bt, (i+1)*bt)`` regardless of which pool row backs it, so the
  kernel never sees (and the engine never computes) a contiguous layout.

Grid ``(B, ceil(NW / P))``: each step streams one tile of ``P`` pool rows
(``P = 16 // bt``, at least one row and at most the whole table) and
updates every KV head's online-softmax state over the tile's ``P*bt``
keys. The pools enter the kernel ``P`` times each, one operand
per row of a tile, so Pallas's own pipeline double-buffers the tile's
``2P`` row copies and starts the next step's (the next sequence's first
tile at the end of a table) before the current one is computed.

A sequence needs its table only up to ``qpos[b, S-1] // bt``, the last
row any of its queries can see. Every row index of a tile is clamped to
that row: past the bound's tile every block of a step names the bound's
row, and since the pipeline skips a block's copy when its index repeats
the step before's, that row is copied once more and then not again, and
those steps compute nothing. Table entries past the bound are never
read, a padded table costs one empty grid step per tile, and a free slot
costs one tile. Rows of the bound's own tile past the bound repeat the
bound's row and are masked like any position past ``qpos``.
On non-TPU backends the interpret mode runs the identical tiling/masking
logic as traced jnp ops.
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
# tokens a K/V tile streams per grid step. Each pool row of a tile is its
# own pipelined operand, and every one adds to the kernel's lowering, paid
# once per step program at set-up; on a v5e, 128-token tiles of 16-token
# rows took 1.5x as long to lower and streamed a full table no faster than
# one row a step, so a tile holds one 16-token row
TILE_TOKENS = 16


def rows_walked(last_pos, bt: int, nw: int):
    """Table rows the kernel walks for a sequence whose last query sits at
    position ``last_pos`` (>= 0): through the row that holds it, at most
    the whole table of ``nw`` rows. The kernel's index maps call it on a
    traced scalar (plain lax ops: the kernel is traced anew for each step
    shape the engine compiles); the engine's ``kv_rows`` stat calls it on
    a host array of every slot's last position."""
    if isinstance(last_pos, np.ndarray):
        return np.minimum(last_pos // bt + 1, nw)
    return jax.lax.min(jax.lax.div(last_pos, bt) + 1, nw)


def _last_row(qpos_ref, b, *, bt: int, nw: int, S: int):
    """The last table row any query of sequence ``b`` can see."""
    return rows_walked(qpos_ref[b, S - 1], bt, nw) - 1


def _paged_kernel(tbl_ref, qpos_ref, q_ref, *refs, bt: int, P: int,
                  nw: int, KV: int, G: int, S: int, scale: float,
                  softcap: Optional[float]):
    k_refs, v_refs = refs[:P], refs[P:2 * P]
    o_ref, m_scr, l_scr, acc_scr = refs[2 * P:]
    b, t = pl.program_id(0), pl.program_id(1)

    @pl.when(t == 0)
    def init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # tiles past the last visible row are not computed, and not copied
    # either: their blocks all name that row, which the pipeline holds
    @pl.when(t * P <= _last_row(qpos_ref, b, bt=bt, nw=nw, S=S))
    def compute():
        # Query row r of the (S*G, D) tile is chunk token r // G, whose
        # position is read from SMEM; a key's logical position is its
        # table index times bt, not its pool row. Rows of the tile past
        # the last visible row repeat it, and are masked like any
        # position past qpos.
        shape = (S * G, P * bt)
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        qp = jnp.full(shape, qpos_ref[b, 0], jnp.int32)
        for j in range(1, S):
            qp = jnp.where(row >= j * G, qpos_ref[b, j], qp)
        mask = t * (P * bt) + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1) <= qp

        for h in range(KV):     # a pool row holds every KV head's tile
            k = jnp.concatenate([r[0, :, h, :] for r in k_refs])
            v = jnp.concatenate([r[0, :, h, :] for r in v_refs])
            # bf16 x bf16 products are exact in the float32 accumulator
            s = jax.lax.dot_general(q_ref[0, h], k,
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(mask, s, NEG_INF)

            # position 0 lies in every sequence's first tile, so m_new is
            # finite from the first tile on
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            m_scr[h] = m_new
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = alpha * acc_scr[h] + jax.lax.dot_general(
                p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(t == pl.num_programs(1) - 1)
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
    query token, ascending within a row. Query (b, j) attends to logical
    positions ``kpos <= qpos[b, j]``; table entries past
    ``qpos[b, S-1] // bt`` are never read. Returns (B, S, H, D)."""
    B, S, H, D = q.shape
    bt, KV = k_pages.shape[1], k_pages.shape[2]
    NW = tables.shape[1]
    G = H // KV
    P = max(1, min(NW, TILE_TOKENS // bt))    # pool rows per K/V tile

    # (B, KV, S*G, D): queries of one KV head share the streamed K/V tile
    qg = q.reshape(B, S, KV, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KV, S * G, D)

    kernel = functools.partial(
        _paged_kernel, bt=bt, P=P, nw=NW, KV=KV, G=G, S=S,
        scale=1.0 / float(np.sqrt(D)), softcap=softcap)
    last = functools.partial(_last_row, bt=bt, nw=NW, S=S)

    def row_spec(j):
        """Row j of a tile: pool row of table entry t*P + j, clamped to
        the sequence's last visible row."""
        return pl.BlockSpec((1, bt, KV, D), lambda b, t, tbl, qp: (
            tbl[b, jax.lax.min(t * P + j, last(qp, b))], 0, 0, 0))

    # Mosaic tiles the last two block dims, so every block keeps them
    # whole: a row block is one full pool row (bt, KV, D), and the kernel
    # walks its KV heads. Per-token positions ride in SMEM with the table.
    rows = [row_spec(j) for j in range(P)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # block table, query positions
        grid=(B, pl.cdiv(NW, P)),
        in_specs=[pl.BlockSpec((1, KV, S * G, D),
                               lambda b, t, tbl, qp: (b, 0, 0, 0))]
        + rows + rows,
        out_specs=pl.BlockSpec((1, KV, S * G, D),
                               lambda b, t, tbl, qp: (b, 0, 0, 0)),
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
    )(tables.astype(jnp.int32), qpos.astype(jnp.int32), qg,
      *[k_pages] * P, *[v_pages] * P)
    return out.reshape(B, KV, S, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, S, H, D)
