"""Shared layers: norms, RoPE, embeddings, attention (GQA, sliding-window,
softcap, bias), MLPs. Pure functions over param dicts; fp32 where numerics
demand it (norms, softmax, rope), bf16 elsewhere.

Sequence-dim sharding constraints (SP) are applied by the caller via
``repro.sharding.constrain`` so the layer code stays mesh-agnostic.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .common import ModelConfig, p

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_spec(dim: int):
    return {"scale": p((dim,), ("embed",), init="zeros")}  # (1+scale) param.


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(dt)


def layernorm_spec(dim: int):
    return {"scale": p((dim,), ("embed",), init="ones"),
            "bias": p((dim,), ("embed",), init="zeros")}


def layernorm(params, x, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(dt)


def norm_spec(cfg: ModelConfig, dim: Optional[int] = None):
    dim = dim or cfg.d_model
    return layernorm_spec(dim) if cfg.norm == "layernorm" else rmsnorm_spec(dim)


def norm(cfg: ModelConfig, params, x):
    return layernorm(params, x) if cfg.norm == "layernorm" else rmsnorm(params, x)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None, None].astype(jnp.float32) * freq  # (...,S,1,half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def attention_spec(cfg: ModelConfig, cross: bool = False) -> Dict:
    d, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.d_head
    spec = {
        "wq": p((d, H, Dh), ("embed", "heads", "head_dim"), init="scaled"),
        "wk": p((d, KV, Dh), ("embed", "kv_heads", "head_dim"), init="scaled"),
        "wv": p((d, KV, Dh), ("embed", "kv_heads", "head_dim"), init="scaled"),
        "wo": p((H, Dh, d), ("heads", "head_dim", "embed"), init="scaled"),
    }
    if cfg.qkv_bias:
        spec["bq"] = p((H, Dh), ("heads", "head_dim"), init="zeros")
        spec["bk"] = p((KV, Dh), ("kv_heads", "head_dim"), init="zeros")
        spec["bv"] = p((KV, Dh), ("kv_heads", "head_dim"), init="zeros")
    return spec


def _qkv(cfg: ModelConfig, params, xq, xkv):
    q = jnp.einsum("bsd,dhk->bshk", xq, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", xkv, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", xkv, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    return q, k, v


def _sdpa(cfg: ModelConfig, q, k, v, mask) -> jax.Array:
    """q: (B,Sq,H,D); k,v: (B,Skv,KV,D); mask: (B|1, 1, Sq, Skv) bool."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    groups = H // KV
    qg = q.reshape(B, Sq, KV, groups, D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(D)
    if cfg.attn_logit_softcap:
        c = cfg.attn_logit_softcap
        logits = c * jnp.tanh(logits / c)
    logits = jnp.where(mask[:, :, None, :, :] if mask.ndim == 4 else mask,
                       logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, H, D)


def causal_mask(Sq: int, Skv: int, q_offset=0, window: Optional[int] = None):
    """(1,1,Sq,Skv) bool. ``q_offset``: absolute position of query 0 (may be
    a traced scalar). ``window``: sliding window (local attention)."""
    qpos = q_offset + jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    m = kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return m[None, None]


def _use_chunked(cfg: ModelConfig, Sq: int) -> bool:
    if cfg.attn_impl == "chunked":
        return True
    if cfg.attn_impl == "xla":
        return False
    return Sq > 2048  # auto: full logits past 2k are prohibitive


def _use_flash_decode(cfg: ModelConfig) -> bool:
    """Route decode attention through the Pallas flash-decoding kernels
    (plain or paged). "auto" compiles the real Mosaic kernels on TPU and
    keeps the dense-mask XLA path elsewhere — off-TPU the kernels only
    run in interpret mode, which validates tiling but wins nothing."""
    if cfg.decode_kernel == "flash":
        return True
    if cfg.decode_kernel == "xla":
        return False
    return jax.default_backend() == "tpu"


def _paged_attention(cfg: ModelConfig, q, k_pages, v_pages, tables, qpos):
    """Attention for a (B,Sq,H,D) query chunk straight out of KV pool
    pages: ``k_pages``/``v_pages`` are (num_blocks, bt, KV, D), block
    ``i`` of ``tables[b]`` backs logical positions [i*bt, (i+1)*bt) and
    query token (b, j) attends positions <= qpos[b, j]. The kernel path
    streams K/V tiles from pool rows named by the (scalar-prefetched)
    table; the XLA path gathers the pages and reuses ``_sdpa`` so the
    numerics match the gather engine's dense decode exactly."""
    if _use_flash_decode(cfg):
        from ..kernels import paged_decode_attention
        return paged_decode_attention(q, k_pages, v_pages, tables, qpos,
                                      softcap=cfg.attn_logit_softcap)
    B, Sq = q.shape[:2]
    NW, bt = tables.shape[1], k_pages.shape[-3]
    kc = k_pages[tables].reshape((B, NW * bt) + k_pages.shape[-2:])
    vc = v_pages[tables].reshape((B, NW * bt) + v_pages.shape[-2:])
    m = jnp.arange(NW * bt)[None, None, :] <= qpos[:, :, None]
    return _sdpa(cfg, q, kc, vc, m[:, None])


def _paged_write_attend(cfg: ModelConfig, q, k, v, kp, vp, tables, lens,
                        cache_pos):
    """Zero-copy paged data plane: write the chunk's k/v into the pool
    rows the block table names, attend straight out of the pool. Returns
    (out, new_k_pages, new_v_pages). Also the per-device body of the TP
    shard_map — q/k/v/pages arrive head-sliced there, everything else
    replicated, and the ops below never mix KV heads."""
    B, Sq = q.shape[:2]
    bt = kp.shape[-3]
    tpos = cache_pos[:, None] + jnp.arange(Sq)[None, :]          # (B,Sq)
    blk = jnp.minimum(tpos // bt, tables.shape[1] - 1)
    rows = jnp.take_along_axis(tables, blk, axis=1)
    # right-padded (and inactive-slot) tokens land in pool row 0, the
    # engine's reserved junk row — real rows only ever see writes of real
    # tokens
    rows = jnp.where(jnp.arange(Sq)[None, :] < lens[:, None], rows, 0)
    widx = (rows.reshape(-1), (tpos % bt).reshape(-1))
    ck = kp.at[widx].set(k.reshape((B * Sq,) + k.shape[2:]).astype(kp.dtype))
    cv = vp.at[widx].set(v.reshape((B * Sq,) + v.shape[2:]).astype(vp.dtype))
    out = _paged_attention(cfg, q, ck, cv, tables, tpos)
    return out, ck, cv


def _paged_write_attend_tp(cfg: ModelConfig, kv_shard, q, k, v, kp, vp,
                           tables, lens, cache_pos):
    """Tensor-parallel paged write+attend: the per-device body above under
    ``shard_map``, q/k/v and the pool pages sliced on their head dims, the
    block table (and every other host-derived operand) replicated. GQA
    packing groups queries by KV head, so a contiguous H/tp query slice
    owns exactly its KV slice's whole head groups — no cross-device
    attention math. The attention outputs are all-gathered over heads
    inside the map so the (replicated) ``wo`` projection runs on the full
    head set on every device: at tp=1 the gather is the identity, and at
    any tp the summation ORDER of the output projection is the single-
    device order — generations stay token-identical (the psum formulation
    would instead reduce partial wo products in mesh order, perturbing
    bf16 rounding). The pages come back head-sharded, matching the pool's
    committed layout, so the engine's donated step keeps them in place."""
    from jax.sharding import PartitionSpec

    ax = kv_shard.axis
    heads = PartitionSpec(None, None, ax, None)   # q/k/v (B,S,h,D) and
    repl = PartitionSpec()                        # pages (nb,bt,kv,D)

    def body(q, k, v, kp, vp, tables, lens, cache_pos):
        out, ck, cv = _paged_write_attend(cfg, q, k, v, kp, vp, tables,
                                          lens, cache_pos)
        out = jax.lax.all_gather(out, ax, axis=2, tiled=True)
        return out, ck, cv

    return jax.shard_map(
        body, mesh=kv_shard.mesh,
        in_specs=(heads, heads, heads, heads, heads, repl, repl, repl),
        out_specs=(PartitionSpec(), heads, heads),
        check_vma=False,
    )(q, k, v, kp, vp, tables, lens, cache_pos)


def _tp_qkv_constraints(mesh_ctx, q, k, v):
    """Inside the TP region: heads over model, batch over data. When the
    head count does not divide the model axis (qwen2: 28H, whisper: 8H on
    TP=16), fall back to CONTEXT parallelism for long inputs: queries
    sharded over model along the sequence (each rank attends its query
    slice against replicated KV) — otherwise a 32k prefill keeps full
    (B, S, H, D) projections replicated on every chip."""
    dp, mdl = mesh_ctx.data_axes, mesh_ctx.model_axis
    tp = mesh_ctx.tp_size
    H = q.shape[2]
    if H % max(tp, 1) == 0 or tp <= 1:
        q = mesh_ctx.constrain_dims(q, (dp, None, mdl, None))
        k = mesh_ctx.constrain_dims(k, (dp, None, mdl, None))
        v = mesh_ctx.constrain_dims(v, (dp, None, mdl, None))
    elif q.shape[1] > 1 and q.shape[1] % tp == 0:
        q = mesh_ctx.constrain_dims(q, (dp, mdl, None, None))
        k = mesh_ctx.constrain_dims(k, (dp, None, None, None))
        v = mesh_ctx.constrain_dims(v, (dp, None, None, None))
    return q, k, v


def attention(cfg: ModelConfig, params, x, *, positions, window=None,
              cache: Optional[Dict] = None, cache_pos=None,
              cache_valid_len=None, paged: Optional[Dict] = None,
              cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
              bidirectional: bool = False, prefix_len: int = 0,
              mesh_ctx=None, kv_shard=None):
    """Full attention layer (proj → rope → sdpa → proj).

    Modes:
      * training/prefill: cache=None, causal (or bidirectional for encoders)
      * decode: ``cache`` = {"k","v"} (B, S_cache, KV, D); the new token is
        written at slot ``cache_pos`` (callers pass ``pos % window`` for
        rolling local-attention caches) and attends to the first
        ``cache_valid_len`` slots. Keys keep the RoPE phase of the absolute
        position they were written with, so slot order is irrelevant.
      * paged decode: ``cache`` = {"k","v"} per-layer KV *pool* views
        (num_blocks, bt, KV, D) and ``paged`` = {"tables": (B, NW) pool
        rows in chain order, "seq_lens": (B,) real tokens per row}. Each
        slot's chunk is written into the tail pool rows its block table
        names (right-padded and inactive-slot tokens land in reserved junk
        row 0) and attention streams from the table's rows — no per-slot
        contiguous cache exists. Absolute positions only (G/M layers).
      * cross: ``cross_kv`` provides precomputed (k, v) from the encoder.
    Returns (out, new_cache).
    """
    B, Sq, d = x.shape
    if mesh_ctx is not None:
        x = mesh_ctx.gather_seq(x)     # SP all-gather on TP-region entry
    if cross_kv is not None:
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
        if cfg.qkv_bias:
            q = q + params["bq"]
        k, v = cross_kv
        if mesh_ctx is not None:
            q, k, v = _tp_qkv_constraints(mesh_ctx, q, k, v)
        mask = jnp.ones((1, 1, Sq, k.shape[1]), bool)
        out = _sdpa(cfg, q, k, v, mask)
        return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), cache

    q, k, v = _qkv(cfg, params, x, x)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    if mesh_ctx is not None:
        q, k, v = _tp_qkv_constraints(mesh_ctx, q, k, v)

    if cache is not None:
        # ``cache_valid_len`` is the valid cache length as seen by the
        # FIRST query token; query token j of a chunk sees j more (its own
        # write and its intra-chunk predecessors) — per-token causality for
        # Sq > 1 (chunked prefill), and exactly the old semantics at Sq=1.
        if paged is not None:
            # zero-copy paged data plane: write the chunk into the pool
            # rows the block table names, attend straight out of the pool
            # (one shard_map over the head-sharded pool under serve TP)
            tables, lens = paged["tables"], paged["seq_lens"]
            fn = (partial(_paged_write_attend_tp, cfg, kv_shard)
                  if kv_shard is not None
                  else partial(_paged_write_attend, cfg))
            out, ck, cv = fn(q, k, v, cache["k"], cache["v"], tables,
                             lens, cache_pos)
        elif getattr(cache_pos, "ndim", 0) == 1:
            # per-slot positions (continuous batching): each slot scatters
            # its Sq-token chunk at its own offset. Positions are absolute
            # (slot order == position) — rolling-window caches take the
            # bulk path.
            bidx = jnp.arange(B)[:, None]                        # (B,1)
            tpos = cache_pos[:, None] + jnp.arange(Sq)[None, :]  # (B,Sq)
            ck = cache["k"].at[bidx, tpos].set(k.astype(cache["k"].dtype))
            cv = cache["v"].at[bidx, tpos].set(v.astype(cache["v"].dtype))
            Skv = ck.shape[1]
            base = (cache_pos + 1 if cache_valid_len is None
                    else cache_valid_len)
            if Sq == 1 and _use_flash_decode(cfg):
                # flash-decoding: split-K streaming over the valid cache,
                # no dense (Sq, Skv) mask materialized
                from ..kernels import decode_attention as _flash_dec
                out = _flash_dec(q[:, 0], ck, cv, base,
                                 softcap=cfg.attn_logit_softcap)[:, None]
            else:
                valid = base[:, None] + jnp.arange(Sq)[None, :]  # (B,Sq)
                m = jnp.arange(Skv)[None, None, :] < valid[:, :, None]
                out = _sdpa(cfg, q, ck, cv, m[:, None])          # (B,1,Sq,Skv)
        else:
            # bulk decode: one shared position, dynamic-update-slice
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, cache_pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, cache_pos, 0, 0))
            Skv = ck.shape[1]
            base = (cache_pos + 1 if cache_valid_len is None
                    else cache_valid_len)
            if Sq == 1 and _use_flash_decode(cfg):
                # rolling (L) caches pass valid = min(pos+1, window): the
                # whole wrapped buffer is live, so no window mask applies
                # to cache slots and slot order stays irrelevant
                from ..kernels import decode_attention as _flash_dec
                out = _flash_dec(q[:, 0], ck, cv,
                                 jnp.broadcast_to(base, (B,)),
                                 softcap=cfg.attn_logit_softcap)[:, None]
            else:
                valid = base + jnp.arange(Sq)                    # (Sq,)
                m = jnp.arange(Skv)[None, :] < valid[:, None]    # (Sq,Skv)
                out = _sdpa(cfg, q, ck, cv, m[None, None, :, :])
        new_cache = {"k": ck, "v": cv}
    else:
        if _use_chunked(cfg, Sq):
            from .attention import chunked_attention
            out = chunked_attention(
                q, k, v, causal=not bidirectional, window=window,
                softcap=cfg.attn_logit_softcap, prefix_len=prefix_len,
                q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
                exact_causal=cfg.exact_causal)
        else:
            if bidirectional:
                mask = jnp.ones((1, 1, Sq, Sq), bool)
            else:
                qpos = jnp.arange(Sq)[:, None]
                kpos = jnp.arange(Sq)[None, :]
                m = kpos <= qpos
                if window is not None:
                    m &= kpos > qpos - window
                if prefix_len:
                    m |= kpos < prefix_len
                mask = m[None, None]
            out = _sdpa(cfg, q, k, v, mask)
        new_cache = None
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"]), new_cache


def cross_kv_spec(cfg: ModelConfig):
    """Encoder-side projections for cross attention (computed once)."""
    return {
        "wk": p((cfg.d_model, cfg.kv_heads, cfg.d_head),
                ("embed", "kv_heads", "head_dim"), init="scaled"),
        "wv": p((cfg.d_model, cfg.kv_heads, cfg.d_head),
                ("embed", "kv_heads", "head_dim"), init="scaled"),
    }


def make_cross_kv(params, enc_out):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"])
    return k, v


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_spec(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.act in ("swiglu", "geglu"):
        return {"wi": p((d, 2, f), ("embed", None, "ff"), init="scaled"),
                "wo": p((f, d), ("ff", "embed"), init="scaled")}
    return {"wi": p((d, 1, f), ("embed", None, "ff"), init="scaled"),
            "wo": p((f, d), ("ff", "embed"), init="scaled")}


def mlp(cfg: ModelConfig, params, x, mesh_ctx=None):
    if mesh_ctx is not None:
        x = mesh_ctx.gather_seq(x)     # SP all-gather on TP-region entry
    h = jnp.einsum("bsd,dcf->bscf", x, params["wi"])
    if mesh_ctx is not None:
        # Megatron TP: intermediate sharded over model along d_ff — the
        # second matmul then emits partial sums that reduce-scatter back
        # into the SP layout at the residual add.
        h = mesh_ctx.constrain_dims(
            h, (mesh_ctx.data_axes, None, None, mesh_ctx.model_axis))
    if cfg.act == "swiglu":
        h = jax.nn.silu(h[..., 0, :]) * h[..., 1, :]
    elif cfg.act == "geglu":
        h = jax.nn.gelu(h[..., 0, :], approximate=True) * h[..., 1, :]
    else:
        h = jax.nn.gelu(h[..., 0, :], approximate=True)
    return jnp.einsum("bsf,fd->bsd", h, params["wo"])


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed_spec(cfg: ModelConfig) -> Dict:
    spec = {"tok": p((cfg.vocab, cfg.d_model), ("vocab", "embed"))}
    if not cfg.tie_embeddings:
        spec["unembed"] = p((cfg.d_model, cfg.vocab), ("embed", "vocab"))
    return spec


def embed(cfg: ModelConfig, params, tokens):
    h = params["tok"].astype(cfg.dtype)[tokens]
    if cfg.embed_scale:
        h = h * jnp.asarray(np.sqrt(cfg.d_model), h.dtype)
    return h


def unembed(cfg: ModelConfig, params, h, mesh_ctx=None):
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h, params["tok"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", h, params["unembed"])
    if mesh_ctx is not None:
        # vocab-parallel logits: the unembedding stays sharded over model;
        # the CE loss's logsumexp/gather psum over the vocab shards.
        logits = mesh_ctx.constrain_dims(
            logits, (mesh_ctx.data_axes, None, mesh_ctx.model_axis))
    if cfg.final_logit_softcap:
        c = cfg.final_logit_softcap
        logits = (c * jnp.tanh(logits.astype(jnp.float32) / c)).astype(logits.dtype)
    return logits
