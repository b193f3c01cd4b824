"""Model configuration and the parameter-spec system.

Every parameter is declared once as a ``ParamSpec`` carrying its shape and
*logical axis names*. One spec tree serves four consumers:

* ``init_params``          — deterministic parameter initialization,
* ``abstract_params``      — ShapeDtypeStructs for the AOT dry-run (no
                             allocation),
* ``repro.sharding.rules`` — logical axes → mesh ``PartitionSpec``,
* ``repro.train.checkpoint`` — stable names for sharded save/restore.
"""
from __future__ import annotations

import dataclasses
import functools
import zlib
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    arch: str
    family: str                     # dense | moe | hybrid | ssm | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    vocab: int
    n_kv_heads: int = 0             # 0 -> = n_heads (MHA)
    d_head: int = 128
    # --- attention options -------------------------------------------------
    qkv_bias: bool = False          # qwen-family
    window: Optional[int] = None    # sliding-window size for local layers
    layer_pattern: str = "G"        # repeating pattern: G=global attn,
                                    # L=local attn, R=recurrent(RG-LRU),
                                    # W=rwkv6 block
    attn_logit_softcap: Optional[float] = None   # gemma2: 50.0
    final_logit_softcap: Optional[float] = None  # gemma2: 30.0
    rope_theta: float = 10_000.0
    attn_impl: str = "auto"         # auto | xla | chunked
    attn_q_chunk: int = 2048        # chunked-attention tile sizes
    attn_kv_chunk: int = 2048
    exact_causal: bool = True       # prune upper-triangle chunks (§Perf)
    decode_kernel: str = "auto"     # decode-attention backend: "flash"
                                    # (Pallas flash-decoding / paged kernel,
                                    # interpret mode off-TPU), "xla" (dense
                                    # masked sdpa), "auto" (flash on TPU)
    # --- MLP / MoE ----------------------------------------------------------
    act: str = "swiglu"             # swiglu | geglu | gelu
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    dense_d_ff: int = 0             # d_ff of the dense ("G") layers in a
                                    # mixed dense/MoE pattern (llama4); 0 -> d_ff
    # --- recurrent (RG-LRU / RWKV6) ------------------------------------------
    rnn_width: int = 0              # RG-LRU lru width (0 -> d_model)
    conv_width: int = 4             # temporal-conv window in recurrent block
    # --- encoder-decoder / frontends -----------------------------------------
    n_encoder_layers: int = 0
    frontend: Optional[str] = None  # "audio_frames" | "patch_embed" (stubs)
    frontend_len: int = 0           # frames / patches provided by the stub
    frontend_dim: int = 0           # stub embedding dim (pre-projection)
    # --- misc -----------------------------------------------------------------
    tie_embeddings: bool = True
    embed_scale: bool = False       # gemma family: h *= sqrt(d_model)
    norm: str = "rmsnorm"
    post_norms: bool = False        # gemma2 sandwich norms
    max_seq_len: int = 8192         # positional table size where learned
    dtype: Any = jnp.bfloat16

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def lru_width(self) -> int:
        return self.rnn_width or self.d_model

    def pattern_layers(self) -> Tuple[str, ...]:
        """Per-layer kind for all n_layers, repeating ``layer_pattern``."""
        p = self.layer_pattern
        return tuple(p[i % len(p)] for i in range(self.n_layers))

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]   # logical axis names (len == ndim)
    init: str = "normal"              # normal | zeros | ones | scaled
    scale: float = 0.02
    dtype: Any = None                 # None -> model dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def p(shape, axes, init="normal", scale=0.02, dtype=None) -> ParamSpec:
    return ParamSpec(tuple(shape), tuple(axes), init, scale, dtype)


def is_spec_leaf(x) -> bool:
    return isinstance(x, ParamSpec)


def tree_paths(tree, prefix=()):
    """Yield (path_tuple, leaf) over a nested-dict spec/param tree."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from tree_paths(tree[k], prefix + (k,))
    else:
        yield prefix, tree


@functools.lru_cache(maxsize=None)
def _leaf_filler(sharding):
    """One jitted leaf initializer per output placement. Jitting fuses
    the float32 draw with the cast, so a leaf is written straight out in
    its own dtype (no float32 temporary of the whole stacked leaf) and,
    with ``sharding``, straight into its final placement."""

    def fill(key, shape, init, scale, dtype):
        if init == "zeros":
            return jnp.zeros(shape, dtype)
        if init == "ones":
            return jnp.ones(shape, dtype)
        x = jax.random.normal(key, shape, jnp.float32)
        # a no-op rounding that keeps XLA from folding ``scale`` into the
        # sampler's own sqrt(2) factor: the product then rounds exactly
        # as the unfused ``normal(...) * scale`` does
        x = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=23)
        return (x * scale).astype(dtype)

    return jax.jit(fill, static_argnums=(1, 2, 3, 4), out_shardings=sharding)


def init_params(rng: jax.Array, spec_tree, dtype=jnp.bfloat16,
                sharding=None):
    """Deterministic init: each leaf's key is folded from its path hash, so
    adding/removing parameters never reshuffles the others. ``sharding``
    places every leaf there as it is made (e.g. replicated over a serve
    mesh), so no device ever holds a leaf twice."""
    fill = _leaf_filler(sharding)

    def init_leaf(path, s: ParamSpec):
        # crc32, not hash(): stable across processes (PYTHONHASHSEED)
        key = jax.random.fold_in(
            rng, zlib.crc32("/".join(path).encode()) % (2 ** 31))
        scale = s.scale
        if s.init == "scaled":          # fan-in scaled
            fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
            scale = 1.0 / np.sqrt(fan_in)
        return fill(key, s.shape, s.init, float(scale),
                    jnp.dtype(s.dtype or dtype))

    def walk(tree, prefix=()):
        if isinstance(tree, dict):
            return {k: walk(v, prefix + (k,)) for k, v in tree.items()}
        return init_leaf(prefix, tree)

    return walk(spec_tree)


def abstract_params(spec_tree, dtype=jnp.bfloat16, sharding_fn=None):
    """ShapeDtypeStruct tree (for .lower() AOT compilation). If
    ``sharding_fn(path, spec) -> Sharding`` is given, attach shardings."""

    def walk(tree, prefix=()):
        if isinstance(tree, dict):
            return {k: walk(v, prefix + (k,)) for k, v in tree.items()}
        s: ParamSpec = tree
        sh = sharding_fn(prefix, s) if sharding_fn else None
        return jax.ShapeDtypeStruct(s.shape, s.dtype or dtype, sharding=sh)

    return walk(spec_tree)


def param_count(spec_tree) -> int:
    return sum(int(np.prod(s.shape)) for _, s in tree_paths(spec_tree))
