"""Plain float32 reference of the served model, and its lower-precision
control.

The architecture is Qwen2's (arXiv:2407.10671; Qwen1.5 is the same block):
token embedding; per layer a pre-RMSNorm block of grouped-query attention
with biased q/k/v projections and rotary position embedding (rotate-half,
``theta ** (-2i / d_head)``), then a pre-RMSNorm SwiGLU MLP, each added to
the residual; a final RMSNorm and an untied output projection. An RMSNorm
weight is ``1 + scale``, the leaf the weights carry.

It imports nothing of the program. It reads the weights the benchmark made
(``bench/weights.py``) by their paths, upcasts each to float32 where it is
used, and computes every product at ``Precision.HIGHEST``. One sequence at
a time, padded to a fixed length so that one program serves every
request; layers run one by one and the wide matrices in column blocks, so
it fits on the chip beside the weights.

``quant`` switches on the control: every projection and the output head
with its weights rounded per output column and its input rounded per
token to ``int8`` or ``fp8`` (e4m3), the step below the configuration's
bfloat16.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _qdq(x, axis: int, quant: Optional[str]):
    """Round ``x`` to ``quant`` with one scale per slice along ``axis``
    (the reduced axis), and back to float32."""
    if quant is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if quant == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if quant == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    raise ValueError(f"unknown control precision {quant!r}")


def _mm(x, w, quant):
    """(T, K) @ (K, N) in float32, rounded first under the control."""
    x = _qdq(x, -1, quant)
    w = _qdq(w.astype(F32), 0, quant)
    return jnp.dot(x, w, precision=HI)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(F32))


def _rope(x, theta):
    """x: (T, heads, D), positions 0..T-1."""
    T, _, D = x.shape
    half = D // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _divisor_at_most(n: int, k: int) -> int:
    return max(d for d in range(1, k + 1) if n % d == 0)


@partial(jax.jit, static_argnames=("spec", "quant"))
def _layer(h, unit, li, *, spec, quant):
    """One decoder layer over a whole (T, d) sequence; ``unit`` holds the
    stacked leaves of every layer, ``li`` picks one."""
    d, H, KV, Dh, eps, theta = (spec["d"], spec["H"], spec["KV"], spec["Dh"],
                                spec["eps"], spec["theta"])
    T = h.shape[0]

    def at(a):
        return jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False)

    at_ = unit["attn"]
    x = _rmsnorm(h, at(unit["ln1"]["scale"]), eps)
    q = _mm(x, at(at_["wq"]).reshape(d, H * Dh), quant) \
        + at(at_["bq"]).astype(F32).reshape(-1)
    k = _mm(x, at(at_["wk"]).reshape(d, KV * Dh), quant) \
        + at(at_["bk"]).astype(F32).reshape(-1)
    v = _mm(x, at(at_["wv"]).reshape(d, KV * Dh), quant) \
        + at(at_["bv"]).astype(F32).reshape(-1)
    q = _rope(q.reshape(T, H, Dh), theta)
    k = _rope(k.reshape(T, KV, Dh), theta)
    v = v.reshape(T, KV, Dh)
    G = H // KV
    qg = q.reshape(T, KV, G, Dh).transpose(1, 2, 0, 3)      # (KV, G, T, Dh)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    def head_group(args):
        qh, kh, vh = args                                   # (G,T,Dh),(T,Dh)
        s = jnp.einsum("gtd,sd->gts", qh, kh, precision=HI) / np.sqrt(Dh)
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gts,sd->gtd", p, vh, precision=HI)

    o = jax.lax.map(head_group, (qg, k.transpose(1, 0, 2),
                                 v.transpose(1, 0, 2)))     # (KV, G, T, Dh)
    o = o.transpose(2, 0, 1, 3).reshape(T, H * Dh)
    h = h + _mm(o, at(at_["wo"]).reshape(H * Dh, d), quant)

    mlp = unit["mlp"]
    x = _rmsnorm(h, at(unit["ln2"]["scale"]), eps)
    wi, wo = at(mlp["wi"]), at(mlp["wo"])                   # (d,2,F), (F,d)
    F = wi.shape[-1]
    nc = _divisor_at_most(F, 8)
    fc = F // nc
    xq = _qdq(x, -1, quant)

    def up(c):
        w = jax.lax.dynamic_slice_in_dim(wi, c * fc, fc, axis=2).astype(F32)
        w = _qdq(w, 0, quant)
        g = jnp.dot(xq, w[:, 0], precision=HI)
        u = jnp.dot(xq, w[:, 1], precision=HI)
        return jax.nn.silu(g) * u                           # (T, fc)

    a = jax.lax.map(up, jnp.arange(nc))                     # (nc, T, fc)
    a = a.transpose(1, 0, 2).reshape(T, F)
    a = _qdq(a, -1, quant).reshape(T, nc, fc)
    # per output column of wo: one scale over all F rows, read in bf16
    wscale = (jnp.max(jnp.abs(wo), axis=0).astype(F32)
              if quant is not None else None)

    def down(acc, c):
        w = jax.lax.dynamic_slice_in_dim(wo, c * fc, fc, axis=0).astype(F32)
        if quant is not None:
            full = 127.0 if quant == "int8" else 448.0
            s = jnp.maximum(wscale, 1e-30) / full
            w = (jnp.clip(jnp.round(w / s), -127, 127) * s
                 if quant == "int8" else
                 (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s)
        ac = jax.lax.dynamic_index_in_dim(a, c, 1, keepdims=False)
        return acc + jnp.dot(ac, w, precision=HI), None

    out, _ = jax.lax.scan(down, jnp.zeros_like(h), jnp.arange(nc))
    return h + out


@partial(jax.jit, static_argnames=("spec", "quant", "rows"))
def _head(h, ln_f, unembed, start, *, spec, quant, rows):
    x = jax.lax.dynamic_slice_in_dim(h, start, rows, axis=0)
    x = _qdq(_rmsnorm(x, ln_f, spec["eps"]), -1, quant)
    V = unembed.shape[1]
    nv = _divisor_at_most(V, 8)
    vc = V // nv

    def part(c):
        w = jax.lax.dynamic_slice_in_dim(unembed, c * vc, vc, axis=1)
        return jnp.dot(x, _qdq(w.astype(F32), 0, quant), precision=HI)

    return jax.lax.map(part, jnp.arange(nv)).transpose(1, 0, 2) \
        .reshape(rows, V)


def model_spec(arch: Dict) -> tuple:
    """The reference's sizes from a configuration file's ``model`` block
    (Hugging Face key names), as a hashable static argument."""
    return tuple(sorted({
        "d": arch["hidden_size"], "H": arch["num_attention_heads"],
        "KV": arch["num_key_value_heads"],
        "Dh": arch["hidden_size"] // arch["num_attention_heads"],
        "eps": arch["rms_norm_eps"], "theta": arch["rope_theta"],
    }.items()))


class Reference:
    """Logits of the reference at chosen positions of one sequence."""

    def __init__(self, weights, arch: Dict, layers: int, max_len: int,
                 max_rows: int) -> None:
        self.w = weights
        self.spec = dict(model_spec(arch))
        self._spec_key = model_spec(arch)
        self.layers = layers
        self.T = max_len
        self.rows = max_rows
        (self.unit_key,) = weights["stack"].keys()

    def logits(self, tokens, first: int, n: int, quant: Optional[str] = None
               ) -> np.ndarray:
        """Float32 logits at positions ``first .. first+n-1`` of
        ``tokens``: row i predicts ``tokens[first + i + 1]``."""
        if len(tokens) > self.T or n > self.rows:
            raise ValueError(f"{len(tokens)} tokens and {n} rows exceed the "
                             f"reference's {self.T} and {self.rows}")
        spec = _FrozenSpec(self._spec_key)
        ids = np.zeros((self.T,), np.int32)
        ids[:len(tokens)] = tokens
        h = self.w["embed"]["tok"][jnp.asarray(ids)].astype(F32)
        unit = self.w["stack"][self.unit_key]
        for li in range(self.layers):
            h = _layer(h, unit, jnp.int32(li), spec=spec, quant=quant)
        start = min(first, self.T - self.rows)
        out = _head(h, self.w["ln_f"]["scale"], self.w["embed"]["unembed"],
                    jnp.int32(start), spec=spec, quant=quant,
                    rows=self.rows)
        off = first - start
        return np.asarray(out[off:off + n])


class _FrozenSpec(dict):
    """A dict that jit can take as a static argument."""

    def __init__(self, items):
        super().__init__(items)
        self._key = tuple(items)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _FrozenSpec) and self._key == other._key
