"""Random weights for the served model, made by the benchmark from the
seed: one jitted call on the device, every leaf written straight out in
the type it is served in.

The engine's parameter tree fixes the leaves' paths, shapes and types; the
benchmark fixes their values. Each leaf draws from its own key, folded
from the seed by the crc32 of its path, with a scale the reference
(``bench/reference.py``) reads by the same path. A path that is not in
``SCALES`` is an error: the layout changed and the reference no longer
knows what the leaf means.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Path_ = Tuple[str, ...]


def _fan_in_scale(axes):
    def scale(shape):
        return 1.0 / float(np.sqrt(np.prod([shape[a] for a in axes])))
    return scale


# leaf name (last path element, with its parent) -> std of the draw.
# Matrices are scaled by their contracted dims, so every projection keeps
# the scale of its input; norm scales (applied as 1 + scale) and biases
# are small but nonzero, so that a path which drops them reads wrong.
SCALES = {
    ("embed", "tok"): lambda s: 1.0,
    ("embed", "unembed"): _fan_in_scale([0]),
    ("attn", "wq"): _fan_in_scale([-3]),
    ("attn", "wk"): _fan_in_scale([-3]),
    ("attn", "wv"): _fan_in_scale([-3]),
    ("attn", "wo"): _fan_in_scale([-3, -2]),
    ("attn", "bq"): lambda s: 0.2,
    ("attn", "bk"): lambda s: 0.2,
    ("attn", "bv"): lambda s: 0.2,
    ("mlp", "wi"): _fan_in_scale([-3]),
    ("mlp", "wo"): _fan_in_scale([-2]),
    ("ln1", "scale"): lambda s: 0.1,
    ("ln2", "scale"): lambda s: 0.1,
    ("ln_f", "scale"): lambda s: 0.1,
}


def leaf_paths(tree, prefix: Path_ = ()):
    """(path, leaf) pairs of a nested dict, in sorted key order."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from leaf_paths(tree[k], prefix + (k,))
    else:
        yield prefix, tree


def _set(tree: Dict, path: Path_, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def make_weights(like, seed: int):
    """A tree shaped like ``like`` (arrays or ShapeDtypeStructs), drawn
    from ``seed`` in one jitted call."""
    leaves = []
    for path, leaf in leaf_paths(like):
        rule = SCALES.get(path[-2:])
        if rule is None:
            raise KeyError(f"no weight rule for leaf {'/'.join(path)}")
        leaves.append((path, tuple(leaf.shape), jnp.dtype(leaf.dtype),
                       float(rule(leaf.shape))))

    def draw(key):
        out: Dict = {}
        for path, shape, dtype, std in leaves:
            k = jax.random.fold_in(key, zlib.crc32("/".join(path).encode())
                                   % (2 ** 31))
            x = jax.random.normal(k, shape, jnp.float32) * std
            _set(out, path, x.astype(dtype))
        return out

    key = jax.random.key(seed % (2 ** 31))
    return jax.jit(draw)(key)
