"""The serve path's kernels and step, compiled for a TPU v5e that is
described, not attached.

Interpret mode (every other kernel test) runs the kernels' logic but none
of Mosaic's rules: block shapes that break the (8, 128) tiling, SMEM
operands, or a step that does not fit the chip's memory only fail here.
Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler's library, and every
test worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.models import init_decode_cache, model_spec
from repro.models.common import abstract_params
from repro.serve.kv_pool import _pool_leaf_shape

# qwen2_7b at its published widths, depth cut to what one chip holds
H, KV, D = 28, 4, 128
LAYERS = 14
POOL_ROWS = 8192            # bt=16: 448 KiB a row, 3.5 GiB in all
V5E_HBM = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("bt", [8, 16])
@pytest.mark.parametrize("S", [1, 8])
def test_paged_kernel_compiles_for_v5e(one_chip, bt, S):
    from repro.kernels.paged_attention import paged_decode_attention
    B, NB, NW = 4, 512, 32
    f = jax.jit(lambda q, kp, vp, t, qp: paged_decode_attention(
        q, kp, vp, t, qp, interpret=False))
    compiled = f.lower(
        _spec(one_chip, (B, S, H, D), jnp.bfloat16),
        _spec(one_chip, (NB, bt, KV, D), jnp.bfloat16),
        _spec(one_chip, (NB, bt, KV, D), jnp.bfloat16),
        _spec(one_chip, (B, NW), jnp.int32),
        _spec(one_chip, (B, S), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("S_cache", [1024, 200])   # split-K, one tile
def test_decode_kernel_compiles_for_v5e(one_chip, S_cache):
    from repro.kernels.decode_attention import decode_attention
    B = 4
    f = jax.jit(lambda q, k, v, n: decode_attention(q, k, v, n,
                                                    interpret=False))
    compiled = f.lower(
        _spec(one_chip, (B, H, D), jnp.bfloat16),
        _spec(one_chip, (B, S_cache, KV, D), jnp.bfloat16),
        _spec(one_chip, (B, S_cache, KV, D), jnp.bfloat16),
        _spec(one_chip, (B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("S,NW", [(8, 20), (1, 32)])   # prefill, decode
def test_engine_paged_step_compiles_for_v5e(one_chip, monkeypatch, S, NW):
    """The engine's jitted paged step at published widths and 14 layers,
    with the Mosaic kernel in it, fits one chip beside its KV pool."""
    import repro.kernels.ops as ops
    from repro.serve.engine import _step_fn
    # the CPU backend would pick the XLA page gather and interpret mode;
    # steer both to the TPU branch for this compile
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = configs.get("qwen2_7b").replace(n_layers=LAYERS,
                                          decode_kernel="flash")
    B, bt = 4, 16
    params = abstract_params(model_spec(cfg), dtype=cfg.dtype,
                             sharding_fn=lambda path, s: one_chip)
    template = jax.eval_shape(lambda: init_decode_cache(cfg, 1, 8))
    pool = jax.tree.map(
        lambda leaf: _spec(one_chip,
                           _pool_leaf_shape(leaf.shape, POOL_ROWS, bt),
                           leaf.dtype),
        template)
    step = _step_fn(cfg, True, -1, None)
    compiled = step.lower(
        params, pool,
        _spec(one_chip, (B, S), jnp.int32),           # tokens
        _spec(one_chip, (5, B), jnp.int32),           # meta
        _spec(one_chip, (B, NW), jnp.int32),          # block tables
        _spec(one_chip, (B,), jnp.int32),             # previous argmax
        _spec(one_chip, (B,), jnp.bool_)).compile()   # done mask
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    pool_bytes = sum(leaf.size * leaf.dtype.itemsize
                     for leaf in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes == pool_bytes     # pool updated in place
    assert total < V5E_HBM, f"step needs {total / 2 ** 30:.2f} GiB"
