"""Where JAX keeps its files in a run of the benchmark: everything inside
the checkout. Called by the command-line entries before JAX is imported."""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def use_checkout() -> None:
    """Put the program and the benchmark on the path, the persistent
    compilation cache at ``.jax_cache/`` (the program takes the directory
    the environment names) and the TPU runtime's logs under
    ``.bench_trace/``; then cache every compiled program, however quick,
    and keep every one: a cap on the directory's size, where the
    environment sets one, would evict a cell's programs between runs."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_trace" / "tpu"))
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
