"""The comparison that decides ``correct``, shown to fail.

At smoke widths on the CPU: the reference put in the program's place in
the nearest precision below bfloat16 (the control), and the timed path
broken underneath the harness, once for each fault a one-chip serving
cell can have. Each must read past the limit that sound runs stay under.
(The exchange between chips does not exist on one chip.)
"""
import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests.smoke import smoke_files

SEED = 2 ** 31 + 7


def test_control_fails_where_the_program_passes():
    files = smoke_files()
    info = {}
    out = harness.run_cell(files[1]["name"], SEED, 3.0, False,
                           require_tpu=False, files=files,
                           controls=("fp8",), info=info)
    limit = out["checks"]["max_logit_gap"]["limit"]
    assert out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] <= limit
    # the control, judged by the harness's own rule, is not correct
    assert not info["controls"]["fp8"]["correct"]
    assert info["controls"]["fp8"]["max_logit_gap"] > limit


def _break(fault):
    """A step hook that swaps the engine's compiled step for a broken one
    on its first call."""
    def hook(eng):
        if getattr(eng, "_broken", False):
            return
        orig, B, V = eng._step, eng.B, eng.cfg.vocab

        def step(p, pool, t, meta, tables, prev, done):
            if fault == "state_unchanged":
                keep = jax.tree.map(jnp.copy, pool)
                out, _, d = orig(p, pool, t, meta, tables, prev, done)
                return out, keep, d
            if fault == "half_batch":
                meta = meta.at[1, B // 2:].set(0)
                return orig(p, pool, t, meta, tables, prev, done)
            out, new, d = orig(p, pool, t, meta, tables, prev, done)
            return (out + 1) % V, new, d

        eng._step = step
        eng._broken = True
    return hook


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_broken_step_is_not_correct(fault):
    files = smoke_files()
    out = harness.run_cell(files[1]["name"], SEED, 3.0, False,
                           require_tpu=False, files=files,
                           step_hook=_break(fault))
    assert not out["correct"], out["checks"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
