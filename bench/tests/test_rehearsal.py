"""Every cell's control flow at smoke widths on the CPU, through the
harness's test-only entry (``run_cell(require_tpu=False, files=...)``),
and the command's refusal to run without a TPU or without the program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.smoke import cell_names, smoke_files

ROOT = harness.ROOT


def _no_result(proc) -> bool:
    return not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_command_on_cpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2_7b.sessions",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_command_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2_7b.sessions",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert _no_result(proc)


# every cell, the first one traced, and what the cells left for later
# would play: unique prompts, load above the knee, bursty arrivals and
# qwen1_5_110b
CASES = ([(name, False, None, None) for name in cell_names()]
         + [(cell_names()[0], True, None, None),
            (cell_names()[0], False, {"shared_prefix": False}, None),
            (cell_names()[0], False, {"regime": "over"}, None),
            (cell_names()[0], False, {"arrivals": "bursty"}, None),
            (cell_names()[0], False, None, "qwen1_5_110b")])


@pytest.mark.parametrize("name,trace,mix_overrides,config", CASES)
def test_smoke_cell(name, trace, mix_overrides, config):
    files = smoke_files(name, mix_overrides=mix_overrides, config=config)
    bench, cell, mix = files[0], files[1], files[3]
    if mix["regime"] == "over":
        # above the knee a cell reports no TTFT
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "ttft" in m["name"] or "ttft" in m.get("moves", ""):
                m["workloads"] = []
    info = {}
    out = harness.run_cell(cell["name"], 2 ** 31 + 11, 3.0, trace,
                           require_tpu=False, files=files, info=info)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    json.dumps(out)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    h = info["harness"]
    assert h.window_compiles == 0 and not h.pool_grew
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in harness.reported(bench, cell["name"], kind)}
    assert want
    if trace:
        # the paged kernel runs interpreted on the CPU: no kernel to time
        assert set(out["metrics"]) == want - {"paged_attn_roofline"}
        assert out["device"]["busy_s"] > 0
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(out["metrics"]) == want
        assert ("ttft_p95_ms" in want) == (mix["regime"] == "below")
    run = info["run"]
    if mix["shared_prefix"]:
        assert run.counters["effective_hits"] > 0
    else:
        assert run.counters["effective_hits"] == 0
    # every seed plays the same work: the window's sizes at the rate
    assert len(run.window_requests) == round(mix["rate_rps"] * 3.0)


@pytest.mark.parametrize("arrivals", ["poisson", "bursty"])
def test_seeds_play_the_same_work_in_order(arrivals):
    from bench.traffic import Traffic
    mix = dict(smoke_files()[3], arrivals=arrivals)
    a = Traffic(mix, 1, 512).stream(3, 40, span=10.0)
    b = Traffic(mix, 2 ** 40 + 3, 512).stream(3, 40, span=10.0)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new for r in a] == [r.max_new for r in b]
    assert [r.due for r in a] == [r.due for r in b]
    assert [r.family for r in a] == [r.family for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert max(r.due for r in a) < 10.0
    again = Traffic(mix, 1, 512).stream(3, 40, span=10.0)
    assert [r.prompt for r in a] == [r.prompt for r in again]


def test_prompt_lengths_are_arbitrary():
    from bench.traffic import Traffic
    mix = smoke_files()[3]
    reqs = Traffic(mix, 5, 512).stream(3, 200)
    assert len({len(r.prompt) % 8 for r in reqs}) == 8
