"""The benchmark's cells at smoke widths for the CPU tests: each cell's
configuration file with the widths of the repo's smoke configuration of
the same architecture (``repro.configs.get(arch, smoke=True)``), and its
traffic mix with lengths cut to what a 256-position engine holds. The
harness, the traffic generator and the check are the benchmark's own.
Nothing here is a measurement."""
from __future__ import annotations

import copy
import json

from bench.harness import BENCH, find, load_benchmark
from bench.traffic import load_mix

SLOTS, BT, MAX_SEQ, ROWS = 4, 16, 256, 160


def cell_names():
    return [c["name"] for c in load_benchmark()["workloads"]]


def smoke_files(name: str = None, rate: float = 8.0, limit: float = 0.07,
                mix_overrides=None, config: str = None):
    """(benchmark, cell, configuration, mix) of cell ``name`` (the first
    cell by default) at smoke widths. ``mix_overrides`` and ``config``
    (a file under ``bench/configs/``) play the cell's traffic under
    another mix or configuration: cells not yet in BENCHMARK.json."""
    from repro import configs
    bench = copy.deepcopy(load_benchmark())
    cell = dict(find(bench["workloads"], name or cell_names()[0],
                     "workload"))
    config = json.loads(
        (BENCH / "configs" / f"{config or cell['config']}.json").read_text())
    argv = config["serve"]["argv"]
    arch = argv[argv.index("--arch") + 1]
    c = configs.get(arch, smoke=True)
    config["model"].update({
        "hidden_size": c.d_model, "intermediate_size": c.d_ff,
        "num_attention_heads": c.n_heads, "num_key_value_heads": c.n_kv_heads,
        "num_hidden_layers": c.n_layers, "vocab_size": c.vocab,
        "rope_theta": c.rope_theta})
    row_bytes = 2 * c.n_layers * BT * c.n_kv_heads * c.d_head * 2
    free = ROWS - SLOTS * (MAX_SEQ // BT) - 1
    config["serve"]["argv"] = [
        "--arch", arch, "--smoke", "--slots", str(SLOTS),
        "--block-tokens", str(BT), "--max-seq", str(MAX_SEQ),
        "--pool-blocks", str(ROWS),
        "--cache-kb", str(free * row_bytes // 1024),
        "--policy", "lerc", "--paged-attention", "--requests", "0"]
    config["check"] = dict(config["check"], max_logit_gap=limit,
                           max_requests=12, min_tokens=80)
    mix = load_mix(cell["traffic"])
    mix.update({
        "rate_rps": rate, "families": 8,
        "prefix_tokens": {"median": 64, "sigma": 0.5, "min": 32, "max": 128},
        "suffix_tokens": {"median": 16, "sigma": 0.5, "min": 8, "max": 64},
        "output_tokens": {"median": 8, "sigma": 0.5, "min": 4, "max": 32},
        "warmup": {"fill_requests": 8, "lead_in_s": 1.0},
        "tail_limit_s": 60})
    mix.update(mix_overrides or {})
    return bench, cell, config, mix
