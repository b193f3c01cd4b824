#!/usr/bin/env python3
"""Readings that the correctness limits are set from, many seeds in one
process (set-up is long, and a chip belongs to one process).

  python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \\
      --seconds 10 --controls fp8,int8 --control-seeds 3

For each seed: one run of the cell, as ``bench/run.py`` makes it, at the
cell's own load with a short window, and the check's numbers. For the
first ``--control-seeds`` seeds the same sample is also read by the
control: the reference in the program's place at a lower precision,
judged by the harness's own rule (its widest gap and its ``correct``).
One JSON line a seed goes to standard output. The benchmark's own runs never
run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", default="fp8")
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.jaxenv import use_checkout
    use_checkout()
    from bench import harness
    controls = tuple(c for c in args.controls.split(",") if c)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        info = {}
        out = harness.run_cell(
            args.workload, seed, args.seconds, False,
            t_start=T_START if i == 0 else time.perf_counter(),
            controls=controls if i < args.control_seeds else (), info=info)
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "checks": out["checks"], "controls": info["controls"],
            "sample": info["sample"], "metrics": out["metrics"],
            "attempted": out["attempted"], "failed": out["failed"]}),
            flush=True)
        info.clear()
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
