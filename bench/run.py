#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout of the repository, on a machine that
holds the chips the cell asks for. ``BENCHMARK.json`` names the cell's
configuration (``bench/configs/``) and traffic (``bench/traffic/``); the
metrics it reports are read by ``bench/metrics/<name>.py``. With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer ones, from a run that profiles a slice of its
window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), and last ``checks``: each number the correctness check
compared, with its limit. The same numbers close standard error. A run
that finds no TPU, fewer chips than the cell needs, or no repository
beside the benchmark exits non-zero and prints no result.

JAX's persistent compilation cache lives in ``.jax_cache/`` at the root
of the checkout, so only a cell's first run there compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def fail(msg: str) -> int:
    print(f"bench: FAILED: {msg}", file=sys.stderr, flush=True)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"no repository beside the benchmark "
                    f"({ROOT / 'src' / 'repro'} is missing)")
    sys.path.insert(0, str(ROOT))
    from bench.jaxenv import use_checkout
    use_checkout()
    from bench import harness
    try:
        out = harness.run_cell(args.workload, args.seed % 2 ** 63,
                               args.seconds, bool(args.trace),
                               t_start=T_START)
    except harness.BenchError as e:
        return fail(str(e))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
