#!/usr/bin/env python3
"""Find a cell's knee: the highest Poisson rate at which the backlog of
requests waiting for a slot does not grow over a window.

  python3 bench/sweep.py --workload <cell> --rates 2,2.5,3 --seconds 30

One process builds the cell's engine and fills its store once, then plays
the cell's mix at each rate in turn for ``--seconds``, draining the
engine between rates. For each rate one JSON line: the backlog at the
start and end of the window and its least-squares slope (requests per
second), time to first token, tokens per second and the median step.
The cells hold the rate that this finds, written into their traffic
files; the benchmark's own runs never search.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.jaxenv import use_checkout
    use_checkout()
    import numpy as np
    from bench import harness
    from bench.traffic import Traffic

    bench, cell, config, mix = harness.cell_files(args.workload)
    h = harness.Harness(cell, config, mix, args.seed, args.seconds, False,
                        t_start=T_START)
    h.prepare()
    harness.log(f"sweep set-up {time.perf_counter() - T_START!r} s, "
                f"phases {h.phases}")
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        tr = Traffic(dict(mix, rate_rps=rate), args.seed, h.cfg.vocab)
        t0 = time.perf_counter()
        T = args.seconds
        n = max(1, round(rate * T))
        win = [harness.Served(t0 + r.due, r.prompt, r.max_new, "window")
               for r in tr.stream(100 + i, n, span=T)]
        backlog = []
        first = len(h.steps)

        def on_step(step):
            backlog.append((step.t1 - t0, len(h.queued)))

        h.play(win, lambda now: now >= t0 + T, on_step)
        t, q = np.array(backlog, dtype=float).T
        slope = float(np.polyfit(t, q, 1)[0]) if len(t) > 2 else 0.0
        steps = h.steps[first:]
        ttft = [s.token_times[0] - s.due for s in win if s.token_times]
        toks = sum(1 for s in h.requests for x in s.token_times
                   if t0 <= x <= t0 + T)
        print(json.dumps({
            "rate_rps": rate, "requests": n, "backlog_start": int(q[0]),
            "backlog_end": int(q[-1]), "backlog_slope": slope,
            "first_tokens": len(ttft),
            "ttft_p50_s": float(np.median(ttft)) if ttft else None,
            "ttft_p95_s": float(np.percentile(ttft, 95)) if ttft else None,
            "output_tok_s": toks / T,
            "step_wall_median_s": float(np.median(
                [s.t1 - s.t0 for s in steps])),
            "busy_slots_mean": float(np.mean([s.busy for s in steps]))}),
            flush=True)
        # drain before the next rate
        h.play([], lambda now: False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
