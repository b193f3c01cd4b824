"""Render reports from a serve trace (``repro.launch.serve --trace``).

Reads the Chrome/Perfetto trace-event JSON the ``repro.obs`` recorder
exports and prints, without importing the serving stack:

* a **TTFT waterfall** — per request: arrival, admission wait, time to
  first token, decode time, all on the engine's virtual clock;
* a **step-time breakdown** — wall time by span from the ``X`` events:
  the engine's phases (``engine.step`` holding ``engine.admit`` /
  ``engine.plan`` / ``engine.feed`` / ``engine.avals`` /
  ``engine.launch`` / ``engine.publish`` / ``engine.finish`` /
  ``engine.eos_sync``, and ``engine.submit`` / ``engine.readback``
  outside it) and the store's (``store.register`` / ``store.lookup`` /
  ``store.insert`` / ``store.complete``);
* **tier-flow counts** — a Sankey's edge list: how many blocks moved
  device→host, host→disk, disk→device, … and how many died per tier;
* **top ineffective-hit causes** — the headline analytic: which gaps
  (evicted / demoted-to-host / demoted-to-disk / never-cached) blocked
  otherwise-warm chains, summed from every ``store.lookup`` (a span;
  older traces hold it as an instant);
* **bus traffic** by message kind;
* **latency stats reconstructed from the trace alone** — the same
  TTFT/TPOT percentiles and goodput ``repro.serve.latency_stats``
  computes live (``tests/test_obs.py`` asserts equality), from the
  request lifecycle events' args.

Usage:
  python -m benchmarks.trace_report trace.json
  python -m benchmarks.trace_report trace.json --check   # CI validation

``--check`` exits non-zero unless the file is valid trace-event JSON
with at least one complete request span — the CI gate for the traced
serve smoke.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from typing import Dict, List, Optional


def _pct(xs: List[float], q: float) -> float:
    """``np.percentile(..., q)`` with linear interpolation, dependency-
    free so the report runs anywhere, and 0.0 on an empty sample (the
    same NaN-free convention as ``repro.serve.latency_stats``)."""
    if not xs:
        return 0.0
    s = sorted(xs)
    if len(s) == 1:
        return float(s[0])
    rank = (len(s) - 1) * q / 100.0
    lo = int(rank)
    hi = min(lo + 1, len(s) - 1)
    return float(s[lo] + (s[hi] - s[lo]) * (rank - lo))


def load(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError(f"{path}: not a trace-event JSON object "
                         "(no 'traceEvents' key)")
    return doc


# --------------------------------------------------------------- extraction
def request_records(events: List[dict]) -> List[dict]:
    """One record per request whose lifecycle CLOSED inside the ring: the
    ``e`` event of the ``req`` async track carries everything
    ``latency_stats`` needs. Enriched with the admission time from the
    ``n``/"admitted" event when that survived the ring."""
    admitted_at: Dict[tuple, float] = {}
    out: List[dict] = []
    for ev in events:
        if ev.get("name") != "req" or "id" not in ev:
            continue
        key = (ev.get("pid", 0), ev["id"])
        args = ev.get("args") or {}
        if ev["ph"] == "n" and args.get("event") == "admitted":
            admitted_at[key] = ev.get("ts", 0.0)
        elif ev["ph"] == "e":
            out.append({**args, "_key": key})
    for r in out:
        r["admitted_ts"] = admitted_at.get(r["_key"])
    return out


def rejected_count(events: List[dict]) -> int:
    return sum(1 for ev in events if ev.get("ph") == "i"
               and ev.get("name") == "rejected")


def retried_count(events: List[dict]) -> int:
    """Bounces the trace loop re-offered (``sched.retry`` instants);
    the engine's ``rejected`` instant fires for those too, so final
    rejections are ``rejected_count - retried_count``."""
    return sum(1 for ev in events if ev.get("ph") == "i"
               and ev.get("name") == "sched.retry")


def latency_from_trace(events: List[dict]) -> Dict[str, float]:
    """Reconstruct ``repro.serve.latency_stats`` from the trace alone —
    identical keys, identical rounding."""
    reqs = request_records(events)
    ttft = [r["first_token_at"] - r["arrival"] for r in reqs
            if r.get("first_token_at") is not None]
    tpot = [(r["finished_at"] - r["first_token_at"]) / (r["n_generated"] - 1)
            for r in reqs
            if r.get("finished_at") is not None
            and r.get("first_token_at") is not None
            and r.get("n_generated", 0) > 1]
    met = 0
    for r in reqs:
        if r.get("cancelled") or r.get("first_token_at") is None:
            continue
        if r.get("deadline") is None:
            met += r.get("finished_at") is not None
        else:
            met += r["first_token_at"] <= r["deadline"]
    retried = retried_count(events)
    rejected = rejected_count(events) - retried
    offered = len(reqs) + rejected
    out = {"n_offered": offered, "n_rejected": rejected,
           "n_retried": retried,
           "goodput": round(float(met) / max(offered, 1), 4)}
    for name, xs in (("ttft", ttft), ("tpot", tpot)):
        for q in (50, 95, 99):
            out[f"{name}_p{q}"] = round(_pct(xs, q), 4)
    return out


def step_breakdown(events: List[dict]) -> Dict[str, dict]:
    out: Dict[str, dict] = defaultdict(lambda: {"n": 0, "total_us": 0.0})
    for ev in events:
        if ev.get("ph") != "X":
            continue
        rec = out[ev["name"]]
        rec["n"] += 1
        rec["total_us"] += ev.get("dur", 0.0)
    return dict(out)


def tier_flows(events: List[dict]) -> Dict[tuple, int]:
    """Sankey edge counts from the store's move instants. Eviction
    instants come in two arg shapes: tier-0 kills carry ``tier: 0``
    (plain-store path), slow-tier kills carry ``src`` with no ``dst``."""
    flows: Dict[tuple, int] = defaultdict(int)
    for ev in events:
        if ev.get("ph") != "i":
            continue
        name, args = ev.get("name"), ev.get("args") or {}
        if name in ("store.demote", "store.promote"):
            flows[(args.get("src", "?"), args.get("dst", "?"))] += 1
        elif name == "store.evict":
            src = args.get("src", "device" if args.get("tier", 0) == 0
                            else "?")
            flows[(src, "dead")] += 1
    return dict(flows)


def ineffective_causes(events: List[dict]) -> Dict[str, int]:
    causes: Dict[str, int] = defaultdict(int)
    for ev in events:
        if ev.get("ph") in ("X", "i") and ev.get("name") == "store.lookup":
            for cause, n in ((ev.get("args") or {})
                             .get("ineffective", {}) or {}).items():
                causes[cause] += int(n)
    return dict(causes)


def bus_traffic(events: List[dict]) -> Dict[str, dict]:
    out: Dict[str, dict] = defaultdict(lambda: {"n": 0, "bytes": 0})
    for ev in events:
        name = ev.get("name", "")
        if ev.get("ph") == "i" and name.startswith("bus."):
            rec = out[name[len("bus."):]]
            rec["n"] += 1
            rec["bytes"] += (ev.get("args") or {}).get("bytes", 0)
    return dict(out)


# ----------------------------------------------------------------- reporting
def print_report(doc: dict, top: int = 20) -> None:
    events = doc["traceEvents"]
    other = doc.get("otherData", {})
    print(f"trace: {len(events)} events  timebase={other.get('timebase')}"
          f"  emitted={other.get('events_emitted')}"
          f"  dropped={other.get('events_dropped')}")

    reqs = sorted(request_records(events),
                  key=lambda r: r.get("arrival", 0.0))
    if reqs:
        print(f"\n== TTFT waterfall ({len(reqs)} requests, virtual clock) ==")
        print(f"  {'rid':>6} {'arrival':>10} {'ttft':>10} {'decode':>10} "
              f"{'tokens':>6}  flags")
        for r in reqs[:top]:
            ft, fin = r.get("first_token_at"), r.get("finished_at")
            ttft = (ft - r["arrival"]) if ft is not None else None
            dec = (fin - ft) if ft is not None and fin is not None else None
            flags = []
            if r.get("cancelled"):
                flags.append("cancelled")
            if r.get("deadline") is not None and ft is not None \
                    and ft > r["deadline"]:
                flags.append("late")
            if r.get("prefill_skipped"):
                flags.append(f"skip={r['prefill_skipped']}")
            print(f"  {r.get('rid', '?'):>6} {r.get('arrival', 0):>10.3f} "
                  f"{ttft if ttft is not None else float('nan'):>10.3f} "
                  f"{dec if dec is not None else float('nan'):>10.3f} "
                  f"{r.get('n_generated', 0):>6}  {' '.join(flags)}")
        if len(reqs) > top:
            print(f"  ... {len(reqs) - top} more (--top to widen)")

    steps = step_breakdown(events)
    if steps:
        print("\n== step-time breakdown (wall, from X spans) ==")
        order = sorted(steps, key=lambda k: -steps[k]["total_us"])
        for name in order:
            rec = steps[name]
            mean = rec["total_us"] / max(rec["n"], 1)
            print(f"  {name:16s} n={rec['n']:<7} "
                  f"total={rec['total_us'] / 1e3:10.2f}ms "
                  f"mean={mean:8.1f}us")

    flows = tier_flows(events)
    if flows:
        print("\n== tier flows (blocks) ==")
        for (src, dst), n in sorted(flows.items(), key=lambda kv: -kv[1]):
            print(f"  {src:>7} -> {str(dst):7s} {n}")

    causes = ineffective_causes(events)
    if causes:
        print("\n== ineffective-hit causes (blocked warm blocks) ==")
        total = sum(causes.values())
        for cause, n in sorted(causes.items(), key=lambda kv: -kv[1]):
            print(f"  {cause:14s} {n:8d}  ({100.0 * n / total:5.1f}%)")

    bus = bus_traffic(events)
    if bus:
        print("\n== bus traffic ==")
        for kind, rec in sorted(bus.items(), key=lambda kv: -kv[1]["n"]):
            print(f"  {kind:16s} n={rec['n']:<8} bytes={rec['bytes']}")

    print("\n== latency stats (reconstructed from trace) ==")
    for k, v in latency_from_trace(events).items():
        print(f"  {k:12s} {v}")


def check(doc: dict) -> List[str]:
    """CI validation: Perfetto-loadable shape + nonempty request spans.
    Returns a list of problems (empty = pass)."""
    problems: List[str] = []
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        return ["traceEvents missing or empty"]
    for i, ev in enumerate(events):
        if not isinstance(ev, dict) or "ph" not in ev or "name" not in ev:
            problems.append(f"event {i}: missing ph/name")
            break
        if ev["ph"] != "M" and "ts" not in ev:
            problems.append(f"event {i} ({ev['name']}): missing ts")
            break
    reqs = request_records(events)
    if not reqs:
        problems.append("no complete request lifecycle spans "
                        "(name='req', ph 'b'..'e')")
    for r in reqs:
        for k in ("rid", "arrival", "n_generated", "cancelled"):
            if k not in r:
                problems.append(f"request record missing {k!r}: {r}")
                return problems
    return problems


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="trace-event JSON from --trace")
    ap.add_argument("--check", action="store_true",
                    help="validate instead of report: exit 1 unless the "
                         "trace is loadable and has request spans")
    ap.add_argument("--top", type=int, default=20,
                    help="rows in the TTFT waterfall")
    args = ap.parse_args(argv)
    try:
        doc = load(args.trace)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    if args.check:
        problems = check(doc)
        if problems:
            for p in problems:
                print(f"FAIL: {p}", file=sys.stderr)
            return 1
        reqs = request_records(doc["traceEvents"])
        print(f"OK: {len(doc['traceEvents'])} events, "
              f"{len(reqs)} request spans")
        return 0
    print_report(doc, top=args.top)
    return 0


if __name__ == "__main__":
    sys.exit(main())
