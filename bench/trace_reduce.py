"""From a JAX profiler trace to the numbers the metric readers take.

``load`` reads the newest ``*.xplane.pb`` under a directory with
``jax.profiler.ProfileData`` and keeps three lists, each event as
``[name, start_ns, duration_ns, ...]``:

- ``ops``: operations that ran on a device: the ``XLA Ops`` line of every
  ``/device:`` plane, or, where there is no device plane (the CPU), the
  events that carry an ``hlo_op`` stat. Each keeps its device and a short
  text of its string stats, in which a kernel's name can be found.
- ``modules``: whole programs on a device (``XLA Modules`` lines).
- ``host``: the harness's own ``TraceAnnotation`` spans (``HOST_SPANS``
  and the ``slice`` span around the steps it measures).

Device op names are cut from their HLO text to the op's name and result
type (``short_name``). ``reduce`` turns that into the traced window (the
``slice`` span, or first to last host span where there is none), device
busy time in it (the union of the ops' intervals, averaged over
devices), the idle gaps named by the host span that covers most of
each, and totals by op (leaving out the loops that hold other ops), by
module and by kernel pattern. The loaded form is plain JSON, so a small
recorded trace can be kept beside the tests.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

HOST_SPANS = ("submit", "step", "drain", "wait_arrival")
# ops that hold other ops (a scan's loop): in the busy union, not the top
CONTAINERS = ("while", "conditional", "call")
SLICE = "slice"        # the harness's span around the steps it measures
TOP = 10


def load(directory) -> Dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(str(directory), "**",
                                          "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no trace under {directory}")
    pd = ProfileData.from_file(paths[-1])
    ops, modules, host, cpu_ops = [], [], [], []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for e in line.events:
                start, dur = int(e.start_ns), int(e.duration_ns)
                if device and line.name == "XLA Ops":
                    ops.append([short_name(e.name), start, dur, plane.name,
                                _meta(e)])
                elif device and line.name == "XLA Modules":
                    modules.append([e.name, start, dur, plane.name])
                elif not device and (e.name in HOST_SPANS
                                     or e.name == SLICE):
                    host.append([e.name, start, dur])
                elif not device:
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        cpu_ops.append([e.name, start, dur, "cpu",
                                        _meta(e)])
                        mod = str(stats.get("hlo_module", ""))
                        modules.append([mod, start, dur, "cpu"])
    if not ops:
        ops = cpu_ops
    return {"ops": ops, "modules": modules, "host": host}


def short_name(hlo: str) -> str:
    """An op's name from its HLO text: ``%fusion.3 = bf16[8,128]{1,0}
    fusion(...)`` gives ``fusion.3 bf16[8,128]``, and a tuple result
    ``(bf16[8], s32[8])``. A name that is no HLO text stays as it is."""
    if " = " not in hlo:
        return hlo
    name, rest = hlo.split(" = ", 1)
    rest = re.sub(r"\{[^{}]*\}", "", rest)
    shape = (rest[:rest.index(")") + 1] if rest.startswith("(")
             and ")" in rest else rest.split(" ", 1)[0])
    return f"{name.lstrip('%')} {shape}"


def _meta(e) -> str:
    """The event's string stats, joined: where a kernel's name shows."""
    return " ".join(f"{k}={v}" for k, v in e.stats
                    if isinstance(v, str))[:400]


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    """The parts of [lo, hi) that no merged interval of ``busy`` covers."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def name_gap(gap: Tuple[int, int], host: Sequence) -> str:
    """The host span that overlaps ``gap`` most, or ``other``."""
    best, name = 0, "other"
    for n, s, d in host:
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov > best:
            best, name = ov, n
    return name


def reduce(tr: Dict) -> Dict:
    """Busy and idle time of the traced window, and totals by op, module
    and kernel, in seconds."""
    slices = [(s, s + d) for n, s, d in tr["host"] if n == SLICE]
    host = [h for h in tr["host"] if h[0] != SLICE]
    ops = tr["ops"]
    if slices:
        lo, hi = slices[0]
    elif host:
        lo = min(s for _, s, _ in host)
        hi = max(s + d for _, s, d in host)
    elif ops:
        lo = min(o[1] for o in ops)
        hi = max(o[1] + o[2] for o in ops)
    else:
        raise ValueError("the trace holds no host span and no device op")
    by_dev: Dict[str, List[Tuple[int, int]]] = defaultdict(list)
    for name, s, d, dev, _ in ops:
        by_dev[dev].append((s, s + d))
    busy = {dev: union(clip(iv, lo, hi)) for dev, iv in by_dev.items()}
    busy_ns = [sum(e - s for s, e in iv) for iv in busy.values()]
    first = sorted(busy)[0] if busy else None
    idle = gaps(busy[first], lo, hi) if first else [(lo, hi)]
    idle.sort(key=lambda g: g[0] - g[1])
    op_s: Dict[str, float] = defaultdict(float)
    for name, s, d, dev, _ in ops:
        if lo <= s < hi and not name.startswith(CONTAINERS):
            op_s[name] += d / 1e9
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": (sum(busy_ns) / len(busy_ns) / 1e9) if busy_ns else 0.0,
        "devices": len(busy),
        "top_ops": [[n, v] for n, v in top],
        "idle_gaps": [[name_gap(g, host), (g[1] - g[0]) / 1e9]
                      for g in idle[:TOP]],
        "ops": [[n, s, d, m] for n, s, d, _, m in ops if lo <= s < hi],
        "modules": [[n, d / 1e9] for n, s, d, _ in tr["modules"]
                    if lo <= s < hi],
    }


def op_seconds(reduced: Dict, pattern: str) -> float:
    """Device seconds of the ops whose name or stats hold ``pattern``,
    summed over devices."""
    return sum(d for n, _, d, m in reduced["ops"]
               if pattern in n or pattern in m) / 1e9


def module_seconds(reduced: Dict, pattern: str) -> List[float]:
    """Durations of the whole programs whose name holds ``pattern``."""
    return [d for n, d in reduced["modules"] if pattern in n]
