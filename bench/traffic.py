"""Open-loop request traffic, made from a mix file and a seed.

A mix is a JSON file ``bench/traffic/<name>.json`` of parameters: arrival
process and rate, prefix families and their popularity, and the length
distributions of the shared prefix, the unique suffix and the output. A
file may name a ``base`` mix whose keys it inherits and overrides, so a
new rate or a new arrival shape is a new data file and no new code.

Every seed plays the same work in the same order. The sizes of the
requests (family rank, prefix, suffix and output lengths) and the gaps
between arrivals are drawn from the mix's fixed ``shape_seed``; the run's
seed draws only the token ids. Two seeds then differ in what the tokens
say, not in how much there is to do or when it arrives.

The arrival processes are copies of ``repro.sim.poisson_arrivals`` and
``repro.sim.bursty_arrivals``, written as gap generators.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> Dict:
    """The mix ``name`` with its ``base`` chain resolved."""
    mix = json.loads((directory / f"{name}.json").read_text())
    base = mix.pop("base", None)
    if base is None:
        return mix
    merged = load_mix(base, directory)
    merged.update(mix)
    return merged


def _check_rate(rate: float) -> None:
    if not rate > 0:
        raise ValueError(f"an arrival rate must be above 0, not {rate!r}")


def poisson_gaps(rng: np.random.Generator, n: int, rate: float) -> np.ndarray:
    """``n`` gaps of a homogeneous Poisson process at ``rate`` per second."""
    _check_rate(rate)
    return rng.exponential(1.0 / rate, size=n)


def bursty_gaps(rng: np.random.Generator, n: int, rate: float, *,
                burst_factor: float = 8.0, p_burst: float = 0.15,
                mean_burst: int = 8) -> np.ndarray:
    """On/off Poisson gaps: quiet phases at ``rate``, bursts of about
    ``mean_burst`` requests ``burst_factor`` times faster."""
    _check_rate(rate)
    out, left = [], 0
    while len(out) < n:
        if left == 0 and rng.random() < p_burst:
            left = 1 + rng.geometric(1.0 / mean_burst)
        r = rate * burst_factor if left > 0 else rate
        left = max(left - 1, 0)
        out.append(rng.exponential(1.0 / r))
    return np.asarray(out)


def lognormal_lengths(rng: np.random.Generator, spec: Dict,
                      n: int) -> np.ndarray:
    """``n`` lengths, lognormal around ``median`` with ``sigma``, rounded
    to whole tokens and clipped to ``[min, max]``."""
    x = np.rint(rng.lognormal(math.log(spec["median"]), spec["sigma"], n))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


@dataclass
class Request:
    due: float              # seconds after the stream's origin
    prompt: List[int]
    max_new: int
    family: int             # popularity rank of the shared prefix, -1 if none
    prefix_len: int


class Traffic:
    """Streams of requests for one run: ``seed`` picks the tokens, the mix
    fixes everything else."""

    def __init__(self, mix: Dict, seed: int, vocab: int) -> None:
        self.mix = mix
        self.vocab = vocab
        self.seed = seed
        shape = np.random.default_rng(mix["shape_seed"])
        self.shared = bool(mix["shared_prefix"])
        n_fam = int(mix["families"])
        self.family_len = lognormal_lengths(shape, mix["prefix_tokens"],
                                            n_fam)
        w = 1.0 / np.arange(1, n_fam + 1) ** float(mix["zipf_s"])
        self.family_p = w / w.sum()
        tok = np.random.default_rng([seed, 0])
        self.family_tokens = [tok.integers(0, vocab, int(n)).tolist()
                              for n in self.family_len]

    @property
    def rate(self) -> float:
        return float(self.mix["rate_rps"])

    def working_set_tokens(self) -> int:
        """Tokens of every family prefix: what a store would need to hold
        all of them."""
        return int(self.family_len.sum()) if self.shared else 0

    def stream(self, tag: int, n: int, *, span: Optional[float] = None,
               max_new: Optional[int] = None) -> List[Request]:
        """``n`` requests of stream ``tag``. Sizes, gaps and their order
        come from the mix's shape seed and ``tag``; the run's seed draws
        the tokens. With
        ``span`` the gaps are scaled so that the ``n`` arrivals fill
        exactly ``span`` seconds (the last one due just before its end).
        ``max_new`` overrides every output length."""
        mix = self.mix
        shape = np.random.default_rng([mix["shape_seed"], tag])
        fam = shape.choice(len(self.family_p), size=n, p=self.family_p)
        pre = lognormal_lengths(shape, mix["prefix_tokens"], n)
        suf = lognormal_lengths(shape, mix["suffix_tokens"], n)
        out = lognormal_lengths(shape, mix["output_tokens"], n)
        if mix["arrivals"] == "poisson":
            gaps = poisson_gaps(shape, n, self.rate)
        elif mix["arrivals"] == "bursty":
            gaps = bursty_gaps(shape, n, self.rate,
                               burst_factor=mix.get("burst_factor", 8.0))
        else:
            raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
        if span is not None:
            # n arrivals in [0, span): the first at the first gap, the
            # (n+1)-th gap would end the span
            gaps = gaps * (span / (gaps.sum() * (n + 1) / n))
        due = np.cumsum(gaps)
        tok = np.random.default_rng([self.seed, tag, 2])
        reqs = []
        for j in range(n):
            if self.shared:
                f = int(fam[j])
                prefix = self.family_tokens[f]
            else:
                f = -1
                prefix = tok.integers(0, self.vocab, int(pre[j])).tolist()
            suffix = tok.integers(0, self.vocab, int(suf[j])).tolist()
            reqs.append(Request(
                due=float(due[j]), prompt=prefix + suffix,
                max_new=int(out[j]) if max_new is None else max_new,
                family=f, prefix_len=len(prefix)))
        return reqs

    def longest_request(self) -> int:
        """Tokens the longest request of this mix can need (prompt plus
        output): the engine's ``max_seq`` must hold it."""
        m = self.mix
        return (m["prefix_tokens"]["max"] + m["suffix_tokens"]["max"]
                + m["output_tokens"]["max"])


def describe(mix: Dict) -> str:
    return json.dumps({k: mix[k] for k in sorted(mix) if k != "why"})
