"""Open-loop request arrivals from a traffic mix and a seed.

Every seed offers the same work: a fixed number of requests, whose
prompt and output lengths are one fixed multiset, taken at evenly
spaced quantiles of the mix's clipped lognormal distributions. The seed
draws only the order of the lengths, the arrival times (a Poisson
process conditioned on its count, so the arrivals fill the span) and
the token ids. Without that, the tails and the throughput would spread
with the seed and not with the code.

A run's arrivals come in three parts, on one clock that starts when the
generator starts (offsets in seconds):

* ``lead_in_active`` requests due at 0, with outputs cut to a spread of
  fractions of their lengths, so that slots free up at different times;
* the lead-in: ``rate_per_s * lead_in_s`` requests due in
  ``[0, lead_in_s)``;
* the window: ``rate_per_s * seconds`` requests due in ``[lead_in_s,
  lead_in_s + seconds)``; only these count in the time to first token.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Arrival:
    due: float           # seconds after the generator starts
    prompt: np.ndarray   # int32 token ids
    n_out: int           # tokens to serve
    counted: bool        # due inside the window


def quantile_lengths(spec: Dict, n: int) -> np.ndarray:
    """n lengths at the quantiles (i + 1/2) / n of a lognormal with the
    given median and sigma, rounded and clipped to [min, max]."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def _spread(rng, n: int, lo: float, span: float) -> np.ndarray:
    """n Poisson arrival times conditioned to fill [lo, lo + span)."""
    if n == 0:
        return np.zeros(0)
    c = np.cumsum(rng.exponential(size=n + 1))
    return lo + span * c[:n] / c[n]


def open_loop(mix: Dict, vocab: int, seed: int,
              seconds: float) -> List[Arrival]:
    """The run's arrivals, sorted by due time."""
    rng = np.random.default_rng(seed)
    rate, lead = mix["rate_per_s"], mix["lead_in_s"]
    parts = []
    n_act = mix["lead_in_active"]
    parts.append((np.zeros(n_act), False,
                  (np.arange(n_act) + 0.5) / max(n_act, 1)))
    n_lead = int(round(rate * lead))
    parts.append((_spread(rng, n_lead, 0.0, lead), False, None))
    n_win = int(round(rate * seconds))
    parts.append((_spread(rng, n_win, lead, seconds), True, None))
    out: List[Arrival] = []
    for due, counted, cut in parts:
        n = due.size
        plen = rng.permutation(quantile_lengths(mix["prompt_len"], n))
        olen = quantile_lengths(mix["output_len"], n)
        if cut is not None:
            olen = np.maximum(1, np.ceil(olen * cut)).astype(np.int64)
        olen = rng.permutation(olen)
        for t, p, o in zip(due, plen, olen):
            prompt = rng.integers(0, vocab, size=int(p), dtype=np.int32)
            out.append(Arrival(float(t), prompt, int(o), counted))
    out.sort(key=lambda a: a.due)
    return out

