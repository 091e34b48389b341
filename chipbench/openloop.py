"""The open-loop request generator: one for every serving mix.

A mix file (``traffic/<name>.json``) gives the rate, the length
distributions and a schedule seed.  For a window of ``seconds`` the
generator makes ``round(rate * seconds)`` requests.  Their
inter-arrival gaps are the exponential distribution's quantiles at
(i + 1/2) / n, their prompt and output lengths the lognormal's,
clipped, prompts rounded up to whole prefill chunks; gaps, prompt
lengths and output lengths are each shuffled by the schedule seed.
That schedule is one fixed draw of the mix, replayed by every run as a
recorded trace would be: the order of a few dozen requests decides
the TTFT tail (in six runs on a TPU v5e with the order drawn from each
run's seed, the 90th percentile of TTFT read from 980 to 2695 ms), so
every run sends the same work.  The run's seed draws the token ids.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    due: float                  # seconds after the window opens
    prompt: np.ndarray          # int32 token ids
    out_len: int                # tokens to serve, the first included
    sent: Optional[float] = None
    slot: Optional[int] = None
    times: List[float] = dataclasses.field(default_factory=list)
    served: Optional[List[int]] = None


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal(n: int, median: float, sigma: float, lo: int, hi: int):
    z = np.array([statistics.NormalDist().inv_cdf(q) for q in _quantiles(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)


def requests(mix: dict, seed: int, seconds: float,
             vocab: int) -> List[Request]:
    n = max(1, round(mix["rate_per_s"] * seconds))
    rng = np.random.default_rng(mix["schedule_seed"])
    gaps = rng.permutation(-np.log1p(-_quantiles(n)))
    due = np.cumsum(gaps)
    due *= seconds * n / (n + 1) / due[-1]      # mean gap 1/rate
    p = mix["prompt"]
    chunk = mix["prompt_multiple"]
    plen = lognormal(n, p["median"], p["sigma"], p["min"], p["max"])
    plen = rng.permutation(-(-plen // chunk) * chunk)
    o = mix["output"]
    olen = rng.permutation(lognormal(n, o["median"], o["sigma"], o["min"],
                                     o["max"]))
    ids = np.random.default_rng(seed)
    return [Request(due=float(d),
                    prompt=ids.integers(0, vocab, int(pl), dtype=np.int32),
                    out_len=int(ol))
            for d, pl, ol in zip(due, plen, olen)]


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))
