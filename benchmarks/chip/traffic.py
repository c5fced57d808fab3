"""The one traffic generator: turns a mix's data file and a seed into the
requests of a run.

Open-loop mixes (``"kind": "open_loop"``) are Poisson arrivals at
``rate_per_s``, drawn phase by phase: the warm-up before the window, the
window, and a tail after it. A phase of ``T`` seconds holds
``round(rate * T)`` arrivals at independent uniform times: a Poisson
process conditioned on its count in each phase, so bursts and lulls come
as they come in Poisson traffic, while every seed has the same number of
requests due in the window.

Each phase also holds the same set of lengths for every seed: prompt and
output lengths taken at evenly spaced quantiles of their distributions,
each list shuffled by the seed on its own, with no tie to the arrival
times. Prompt tokens are drawn from the seed. So two seeds differ in
which request comes when and what it says, never in how much there is to
do.

A length distribution is ``{"median", "sigma", "min", "max"}``: log-normal
with that median and log-space deviation, clipped, rounded to whole
tokens.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List, Sequence

import numpy as np


@dataclasses.dataclass
class Request:
    due_s: float               # arrival, seconds from the start of traffic
    prompt: np.ndarray         # int32 token ids
    max_new_tokens: int


def lognormal_lengths(n: int, dist: Dict) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 1/2) / n`` of ``dist``."""
    q = (np.arange(n) + 0.5) / n
    z = np.array([NormalDist().inv_cdf(x) for x in q])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def open_loop(mix: Dict, seed: int, phases: Sequence[float],
              vocab: int) -> List[Request]:
    """The requests of the open-loop ``mix`` over consecutive phases of
    ``phases`` seconds, in order of arrival."""
    if mix["kind"] != "open_loop":
        raise ValueError(f"not an open-loop mix: {mix['kind']!r}")
    rate = float(mix["rate_per_s"])
    rng = np.random.default_rng(seed)
    out: List[Request] = []
    start = 0.0
    for length in phases:
        n = int(round(rate * length))
        due = start + np.sort(rng.random(n)) * length
        prompts = rng.permutation(lognormal_lengths(n, mix["prompt"]))
        outputs = rng.permutation(lognormal_lengths(n, mix["output"]))
        out += [Request(float(t), rng.integers(0, vocab, int(p)
                                               ).astype(np.int32), int(g))
                for t, p, g in zip(due, prompts, outputs)]
        start += length
    return out
