"""What the traffic generators share: one request as the harness issues it,
and stratified draws of lengths and gaps.

Lengths and inter-arrival gaps are quantiles of the mix's distributions at
evenly spaced probabilities, in an order that the seed draws, as it draws
the token ids, which requests are greedy, and the sampling seeds. So every
seed offers the same amount of work, and a schedule is not one that a
change could be tuned to.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Arrival:
    """One request of the mix, before it becomes the program's ``Request``."""
    rid: int
    prompt: np.ndarray            # (len,) int32 token ids
    max_new: int
    temperature: float            # 0: greedy
    top_k: int
    seed: int                     # sampling stream, below 2**31
    due_s: Optional[float] = None  # window-relative due time (open loop)
    client: Optional[int] = None   # closed-loop client that sends it


def round_up(n: float, to: int) -> int:
    return int(math.ceil(n / to) * to)


def length_values(spec: dict, n: int) -> list[int]:
    """``n`` lengths at the quantiles (i + 1/2) / n of a lognormal with the
    spec's ``median`` and ``sigma``, clipped to [``min``, ``max``] and rounded
    up to a multiple of ``round_to``."""
    if spec.get("dist", "lognormal") != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = statistics.NormalDist()
    step = int(spec.get("round_to", 1))
    out = []
    for i in range(n):
        x = spec["median"] * math.exp(spec["sigma"]
                                      * nd.inv_cdf((i + 0.5) / n))
        x = min(max(x, spec["min"]), spec["max"])
        out.append(min(round_up(x, step), spec["max"]))
    return out


def possible_lengths(spec: dict) -> list[int]:
    """Every length ``length_values`` can return, whatever ``n``: the
    multiples of ``round_to`` from the rounded minimum to the maximum."""
    step = int(spec.get("round_to", 1))
    return sorted({min(round_up(v, step), spec["max"])
                   for v in range(int(spec["min"]), int(spec["max"]) + 1)})


def exp_gaps(rate: float, n: int, seconds: float) -> list[float]:
    """``n`` inter-arrival gaps at the exponential's quantiles for ``rate``,
    scaled so that they sum to ``seconds`` (Poisson arrivals, stratified)."""
    g = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    s = sum(g)
    return [x * seconds / s for x in g]


def sampling_flags(mix: dict, n: int) -> list[bool]:
    """``n`` greedy flags: ``greedy_share`` of them True, the rest sample
    with the mix's temperature and top-k."""
    k = int(round(n * float(mix.get("greedy_share", 1.0))))
    return [True] * k + [False] * (n - k)


def make_arrival(rng: np.random.Generator, mix: dict, rid: int, plen: int,
                 max_new: int, greedy: bool, vocab: int, **kw) -> Arrival:
    return Arrival(
        rid=rid, prompt=rng.integers(0, vocab, plen, dtype=np.int32),
        max_new=int(max_new),
        temperature=0.0 if greedy else float(mix["temperature"]),
        top_k=0 if greedy else int(mix.get("top_k", 0)),
        seed=int(rng.integers(0, 2**31 - 1)), **kw)
