"""Open-loop arrivals: independent users sending on a schedule, whatever
the server does. Poisson at ``rate_per_s`` (stratified gaps, see
``bench.workload``), prompt and output lengths from the mix's ``prompt`` and
``output`` specs, ``greedy_share`` of the requests greedy and the rest
sampled at ``temperature`` / ``top_k``.

Every seed offers the same work: the same inter-arrival gaps and the same
lengths (the mix's quantiles), in an order the seed draws. The seed also
draws the token ids, which requests are greedy, and the sampling seeds.
"""
from __future__ import annotations

import numpy as np

from bench.workload import (exp_gaps, length_values, make_arrival,
                            sampling_flags)


class Traffic:
    open_loop = True

    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int):
        n = max(1, int(round(mix["rate_per_s"] * seconds)))
        order = np.random.default_rng([seed, 0])
        gaps = order.permutation(exp_gaps(mix["rate_per_s"], n, seconds))
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        plens = order.permutation(length_values(mix["prompt"], n))
        outs = order.permutation(length_values(mix["output"], n))
        rng = np.random.default_rng([seed, 1])
        greedy = rng.permutation(sampling_flags(mix, n))
        self.arrivals = [
            make_arrival(rng, mix, i, int(plens[i]), int(outs[i]),
                         bool(greedy[i]), vocab, due_s=float(due[i]))
            for i in range(n)]
        self._next = 0

    def due(self, now_s: float) -> list:
        """Arrivals due by ``now_s`` not handed out yet, in due order."""
        out = []
        while (self._next < len(self.arrivals)
               and self.arrivals[self._next].due_s <= now_s):
            out.append(self.arrivals[self._next])
            self._next += 1
        return out

    def next_due(self):
        if self._next < len(self.arrivals):
            return self.arrivals[self._next].due_s
        return None

    def completed(self, arrival, now_s: float) -> None:
        """Open loop: a completion sends nothing."""
