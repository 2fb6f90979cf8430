"""Closed loop: ``clients`` callers, each sending its next request when its
last one completes, so the server is never short of work. Request sizes
cycle through a pool of ``pool`` stratified (prompt, output) pairs, the
same pairs for every seed in an order the seed draws; the seed also draws
which pool entries are greedy, the token ids (fresh for every request) and
the sampling seeds."""
from __future__ import annotations

import numpy as np

from bench.workload import length_values, make_arrival, sampling_flags


class Traffic:
    open_loop = False

    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int):
        self.mix, self.vocab = mix, vocab
        m = int(mix["pool"])
        order = np.random.default_rng([seed, 0])
        self.rng = np.random.default_rng([seed, 2])
        self.pool = list(zip(order.permutation(
                                 length_values(mix["prompt"], m)),
                             order.permutation(
                                 length_values(mix["output"], m)),
                             self.rng.permutation(sampling_flags(mix, m))))
        self._issued = 0
        self._ready = [self._make(c) for c in range(int(mix["clients"]))]

    def _make(self, client: int):
        plen, out, greedy = self.pool[self._issued % len(self.pool)]
        a = make_arrival(self.rng, self.mix, self._issued, int(plen),
                         int(out), bool(greedy), self.vocab, client=client)
        self._issued += 1
        return a

    def due(self, now_s: float) -> list:
        """Requests whose client is ready to send; each is due now."""
        out, self._ready = self._ready, []
        for a in out:
            a.due_s = now_s
        return out

    def next_due(self):
        return None

    def completed(self, arrival, now_s: float) -> None:
        """The client of a finished request sends its next one."""
        self._ready.append(self._make(arrival.client))
