"""Schedule `writers`: many callers, each over documents of its own. The
mix's file gives `callers` (W); writer `w` owns the small documents whose
index is `w` mod W, so that one writer a document keeps a document's
sequence numbers in order whatever the threads' interleaving. Request
number `r = W k + w` is writer `w`'s `k`-th: it draws `draws_per_request`
keys (one, in the mixes there are) from a zipfian distribution with the
mix's constant over the writer's own documents, whose hot set moves
`hot_set_stride` ranks a request of the writer's own (rank `j` of its
`k`-th request is its document `order_w[(j + stride k) mod n_w]`), and
writes one field of each, chosen uniformly, as `traffic.py` does (YCSB's
key chooser and update). In the warm-up a request makes its draws as in
the window: one draw has no spread.

A schedule is a pure function of (mix, fleet, seed, request number):
`request(r)` and `batch(r)` keep nothing between calls, so `replay` makes
every acknowledged change again from the numbers.
"""

from __future__ import annotations

import numpy as np


class Writers:
    def __init__(self, mix: dict, n_small: int, n_fields: int, seed: int):
        self.mix, self.seed = mix, int(seed)
        self.callers = int(mix["callers"])
        self.draws = int(mix["draws_per_request"])
        self.n_fields = int(n_fields)
        self.stride = int(mix["hot_set_stride"])
        self.warmup = int(mix["warmup_requests"])
        if self.callers < 1 or self.draws < 1 or n_small < self.callers:
            raise ValueError(f"callers {self.callers}, draws {self.draws}, "
                             f"{n_small} documents")
        if self.warmup % self.callers:
            raise ValueError(f"warmup_requests {self.warmup} is not the "
                             f"same number for each of {self.callers}")
        self._own, self._cdf = [], []
        for w in range(self.callers):
            own = np.arange(w, n_small, self.callers)
            self._own.append(own[np.random.default_rng(
                [self.seed, 0x0D0C5, w]).permutation(len(own))])
            weights = np.arange(1, len(own) + 1, dtype=np.float64) \
                ** -float(mix["zipfian_constant"])
            self._cdf.append(np.cumsum(weights) / weights.sum())

    def batch(self, r: int) -> bool:
        return bool(self.mix["batch"])

    def writer(self, r: int) -> int:
        return int(r) % self.callers

    def request(self, r: int) -> tuple:
        """What request `r` updates, in `traffic.Schedule.request`'s form:
        the indices of its distinct small documents (sorted), and for each
        the index of the field written and the value."""
        k, w = divmod(int(r), self.callers)
        own, cdf = self._own[w], self._cdf[w]
        rng = np.random.default_rng([self.seed, 1, int(r)])
        ranks = np.searchsorted(cdf, rng.random(self.draws))
        ranks = np.unique(np.minimum(ranks, len(own) - 1))
        docs = np.sort(own[(ranks + self.stride * k) % len(own)])
        return (docs, rng.integers(0, self.n_fields, size=len(docs)),
                rng.integers(0, 1 << 16, size=len(docs)))


def make(mix: dict, fleet, seed: int, root: str | None = None) -> Writers:
    return Writers(mix, len(fleet.small), fleet.n_fields, seed)
