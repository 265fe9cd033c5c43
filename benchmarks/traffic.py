"""The one general traffic generator: a mix is a data file of parameters
(`traffic/<name>.json`), and this module turns it, the fleet's size and a
seed into a schedule: which documents each request updates, which field of
each, and with what value. A schedule is a pure function of (mix, fleet
size, seed, request number), so the requests a window sent can be made
again after it for the comparison, and nothing is kept while it runs.

The key chooser and the update are YCSB's (Cooper et al., SoCC 2010, core
workloads): keys are drawn with replacement from a zipfian distribution
with constant 0.99 over the records, and an update writes one field of the
record, chosen uniformly (`writeallfields=false`). A request's size is what
its draws give: documents drawn more than once in one request carry one
change, as a relay coalesces them.

A mix's parameters:

- `driver`: the file under `drivers/` that issues the requests;
- `draws_per_request`: key draws a request makes;
- `zipfian_constant`: the skew; rank k is drawn with weight k ** -constant;
- `hot_set_stride`: the hot set moves; rank k of request r is document
  order[(k + stride * r) mod n], where `order` is a permutation of the
  small documents drawn from the seed (YCSB scrambles its hot keys over the
  key space likewise);
- `batch`: whether a request is sent under one `svc.batch()` (a coalesced
  round) or as bare `svc.apply_changes` calls;
- `warmup_requests`, `warmup_spread`: requests issued before the window
  through the same driver. They make from (1 - spread) to (1 + spread)
  times the draws, evenly, so that the sizes the window's requests come
  to lie inside what was warmed up, whatever the program compiles by size.

This module is also the schedule a mix gets by naming none: `make(mix,
fleet, seed, root)` and the `Schedule` it returns are what `run.py` and the
drivers ask of any schedule (`schedules/<name>.py`; README, "The three
seams").
"""

from __future__ import annotations

import numpy as np


class Schedule:
    def __init__(self, mix: dict, n_small: int, n_fields: int, seed: int):
        self.mix, self.n, self.seed = mix, int(n_small), int(seed)
        self.n_fields = int(n_fields)
        self.draws = int(mix["draws_per_request"])
        if self.draws < 1:
            raise ValueError(f"draws_per_request {self.draws}")
        w = np.arange(1, self.n + 1, dtype=np.float64) \
            ** -float(mix["zipfian_constant"])
        self._cdf = np.cumsum(w) / w.sum()
        self.stride = int(mix["hot_set_stride"])
        self.warmup = int(mix["warmup_requests"])
        self.warmup_spread = float(mix["warmup_spread"])
        self._order = np.random.default_rng(
            [self.seed, 0x0D0C5]).permutation(self.n)

    def batch(self, r: int) -> bool:
        """Whether request `r` goes under one `svc.batch()`."""
        return bool(self.mix["batch"])

    def warmup_draws(self, k: int, n_warm: int) -> int:
        """Draws of the `k`-th of `n_warm` warm-up requests: from (1 -
        spread) to (1 + spread) times the mix's draws, evenly."""
        along = 2 * k / max(n_warm - 1, 1) - 1     # -1 .. 1
        return max(1, round(self.draws * (1 + self.warmup_spread * along)))

    def request(self, r: int) -> tuple:
        """What request `r` updates: the indices of its distinct small
        documents (sorted), and for each the index of the field written
        and the value."""
        return self.drawn(r, self.warmup_draws(r, self.warmup)
                          if r < self.warmup else self.draws)

    def drawn(self, r: int, draws: int) -> tuple:
        """Request `r` at `draws` key draws."""
        rng = np.random.default_rng([self.seed, 1, int(r)])
        ranks = np.searchsorted(self._cdf, rng.random(draws))
        ranks = np.unique(np.minimum(ranks, self.n - 1))
        docs = np.sort(self._order[(ranks + self.stride * int(r)) % self.n])
        return (docs, rng.integers(0, self.n_fields, size=len(docs)),
                rng.integers(0, 1 << 16, size=len(docs)))


def make(mix: dict, fleet, seed: int, root: str | None = None) -> Schedule:
    """The schedule of a mix that names none of its own."""
    return Schedule(mix, len(fleet.small), fleet.n_fields, seed)
