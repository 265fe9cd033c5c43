"""Schedule `cycle`: a mix built from other mixes, as data. The mix's file
lists the parts of one cycle in order, `"cycle": [{"mix": <name>,
"requests": <n>}, ...]`: request `r` belongs to the part that position
`r mod (requests a cycle)` falls in, draws its documents, fields and values
as that part's own mix draws them (`traffic.Schedule`, the one general
generator: the part's `draws_per_request` and `zipfian_constant`), and goes
under one `svc.batch()` or not as that part's `batch` says. The parts'
files are read, never edited.

What belongs to the cycle as a whole is in the cycle's own file and is
counted over all its requests: `hot_set_stride` (rank k of request r is
document order[(k + stride * r) mod n], whichever part r belongs to) and
the warm-up, `warmup_requests`, a whole number of cycles. In the warm-up a
part's requests make from (1 - `warmup_spread`) to (1 + `warmup_spread`)
times its draws, evenly over that part's own warm-up requests, so that a
part that comes once a cycle still spans its sizes.

A schedule is a pure function of (mix, fleet, seed, request number):
`request(r)` and `batch(r)` keep nothing between calls.
"""

from __future__ import annotations

import fleet as fleetlib
import traffic


class Cycle:
    def __init__(self, mix: dict, fleet, seed: int, root: str):
        self.warmup = int(mix["warmup_requests"])
        self._parts, self._slots = [], []
        for part in mix["cycle"]:
            base = fleetlib.load_json("traffic", part["mix"], root)
            if "schedule" in base:
                raise ValueError(f"part {part['mix']!r} names a schedule of "
                                 f"its own; a cycle is made of plain mixes")
            n = int(part["requests"])
            self._slots += [(len(self._parts), k) for k in range(n)]
            self._parts.append((traffic.Schedule(dict(
                base, hot_set_stride=mix["hot_set_stride"],
                warmup_spread=mix["warmup_spread"]),
                len(fleet.small), fleet.n_fields, seed), n,
                bool(base["batch"])))
        self.length = len(self._slots)
        if not self.length or self.warmup % self.length:
            raise ValueError(f"warmup_requests {self.warmup} is not a whole "
                             f"number of cycles of {self.length} requests")

    def batch(self, r: int) -> bool:
        return self._parts[self._slots[r % self.length][0]][2]

    def request(self, r: int) -> tuple:
        cycle, at = divmod(int(r), self.length)
        part, k = self._slots[at]
        schedule, n, _batch = self._parts[part]
        if r < self.warmup:
            # the part's own count of warm-up requests, not the cycle's
            return schedule.drawn(r, schedule.warmup_draws(
                cycle * n + k, self.warmup // self.length * n))
        return schedule.drawn(r, schedule.draws)


def make(mix: dict, fleet, seed: int, root: str) -> Cycle:
    return Cycle(mix, fleet, seed, root)
