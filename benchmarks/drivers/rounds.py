"""Driver `rounds`: one caller, closed loop. A request is what the caller
submits and waits for: where the schedule says `batch(r)`, one coalesced
round, entry of `with svc.batch():` to its exit, which is what a relay
upstream waits for; where it does not, the request's `svc.apply_changes`
calls one after another, each its own flush. The next request is built and
sent only after the last one returned. The driver asks the schedule what
request `r` draws and the fleet for its changes, calls `svc.batch()` /
`svc.apply_changes` and nothing below them, and keeps no change it sent:
the fleet's `replay` makes them again for the comparison.
"""

from __future__ import annotations

import time
from dataclasses import dataclass


# The program bumps its counter of ingested ops just after it has released
# the caller that waited for the flush (another thread does the flush when
# a change comes outside a batch), so a read right after the return can
# come microseconds early: the count is read again for up to some 10 ms
# before the acknowledgement is held to have come first.
GRACE_READS = 50
GRACE_SLEEP_S = 0.0002


@dataclass
class Request:
    number: int
    submitted: float      # perf_counter at entry of the batch
    returned: float       # perf_counter after its exit
    ops: int
    flushed: bool         # on return the service had ingested its ops
    error: str | None = None


def run(svc, fleet, schedule, *, first: int, max_requests: int,
        seconds: float | None = None, between=None) -> dict:
    """Issue requests `first`, `first + 1`, ... until `seconds` have passed
    (checked before each request), `max_requests` were issued, a request
    raised, or the fleet can take no more (the maps fleet: the next
    request would take a document past its `history_cap`). `between(now)`
    runs between requests (the harness starts and stops its trace there).
    After each return, outside the timed span,
    the driver reads how many ops the service has flushed through its
    engine: the acknowledgement must not come before them. Returns the
    requests with the window's own begin and end."""
    from fleet import ops_ingested

    requests: list = []
    stopped = "max_requests"
    begin = time.perf_counter()
    building = 0.0
    ingested = ops_ingested(svc)
    for r in range(first, first + max_requests):
        now = time.perf_counter()
        if seconds is not None and now - begin >= seconds:
            stopped = "seconds"
            break
        if between is not None:
            between(now)
        round_ = fleet.request_changes(schedule.request(r))
        if isinstance(round_, str):      # the fleet can take no more
            stopped = round_
            break
        batch, n_ops = schedule.batch(r), fleet.request_ops(round_)
        t0 = time.perf_counter()
        building += t0 - now
        err = None
        try:
            if batch:
                with svc.batch():
                    for d, chs in round_.items():
                        svc.apply_changes(d, chs)
            else:
                for d, chs in round_.items():
                    svc.apply_changes(d, chs)
        except Exception as e:   # the request failed; the run is over
            err = repr(e)[:400]
        t1 = time.perf_counter()
        after = ops_ingested(svc)
        for _ in range(GRACE_READS):
            if after - ingested >= n_ops or err is not None:
                break
            time.sleep(GRACE_SLEEP_S)
            after = ops_ingested(svc)
        requests.append(Request(r, t0, t1, n_ops,
                                after - ingested >= n_ops, err))
        ingested = after
        if err is not None:
            stopped = "error"
            break
    return {"requests": requests, "begin": begin,
            "end": time.perf_counter(), "stopped": stopped,
            "building_s": building}
