"""Driver `writers`: many callers, closed loop. The mix's `callers` (W)
threads each submit a request, wait for its acknowledgement, build the
next and submit it: writer `w` issues the request numbers `r` with
`r mod W == w`, in order (the schedule `writers` gives each writer
documents of its own). A request is its `svc.apply_changes` calls outside
any batch, each its own ingress, as the `edits` mix sends them; what the
service makes of sixteen at once (the epoch buffer's group commit, the
service lock, the flusher) is what the cell measures. The driver calls
`svc.apply_changes` and nothing below it, and keeps no change it sent.

`ops_per_s` is every writer's acknowledged ops over the window's seconds
and the percentiles run over all writers' requests: the requests come
back in the order they returned, so the last of them closes the window
and every writer's own are in its order (`replay` makes a document's
changes again in that order). `between(now)` is called from the first
writer's loop alone: the harness's tracer holds one span from one call to
the next. The first two requests of a run are each acknowledged before the
next is submitted, and only then do all writers run at once: a run, the
warm-up among them, so always meets the flush of a single document, after
a grouped one and after a single one (the two programs of the `edits`
mix), whatever the threads' start makes of the rest.

**The guarantee is held a request.** The rounds driver reads the
service's count of ingested ops after a return; with sixteen callers
another writer's flush could lift that count for a request whose own
change was not flushed. So before a request is submitted and after it
returned, outside the timed span, the driver reads the DOCUMENT's own
count of admitted changes as its engine keeps it (`change_count`, a row a
lane, written where a flush commits its round): no other writer writes
that document, so `flushed` says exactly whether this request's change
had been flushed when the call returned, with no grace. Where the plain
reference stands in the program's place (`prove.py --control 1`) there is
no engine: its own count of flushed ops is read instead, around the
call, and its calls are serialized here, because it is one thread's; its
modes flush all or nothing, so the count decides them as exactly.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass


@dataclass
class Request:
    """As the rounds driver's: what `run.py` and the checks read."""
    number: int
    submitted: float      # perf_counter before the first apply_changes
    returned: float       # perf_counter after the last returned
    ops: int
    flushed: bool         # on return the engine had admitted its changes
    error: str | None = None


def _admitted_of(svc):
    """`count(doc id)`: changes of the document its engine has admitted;
    None where the service has no engine (the plain reference)."""
    from fleet import engines
    held = engines(svc)
    if not held:
        return None

    def count(doc_id: str) -> int:
        for e in held:
            i = e.doc_index.get(doc_id)
            if i is not None:
                return int(e.change_count[i])
        return 0
    return count


def run(svc, fleet, schedule, *, first: int, max_requests: int,
        seconds: float | None = None, between=None) -> dict:
    """Issue the requests `first` .. `first + max_requests - 1`, each by
    its writer, until `seconds` have passed (checked by each writer before
    each of its requests), they are all issued, a request raised, or the
    fleet can take no more. Returns the requests in the order they
    returned, with the window's own begin and end."""
    from fleet import ops_ingested

    callers = schedule.callers
    count = _admitted_of(svc)
    one_thread = threading.Lock() if count is None else None
    last = first + max_requests
    done: list = [[] for _ in range(callers)]
    building = [0.0] * callers
    why = [None] * callers
    stop = threading.Event()
    begin = time.perf_counter()

    def write(w: int, r: int, until: int) -> None:
        """Writer `w`'s requests `r`, `r + W`, ... below `until`."""
        mine = done[w]
        while r < until and not stop.is_set():
            now = time.perf_counter()
            if seconds is not None and now - begin >= seconds:
                why[w] = "seconds"
                return
            if w == 0 and between is not None:
                between(now)
            round_ = fleet.request_changes(schedule.request(r))
            if isinstance(round_, str):      # the fleet can take no more
                why[w] = round_
                stop.set()
                return
            n_ops = fleet.request_ops(round_)
            n_changes = {d: len(chs) for d, chs in round_.items()}
            if count is not None:
                before = {d: count(d) for d in round_}
            else:
                one_thread.acquire()
                before = ops_ingested(svc)
            t0 = time.perf_counter()
            building[w] += t0 - now
            err = None
            try:
                for d, chs in round_.items():
                    svc.apply_changes(d, chs)
            except Exception as e:   # the request failed; the run is over
                err = repr(e)[:400]
            t1 = time.perf_counter()
            if count is not None:
                flushed = all(count(d) - before[d] >= n
                              for d, n in n_changes.items())
            else:
                flushed = ops_ingested(svc) - before >= n_ops
                one_thread.release()
            mine.append(Request(r, t0, t1, n_ops, flushed, err))
            if err is not None:
                why[w] = "error"
                stop.set()
                return
            r += callers
        why[w] = why[w] or ("max_requests" if r >= until else "stopped")

    alone = min(2, max_requests)       # each acknowledged before the next
    waves = [[(r % callers, r, r + 1)] for r in range(first, first + alone)]
    waves.append([((first + alone + j) % callers, first + alone + j, last)
                  for j in range(min(callers, max_requests - alone))])
    for wave in waves:
        threads = [threading.Thread(target=write, args=part,
                                    name=f"writer-{part[0]}")
                   for part in wave]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    requests = sorted((q for mine in done for q in mine),
                      key=lambda q: q.returned)
    named = [x for x in why if x not in (None, "max_requests", "stopped")]
    stopped = "error" if "error" in named else (
        next((x for x in named if x != "seconds"), None)
        or ("seconds" if named else "max_requests"))
    return {"requests": requests, "begin": begin,
            "end": time.perf_counter(), "stopped": stopped,
            "building_s": sum(building)}
