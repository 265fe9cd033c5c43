"""Process-wide generational-GC pause with a refcount, and the freeze of
what a burst leaves alive.

Burst allocation phases (coalesced ingress, bulk builds, round encodes)
trigger gen-2 collections that scan the WHOLE service heap — measured at
~2/3 of ingress cost on a 2K-doc node and ~4x the round cost on a
100K-doc fleet node. Python's gc enable/disable is process-global, so
independent pause sites on concurrent threads (two service nodes syncing
over Connections) would re-enable each other mid-burst if each tracked
its own was-enabled flag; this refcount makes nesting and concurrency
safe: GC re-enables only when the LAST pauser exits, and never if
something outside had already disabled it.

Re-enabling alone only defers the cost: the next allocation collects the
burst's young objects, and their promotion schedules the next full
collection, which re-proves that the whole loaded fleet is alive (200-250
ms a pass on a 10K-document node, 86 % of the collector's time in a storm
of rounds). So the last exit of a section that allocated more than the
collector's own first threshold (the net gen-0 count, last exit less first
entry) runs `gc.collect(1)` — the young collection the next allocation
would have run, which reclaims the burst's cyclic garbage — and then
`gc.freeze()`: what survives moves to the permanent generation, which no
generational pass walks again. A cycle that becomes garbage after it was
frozen is found only by a full pass, so one runs (`unfreeze`, `collect`,
`freeze`) when the objects frozen since the last full pass exceed what
that pass left: leaked cycles stay bounded by the heap's own size. What is
frozen is counted at each freeze and measured on the heap only when the
count passes what the pass left, then each time the count has doubled: a
freeze also holds temporaries that die by refcount after it, and a
measurement walks the whole frozen heap. A small section (one edit's
drain, a read) re-enables the collector as before and freezes nothing.
The collector is process-wide, so a freeze covers every object the
process holds, not only the service's.

Counters: `obs_gc_freezes` (sections frozen), `obs_gc_full_passes`
(doubling passes) and the gauge `obs_gc_frozen_since_pass`.
"""

from __future__ import annotations

import contextlib
import gc
import threading

from . import metrics

_lock = threading.Lock()
_depth = 0
_we_disabled = False
_count0 = 0     # gen-0 count at the section's first entry
_base = 0       # objects frozen when the last full pass ended
_since = 0      # objects counted at each freeze since then (an overcount)
_due = 0        # the count at which the frozen heap is next measured


@contextlib.contextmanager
def gc_paused():
    global _depth, _we_disabled, _count0
    with _lock:
        _depth += 1
        if _depth == 1:
            _we_disabled = gc.isenabled()
            if _we_disabled:
                gc.disable()
                _count0 = gc.get_count()[0]
    try:
        yield
    finally:
        _exit()


def _exit() -> None:
    global _depth, _we_disabled
    with _lock:
        _depth -= 1
        if _depth or not _we_disabled:
            return
        if gc.get_count()[0] - _count0 <= gc.get_threshold()[0]:
            gc.enable()
            _we_disabled = False
            return
        # the exit holds the pause while it freezes, outside the lock: a
        # finalizer the collection runs may itself take the pause
        _depth = 1
    try:
        _freeze()
    finally:
        with _lock:
            _depth -= 1
            if not _depth:
                gc.enable()
                _we_disabled = False


def _freeze() -> None:
    """Collect the young generations, freeze what survives, and run a full
    pass when the frozen heap has doubled since the last one. Runs with
    the collector disabled, one caller at a time (the pause's last exit)."""
    global _base, _since, _due
    gc.collect(1)
    # generation 2 holds only what was promoted since the last freeze
    _since += len(gc.get_objects(generation=2))
    gc.freeze()
    if _since > _due:
        # the count also holds what died by refcount after its freeze, so
        # it is confirmed on the heap itself; gc.get_freeze_count() walks
        # the whole permanent list (tens of ms on a fleet's heap), so a
        # count found short of doubled doubles before the next walk
        frozen = gc.get_freeze_count()
        if frozen - _base > _base:
            gc.unfreeze()
            gc.collect()
            gc.freeze()
            _base = _due = gc.get_freeze_count()
            _since = 0
            metrics.bump("obs_gc_full_passes")
        elif frozen < _base:
            # frozen objects died, or someone else unfroze them
            _base, _since, _due = frozen, 0, frozen
        else:
            _due = 2 * _since
    metrics.bump("obs_gc_freezes")
    metrics.gauge("obs_gc_frozen_since_pass", _since)
