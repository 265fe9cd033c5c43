"""The collector's pause and the freeze at its last exit
(`automerge_tpu/utils/gcpause.py`): a section that allocated more than the
collector's first threshold has its young cycles collected and its
survivors frozen, once, where the last pauser leaves; a small one only
re-enables the collector; a full pass reclaims what died frozen once the
frozen heap has doubled; the collector is on after every exit."""

import gc
import threading
import weakref

import pytest

from automerge_tpu.utils import gcpause, metrics
from automerge_tpu.utils.gcpause import gc_paused


class Node:
    """A weakly referable object to tie into a cycle."""


def burst(n: int) -> list:
    """`n` tracked objects, kept alive by the returned list."""
    return [[i] for i in range(n)]


def engaging() -> int:
    """A burst size well above the collector's first threshold."""
    return 5 * gc.get_threshold()[0]


def cycle() -> tuple:
    """A cycle only the collector can reclaim, and a weak reference to it."""
    node = Node()
    node.me = node
    return node, weakref.ref(node)


def is_frozen(obj) -> bool:
    """Tracked, and in none of the three generations."""
    return gc.is_tracked(obj) and not any(o is obj for o in gc.get_objects())


def counters() -> tuple:
    snap = metrics.snapshot()
    return (snap.get("obs_gc_freezes", 0), snap.get("obs_gc_full_passes", 0))


@pytest.fixture
def no_full_pass(monkeypatch):
    """The doubling pass out of the way: a base no test heap reaches. The
    module's own values come back after the test."""
    monkeypatch.setattr(gcpause, "_base", 10 ** 12)
    monkeypatch.setattr(gcpause, "_since", 0)
    monkeypatch.setattr(gcpause, "_due", 10 ** 12)
    metrics.reset()
    yield
    assert gc.isenabled()


def pass_next():
    """The base as small as it can be, so that the next engaged freeze
    measures the heap and runs the full pass, which re-bases the module
    for every later test."""
    gcpause._base, gcpause._since, gcpause._due = 1, 0, 0


@pytest.fixture
def next_freeze_passes():
    pass_next()
    metrics.reset()
    yield
    assert gc.isenabled()


@pytest.mark.parametrize("engaged", [True, False], ids=["above", "below"])
def test_a_burst_above_the_threshold_is_frozen_once_and_one_below_is_not(
        no_full_pass, engaged):
    n = engaging() if engaged else gc.get_threshold()[0] // 10
    with gc_paused():
        assert not gc.isenabled()
        kept = burst(n)
    assert gc.isenabled()
    assert is_frozen(kept) is engaged
    assert is_frozen(kept[-1]) is engaged
    assert counters() == ((1, 0) if engaged else (0, 0))
    del kept


def test_a_section_that_frees_what_it_allocates_is_not_engaged(
        no_full_pass):
    kept = burst(engaging())
    with gc_paused():
        kept = burst(engaging())        # the old burst dies as this one lives
    assert counters() == (0, 0)
    assert not is_frozen(kept)
    del kept


def test_nested_pausers_freeze_only_at_the_last_exit(no_full_pass):
    with gc_paused():
        with gc_paused():
            kept = burst(engaging())
        assert not gc.isenabled()
        assert counters() == (0, 0)
        with gc_paused():
            pass
        assert counters() == (0, 0)
    assert gc.isenabled()
    assert counters() == (1, 0)
    del kept


def test_concurrent_pausers_freeze_only_at_the_last_exit(no_full_pass):
    entered, left = threading.Event(), threading.Event()
    kept = []

    def other():
        with gc_paused():
            entered.set()
            kept.append(burst(engaging()))
        left.set()

    with gc_paused():
        t = threading.Thread(target=other)
        t.start()
        assert entered.wait(10) and left.wait(10)
        t.join(10)
        assert not t.is_alive()
        # the other thread's exit was not the last: nothing frozen, the
        # collector still off
        assert not gc.isenabled()
        assert counters() == (0, 0)
    assert gc.isenabled()
    assert counters() == (1, 0)


def test_young_cyclic_garbage_of_an_engaged_burst_is_reclaimed_not_frozen(
        no_full_pass):
    refs = []
    with gc_paused():
        kept = burst(engaging())
        for _ in range(100):
            node, ref = cycle()
            refs.append(ref)
        del node
    assert counters() == (1, 0)
    assert all(r() is None for r in refs)
    del kept


def test_a_cycle_dropped_after_its_freeze_is_reclaimed_by_the_doubling_pass(
        no_full_pass):
    with gc_paused():
        kept = burst(engaging())
        node, ref = cycle()
    assert counters() == (1, 0)
    del node
    gc.collect()
    assert ref() is not None        # frozen: no generational pass sees it
    # the frozen heap has "doubled": the next engaged exit runs the pass
    pass_next()
    with gc_paused():
        more = burst(engaging())    # net of what dies: kept stays
    assert ref() is None
    assert counters() == (2, 1)
    frozen = gc.get_freeze_count()
    assert gcpause._since == 0
    assert 0 < gcpause._base and abs(gcpause._base - frozen) < engaging()
    assert gcpause._due == gcpause._base
    assert gc.isenabled()
    del kept, more


def test_each_counter_moves_exactly_when_it_should(next_freeze_passes):
    with gc_paused():
        kept = [burst(engaging())]
    # the first freeze confirmed the estimate (above a base of 1) and ran
    # the full pass
    assert counters() == (1, 1)
    base = gcpause._base
    assert base > 0
    assert metrics.snapshot()["obs_gc_frozen_since_pass"] == 0
    # a small section moves nothing
    with gc_paused():
        kept.append(burst(10))
    assert counters() == (1, 1)
    # an engaged one adds its survivors to the estimate, and no pass:
    # the heap has not doubled
    n = engaging()
    with gc_paused():
        kept.append(burst(n))
    assert counters() == (2, 1)
    since = metrics.snapshot()["obs_gc_frozen_since_pass"]
    assert since == gcpause._since
    assert n <= since < base
    assert gcpause._base == base
    del kept


def test_a_count_short_of_doubled_waits_for_twice_as_many_before_the_next_walk(
        next_freeze_passes, monkeypatch):
    with gc_paused():
        kept = [burst(engaging())]
    assert counters() == (1, 1)
    base = gcpause._base
    assert gcpause._due == base and gcpause._since == 0
    walks = []
    walk = gc.get_freeze_count
    monkeypatch.setattr(gc, "get_freeze_count",
                        lambda: walks.append(1) or walk())
    # a count above what the pass left is measured on the heap: short of
    # doubled, so the next measurement waits for twice the count
    gcpause._since = base
    with gc_paused():
        kept.append(burst(engaging()))
    assert len(walks) == 1 and counters() == (2, 1)
    since = gcpause._since
    assert since > base and gcpause._due == 2 * since
    with gc_paused():
        kept.append(burst(engaging()))
    assert len(walks) == 1 and counters() == (3, 1)
    assert gcpause._base == base
    del kept


def test_a_base_above_the_frozen_count_is_rebased(next_freeze_passes):
    with gc_paused():
        kept = [burst(engaging())]
    base = gcpause._base
    assert counters() == (1, 1)
    # a fixture's unfreeze: everything frozen is back in generation 2, so
    # the next freeze counts the whole heap and measures it: not doubled
    gc.unfreeze()
    with gc_paused():
        kept.append(burst(engaging()))
    assert counters() == (2, 1)
    assert gcpause._base == base
    assert gcpause._since > base // 2 and gcpause._due == 2 * gcpause._since
    # frozen objects that died: the heap is found below the base, which
    # follows it down
    gcpause._base = 10 * gc.get_freeze_count()
    gcpause._due = 0                    # so that the next one measures
    with gc_paused():
        kept.append(burst(engaging()))
    assert counters() == (3, 1)
    assert gcpause._since == 0 and gcpause._due == gcpause._base
    assert abs(gcpause._base - gc.get_freeze_count()) < engaging()
    del kept


@pytest.mark.parametrize("engaged", [True, False], ids=["engaged", "small"])
@pytest.mark.parametrize("raises", [True, False], ids=["raising", "returning"])
def test_the_collector_is_enabled_after_every_exit(no_full_pass, engaged,
                                                   raises):
    n = engaging() if engaged else 10

    class Boom(Exception):
        pass

    try:
        with gc_paused():
            with gc_paused():
                kept = burst(n)
                if raises:
                    raise Boom
    except Boom:
        pass
    assert gc.isenabled()
    assert gcpause._depth == 0
    assert counters() == ((1 if engaged else 0), 0)
    del kept


def test_a_collector_disabled_outside_stays_off_and_nothing_freezes(
        no_full_pass):
    gc.disable()
    try:
        with gc_paused():
            kept = burst(engaging())
        assert not gc.isenabled()
        assert counters() == (0, 0)
    finally:
        gc.enable()
    del kept
