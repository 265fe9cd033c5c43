"""A round's device work starts when its triplets exist and is collected
when a caller has to be released (ISSUE 39).

The engine's round entry has a dispatch half and a collect half. The
scatter of a planning round goes out before the router runs
(`dispatch.scatters_first`); the service runs the part of its tail that
releases nobody between the halves and reads the round's hashes back
before it releases anybody. Held here:

(a) the order, from recorded phase entries, dispatches and ledger calls;
(b) the hashes on every route of the router's table, against
    `apply_round_frames` and the plain reference;
(c) every entry of the engine that reads a hash or can dirty or re-lay a
    lane, called between the halves;
(d) the share of flushed rounds whose collect half found them unsettled.
"""

import threading

import numpy as np
import pytest

from automerge_tpu.core.change import Change, Op
from automerge_tpu.core.ids import ROOT_ID
from automerge_tpu.engine import dispatch
from automerge_tpu.native.wire import changes_to_columns
from automerge_tpu.sync import tenantledger
from automerge_tpu.sync.service import EngineDocSet
from automerge_tpu.utils import metrics, perfscope

from tests.test_reconcile_route import Fleet, _oracle


@pytest.fixture
def declines(monkeypatch):
    """Rounds of two documents and more plan, and the plan declines: what
    the link prices say in every cell of the benchmark."""
    monkeypatch.setattr(dispatch, "_megabatch", True)
    monkeypatch.setattr(
        dispatch, "plan_round",
        lambda rset, idxs: dispatch.RoundPlan("per_doc", list(idxs)))


@pytest.fixture
def routes(monkeypatch):
    """The kinds of the routes the router hands out, call by call."""
    seen = []
    real = dispatch.reconcile_route

    def spy(rset, lanes, round_docs=None):
        route = real(rset, lanes, round_docs)
        seen.append(route.kind if round_docs is not None
                    else f"read:{route.kind}")
        return route

    monkeypatch.setattr(dispatch, "reconcile_route", spy)
    return seen


def _count(name) -> int:
    return metrics.snapshot().get(name, 0)


def _collects(rset, monkeypatch) -> list:
    """The collects that found a round unsettled, recorded: calls of the
    engine's `_collect_round` (collect_round makes one exactly then)."""
    seen: list = []
    real = rset._collect_round

    def collect(interpret):
        seen.append(interpret)
        return real(interpret)

    monkeypatch.setattr(rset, "_collect_round", collect)
    return seen


# -- (a) the order of one round through the service ---------------------------


def _edit(log) -> Change:
    c = Change(actor="W", seq=len(log) + 1, deps={},
               ops=[Op("set", ROOT_ID, key="n", value=len(log))])
    log.append(c)
    return c


class Service:
    """An eager rows service over `n` one-change documents whose device
    copy is current, and the logs the oracle replays."""

    def __init__(self, n=300):
        self.svc = EngineDocSet(backend="rows")
        self.rset = self.svc._resident
        if self.rset._native is None:
            pytest.skip("round frames need the native encoder")
        # the chip's road: reconcile at the flush, not at the hash read
        self.svc._lazy_resolved = True
        self.rset.lazy_dispatch = False
        self.logs = {f"d{i:04d}": [] for i in range(n)}
        self.ids = list(self.logs)
        self.batch(self.ids)

    def batch(self, docs):
        with self.svc.batch():
            for d in docs:
                self.svc.apply_changes(d, [_edit(self.logs[d])])

    def tickets(self, docs):
        """One ingest a document from 'writers' of the epoch buffer, all
        riding one flush: the flusher cannot seal before the lock is
        free."""
        with self.svc._lock:
            pend = [self.svc.apply_columns_async(
                d, changes_to_columns([_edit(self.logs[d])])) for d in docs]
        for p in pend:
            p.wait()

    def check(self):
        h = self.svc.hashes()
        want = _oracle([self.logs[d] for d in self.ids])
        np.testing.assert_array_equal(
            np.asarray([h[d] for d in self.ids], np.uint32), want)


@pytest.fixture
def recorded(monkeypatch):
    """Every phase entry and exit, jit dispatch, ledger round call, bump
    of `sync_ops_ingested` and release of the riders, in order, from the
    thread that flushes."""
    log = []
    enter, leave = perfscope.phase.__enter__, perfscope.phase.__exit__

    def _enter(self):
        log.append(("enter", self._name))
        return enter(self)

    def _leave(self, *exc):
        log.append(("exit", self._name))
        return leave(self, *exc)

    monkeypatch.setattr(perfscope.phase, "__enter__", _enter)
    monkeypatch.setattr(perfscope.phase, "__exit__", _leave)
    real_jit, real_bump = metrics.dispatch_jit, metrics.bump

    def jit(kernel, fn, *a, **k):
        log.append(("dispatch", kernel))
        return real_jit(kernel, fn, *a, **k)

    def bump(name, *a, **k):
        if name == "sync_ops_ingested":
            log.append(("bump", name))
        return real_bump(name, *a, **k)

    monkeypatch.setattr(metrics, "dispatch_jit", jit)
    monkeypatch.setattr(metrics, "bump", bump)
    real_ingress = tenantledger.note_ingress_round

    def ingress(counts):
        log.append(("ledger", "tenant"))
        return real_ingress(counts)

    monkeypatch.setattr(tenantledger, "note_ingress_round", ingress)
    return log


@pytest.mark.parametrize("how", ["batch", "tickets"])
def test_a_round_scatters_before_it_plans_and_reads_back_before_it_releases(
        how, declines, recorded):
    """One 40-document round on an eager engine with a current copy: the
    scatter is dispatched before `route` is entered, the gather and the
    reconcile after it; both ledgers are written before `readback` opens;
    `sync_ops_ingested` rises and the riders are released after it closes,
    when the round's hashes are in the host mirror and nothing is
    unsettled."""
    s = Service()
    rset, svc = s.rset, s.svc
    assert rset._dev_current
    real_admit = svc.doc_ledger.note_admit_round
    real_resolve = svc._early_resolve_locked
    state = {}

    def admit(counts):
        recorded.append(("ledger", "doc"))
        # between the halves: the round's device work is under way
        state["unsettled_at_tail"] = rset._unsettled is not None
        return real_admit(counts)

    def resolve():
        recorded.append(("release", len(svc._inflight_tickets)))
        state["clean_at_release"] = (
            rset._unsettled is None and not rset._doc_dirty)
        return real_resolve()

    svc.doc_ledger.note_admit_round = admit
    svc._early_resolve_locked = resolve
    del recorded[:]
    docs = s.ids[100:140]
    (s.batch if how == "batch" else s.tickets)(docs)
    svc.doc_ledger.note_admit_round = real_admit
    svc._early_resolve_locked = real_resolve
    log = list(recorded)

    def at(event):
        assert log.count(event) == 1, (event, log)
        return log.index(event)

    scatter = at(("dispatch", "scatter_trips"))
    route_in, route_out = at(("enter", "route")), at(("exit", "route"))
    gather = at(("dispatch", "gather_lanes"))
    reconcile = at(("dispatch", "reconcile_rows_hash"))
    ledgers = [at(("ledger", "doc")), at(("ledger", "tenant"))]
    rb_in, rb_out = at(("enter", "readback")), at(("exit", "readback"))
    counted = at(("bump", "sync_ops_ingested"))
    released = at(("release", 40 if how == "tickets" else 0))
    assert scatter < route_in < route_out < gather < reconcile
    assert reconcile < min(ledgers) and max(ledgers) < rb_in
    assert rb_in < at(("enter", "device_wait")) < rb_out
    assert rb_out < counted < released
    # `publish` closes before `readback` opens, and opens again after it
    publish = [i for i, ev in enumerate(log) if ev == ("enter", "publish")]
    closes = [i for i, ev in enumerate(log) if ev == ("exit", "publish")]
    inner = [(a, b) for a, b in zip(publish, closes)
             if a > reconcile and a < released + 2]
    assert len(inner) == 2 and inner[0][1] < rb_in and rb_out < inner[1][0]
    assert state == {"unsettled_at_tail": True, "clean_at_release": True}
    s.check()
    svc.close()


def test_a_batch_returns_with_its_hashes_in_the_mirror(declines):
    """What an acknowledgement means is unchanged: when `batch()` returns,
    the round's documents read their new hashes from the host mirror with
    no device work."""
    s = Service()
    docs = s.ids[10:50]
    s.batch(docs)
    assert s.rset.hashes_clean and s.rset._unsettled is None
    jits0 = _count("engine_kernels_dispatched{kernel=reconcile_rows_hash}")
    s.check()
    assert _count(
        "engine_kernels_dispatched{kernel=reconcile_rows_hash}") == jits0
    s.svc.close()


# -- (b) every route, dispatch + collect against apply_round_frames -----------


def _lanes_current(f):
    return [3, 4, 200, 201]


def _lanes_after_add_docs(f):
    new = [f"e{j:03d}" for j in range(250)]     # past the pad: re-laid
    f.rset.add_docs(new)
    f.ids += new
    f.logs += [[] for _ in new]
    assert not f.rset._dev_current
    return [0, 549]


def _whole(f):
    return list(range(200))                     # a majority of 300


def _one_document(f):
    return [130]


ROUTES = {
    # name: (fixtures, set-up -> the round's documents, the route, what
    #        the dispatch half leaves unsettled, gathered on)
    "lanes-copy-current": (("declines",), _lanes_current, "lanes", True),
    "lanes-after-add_docs": (("declines",), _lanes_after_add_docs,
                             "lanes", True),
    "whole-read-back": (("declines",), _whole, "whole", True),
    "fused": (("cpu_link",), _lanes_current, "fused", False),
    "blocks": (("no_fused_route",), _one_document, "blocks", False),
    "deferred": (("declines",), _lanes_current, "deferred", False),
}


@pytest.mark.parametrize("name", sorted(ROUTES))
def test_dispatch_then_collect_equals_apply_round_frames(
        name, routes, request, monkeypatch):
    fixtures, setup, kind, unsettled = ROUTES[name]
    monkeypatch.setattr(dispatch, "_megabatch", True)
    for fx in fixtures:
        request.getfixturevalue(fx)
    heavy = (lambda i: 40 if i == 0 else 1) if kind == "fused" \
        else (lambda i: 1)
    whole, halves = Fleet(history=heavy), Fleet(history=heavy)
    if kind == "blocks":
        # a single edit first: the whole buffer, which leaves _h_prev
        for f in (whole, halves):
            f.rset.apply_round_frames([f.frame([7])])
    if kind == "deferred":
        whole.rset.lazy_dispatch = halves.rset.lazy_dispatch = True
    docs = setup(whole)
    assert setup(halves) == docs
    del routes[:]
    got = whole.rset.apply_round_frames([whole.frame(docs)])
    collects = _collects(halves.rset, monkeypatch)
    halves.rset.dispatch_round_frames([halves.frame(docs)])
    assert [r for r in routes if not r.startswith("read:")] == [kind, kind]
    assert (halves.rset._unsettled is not None) == unsettled
    if unsettled:
        # the round's lanes stay dirty until the collect
        assert {halves.rset.doc_index[halves.ids[i]] for i in docs} \
            <= halves.rset._doc_dirty
    halves.rset.collect_round()
    assert halves.rset._unsettled is None
    assert len(collects) == int(unsettled)
    want = _oracle(whole.logs)
    if kind == "deferred":
        assert got is None and halves.rset._doc_dirty
    else:
        np.testing.assert_array_equal(np.asarray(got)[:len(want)], want)
        if kind != "blocks":        # blocks: the vector stays on the device
            assert not halves.rset._doc_dirty
            np.testing.assert_array_equal(
                halves.rset._hash_mirror[:len(want)], want)
        assert halves.rset._dev_current == whole.rset._dev_current
    np.testing.assert_array_equal(halves.rset.hashes(), want)
    np.testing.assert_array_equal(whole.rset.hashes(), want)
    # and the round after finds both engines in the same state
    for f in (whole, halves):
        f.check(f.rset.apply_round_frames([f.frame([1, 250])])
                if kind != "deferred" else f.rset.hashes())


# -- (c) every entry of the engine, between the halves ------------------------


def _hashes(f):
    f.rset.hashes()


def _hashes_for(f):
    f.rset.hashes_for([3, 50])


def _next_dispatch(f):
    f.rset.dispatch_round_frames([f.frame([4, 5, 260])])


def _next_apply(f):
    f.rset.apply_round_frames([f.frame([4, 5, 260])])


def _single_edit(f):
    f.rset.apply_round_frames([f.frame([3])])


def _apply_rounds_cols(f):
    i = 4
    new = [Change(actor="W", seq=len(f.logs[i]) + 1, deps={},
                  ops=[Op("set", ROOT_ID, key="k0", value=9)])]
    f.logs[i] += new
    f.rset.apply_rounds_cols([{f.ids[i]: changes_to_columns(new)}])


def _add_docs_in_the_pad(f):
    f.rset.add_docs(["e000"])
    f.ids.append("e000")
    f.logs.append([])


def _add_docs_past_the_pad(f):
    new = [f"e{j:03d}" for j in range(100)]
    f.rset.add_docs(new)
    f.ids += new
    f.logs += [[] for _ in new]
    assert f.rset.rows_dev is None


def _grow(f):
    f.rset._grow(cap_ops=2 * f.rset.cap_ops)


def _compact(f):
    stats = f.rset.compact({f.ids[3]: {"W": len(f.logs[3])}})
    assert stats[f.ids[3]]["ops_after"] < stats[f.ids[3]]["ops_before"]


def _seed_clock(f):
    # a second actor joins document 3: its lane is rewritten
    f.rset.seed_clock(f.ids[3], {"V": 0})


def _rebuild(f):
    f.rset._rebuild_from_log()


def _drop_copy(f):
    f.rset._drop_copy()


ENTRIES = {
    "hashes": (_hashes, "settles"),
    "hashes_for": (_hashes_for, "settles"),
    "next-dispatch_round_frames": (_next_dispatch, "replaces"),
    "next-apply_round_frames": (_next_apply, "drops"),
    "a-single-edit": (_single_edit, "drops"),
    "apply_rounds_cols": (_apply_rounds_cols, "drops"),
    "add_docs-in-the-pad": (_add_docs_in_the_pad, "drops"),
    "add_docs-past-the-pad": (_add_docs_past_the_pad, "drops"),
    "_grow": (_grow, "drops"),
    "compact": (_compact, "drops"),
    "seed_clock": (_seed_clock, "drops"),
    "_rebuild_from_log": (_rebuild, "drops"),
    "_drop_copy": (_drop_copy, "drops"),
}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_an_entry_between_the_halves_settles_or_drops_the_round(
        name, declines):
    """Document 3 is edited twice (30 changes behind it, so a compaction
    has slots to reclaim): its lane is in the unsettled round. Whatever
    the entry does, no hash of the unsettled vector reaches the mirror
    behind a later write, the late collect is harmless, and the hashes
    equal the reference's."""
    entry, effect = ENTRIES[name]
    f = Fleet(history=lambda i: 30 if i == 3 else 1)
    f.rset.dispatch_round_frames([f.frame([3, 4, 200])])
    first = f.rset._unsettled
    assert first is not None and list(first[0]) == [3, 4, 200]
    entry(f)
    if effect == "settles":
        assert f.rset._unsettled is None and not f.rset._doc_dirty
    elif effect == "drops":
        assert f.rset._unsettled is None
    else:
        assert f.rset._unsettled is not None \
            and f.rset._unsettled is not first
    f.rset.collect_round()          # the caller's, late
    assert f.rset._unsettled is None
    np.testing.assert_array_equal(f.rset.hashes(), _oracle(f.logs))
    assert not f.rset._doc_dirty
    f.check(f.rset.apply_round_frames([f.frame([3, 250])]))


def test_a_round_never_collected_is_reconciled_by_the_next_read(declines):
    """The caller owes the collect; the engine does not depend on it."""
    f = Fleet()
    for docs in ([3, 4], [4, 5], [200, 201]):
        f.rset.dispatch_round_frames([f.frame(docs)])
    assert {3, 4, 5} <= f.rset._doc_dirty       # dropped, still dirty
    np.testing.assert_array_equal(f.rset.hashes(), _oracle(f.logs))


def test_a_concurrent_hash_read_waits_for_the_flush(declines):
    """A reader on another thread takes the service lock: it never sees
    the engine between the halves."""
    s = Service()
    seen, errs = [], []
    real = s.rset.collect_round
    reading = threading.Event()

    def reader():
        reading.set()
        try:
            h = s.svc.hashes()
            seen.append(np.asarray([h[d] for d in s.ids], np.uint32))
        except BaseException as e:      # surfaced below
            errs.append(e)

    def collect(*a, **k):
        t = threading.Thread(target=reader, daemon=True, name="t-reader")
        t.start()
        assert reading.wait(5.0)
        t.join(timeout=0.2)             # parked on the lock
        assert t.is_alive() and not seen
        collect.thread = t
        return real(*a, **k)

    s.rset.collect_round = collect
    s.batch(s.ids[20:60])
    s.rset.collect_round = real
    collect.thread.join(timeout=10)
    assert not collect.thread.is_alive() and not errs
    np.testing.assert_array_equal(
        seen[0], _oracle([s.logs[d] for d in s.ids]))
    s.svc.close()


# -- (d) the counter ----------------------------------------------------------


def _storm_rounds(s):
    for k in range(3):
        s.batch(s.ids[40 * k:40 * k + 40])


def _single_edits(s):
    for d in s.ids[:5]:
        s.svc.apply_changes(d, [_edit(s.logs[d])])


OVERLAP = {
    "storm-rounds": (("declines",), _storm_rounds, 3, 1.0),
    "single-edits": (("declines",), _single_edits, 5, 0.0),
    "fused-rounds": (("cpu_link",), _storm_rounds, 3, 0.0),
}


@pytest.mark.parametrize("name", sorted(OVERLAP))
def test_rounds_collected_apart_over_rounds_flushed(name, request,
                                                    monkeypatch):
    fixtures, drive, flushes, share = OVERLAP[name]
    monkeypatch.setattr(dispatch, "_megabatch", True)
    for fx in fixtures:
        request.getfixturevalue(fx)
    s = Service()
    if name == "fused-rounds":
        # one heavy document sets the resident dims: the small ones are
        # one bucket under them, which the CPU-scale prices choose
        for _ in range(40):
            s.svc.apply_changes(s.ids[299], [_edit(s.logs[s.ids[299]])])
        s.svc.hashes()
    collects = _collects(s.rset, monkeypatch)
    flushed0 = _count("sync_rounds_flushed")
    fused0 = _count("engine_megabatch_rounds")
    drive(s)
    flushed = _count("sync_rounds_flushed") - flushed0
    assert flushed == flushes
    assert len(collects) / flushed == share
    assert (_count("engine_megabatch_rounds") - fused0 > 0) \
        == (name == "fused-rounds")
    s.check()
    s.svc.close()


def _kernel(name, what) -> int:
    kernels = (metrics.snapshot().get("perf") or {}).get("kernels") or {}
    return (kernels.get(name) or {}).get(what, 0)


def test_the_first_round_at_a_pad_compiles_the_next_pad_up(declines,
                                                           monkeypatch):
    """A round's scatter pads its triplets to a power of two. The first
    round at a pad also runs the scatter once at the next pad up, every
    triplet dropped (`scatter_trips_warm`): a later round whose count
    crosses the pad's edge finds its program and compiles nothing."""
    s = Service()
    rset = s.rset
    pads = []
    real = rset._merged_trips

    def merged(trip_list, least=8):
        out = real(trip_list, least)
        pads.append(len(out[0]))
        return out

    monkeypatch.setattr(rset, "_merged_trips", merged)
    warm = _kernel("scatter_trips_warm", "dispatches")
    s.batch(s.ids[:100])
    assert pads == [1024]
    assert (rset.rows_host.shape, 2048) in rset._scatters_warm
    assert _kernel("scatter_trips_warm", "dispatches") == warm + 1
    compiles = _kernel("scatter_trips", "compiles")
    s.batch(s.ids[:200])
    assert pads == [1024, 2048]
    assert _kernel("scatter_trips", "compiles") == compiles
    assert _kernel("scatter_trips_warm", "dispatches") == warm + 1
    s.check()
    s.svc.close()
