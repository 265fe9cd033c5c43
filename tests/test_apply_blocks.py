"""The classic resident route reconciles the 128-lane blocks a round
dirtied, not the fleet (`resident_rows._apply_final` with `blocks`).

After every round the handle `apply_round_frames` returns must be
byte-equal, over every document, to (a) a fresh `reconcile_rows_hash` of
the whole host mirror and (b) the oracle's hashes: a block-route call
patches the dirty blocks' hashes into the vector the last call on the same
device buffer returned, so a stale vector (a buffer re-laid, re-uploaded
or dropped in between) or a wrong block would show as a wrong hash of a
document the round never touched.

The fleet spans eight blocks, the last partly padding: three dirty blocks
pad to four, which is still a minority of eight.

The round route keeps the device copy too (`_dispatch_final`'s megabatch
branch scatters the round's triplets into it, `_reconcile_lanes` gathers
the dirty lanes out of it), so the same parity holds after rounds, and
wherever the copy is current it must equal the host mirror cell for cell:
a triplet the scatter lost would show there, and as a wrong hash one
round later. On the CPU `jnp.asarray` may alias the mirror's memory, which
would make that comparison empty: the fleet's uploads copy.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from automerge_tpu.core.change import Change, Op
from automerge_tpu.core.ids import ROOT_ID
from automerge_tpu.engine import dispatch as round_dispatch
from automerge_tpu.engine import resident_rows
from automerge_tpu.engine.batchdoc import apply_batch
from automerge_tpu.engine.pallas_kernels import reconcile_rows_hash
from automerge_tpu.engine.resident_rows import (DeviceDispatchError,
                                                ResidentRowsDocSet)
from automerge_tpu.native.wire import changes_to_columns
from automerge_tpu.sync.frames import round_from_parts
from automerge_tpu.utils import metrics

N_DOCS = 1000                  # lanes 0..999 of 1,024: eight blocks
N_BLOCKS = 8


def _oracle(logs) -> np.ndarray:
    _, _, out = apply_batch(logs)
    return np.asarray(out["hash"]).astype(np.uint32)


def _edit(log) -> Change:
    """The next one-op change of a document's only writer."""
    c = Change(actor="W", seq=len(log) + 1, deps={},
               ops=[Op("set", ROOT_ID, key="n", value=len(log))])
    log.append(c)
    return c


@pytest.fixture(scope="module")
def loaded_hashes():
    """The oracle's hashes of the fleet as every case loads it."""
    return _oracle([[_edit([])] for _ in range(N_DOCS)])


class Fleet:
    """An eager rows engine over N_DOCS one-change documents, the logs
    the oracle replays, and the oracle's hash of every document."""

    def __init__(self, loaded_hashes):
        self.ids = [f"d{i:04d}" for i in range(N_DOCS)]
        self.logs = [[] for _ in self.ids]
        self.rset = ResidentRowsDocSet(self.ids, actors=["W"])
        if self.rset._native is None:
            pytest.skip("round frames need the native encoder")
        self.rset._to_dev = jnp.array       # a copy, never an alias
        self.want = loaded_hashes.copy()
        # every document in one round finds no device buffer and dirties
        # every block: the mirror uploads and the whole buffer reconciles
        before = _counters()
        self.check(self.rset.apply_round_frames(
            [self._frame(range(N_DOCS))]))
        assert _counters() == before and self.rset._h_prev is not None

    def _frame(self, docs):
        return round_from_parts(
            {self.ids[i]: [changes_to_columns([_edit(self.logs[i])])]
             for i in docs})

    def apply(self, docs, check=True):
        """One round that edits `docs`; returns its hash handle."""
        docs = list(docs)
        handle = self.rset.apply_round_frames([self._frame(docs)])
        if check:
            for i in docs:
                self.want[i] = _oracle([self.logs[i]])[0]
            self.check(handle)
        return handle

    def check(self, handle):
        got = np.asarray(handle)
        assert got.shape == (self.rset.n_pad,)
        n = len(self.want)
        fresh = np.asarray(reconcile_rows_hash(
            jnp.asarray(self.rset.rows_host), self.rset.dims(), True))
        np.testing.assert_array_equal(got, fresh)
        np.testing.assert_array_equal(got[:n], self.want)
        if self.current():
            np.testing.assert_array_equal(np.asarray(self.rset.rows_dev),
                                          self.rset.rows_host)

    def current(self) -> bool:
        return self.rset.rows_dev is not None and not self.rset._dirty

    def round(self, docs, gathered):
        """One checked round of the round route (two documents or more)
        whose dirty lanes must be gathered on the `gathered` side
        ("device" or "host"; None: the router fused it, no lane gather);
        the device copy is current afterwards either way."""
        before, jits = _gathers(), _dispatched()
        handle = self.apply(docs)
        after = _gathers()
        want = {"device": (1, 0), "host": (0, 1), None: (0, 0)}[gathered]
        assert (after[0] - before[0], after[1] - before[1]) == want, (
            f"round over {docs}")
        assert self.current() and self.rset._h_prev is None
        if gathered == "device":
            # the reconcile stays a dispatch of the top-level jit on the
            # gathered lanes (megakernel_roofline finds its kernel by the
            # jit's name), behind one scatter and one gather
            assert [b - a for a, b in zip(jits, _dispatched())] == [1, 1, 1]
        return handle

    def routed(self, docs, calls, blocks=0):
        """One checked round that must make `calls` block-route calls
        (0: the whole buffer) over `blocks` blocks."""
        before = _counters()
        handle = self.apply(docs)
        after = _counters()
        assert (after[0] - before[0], after[1] - before[1]) == (
            calls, blocks), f"round over {docs}"
        return handle


# the blocks each block-route `apply_final` dispatch was handed
_BLOCKS: list = []


@pytest.fixture(autouse=True)
def _block_counts(monkeypatch):
    real = metrics.dispatch_jit

    def dispatch_jit(kernel, fn, *args, **kwargs):
        if kernel == "apply_final" and args[2] is not None:
            _BLOCKS.append(len(args[2]))
        return real(kernel, fn, *args, **kwargs)

    monkeypatch.setattr(metrics, "dispatch_jit", dispatch_jit)
    yield
    _BLOCKS.clear()


def _counters():
    snap = metrics.snapshot()
    return (snap.get("rows_apply_block_calls", 0), sum(_BLOCKS))


def _gathers():
    snap = metrics.snapshot()
    return (snap.get("rows_lane_gathers_device", 0),
            snap.get("rows_lane_gathers_host", 0))


def _dispatched():
    kernels = (metrics.snapshot().get("perf") or {}).get("kernels") or {}
    return [(kernels.get(k) or {}).get("dispatches", 0) for k in
            ("scatter_trips", "gather_lanes", "reconcile_rows_hash")]


def _round_route(monkeypatch, fuses=False):
    """Rounds of two and more documents take the round route again; the
    router fuses them, or declines and leaves them to the lane gather
    (what it does in every cell of the benchmark)."""
    monkeypatch.setattr(round_dispatch, "_megabatch", True)
    if not fuses:
        monkeypatch.setattr(round_dispatch, "apply_round_adaptive",
                            lambda rset, plan, interpret=False: None)


def _first_block(f, monkeypatch):
    f.routed([5], 1, 1)
    f.routed([127], 1, 1)


def _last_block_partly_padding(f, monkeypatch):
    f.routed([N_DOCS - 1], 1, 1)
    f.routed([7 * 128], 1, 1)


def _two_documents_one_block(f, monkeypatch):
    f.routed([130, 200], 1, 1)


def _three_blocks_padded_to_four(f, monkeypatch):
    f.routed([1, 300, 900], 1, 4)
    f.routed([100, 400], 1, 2)


def _majority_of_blocks_takes_whole_buffer(f, monkeypatch):
    f.routed([1, 200, 300, 500, 900], 0)      # five blocks pad to eight
    f.routed([200], 1, 1)


def _after_fused_round(f, monkeypatch):
    """A round the router fuses leaves the copy on the device, with the
    round scattered into it; the hash vector beside it is gone, so the
    next single edit reconciles the whole buffer once and the one after
    it a block."""
    _round_route(monkeypatch, fuses=True)
    f.rset.hashes()            # the load's hashes read: no lane is dirty
    f.round([10, 600], None)   # two documents: the fused route's round
    f.routed([10], 0)
    f.routed([600], 1, 1)


def _round_on_current_copy(f, monkeypatch):
    _round_route(monkeypatch)
    f.rset.hashes()
    f.round([10, 600, 601], "device")
    f.routed([10], 0)
    f.routed([601], 1, 1)


def _two_rounds_in_a_row(f, monkeypatch):
    """The second round overwrites cells the first wrote (the same
    documents' next ops land on new rows, their clocks on the same)."""
    _round_route(monkeypatch)
    f.rset.hashes()
    f.round([3, 130, 999], "device")
    f.round([3, 4, 130, 500], "device")
    f.routed([4], 0)


def _round_after_grow(f, monkeypatch):
    _round_route(monkeypatch)
    f.rset._grow(cap_ops=2 * f.rset.cap_ops)
    f.rset.hashes()            # every lane dirty: the whole buffer, primed
    f.rset._grow(cap_ops=2 * f.rset.cap_ops)
    assert not f.current()
    # most lanes are dirty again: a hashes_for read reconciles a few on
    # the host and primes; the round after it gathers on the device
    before = _gathers()
    f.rset.hashes_for([700, 701])
    assert _gathers() == (before[0], before[1] + 1) and f.current()
    f.rset.hashes()
    f.round([700, 701], "device")


def _round_after_add_docs(f, monkeypatch):
    _round_route(monkeypatch)
    f.rset.hashes()
    _add_docs(f, 30)           # a ninth block: the buffer is dropped
    assert f.rset.rows_dev is None
    f.round([5, N_DOCS + 20], "host")      # reads the mirror, then primes
    f.round([5, N_DOCS + 21], "device")
    _add_docs(f, 10)           # inside the padding: the buffer stays
    f.round([6, N_DOCS + 35], "device")    # and the fresh lanes with it


def _round_after_compact(f, monkeypatch):
    f.routed([42], 1, 1)       # a second write of "n": the first is dominated
    f.rset.hashes()
    _round_route(monkeypatch)
    stats = f.rset.compact({f.ids[42]: {"W": len(f.logs[42])}})
    assert stats[f.ids[42]]["ops_after"] < stats[f.ids[42]]["ops_before"]
    # the copy stays current: the rewritten lane was written into it
    assert f.rset._dev_current and np.array_equal(
        np.asarray(f.rset.rows_dev)[:, 42], f.rset.rows_host[:, 42])
    f.round([42, 43], "device")
    f.round([42, 44], "device")


def _failed_gather_then_retry(f, monkeypatch):
    """tests/test_dispatch_failure.py's contract on the round route: the
    admission stands in the host mirror, the device copy is dropped, the
    lanes stay dirty, and the next round reconciles them from the mirror."""
    _round_route(monkeypatch)
    f.rset.hashes()

    def lost(*args, **kwargs):
        raise RuntimeError("device lost mid-gather")
    failed = metrics.snapshot().get("rows_dispatch_failed", 0)
    with monkeypatch.context() as m:
        m.setattr(resident_rows, "gather_lanes", lost)
        with pytest.raises(DeviceDispatchError) as err:
            f.apply([300, 301], check=False)
    assert err.value.admission_complete
    assert metrics.snapshot()["rows_dispatch_failed"] == failed + 1
    assert f.rset.rows_dev is None and f.rset._h_prev is None
    assert {300, 301} <= f.rset._doc_dirty
    for i in (300, 301):
        f.want[i] = _oracle([f.logs[i]])[0]
    f.round([302, 303], "host")
    f.round([300, 302], "device")


def _after_fused_round_and_majority_read(f, monkeypatch):
    """The load's hashes were never read, so the fused round's refresh
    finds most lanes dirty: it uploads the mirror, reconciles the whole
    buffer, and that vector serves the next round's block call."""
    monkeypatch.setattr(round_dispatch, "_megabatch", True)
    f.routed([10, 600], 0)
    assert f.rset.rows_dev is not None and f.rset._h_prev is not None
    f.routed([10], 1, 1)


def _after_grow(f, monkeypatch):
    f.rset._grow(cap_ops=2 * f.rset.cap_ops)
    assert f.rset._h_prev is None
    f.routed([700], 0)
    f.routed([701], 1, 1)


def _after_compact(f, monkeypatch):
    f.routed([42], 1, 1)       # a second write of "n": the first is dominated
    stats = f.rset.compact({f.ids[42]: {"W": len(f.logs[42])}})
    assert stats[f.ids[42]]["ops_after"] < stats[f.ids[42]]["ops_before"]
    # the copy and the vector stay: the rewritten lane was written into
    # the copy, and its hash did not move
    assert f.rset._dev_current and f.rset._h_prev is not None
    assert np.array_equal(np.asarray(f.rset.rows_dev)[:, 42],
                          f.rset.rows_host[:, 42])
    f.routed([42], 1, 1)
    f.routed([43], 1, 1)


def _add_docs(f, k):
    new = [f"e{len(f.ids) + j:04d}" for j in range(k)]
    f.rset.add_docs(new)
    f.ids += new
    f.logs += [[] for _ in new]
    f.want = np.concatenate([f.want, _oracle([[]] * k)])


def _after_add_docs(f, monkeypatch):
    _add_docs(f, 10)           # inside the padding: the buffer stays
    assert f.rset.n_pad == N_BLOCKS * 128 and f.rset._h_prev is not None
    f.routed([N_DOCS + 2], 1, 1)
    _add_docs(f, 30)           # a ninth block: the buffer is dropped
    assert f.rset.n_pad == (N_BLOCKS + 1) * 128
    assert f.rset.rows_dev is None and f.rset._h_prev is None
    f.routed([N_DOCS + 20], 0)
    f.routed([N_DOCS + 21], 1, 1)


def _kept_handle_is_not_donated(f, monkeypatch):
    first = f.routed([256], 1, 1)
    seen = np.asarray(first).copy()
    f.routed([257], 1, 1)
    f.routed([900], 1, 1)
    np.testing.assert_array_equal(np.asarray(first), seen)


def _failed_dispatch_then_retry(f, monkeypatch):
    def lost(*args, **kwargs):
        raise RuntimeError("device lost mid-dispatch")
    failed = metrics.snapshot().get("rows_dispatch_failed", 0)
    with monkeypatch.context() as m:
        m.setattr(resident_rows, "_apply_final", lost)
        with pytest.raises(DeviceDispatchError):
            f.apply([300], check=False)
    assert metrics.snapshot()["rows_dispatch_failed"] == failed + 1
    assert f.rset.rows_dev is None and f.rset._h_prev is None
    # the admission stands in the host mirror: the next round uploads it
    f.want[300] = _oracle([f.logs[300]])[0]
    f.routed([301], 0)
    f.routed([300], 1, 1)


SCENARIOS = {
    "first-block": _first_block,
    "last-block-partly-padding": _last_block_partly_padding,
    "two-documents-one-block": _two_documents_one_block,
    "three-blocks-padded-to-four": _three_blocks_padded_to_four,
    "majority-of-blocks": _majority_of_blocks_takes_whole_buffer,
    "after-fused-round": _after_fused_round,
    "after-fused-round-and-majority-read":
        _after_fused_round_and_majority_read,
    "after-grow": _after_grow,
    "after-compact": _after_compact,
    "after-add-docs": _after_add_docs,
    "kept-handle-not-donated": _kept_handle_is_not_donated,
    "failed-dispatch-then-retry": _failed_dispatch_then_retry,
    "round-on-current-copy": _round_on_current_copy,
    "two-rounds-in-a-row": _two_rounds_in_a_row,
    "round-after-grow": _round_after_grow,
    "round-after-add-docs": _round_after_add_docs,
    "round-after-compact": _round_after_compact,
    "failed-gather-then-retry": _failed_gather_then_retry,
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_block_route_hashes_equal_whole_fleet(scenario, loaded_hashes,
                                              no_fused_route, monkeypatch):
    f = Fleet(loaded_hashes)
    SCENARIOS[scenario](f, monkeypatch)


def test_block_route_compiles_a_shape_for_each_power_of_two(
        loaded_hashes, no_fused_route, monkeypatch):
    """However the dirty sets fall, the block route makes at most
    log2(blocks) shapes of `blocks`: 1, 2, 4 of eight; more dirty blocks
    than half take the whole buffer."""
    real = resident_rows._apply_final
    shapes = []

    def spy(rows, trips, blocks, h_prev, dims, interpret):
        shapes.append(None if blocks is None else blocks.shape)
        return real(rows, trips, blocks, h_prev, dims, interpret)

    monkeypatch.setattr(resident_rows, "_apply_final", spy)
    f = Fleet(loaded_hashes)
    rng = np.random.default_rng(28)
    for _ in range(12):
        k = int(rng.integers(1, N_BLOCKS + 1))
        in_blocks = rng.choice(N_BLOCKS, size=k, replace=False)
        docs = sorted({min(int(b) * 128 + int(rng.integers(128)),
                           N_DOCS - 1) for b in in_blocks})
        f.apply(docs)
    by_block = {s for s in shapes if s is not None}
    assert by_block and by_block <= {(1,), (2,), (4,)}, shapes
    assert None in shapes[1:], "no round dirtied a majority of the blocks"


def test_ledger_row_says_the_lanes_reconciled(loaded_hashes, no_fused_route,
                                              monkeypatch):
    """`rows_apply`'s docs axis is the lanes the call reconciled: the
    fleet's padded lanes for the whole buffer, the dirty blocks' for a
    block-route call."""
    from automerge_tpu.engine import dispatchledger
    seen = []
    real = dispatchledger.call_scope

    def spy(family, **kw):
        if family == "rows_apply":
            seen.append(kw["axes"]["docs"])
        return real(family, **kw)

    monkeypatch.setattr(dispatchledger, "call_scope", spy)
    f = Fleet(loaded_hashes)
    f.apply([1, 300, 900])
    assert seen == [(N_DOCS, N_BLOCKS * 128), (3, 4 * 128)]
