"""How a resident rows set reconciles its dirty lanes is decided once, in
one place (engine/dispatch.reconcile_route): a case below for every row of
the table in its docstring, on a stand-in for the engine that holds what
the router may observe and nothing else; then the engine itself: a
coalesced round asks the router once and plans once, a single edit after a
re-layout reconciles without a copy of the host mirror, and a round that
touches no lane survives a dropped device copy."""

from types import SimpleNamespace

import numpy as np
import pytest

from automerge_tpu.core.change import Change, Op
from automerge_tpu.core.ids import ROOT_ID
from automerge_tpu.engine import dispatch
from automerge_tpu.engine.batchdoc import apply_batch
from automerge_tpu.engine.resident_rows import ResidentRowsDocSet
from automerge_tpu.native.wire import changes_to_columns
from automerge_tpu.sync.frames import round_from_parts
from automerge_tpu.utils import metrics

N = 1000                # documents of the stand-in: lanes 0..999 of 1,024
FUSES, DECLINES = True, False


def standin(lazy=False, current=True, h_prev=True, handle=False, dirty=()):
    """What the router reads of an engine. `dirty`: the lanes marked dirty
    besides the ones the case hands the router (which a round has marked
    before it asks)."""
    return SimpleNamespace(
        doc_ids=[None] * N, n_pad=1024, lazy_dispatch=lazy,
        _dev_current=current, _h_prev=object() if h_prev else None,
        _hash_handle=object() if handle else None, _doc_dirty=set(dirty))


def case(name, want, lanes, round_docs=None, verdicts=(), planned=None,
         over=None, readback=True, blocks=(), megabatch=True,
         scatters=False, **engine):
    """`verdicts`: what plan_round answers, call by call; `planned`: the
    lane lists it must have been asked about (default: none); `over`: the
    lanes the route reconciles (default: `lanes`); `scatters`: the
    table's last column, what scatters_first answers before the plan."""
    lanes = list(lanes)
    return pytest.param(
        SimpleNamespace(
            engine=engine, lanes=lanes, round_docs=round_docs,
            verdicts=list(verdicts), want=want, readback=readback,
            scatters=scatters,
            planned=[list(p) for p in (planned or [])],
            over=lanes if over is None else list(over),
            blocks=tuple(blocks), megabatch=megabatch), id=name)


TABLE = [
    # -- a round ----------------------------------------------------------
    case("round-lazy", "deferred", [3, 400], round_docs=2, lazy=True,
         over=[]),
    case("round-one-document-valid-h_prev", "blocks", [130], round_docs=1,
         readback=False, blocks=[1]),
    case("round-one-document-no-h_prev", "whole", [130], round_docs=1,
         readback=False, h_prev=False),
    case("round-one-document-copy-not-current", "whole", [130],
         round_docs=1, readback=False, current=False),
    case("round-megabatch-off-three-blocks-pad-to-four", "blocks",
         [1, 300, 900], round_docs=3, megabatch=False, readback=False,
         blocks=[0, 2, 7, 7]),
    case("round-megabatch-off-block-majority", "whole",
         [1, 200, 300, 500, 900], round_docs=5, megabatch=False,
         readback=False),
    case("round-of-two-touching-no-lane", "whole", [], round_docs=2,
         readback=False),
    case("round-fuses-copy-current", "fused", [10, 600], round_docs=2,
         verdicts=[FUSES], planned=[[10, 600]], scatters=True),
    case("round-fuses-copy-not-current", "fused", [10, 600], round_docs=2,
         verdicts=[FUSES], planned=[[10, 600]], current=False,
         h_prev=False),
    case("round-declined-copy-current", "lanes", [10, 600], round_docs=2,
         verdicts=[DECLINES], planned=[[10, 600]], scatters=True),
    case("round-declined-copy-not-current", "lanes", [10, 600],
         round_docs=2, verdicts=[DECLINES], planned=[[10, 600]],
         current=False, h_prev=False),
    case("round-one-lane-of-two-documents", "lanes", [10], round_docs=2,
         verdicts=[DECLINES], planned=[[10]], scatters=True),
    case("round-majority-declined", "whole", range(600), round_docs=600,
         verdicts=[DECLINES], planned=[range(600)], scatters=True),
    case("round-majority-still-plans-and-fuses", "fused", range(600),
         round_docs=600, verdicts=[FUSES], planned=[range(600)],
         scatters=True),
    case("round-declined-lanes-from-outside", "lanes", [10, 600],
         round_docs=2, dirty=[300, 301], verdicts=[DECLINES, DECLINES],
         planned=[[10, 600], [10, 300, 301, 600]],
         over=[10, 300, 301, 600], scatters=True),
    case("round-declined-lanes-from-outside-fuse", "fused", [10, 600],
         round_docs=2, dirty=[300, 301], verdicts=[DECLINES, FUSES],
         planned=[[10, 600], [10, 300, 301, 600]],
         over=[10, 300, 301, 600], scatters=True),
    case("round-declined-outside-makes-a-majority", "whole", [10, 600],
         round_docs=2, dirty=range(700), verdicts=[DECLINES],
         planned=[[10, 600]], over=range(700), scatters=True),
    case("round-fuses-whatever-else-is-dirty", "fused", [10, 600],
         round_docs=2, dirty=range(700), verdicts=[FUSES],
         planned=[[10, 600]], scatters=True),
    # -- a read -----------------------------------------------------------
    case("read-pending-handle", "handle", [], handle=True, over=[]),
    case("read-pending-handle-wins-over-dirty-lanes", "handle", [5, 6],
         handle=True, over=[]),
    case("read-stale-handle-is-not-a-route", "lanes", [5, 6], handle=True,
         current=False, h_prev=False, verdicts=[DECLINES],
         planned=[[5, 6]]),
    case("read-minority-fuses", "fused", [5, 6, 7], verdicts=[FUSES],
         planned=[[5, 6, 7]]),
    case("read-minority-declined", "lanes", [5, 6, 7], verdicts=[DECLINES],
         planned=[[5, 6, 7]]),
    case("read-majority-never-plans", "whole", range(501)),
    case("read-exactly-half-is-no-minority", "whole", range(500)),
    case("read-just-under-half", "lanes", range(499), verdicts=[DECLINES],
         planned=[range(499)]),
]


@pytest.mark.parametrize("c", TABLE)
def test_the_routers_table(c, monkeypatch):
    asked = []

    def plan_round(rset, idxs):
        asked.append(list(idxs))
        fuses = c.verdicts.pop(0)
        return dispatch.RoundPlan("megabatch" if fuses else "per_doc",
                                  list(idxs), [{"a bucket": 0}] * fuses)

    monkeypatch.setattr(dispatch, "plan_round", plan_round)
    monkeypatch.setattr(dispatch, "_megabatch", c.megabatch)
    rset = standin(**c.engine)
    if c.round_docs is not None:
        rset._doc_dirty.update(c.lanes)     # a round marks, then asks
    before = (set(rset._doc_dirty), rset._hash_handle, rset._h_prev)
    if c.round_docs is not None:
        # the half a round takes first, with no plan; a read never asks
        assert dispatch.scatters_first(
            rset, c.lanes, c.round_docs) is c.scatters
        assert asked == []
    route = dispatch.reconcile_route(rset, c.lanes, c.round_docs)
    # only a route that reads back (a round that planned) scatters first
    assert not c.scatters or (route.readback and route.kind != "deferred")
    assert (route.kind, route.readback) == (c.want, c.readback)
    assert list(route.lanes) == c.over
    assert route.blocks == c.blocks
    assert asked == c.planned and not c.verdicts
    if c.want == "fused":
        assert route.plan.route == "megabatch" and route.plan.docs == c.over
    else:
        assert route.plan is None
    # the router reads; the engine acts
    assert (rset._doc_dirty, rset._hash_handle, rset._h_prev) == before


def test_every_route_of_the_docstring_has_a_case():
    doc = dispatch.reconcile_route.__doc__
    kinds = {"deferred", "blocks", "whole", "lanes", "fused", "handle"}
    assert all(f"| {k}" in doc for k in kinds)
    assert {c.values[0].want for c in TABLE} == kinds
    one_program = {c.values[0].want for c in TABLE
                   if not c.values[0].readback}
    assert one_program == {"blocks", "whole"}
    # the last column: every kind a planning round can get, on both sides
    assert "scatters before the plan" in doc
    rounds = [c.values[0] for c in TABLE
              if c.values[0].round_docs is not None]
    assert {c.want for c in rounds if c.scatters} \
        == {"fused", "lanes", "whole"}
    assert {c.want for c in rounds if not c.scatters} \
        == {"deferred", "blocks", "whole", "fused", "lanes"}


# -- the engine ---------------------------------------------------------------


def _oracle(logs) -> np.ndarray:
    _, _, out = apply_batch(logs)
    return np.asarray(out["hash"]).astype(np.uint32)


class Fleet:
    """An eager rows engine over `n` documents of one writer, loaded with
    `history(i)` one-op changes each, and the logs the oracle replays."""

    def __init__(self, n=300, history=lambda i: 1):
        self.ids = [f"d{i:04d}" for i in range(n)]
        self.logs = [[] for _ in self.ids]
        self.rset = ResidentRowsDocSet(self.ids, actors=["W"])
        if self.rset._native is None:
            pytest.skip("round frames need the native encoder")
        self.rset.apply_round_frames(
            [self.frame({i: history(i) for i in range(n)})])
        self.rset.hashes()

    def frame(self, edits):
        """A round frame of `edits`: {document: how many new changes}, or
        documents (one change each)."""
        if not isinstance(edits, dict):
            edits = dict.fromkeys(edits, 1)
        parts = {}
        for i, k in edits.items():
            new = [Change(actor="W", seq=len(self.logs[i]) + j + 1, deps={},
                          ops=[Op("set", ROOT_ID, key=f"k{j % 7}",
                                  value=len(self.logs[i]) + j)])
                   for j in range(k)]
            self.logs[i] += new
            parts[self.ids[i]] = [changes_to_columns(new)]
        return round_from_parts(parts)

    def check(self, handle):
        np.testing.assert_array_equal(
            np.asarray(handle)[:len(self.ids)], _oracle(self.logs))


def _route_phase() -> int:
    phases = (metrics.snapshot().get("perf") or {}).get("phases") or {}
    return (phases.get("route") or {}).get("count", 0)


def _count(name) -> int:
    return metrics.snapshot().get(name, 0)


@pytest.fixture
def plans(monkeypatch):
    """The lane lists plan_round is asked about, and its verdicts."""
    monkeypatch.setattr(dispatch, "_megabatch", True)
    seen = []
    real = dispatch.plan_round

    def spy(rset, idxs):
        plan = real(rset, idxs)
        seen.append((list(idxs), plan.route))
        return plan

    monkeypatch.setattr(dispatch, "plan_round", spy)
    return seen


def test_a_coalesced_round_plans_once_and_enters_route_once(plans):
    """The storm path of every cell of the benchmark: documents of two
    sizes make two buckets, which the link prices decline; the round's
    lanes then go straight to the lane gather. (The parent planned the same
    lanes a second time on the way there.)"""
    f = Fleet(history=lambda i: 30 if i < 10 else 1)
    del plans[:]                                   # the load's own
    route0, declined0 = _route_phase(), _count("engine_megabatch_fallbacks")
    gathers0 = _count("rows_lane_gathers_device")
    f.check(f.rset.apply_round_frames([f.frame([0, 1, 100, 101])]))
    assert plans == [([0, 1, 100, 101], "per_doc")]
    assert _route_phase() - route0 == 1
    assert _count("engine_megabatch_fallbacks") - declined0 == 1
    assert _count("rows_lane_gathers_device") - gathers0 == 1
    assert f.rset._dev_current and not f.rset._doc_dirty
    # a single edit asks the router too, and never plans
    f.check(f.rset.apply_round_frames([f.frame([5])]))
    assert len(plans) == 1 and _route_phase() - route0 == 2


def test_a_declined_round_after_add_docs_reaches_the_fresh_lanes(plans):
    """Documents added past the hash mirror's length, then a round over an
    old and a fresh one that the prices decline: the lane route is the
    first to write the fresh lane's hash, from the host mirror (the copy
    was dropped with the re-layout), and primes."""
    f = Fleet(history=lambda i: 30 if i < 10 else 1)
    new = [f"e{j:03d}" for j in range(250)]        # 550 documents
    f.rset.add_docs(new)
    f.ids += new
    f.logs += [[] for _ in new]
    assert len(f.rset._hash_mirror) < len(f.ids)
    del plans[:]
    host0 = _count("rows_lane_gathers_host")
    f.check(f.rset.apply_round_frames([f.frame([0, 549])]))
    # the fresh lanes were dirty from outside the round, and with them a
    # minority still: all of them routed as a read, which plans again
    assert [p[1] for p in plans] == ["per_doc", "per_doc"]
    assert len(plans[1][0]) == 251
    assert _count("rows_lane_gathers_host") - host0 == 1
    assert f.rset._dev_current and not f.rset._doc_dirty
    del plans[:]
    f.check(f.rset.apply_round_frames([f.frame([1, 548])]))
    assert plans == [([1, 548], "per_doc")]


def test_a_fused_verdict_under_cpu_link(plans, cpu_link):
    """One heavy document sets the resident dims; a round of small ones is
    one bucket under them, which the CPU-scale prices choose."""
    f = Fleet(history=lambda i: 40 if i == 0 else 1)
    route = dispatch.reconcile_route(f.rset, [3, 4, 200], round_docs=3)
    assert route.kind == "fused" and len(route.plan.buckets) == 1
    assert route.plan.est_mega_s <= route.plan.est_alt_s
    fused0 = _count("engine_megabatch_rounds")
    f.check(f.rset.apply_round_frames([f.frame([3, 4, 200])]))
    assert _count("engine_megabatch_rounds") - fused0 == 1
    assert plans[-1] == ([3, 4, 200], "megabatch")
    # the buckets come out of the host mirror; then the round uploads the
    # mirror (a fused round reads its hashes back: no _h_prev)
    assert f.rset._dev_current and f.rset._h_prev is None
    # the next fuses over the current copy and runs the lane route once at
    # its width
    f.check(f.rset.apply_round_frames([f.frame([3, 4, 200])]))
    assert plans[-1] == ([3, 4, 200], "megabatch")
    assert (128, f.rset.n_pad, f.rset.dims()) in f.rset._lanes_warm


def test_a_round_declined_after_fused_ones_compiles_nothing(
        plans, cpu_link, monkeypatch):
    """Fused rounds, then one the plan declines (its documents crossed a
    bucket's size): it gathers its lanes out of the copy the fused rounds
    left current, with the programs they ran at its width."""
    f = Fleet(history=lambda i: 40 if i == 0 else 1)
    f.check(f.rset.apply_round_frames([f.frame([3, 4, 200])]))
    assert plans[-1] == ([3, 4, 200], "megabatch")
    monkeypatch.setattr(dispatch, "plan_round",
                        lambda rset, idxs: dispatch.RoundPlan("per_doc",
                                                              list(idxs)))
    retraced0 = {k: _count(f"engine_kernels_retraced{{kernel={k}}}")
                 for k in ("gather_lanes", "reconcile_rows_hash")}
    gathers0 = _count("rows_lane_gathers_device")
    f.check(f.rset.apply_round_frames([f.frame([5, 6, 201])]))
    assert _count("rows_lane_gathers_device") - gathers0 == 1
    assert {k: _count(f"engine_kernels_retraced{{kernel={k}}}")
            for k in retraced0} == retraced0


def test_a_single_edit_after_a_relayout_copies_no_mirror(plans):
    """add_docs past the lane padding re-lays the mirror and drops the
    device copy. The edit that follows uploads the mirror as committed and
    scatters its triplets onto equal cells; the parent copied the whole
    mirror before the commit to upload that."""
    f = Fleet()
    del plans[:]                                   # the load's own
    new = [f"e{j:03d}" for j in range(100)]        # 400 lanes: a 4th block
    f.rset.add_docs(new)
    f.ids += new
    f.logs += [[] for _ in new]
    assert f.rset.rows_dev is None and f.rset.n_pad == 512
    whole = f.rset.rows_host.shape
    copies = []

    class Watched(np.ndarray):
        def copy(self, *a, **k):
            if self.shape == whole:
                copies.append(self.shape)
            return np.asarray(self).copy(*a, **k)

    f.rset.rows_host = f.rset.rows_host.view(Watched)
    blocks0 = _count("rows_apply_block_calls")
    f.check(f.rset.apply_round_frames([f.frame([7])]))     # whole, primed
    assert isinstance(f.rset.rows_host, Watched) and copies == []
    assert f.rset._dev_current and f.rset._h_prev is not None
    f.check(f.rset.apply_round_frames([f.frame([350])]))   # a fresh lane
    f.check(f.rset.apply_round_frames([f.frame([7])]))
    assert _count("rows_apply_block_calls") - blocks0 == 2
    assert copies == [] and plans == []
    np.testing.assert_array_equal(f.rset.hashes(), _oracle(f.logs))


def test_a_round_touching_no_lane_survives_a_dropped_copy(plans):
    """Two documents' changes arrive a second time, after a re-layout: the
    round names two documents, admits nothing and touches no lane. It
    never plans, and its whole-buffer route uploads the mirror (the parent
    handed `_apply_final` no buffer: a DeviceDispatchError)."""
    f = Fleet()
    del plans[:]
    again = round_from_parts(
        {f.ids[i]: [changes_to_columns(f.logs[i])] for i in (3, 4)})
    f.rset._grow(cap_ops=2 * f.rset.cap_ops)
    assert not f.rset._dev_current
    f.check(f.rset.apply_round_frames([again]))
    assert plans == [] and f.rset._dev_current
