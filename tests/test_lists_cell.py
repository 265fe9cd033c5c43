"""The long-lived list cell, `lists10k.storm`, on the CPU at a small size:
lists whose history passes the resident op rows within a few rounds, and
the served round compacting them one document at a time.

- the system against the plain RGA (`benchmarks/reference_boards.py`) on
  seeded fleets, list by list;
- the round compacts the documents it would take past the caps, and no
  others; the device copy stays current (no re-upload, no host gather); the
  caps do not grow where compaction made room;
- a concurrent insert anchored at a tombstone above the floor survives a
  compaction, and a floor that does not hold gives a loud rejection, never a
  silent loss; a floor an idle device holds down ends in RowsBudgetError;
- the benchmark's harness at a small fleet (a run is correct; a traced run
  reads the three metrics this cell adds) and every control of the check;
- the fleet kind: its load is tombstone-heavy, it never stops at a cap, and
  it makes its changes again from the seed, each naming only what its writer
  has seen.

Small dims: the full document holds 60 ops and two lists of 17 and 11
elements, so the resident caps are 64 op rows, 4 actors and two lists of
32 slots; a list loads with 20-56 op rows.
"""

import json
import os
import shutil
import sys

import numpy as np
import pytest

import jax

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
for _p in (os.path.join(BENCH, "tests"), BENCH, os.path.dirname(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import fleet as fleetlib  # noqa: E402
import reference_boards as rb  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402
from test_benchmark import DEVICE_METRICS, _rewrite, eager  # noqa: E402
from test_boards_cell import _names_unseen  # noqa: E402

from automerge_tpu.core.change import Change, Op  # noqa: E402
from automerge_tpu.engine.resident_rows import (  # noqa: E402
    CompactionAnchorError, RowsBudgetError)
from automerge_tpu.sync.service import EngineDocSet  # noqa: E402
from automerge_tpu.utils import metrics  # noqa: E402

CELL = "lists10k.storm"
SEED = 2**31 + 44
NEW_METRICS = ("compact_share", "compacted_per_round",
               "compact_reclaim_share")
SMALL_FLEET = {"n_small": 200, "n_heavy": 1, "heavy_ops": 60,
               "load_batch": 100, "history_cap": 64}
SMALL_LISTS = {"ops_at_load": [20, 56], "visible_at_load": [4, 8],
               "full_slots": 17}
CONTROLS = ("ack_before_flush", "lose_acknowledged", "stale_hash",
            "first_writer_wins", "reclaim_above_floor")
ROOT = "00000000-0000-0000-0000-000000000000"


@pytest.fixture
def small(tmp_path):
    """The benchmark's data files with 200 small lists and one full
    document, and rounds of 60 draws: some 45 lists a round."""
    root = str(tmp_path / "benchmarks")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    _rewrite(os.path.join(root, "configs", "lists10k.json"),
             fleet=SMALL_FLEET, lists=SMALL_LISTS,
             writers={"concurrent_share": 0.3})
    _rewrite(os.path.join(root, "traffic", "storm.json"),
             draws_per_request=60, warmup_requests=3)
    return root


def run_small(root, steer=eager, trace=0, max_requests=16):
    return run.run_cell(CELL, SEED, 600.0, trace, jax.devices(), root=root,
                        steer=steer, max_requests=max_requests,
                        may_miss=DEVICE_METRICS)


def counter(name: str) -> int:
    return int(metrics.snapshot().get(name, 0))


# ---------------------------------------------------------------------------
# the harness at a small size


def test_a_small_run_of_the_lists_cell_is_correct(small, capsys):
    before = counter("rows_compact_docs")
    res = run_small(small)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 16
    assert all(row["value"] == row["limit"] == 0
               for row in res["compared"].values())
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    load = next(ln for ln in lines if ln["stage"] == "load")
    assert load["dims"][0][:3] == [64, 4, 64]
    assert load["full_ops"] == [60]
    window = next(ln for ln in lines if ln["stage"] == "window")
    assert window["dims_before"] == window["dims_after"]
    assert window["phases_s"].get("compact", 0) > 0
    assert counter("rows_compact_docs") > before
    check = next(ln for ln in lines if ln["stage"] == "check")
    assert check["compared"]["states_compacted"] > 0


def test_the_traced_run_reads_the_new_metrics(small, monkeypatch):
    monkeypatch.setattr(run, "TRACE_DIR", os.path.join(small, ".bench_trace"))
    monkeypatch.setattr(run, "TRACE_START_SHARE", 0.0)
    res = run_small(small, trace=1, max_requests=10)
    assert res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NEW_METRICS) <= set(got)
    assert got["compact_share"] > 0 and got["compacted_per_round"] > 0
    assert 0 < got["compact_reclaim_share"] < 100
    want = {m["name"] for m in run.cell_metrics(CELL, small)}
    assert {"flush_mean_ms", "encode_share", "resident_gather_share",
            "compiles_in_window", "megakernel_roofline"} <= want
    assert want - set(DEVICE_METRICS) <= set(got)


def test_a_traced_run_places_most_lists_and_relinearizes_the_rest(
        small, monkeypatch):
    """The round's inserts are placed against the mirror's positions where
    each is its list's newest element, and the lists a concurrent insert
    reaches are re-linearized: both engage in a traced run, which stays
    correct with every compared number 0."""
    monkeypatch.setattr(run, "TRACE_DIR", os.path.join(small, ".bench_trace"))
    monkeypatch.setattr(run, "TRACE_START_SHARE", 0.0)
    names = ("rows_elem_lists_placed", "rows_elem_lists_relinearized",
             "rows_elem_pos_rows_shipped")
    before = {n: counter(n) for n in names}
    res = run_small(small, trace=1, max_requests=10)
    assert res["correct"] is True and res["failed"] == 0
    assert all(row["value"] == row["limit"] == 0
               for row in res["compared"].values())
    placed, relin, shipped = (counter(n) - before[n] for n in names)
    assert placed > relin > 0
    assert shipped > 0


def test_the_new_metrics_read_zero_on_a_program_without_the_phase():
    """A program older than the per-document compaction has no `compact`
    phase and no counters of it: its traced run still ends with a result."""
    ctx = {"window_s": 24.0, "delta": {"sync_rounds_flushed": 240}}
    for name in NEW_METRICS:
        m = fleetlib.load_json("metrics", name)
        value = run.load_by_path("readers", m["reader"]).read(m["args"], ctx)
        assert value == 0.0, name


def test_the_check_declares_five_controls():
    config = fleetlib.load_json("configs", "lists10k")
    assert sorted(run.seam(config, "check", "checks", run.check).CONTROLS) \
        == sorted(CONTROLS)


@pytest.mark.parametrize("control", CONTROLS)
def test_a_control_comes_out_not_correct(small, control):
    config = fleetlib.load_json("configs", "lists10k", small)
    make = run.seam(config, "check", "checks", run.check, small) \
        .CONTROLS[control]

    def stand_in(svc):
        svc.close()
        return make()
    res = run_small(small, steer=stand_in, max_requests=40)
    assert res["correct"] is False
    bad = {k for k, row in res["compared"].items()
           if row["value"] > row["limit"]}
    want = {"ack_before_flush": {"acks_before_flush"},
            "lose_acknowledged": {"changes_unserved", "hashes_wrong"},
            "stale_hash": {"hashes_wrong"},
            "first_writer_wins": {"hashes_wrong", "states_wrong"},
            "reclaim_above_floor": {"hashes_wrong", "states_wrong"}}[control]
    assert want <= bad, (control, res["compared"])


def test_the_reclaiming_reference_differs_only_where_a_straggler_anchors():
    """The control's rule on a hand-built list: A deletes `A:2`; B, not
    having seen that, inserts after it. The control drops `A:2` and B's
    item with it; without B's insert it renders as the RGA does."""
    a, b, obj = "a" * 32, "b" * 32, "list"
    base = [Change(a, 1, {}, [
        Op("makeList", obj), Op("link", ROOT, key="items", value=obj),
        Op("ins", obj, key="_head", elem=1),
        Op("set", obj, key=f"{a}:1", value="x"),
        Op("ins", obj, key=f"{a}:1", elem=2),
        Op("set", obj, key=f"{a}:2", value="y")])]
    delete = Change(a, 2, {}, [Op("del", obj, key=f"{a}:2")])
    straggler = Change(b, 1, {a: 1}, [
        Op("ins", obj, key=f"{a}:2", elem=3),
        Op("set", obj, key=f"{b}:3", value="z")])
    check = run.load_by_path("checks", "lists")
    log = base + [delete, straggler]
    assert rb.state(log)["data"]["items"] == ["x", "z"]
    assert check.ReclaimedDoc(log).state()["data"]["items"] == ["x"]
    assert check.ReclaimedDoc(log).state_hash() != rb.state_hash(log)
    quiet = base + [delete]
    assert check.ReclaimedDoc(quiet).state() == rb.state(quiet)
    # an insert after an element its own change then deletes, and one
    # that had seen the deletion, are no stragglers
    own = base + [Change(a, 2, {}, [
        Op("ins", obj, key=f"{a}:2", elem=3),
        Op("set", obj, key=f"{a}:3", value="z"),
        Op("del", obj, key=f"{a}:2")])]
    seen = base + [delete, Change(b, 1, {a: 2}, [
        Op("ins", obj, key=f"{a}:2", elem=3),
        Op("set", obj, key=f"{b}:3", value="z")])]
    for log in (own, seen):
        assert rb.state(log)["data"]["items"] == ["x", "z"]
        assert check.ReclaimedDoc(log).state() == rb.state(log)


# ---------------------------------------------------------------------------
# the served round against the plain reference


def _fleet(seed, n_lists=40, concurrent_share=0.1):
    config = fleetlib.load_json("configs", "lists10k")
    config["fleet"].update(SMALL_FLEET, n_small=n_lists, load_batch=20)
    config["lists"].update(SMALL_LISTS)
    config["writers"]["concurrent_share"] = concurrent_share
    return run.seam(config, "fleet_kind", "fleets", fleetlib).make(
        config, seed)


def _schedule(fleet, seed, draws=30):
    mix = dict(fleetlib.load_json("traffic", "storm"),
               draws_per_request=draws, warmup_requests=0)
    return traffic.make(mix, fleet, seed)


def _over_caps(rset, round_) -> set:
    """The documents a round would take past the current caps, counted as
    the engine's precheck counts them."""
    over = set()
    for d, chs in round_.items():
        i = rset.doc_index[d]
        ops = [o for c in chs for o in c.ops]
        ins = sum(o.action == "ins" for o in ops)
        if rset.op_count[i] + len(ops) > rset.cap_ops \
                or rset.tables[i].max_elems + ins > rset.cap_elems:
            over.add(d)
    return over


@pytest.fixture(scope="module")
def served():
    """40 rounds of a small fleet through the eager served path (the road
    the chip takes), with what each round did to the engine."""
    seed = 2**31 + 5
    fleet = _fleet(seed, concurrent_share=0.3)
    schedule = _schedule(fleet, seed)
    svc = EngineDocSet(backend="rows")
    eager(svc)
    sent: dict = {}
    rounds = []
    try:
        for round_ in fleet.load_rounds():
            fleetlib.apply_round(svc, round_)
            for d, chs in round_.items():
                sent.setdefault(d, []).extend(chs)
        svc.hashes()
        rset = svc._resident
        for r in range(40):
            round_ = fleet.request_changes(schedule.request(r))
            over = _over_caps(rset, round_)
            c0 = {k: counter(k) for k in (
                "rows_compact_docs", "rows_lane_gathers_host",
                "rows_caps_grown", "rows_lanes_put")}
            dims = rset.dims()
            fleetlib.apply_round(svc, round_)
            for d, chs in round_.items():
                sent[d].extend(chs)
            rounds.append({
                "over": over, "dims": (dims, rset.dims()),
                "delta": {k: counter(k) - v for k, v in c0.items()},
                "current": rset._dev_current,
                "copy_is_mirror": rset.rows_dev is not None and bool(
                    np.array_equal(np.asarray(rset.rows_dev),
                                   rset.rows_host))})
        hashes = svc.hashes()
        states = {d: svc.materialize(d) for d in fleet.doc_ids}
        logs = {d: list(svc.missing_changes(d, {})) for d in fleet.doc_ids}
    finally:
        svc.close()
    return {"fleet": fleet, "sent": sent, "rounds": rounds,
            "hashes": hashes, "states": states, "logs": logs}


def test_the_served_lists_agree_with_the_reference(served):
    fleet, sent = served["fleet"], served["sent"]
    assert fleet.anchored and fleet.reanchored
    for d in fleet.doc_ids:
        assert np.uint32(served["hashes"][d]) == rb.state_hash(sent[d]), d
        assert served["states"][d] == rb.state(sent[d]), d
        # compaction touches rows, never the log
        assert sorted((c.actor, c.seq) for c in served["logs"][d]) \
            == sorted((c.actor, c.seq) for c in sent[d]), d


def test_a_round_compacts_only_the_documents_it_takes_past_the_caps(served):
    rounds = served["rounds"]
    assert sum(r["delta"]["rows_compact_docs"] for r in rounds) > 0
    for r in rounds:
        assert r["delta"]["rows_compact_docs"] == len(r["over"])
        # never the fleet: at most the round's own documents
        assert len(r["over"]) < len(served["fleet"].doc_ids) // 4


def test_the_device_copy_stays_current_through_compaction(served):
    rounds = served["rounds"]
    compacting = [r for r in rounds if r["delta"]["rows_compact_docs"]]
    assert compacting
    for r in rounds:
        assert r["current"] and r["copy_is_mirror"]
        assert r["delta"]["rows_lane_gathers_host"] == 0
    assert all(r["delta"]["rows_lanes_put"] >= 1 for r in compacting)


def test_compaction_makes_room_so_the_caps_do_not_grow(served):
    for r in served["rounds"]:
        assert r["delta"]["rows_caps_grown"] == 0
        assert r["dims"][0] == r["dims"][1]
    # the lists' histories went well past the op rows the layout holds
    fleet = served["fleet"]
    assert max(fleet.lists[d].depth for d in fleet.small) \
        > SMALL_FLEET["history_cap"] + 32


@pytest.mark.parametrize("seed", [7, 2**31 + 9, 2**32 + 13])
def test_the_service_agrees_with_the_reference_list_by_list(seed):
    """Nine changes in ten concurrent with the list's latest, through the
    lazy served path (the CPU's own), compacted as they pass the caps."""
    fleet = _fleet(seed, concurrent_share=0.9)
    schedule = _schedule(fleet, seed)
    svc = EngineDocSet(backend="rows")
    sent: dict = {}
    before = counter("rows_compact_docs")
    try:
        for round_ in fleet.load_rounds():
            fleetlib.apply_round(svc, round_)
            for d, chs in round_.items():
                sent.setdefault(d, []).extend(chs)
        for r in range(30):
            round_ = fleet.request_changes(schedule.request(r))
            fleetlib.apply_round(svc, round_)
            for d, chs in round_.items():
                sent[d].extend(chs)
        hashes = svc.hashes()
        states = {d: svc.materialize(d) for d in fleet.doc_ids}
    finally:
        svc.close()
    assert counter("rows_compact_docs") > before
    assert fleet.anchored and fleet.reanchored
    for d in fleet.doc_ids:
        assert np.uint32(hashes[d]) == rb.state_hash(sent[d]), d
        assert states[d] == rb.state(sent[d]), d


# ---------------------------------------------------------------------------
# the floor


def _two_device_list():
    """A list by devices A and B, both of whom have written: B's change
    saw everything. Returns (changes, a, b, obj)."""
    a, b, obj = "a" * 32, "b" * 32, "list"
    ops = [Op("makeList", obj), Op("link", ROOT, key="items", value=obj)]
    prev = "_head"
    for k in range(1, 6):
        ops += [Op("ins", obj, key=prev, elem=k),
                Op("set", obj, key=f"{a}:{k}", value=f"v{k}")]
        prev = f"{a}:{k}"
    chs = [Change(a, 1, {}, ops),
           Change(b, 1, {a: 1}, [Op("set", obj, key=f"{a}:1", value="w")])]
    return chs, a, b, obj


def test_a_concurrent_insert_at_a_tombstone_above_the_floor_survives():
    """A deletes `A:3` and overwrites an item; the list is compacted to its
    causal floor, which B (not having seen the deletion) holds below it: the
    tombstone keeps its slot, and B's insert anchored at it admits and
    lands where the RGA puts it."""
    chs, a, b, obj = _two_device_list()
    later = [Change(a, 2, {b: 1}, [Op("del", obj, key=f"{a}:3"),
                                   Op("set", obj, key=f"{a}:2", value="u")])]
    straggler = Change(b, 2, {a: 1}, [Op("ins", obj, key=f"{a}:3", elem=6),
                                      Op("set", obj, key=f"{b}:6",
                                         value="s")])
    svc = EngineDocSet(backend="rows")
    try:
        svc.apply_changes("doc", chs + later)
        rset = svc._resident
        with svc._lock:
            floor = svc._compaction_floor_locked("doc")
            stats = rset.compact({"doc": floor})["doc"]
        assert stats["ops_after"] < stats["ops_before"]
        assert f"{a}:3" not in rset.lists.ghosts(rset.doc_index["doc"], obj)
        svc.apply_changes("doc", [straggler])
        log = chs + later + [straggler]
        assert np.uint32(svc.hashes()["doc"]) == rb.state_hash(log)
        assert svc.materialize("doc") == rb.state(log)
        assert rb.state(log)["data"]["items"] == ["w", "u", "s", "v4", "v5"]
    finally:
        svc.close()


def test_a_floor_past_the_deletion_rejects_the_straggler_loudly():
    """The same list compacted to A's whole clock (a floor no peer
    guarantees): the tombstone is reclaimed, and B's insert anchored at it
    is refused before admission, never admitted out of place."""
    chs, a, b, obj = _two_device_list()
    later = [Change(a, 2, {b: 1}, [Op("del", obj, key=f"{a}:3")])]
    straggler = Change(b, 2, {a: 1}, [Op("ins", obj, key=f"{a}:3", elem=6),
                                      Op("set", obj, key=f"{b}:6",
                                         value="s")])
    svc = EngineDocSet(backend="rows")
    try:
        svc.apply_changes("doc", chs + later)
        rset = svc._resident
        with svc._lock:
            rset.compact({"doc": {a: 2, b: 1}})
        assert f"{a}:3" in rset.lists.ghosts(rset.doc_index["doc"], obj)
        with pytest.raises(CompactionAnchorError):
            svc.apply_changes("doc", [straggler])
        assert svc.materialize("doc") == rb.state(chs + later)
    finally:
        svc.close()


def _two_lists():
    """Lists `la` and `lb` of one document, each holding A's elements
    `A:1`..`A:5` (a list's element counters are its own), and B's change
    that saw them. Returns (changes, a, b)."""
    a, b = "a" * 32, "b" * 32
    ops = []
    for obj, key in (("la", "a"), ("lb", "b")):
        ops += [Op("makeList", obj), Op("link", ROOT, key=key, value=obj)]
        prev = "_head"
        for k in range(1, 6):
            ops += [Op("ins", obj, key=prev, elem=k),
                    Op("set", obj, key=f"{a}:{k}", value=f"{key}{k}")]
            prev = f"{a}:{k}"
    chs = [Change(a, 1, {}, ops),
           Change(b, 1, {a: 1}, [Op("set", "la", key=f"{a}:1", value="w")])]
    return chs, a, b


def test_a_ghost_of_one_list_does_not_refuse_an_insert_into_another():
    """A deletes list a's `A:3` and the list is compacted past the
    deletion: `A:3` is a ghost of list a alone. A's insert anchored at
    list b's `A:3`, which is visible, admits and lands where the RGA puts
    it; B's straggler anchored at list a's ghost is still refused before
    admission."""
    chs, a, b = _two_lists()
    later = [Change(a, 2, {b: 1}, [Op("del", "la", key=f"{a}:3")])]
    into_b = Change(a, 3, {}, [Op("ins", "lb", key=f"{a}:3", elem=6),
                               Op("set", "lb", key=f"{a}:6", value="s")])
    straggler = Change(b, 2, {a: 1}, [Op("ins", "la", key=f"{a}:3", elem=6),
                                      Op("set", "la", key=f"{b}:6",
                                         value="t")])
    svc = EngineDocSet(backend="rows")
    try:
        svc.apply_changes("doc", chs + later)
        rset = svc._resident
        i = rset.doc_index["doc"]
        with svc._lock:
            rset.compact({"doc": {a: 2, b: 1}})
        svc.apply_changes("doc", [into_b])
        assert f"{a}:3" in rset.lists.ghosts(i, "la")
        assert f"{a}:3" not in rset.lists.ghosts(i, "lb")
        log = chs + later + [into_b]
        assert np.uint32(svc.hashes()["doc"]) == rb.state_hash(log)
        assert svc.materialize("doc") == rb.state(log)
        assert rb.state(log)["data"]["b"] == ["b1", "b2", "b3", "s", "b4",
                                              "b5"]
        with pytest.raises(CompactionAnchorError):
            svc.apply_changes("doc", [straggler])
        assert svc.materialize("doc") == rb.state(log)
    finally:
        svc.close()


def _idle_floor_rounds():
    """A list B made and then left idle, and A's rounds on it: six inserts
    and their deletion a round. Returns (B's change, the rounds)."""
    a, b, obj = "a" * 32, "b" * 32, "list"
    first = [Change(b, 1, {}, [Op("makeList", obj),
                               Op("link", ROOT, key="items", value=obj)])]

    def rounds():
        elem = 0
        for k in range(100):
            ops = []
            for _ in range(6):
                elem += 1
                ops += [Op("ins", obj, key="_head", elem=elem),
                        Op("set", obj, key=f"{a}:{elem}", value=elem)]
            yield [Change(a, 2 * k + 1, {b: 1}, ops),
                   Change(a, 2 * k + 2, {b: 1}, [
                       Op("del", obj, key=f"{a}:{e}")
                       for e in range(elem - 5, elem + 1)])]
    return first, rounds()


def test_an_idle_device_holds_the_floor_down_until_a_visible_refusal():
    """B wrote once and went idle: every deletion A makes stays above the
    floor, so compaction frees only the inserts and the overwritten values,
    and the list's op rows climb to the wall. The round that would pass it
    is refused with RowsBudgetError before admission, naming the list, its
    changes unacknowledged and dropped; every acknowledged change is still
    served and materialized as sent, and later reads go on."""
    first, rounds = _idle_floor_rounds()
    svc = EngineDocSet(backend="rows")
    log = list(first)
    before = counter("rows_compact_docs")
    refused = None
    try:
        svc.apply_changes("doc", first)
        for chs in rounds:
            try:
                svc.apply_changes("doc", chs)
            except RowsBudgetError as e:
                refused = e
                break
            log += chs
        assert refused is not None, "the wall was never reached"
        assert refused.doc_ids == ("doc",)
        assert counter("rows_compact_docs") > before
        rset = svc._resident
        assert rset.op_count[rset.doc_index["doc"]] <= 512 < len(log) * 6
        # the refused round is not left pending to fail every later flush:
        # reads serve exactly what was acknowledged
        assert svc._pending == {}
        served = svc.missing_changes("doc", {})
        assert sorted((c.actor, c.seq) for c in served) \
            == sorted((c.actor, c.seq) for c in log)
        admitted = rset.change_log[rset.doc_index["doc"]]
        assert sorted((c.actor, c.seq) for c in admitted) \
            == sorted((c.actor, c.seq) for c in log)
        assert rset.materialize("doc") == rb.state(log)
    finally:
        svc.close()


def test_a_refused_list_does_not_stop_the_other_documents_of_its_round():
    """Each of A's rounds on the idle-floor list rides one flush with a
    change to another document. The round that the list cannot take is
    refused for the list alone: the other document's change admits in that
    flush, and both documents hash as the reference does."""
    first, rounds = _idle_floor_rounds()
    c = "c" * 32
    svc = EngineDocSet(backend="rows")
    log, other = list(first), []
    refused = None
    try:
        svc.apply_changes("doc", first)
        for k, chs in enumerate(rounds):
            other.append(Change(c, k + 1, {}, [
                Op("set", ROOT, key="n", value=k)]))
            try:
                with svc.batch():
                    svc.apply_changes("doc", chs)
                    svc.apply_changes("other", other[-1:])
            except RowsBudgetError as e:
                refused = e
                break
            log += chs
        assert refused is not None and refused.doc_ids == ("doc",)
        rset = svc._resident
        assert svc._pending == {}
        assert len(rset.change_log[rset.doc_index["other"]]) == len(other)
        assert len(rset.change_log[rset.doc_index["doc"]]) == len(log)
        # the node goes on: the other document's next change admits alone
        other.append(Change(c, len(other) + 1, {}, [
            Op("set", ROOT, key="n", value=-1)]))
        svc.apply_changes("other", other[-1:])
        hashes = svc.hashes()
        assert np.uint32(hashes["other"]) == rb.state_hash(other)
        assert np.uint32(hashes["doc"]) == rb.state_hash(log)
        assert svc.materialize("other") == rb.state(other)
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# the fleet kind


def test_the_load_is_tombstone_heavy_and_spread_as_drawn():
    config = fleetlib.load_json("configs", "lists10k")
    config["fleet"].update(n_small=300, load_batch=300)
    fleet = run.seam(config, "fleet_kind", "fleets", fleetlib).make(
        config, 2**31 + 3)
    rounds = list(fleet.load_rounds())
    line = fleet.load_line()
    lo, hi = config["lists"]["ops_at_load"]
    depths = line["list_ops_min_median_max"]
    assert lo <= depths[0] and depths[2] <= hi + 2
    assert depths[1] > (lo + hi) / 2 * 0.85
    assert line["tombstone_share_min_median"][0] >= 0.6
    assert line["visible_min_median_max"][2] <= 96
    assert line["full_ops"] == [508] * 4
    # the full documents' first list fixes 256 slots a list, two lists
    full = rb.Doc(rounds[0]["full00"])
    assert sorted(len(e) for e in full.elems.values()) == [120, 132]
    # every device wrote after the churn: each one's latest change saw all
    for d in fleet.small[:20]:
        log = rounds[1][d]
        last = {c.actor: c for c in log}
        assert len(last) == 4
        assert all(c.seq == max(x.seq for x in log if x.actor == c.actor)
                   for c in last.values())


def test_the_lists_kind_never_stops_at_a_cap():
    fleet = _fleet(17, n_lists=30)
    schedule = _schedule(fleet, 17, draws=40)
    for _round in fleet.load_rounds():
        pass
    for r in range(80):
        got = fleet.request_changes(schedule.request(r))
        assert isinstance(got, dict) and got, (r, got)
    deepest = max(fleet.lists[d].depth for d in fleet.small)
    assert deepest > 3 * fleet.spec.history_cap
    assert max(fleet.lists[d].slots for d in fleet.small) \
        > SMALL_LISTS["full_slots"]


@pytest.mark.parametrize("seed", [3, 2**31 + 77])
def test_the_fleet_kind_makes_its_changes_again(seed):
    fleet = _fleet(seed, concurrent_share=0.3)
    schedule = _schedule(fleet, seed)
    sent: dict = {}
    for round_ in fleet.load_rounds():
        sent.update({d: list(chs) for d, chs in round_.items()})
    for r in range(20):
        for d, chs in fleet.request_changes(schedule.request(r)).items():
            sent[d].extend(chs)
    again, origin = fleet.replay(schedule, range(20))

    def plain(log):
        return [(c.actor, c.seq, dict(c.deps),
                 [(o.action, o.obj, o.key, o.value, o.elem) for o in c.ops])
                for c in log]
    assert {d: plain(v) for d, v in again.items()} \
        == {d: plain(v) for d, v in sent.items()}
    assert set(origin.values()) == set(range(20))
    owners: dict = {}
    for d, log in sent.items():
        assert len(rb.reference.causal_order(log)) == len(log)
        assert _names_unseen(log) == [], d
        for c in log:
            assert len(c.actor) == 32
            assert owners.setdefault(c.actor, d) == d
            keys = [(o.obj, o.key) for o in c.ops
                    if o.action in ("set", "del", "link")]
            assert len(keys) == len(set(keys))
            if d in fleet.small and c.ops[0].action != "makeList":
                # 1-4 actions: an insert is its `ins` and its `set`
                n_ins = sum(o.action == "ins" for o in c.ops)
                assert 1 <= len(c.ops) - n_ins <= 4
