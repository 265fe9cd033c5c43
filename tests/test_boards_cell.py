"""The card-board cell, `boards10k.storm`, on the CPU: the system against its
plain reference (`benchmarks/reference_boards.py`) on seeded boards, the
reference against `bench.py`'s Trellis board, the check's six controls, and
the benchmark's harness at a small fleet (a run is correct; a traced run
reads the metric this cell adds and the storm metrics its mix and service
give), and the fleet's changes against what their writers have seen. Every
window is bounded by a count of requests.

The service runs at the link prices the chip has (no `cpu_link`): a round
of boards under 256 ops fuses out of the host mirror, and once its hot
boards pass 256 ops it declines to the lane route, whose dims (512, 8, 512)
only the XL variant of the reconcile kernel takes, as on the chip.
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

from automerge_tpu.utils import metrics  # noqa: E402

CELL = "boards10k.storm"
SEED = 2**31 + 42
NEW_METRICS = ("elem_admit_mean_ms",)
SMALL = {"n_small": 200, "n_heavy": 1, "load_batch": 100}
CONTROLS = ("ack_before_flush", "lose_acknowledged", "stale_hash",
            "first_writer_wins", "ascending_siblings", "tombstone_visible")


@pytest.fixture
def small(tmp_path):
    """The benchmark's data files with 200 boards and one full board, and
    rounds of 60 draws: some 45 boards a round, a minority of the fleet,
    so that a round reconciles its gathered lanes."""
    root = str(tmp_path / "benchmarks")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    _rewrite(os.path.join(root, "configs", "boards10k.json"), fleet=SMALL)
    _rewrite(os.path.join(root, "traffic", "storm.json"),
             draws_per_request=60, warmup_requests=3)
    return root


def run_small(root, steer=eager, trace=0, max_requests=12):
    return run.run_cell(CELL, SEED, 600.0, trace, jax.devices(), root=root,
                        steer=steer, max_requests=max_requests,
                        may_miss=DEVICE_METRICS)


def test_a_small_run_of_the_boards_cell_is_correct(small, capsys):
    res = run_small(small)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 12
    assert all(row["value"] == row["limit"] == 0
               for row in res["compared"].values())
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    load = next(ln for ln in lines if ln["stage"] == "load")
    # eight devices, the element slots of the full board's first column
    assert load["dims"][0][:3] == [512, 8, 512]
    assert load["full_board_ops"] == [416]
    window = next(ln for ln in lines if ln["stage"] == "window")
    assert window["dims_before"] == window["dims_after"]
    # every board drawn is under 256 ops: a round's lanes are one bucket,
    # which the link prices fuse out of the host mirror
    assert window["megabatch_rounds"] == 12
    assert not any(k.startswith("compiles.reconcile")
                   for k in window["compiles_in_window"])


def test_rounds_past_a_bucket_decline_and_compile_nothing(small, capsys):
    """Once the hot boards pass 256 ops a round holds two buckets and the
    plan declines: the lanes are gathered out of the copy the fused rounds
    kept current, by programs they ran at that lane width."""
    res = run_small(small, max_requests=30)
    assert res["correct"] is True and res["attempted"] == 30
    window = next(json.loads(ln) for ln in capsys.readouterr().out
                  .splitlines() if '"window"' in ln)
    assert 0 < window["megabatch_rounds"] < 30
    assert not any(k.startswith(("compiles.reconcile", "compiles.gather"))
                   for k in window["compiles_in_window"])


def test_the_traced_run_reads_the_new_metrics(small, monkeypatch):
    # a trace directory of its own, and the slice from the first request
    # on, so that the window is bounded by its count of requests
    monkeypatch.setattr(run, "TRACE_DIR", os.path.join(small, ".bench_trace"))
    monkeypatch.setattr(run, "TRACE_START_SHARE", 0.0)
    res = run_small(small, trace=1, max_requests=8)
    assert res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NEW_METRICS) <= set(got)
    assert got["elem_admit_mean_ms"] > 0
    want = {m["name"] for m in run.cell_metrics(CELL, small)}
    assert {"flush_mean_ms", "encode_share", "resident_gather_share",
            "compiles_in_window", "megakernel_roofline"} <= want
    assert want - set(DEVICE_METRICS) <= set(got)


def test_a_traced_run_places_most_lists_and_relinearizes_the_rest(
        small, monkeypatch):
    """Add-card and reorder insert into the boards' lists: where each
    insert is its list's newest element it is placed against the mirror's
    positions, and a list a concurrent insert reaches is re-linearized.
    Both engage in a traced run, which stays correct with every compared
    number 0."""
    monkeypatch.setattr(run, "TRACE_DIR", os.path.join(small, ".bench_trace"))
    monkeypatch.setattr(run, "TRACE_START_SHARE", 0.0)
    names = ("rows_elem_lists_placed", "rows_elem_lists_relinearized")
    before = {n: int(metrics.snapshot().get(n, 0)) for n in names}
    res = run_small(small, trace=1, max_requests=8)
    assert res["correct"] is True and res["failed"] == 0
    assert all(row["value"] == row["limit"] == 0
               for row in res["compared"].values())
    placed, relin = (int(metrics.snapshot().get(n, 0)) - before[n]
                     for n in names)
    assert placed > relin > 0


def test_the_check_declares_six_controls():
    config = fleetlib.load_json("configs", "boards10k")
    assert sorted(run.seam(config, "check", "checks", run.check).CONTROLS) \
        == sorted(CONTROLS)


@pytest.mark.parametrize("control", CONTROLS)
def test_a_control_comes_out_not_correct(small, control):
    config = fleetlib.load_json("configs", "boards10k", small)
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
            "ascending_siblings": {"hashes_wrong", "states_wrong"},
            "tombstone_visible": {"hashes_wrong", "states_wrong"}}[control]
    assert want <= bad, (control, res["compared"])


# ---------------------------------------------------------------------------
# the system against the plain reference, board by board


def _fleet(seed, n_boards=48, concurrent_share=0.1, dense=False):
    """A small fleet; `dense`: one card a device at load and a mix heavy in
    reorders, so that two concurrent changes often meet at one card or one
    anchor."""
    config = fleetlib.load_json("configs", "boards10k")
    config["fleet"].update(n_small=n_boards, n_heavy=1, load_batch=24)
    config["writers"]["concurrent_share"] = concurrent_share
    if dense:
        config["boards"]["cards_at_load"] = 1
        config["actions"] = {"add_card": 0.3, "mark_done": 0.3,
                             "reorder": 0.4}
    return run.seam(config, "fleet_kind", "fleets", fleetlib).make(
        config, seed)


def _schedule(fleet, seed, draws=40):
    mix = dict(fleetlib.load_json("traffic", "storm"),
               draws_per_request=draws, warmup_requests=0)
    return traffic.make(mix, fleet, seed)


def _done_conflicts(changes) -> int:
    """Card maps whose `done` holds two surviving values."""
    doc = rb.Doc(changes)
    return sum(1 for (obj, key), held in doc.fields.items()
               if key == "done" and len(held) > 1)


@pytest.mark.parametrize("seed", [7, 2**31 + 5, 2**32 + 11])
def test_the_service_agrees_with_the_reference_board_by_board(seed):
    """Nine changes in ten concurrent with the board's latest: concurrent
    inserts at one anchor, duplicated cards, tombstones beside live
    siblings and `done` conflicts on card maps, all through the served
    round path, compared hash by hash and state by state."""
    from automerge_tpu.sync.service import EngineDocSet
    fleet = _fleet(seed, concurrent_share=0.9, dense=True)
    schedule = _schedule(fleet, seed, draws=30)
    svc = EngineDocSet(backend="rows")
    eager(svc)
    sent: dict = {}
    try:
        for round_ in fleet.load_rounds():
            fleetlib.apply_round(svc, round_)
            for d, chs in round_.items():
                sent.setdefault(d, []).extend(chs)
        for r in range(40):
            round_ = fleet.request_changes(schedule.request(r))
            fleetlib.apply_round(svc, round_)
            for d, chs in round_.items():
                sent[d].extend(chs)
        hashes = svc.hashes()
        states = {d: svc.materialize(d) for d in fleet.doc_ids}
    finally:
        svc.close()
    assert fleet.anchored and fleet.duplicated and fleet.tombstoned
    assert sum(_done_conflicts(sent[d]) for d in fleet.small) > 0
    for d in fleet.doc_ids:
        assert np.uint32(hashes[d]) == rb.state_hash(sent[d]), d
        assert states[d] == rb.state(sent[d]), d
    # a duplicated card: the board shows one title twice
    for d in fleet.duplicated:
        titles = [c["title"] for col in states[d]["data"]["board"]["lists"]
                  for c in col["cards"]]
        assert len(titles) > len(set(titles)), d


def test_the_reference_agrees_with_the_trellis_board():
    """`bench.py`'s one board of BASELINE.json configs[1], made by the
    frontend: eight replicas append five cards each and half of them check
    one off, merged once. The reference renders it as the frontend does and
    hashes it as the engine's kernel does."""
    import bench
    import automerge_tpu as am
    from automerge_tpu.engine.batchdoc import apply_batch, oracle_state
    bench._load_package()
    changes = bench.gen_trellis(1)[0]
    doc = am.apply_changes(am.init("reader"), changes)
    assert rb.state(changes) == oracle_state(doc)
    cards = rb.state(changes)["data"]["board"]["lists"][0]["cards"]
    assert len(cards) == 40 and sum(c["done"] for c in cards) == 4
    _enc, _batch, out = apply_batch([changes])
    assert int(np.asarray(out["hash"])[0]) == rb.state_hash(changes)


@pytest.mark.parametrize("mode", [
    {"siblings": "ascending"}, {"tombstones": "visible"},
    {"first_writer": True}])
def test_each_broken_rule_changes_a_trellis_board(mode):
    """The rules the controls break all show on the Trellis board: eight
    concurrent first cards at the head of one list, and, after a card is
    dragged, its tombstone."""
    import bench
    import automerge_tpu as am
    bench._load_package()
    changes = bench.gen_trellis(1)[0]
    doc = am.apply_changes(am.init("reader"), changes)
    doc = am.change(doc, lambda d: d["board"]["lists"][0]["cards"]
                    .delete_at(3))
    changes = doc._doc.opset.get_missing_changes({})
    assert rb.state(changes, **mode) != rb.state(changes)
    assert rb.state_hash(changes, **mode) != rb.state_hash(changes)


@pytest.mark.parametrize("seed", [3, 2**31 + 77])
def test_the_fleet_kind_makes_its_changes_again(seed):
    fleet = _fleet(seed, concurrent_share=0.1)
    schedule = _schedule(fleet, seed)
    sent: dict = {}
    for round_ in fleet.load_rounds():
        sent.update({d: list(chs) for d, chs in round_.items()})
    for r in range(10):
        for d, chs in fleet.request_changes(schedule.request(r)).items():
            sent[d].extend(chs)
    again, origin = fleet.replay(schedule, range(10))

    def plain(log):
        return [(c.actor, c.seq, dict(c.deps),
                 [(o.action, o.obj, o.key, o.value, o.elem) for o in c.ops])
                for c in log]
    assert {d: plain(v) for d, v in again.items()} \
        == {d: plain(v) for d, v in sent.items()}
    assert set(origin.values()) == set(range(10))
    owners: dict = {}
    for d, log in sent.items():
        # every change's deps were there before it; no change assigns one
        # key twice
        assert len(rb.reference.causal_order(log)) == len(log)
        for c in log:
            assert len(c.actor) == 32
            assert owners.setdefault(c.actor, d) == d
            keys = [(o.obj, o.key) for o in c.ops
                    if o.action in ("set", "del", "link")]
            assert len(keys) == len(set(keys))
    # every board loads as gen_trellis's: its base change's 16 ops, then 8
    # devices of 5 cards of 5 ops
    depths = {len([o for c in sent[d][:9] for o in c.ops])
              for d in fleet.small}
    assert depths == {16 + 8 * 5 * 5}


def _names_unseen(log) -> list:
    """The ops of a board's log that name an element outside their change's
    causal past (an anchor, a deleted or assigned element), or insert with a
    counter not above every counter of that list the change has seen."""
    by_id = {(c.actor, c.seq): c for c in log}
    past: dict = {}

    def seen(c) -> set:
        if (c.actor, c.seq) not in past:
            deps = dict(c.deps, **{c.actor: c.seq - 1})
            past[(c.actor, c.seq)] = set().union(*(
                seen(by_id[(a, s)]) | {(a, s)}
                for a, s in deps.items() if s > 0))
        return past[(c.actor, c.seq)]

    faults = []
    for c in log:
        elems: dict = {}        # list -> {element id: counter}
        for a, s in seen(c):
            for o in by_id[(a, s)].ops:
                if o.action == "ins":
                    elems.setdefault(o.obj, {})[f"{a}:{o.elem}"] = o.elem
        for o in c.ops:
            have = elems.setdefault(o.obj, {})
            if o.action == "ins":
                if o.key != "_head" and o.key not in have \
                        or o.elem <= max(have.values(), default=0):
                    faults.append((c.actor, c.seq, o))
                have[f"{c.actor}:{o.elem}"] = o.elem
            elif o.action in ("set", "del", "link") and ":" in o.key \
                    and o.key not in have:
                faults.append((c.actor, c.seq, o))
    return faults


@pytest.mark.parametrize("seed", [5, 2**31 + 19, 2_600_000_033])
def test_every_change_names_only_what_its_writer_has_seen(seed):
    """A device's counters are a list's own, so one device names an element
    of each column alike: a writer that has not seen the board's latest
    change (a third of the changes here) anchors, deletes and counts only
    what it has seen, after reorders between the columns."""
    fleet = _fleet(seed, concurrent_share=0.3)
    schedule = _schedule(fleet, seed)
    sent: dict = {}
    for round_ in fleet.load_rounds():
        sent.update({d: list(chs) for d, chs in round_.items()})
    for r in range(60):
        for d, chs in fleet.request_changes(schedule.request(r)).items():
            sent[d].extend(chs)
    assert sum(len(log) for log in sent.values()) > 9 * len(sent) + 1_000
    assert {d: _names_unseen(log) for d, log in sent.items()
            if _names_unseen(log)} == {}


def test_a_writer_behind_a_reorder_names_no_card_it_has_not_seen():
    """A device drags its card `X:n` from one column to the other, where its
    new element is `X:n` too (a list's counters are its own); a device that
    has not seen the drag still sees the card in its old column and anchors
    a card of its own after the last one it sees in the new column."""
    fleet = _fleet(9, n_boards=1)
    sent: dict = {}
    for round_ in fleet.load_rounds():
        sent.update({d: list(chs) for d, chs in round_.items()})
    d = fleet.small[0]
    b = fleet.boards[d]
    src = int(b.cols[1].max_elem > b.cols[0].max_elem)
    dst, w = 1 - src, 0

    def write(w, seen, action, card=0.0, column=0.0, anchor=0.0):
        u = [(w + 0.5) / len(b.devices), 0.99 if seen else 0.0,
             {"add_card": 0.1, "reorder": 0.9}[action], card, column, anchor]
        sent[d].append(fleet._write(d, u, "t"))

    def to(c):
        return (c + 0.5) / len(b.cols)
    write(w, True, "add_card", column=to(src), anchor=0.999)
    n = b.cols[src].max_elem
    while b.cols[dst].max_elem < n - 1:
        write(w, True, "add_card", column=to(dst))
    assert b.cols[dst].max_elem == n - 1
    card = f"{b.devices[w]}:{n}"
    n_seen = sum(len(col.alive) for col in b.cols)
    k = (b.cols[0].alive + b.cols[1].alive).index(card) if src == 0 \
        else len(b.cols[0].alive) + b.cols[1].alive.index(card)
    write(w, True, "reorder", card=(k + 0.5) / n_seen, column=to(dst))
    assert card in b.cols[dst].alive and card not in b.cols[src].alive
    write(w + 1, False, "add_card", column=to(dst), anchor=0.999)
    assert _names_unseen(sent[d]) == []


def test_a_request_stops_before_a_cap():
    fleet = _fleet(11, n_boards=24)
    schedule = _schedule(fleet, 11, draws=60)
    for _round in fleet.load_rounds():
        pass
    # every board holds a list at the cap: no insert may come
    fleet.spec.elem_cap = min(max(c.slots for c in fleet.boards[d].cols)
                              for d in fleet.small)
    assert fleet.request_changes(schedule.request(0)) == "elem_cap"
    fleet.spec.elem_cap = 128
    # a board 5 ops under the cap could pass it with a reorder's 6
    fleet.spec.history_cap = min(fleet.boards[d].depth
                                 for d in fleet.small) + 5
    assert fleet.request_changes(schedule.request(0)) == "history_cap"
    fleet.spec.history_cap = 512
    assert isinstance(fleet.request_changes(schedule.request(0)), dict)
