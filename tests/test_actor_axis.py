"""A document's own actor axis in the resident engines.

A rank is a position in its document's sorted actor list, `cap_actors` is
the widest document's count, and a device that joins a document rewrites
that document's lane alone. The rows engine is held to the benchmark's
plain reference (benchmarks/reference.py, which imports nothing of the
program) and to the oracle (the batch engine over `core`'s changes; the
service's `materialize` replays a log through `core/opset.py`) on seeded
fleets written by the rule of the benchmark's fleet kind `devices`: every
document by its own devices, some writes concurrent, devices joining.
"""

import os
import sys

import numpy as np
import pytest

import jax

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
for _p in (BENCH, os.path.dirname(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import fleet as fleetlib  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402

from automerge_tpu.core.change import Change, Op  # noqa: E402
from automerge_tpu.core.ids import ROOT_ID  # noqa: E402
from automerge_tpu.engine.batchdoc import apply_batch  # noqa: E402
from automerge_tpu.engine.resident import ResidentDocSet  # noqa: E402
from automerge_tpu.engine.resident_rows import ResidentRowsDocSet  # noqa: E402
from automerge_tpu.sync.frames import round_from_parts  # noqa: E402
from automerge_tpu.native.wire import changes_to_columns  # noqa: E402
from automerge_tpu.sync.service import EngineDocSet  # noqa: E402
from automerge_tpu.sync.sharded_service import ShardedEngineDocSet  # noqa: E402
from automerge_tpu.utils import metrics  # noqa: E402

devices = run.load_by_path("fleets", "devices")
SEED = 2**31 + 38
MIX = dict(fleetlib.load_json("traffic", "storm"), warmup_requests=0)


def eager(svc):
    """The road the chip takes: reconcile at the flush, on the copy the
    device holds."""
    for s in getattr(svc, "shards", [svc]):
        s._lazy_resolved = True
        s._resident.lazy_dispatch = False
    return svc


def make_fleet(n_small, at_load, concurrent, join, seed=SEED):
    return devices.Fleet(devices.Spec(
        history_changes_max=4, history_cap=4096, n_small=n_small, n_heavy=1,
        heavy_ops=16, n_list=1, n_text=1, n_move=1,
        load_batch=(n_small + 1) // 2, devices_at_load=at_load,
        devices_cap=8, concurrent_share=concurrent, join_share=join), seed)


def oracle_hashes(logs: list) -> list:
    _, _, out = apply_batch(logs)
    return [int(h) for h in np.asarray(out["hash"])[:len(logs)]]


def plain(log) -> list:
    return [(c.actor, c.seq, dict(c.deps),
             [(o.action, o.obj, o.key, o.value) for o in c.ops])
            for c in log]


def hold(svc, sent: dict, sample: list) -> None:
    """Every map document's hash against the reference's; the sample's
    hash against the oracle's, its state (conflicts are state) against
    the reference's, and its log as the service serves it."""
    hashes = svc.hashes()
    covered = [d for d, log in sent.items() if reference.covers(log)]
    wrong = [d for d in covered
             if hashes.get(d) != reference.state_hash(sent[d])]
    assert not wrong, wrong[:5]
    assert [int(hashes[d]) for d in sample] == oracle_hashes(
        [sent[d] for d in sample])
    for d in sample:
        assert svc.materialize(d) == reference.state(sent[d]), d
        served = sorted(plain(svc.missing_changes(d, {})))
        assert served == sorted(plain(sent[d])), d
        first = sent[d][0]
        behind = [c for c in sent[d]
                  if not (c.actor == first.actor and c.seq == 1)]
        assert sorted(plain(svc.missing_changes(
            d, {first.actor: 1}))) == sorted(plain(behind)), d


CASES = [(at_load, shares)
         for at_load in ((1, 1), (2, 4), (8, 8))
         for shares in ((0.0, 0.0), (0.10, 0.01), (0.5, 0.5))]


@pytest.mark.parametrize("at_load,shares", CASES, ids=[
    f"devices-{lo}-{hi}-concurrent-{c}-join-{j}"
    for (lo, hi), (c, j) in CASES])
def test_engine_holds_to_the_reference_and_the_oracle(at_load, shares):
    """Hashes, states, conflicts and served changes after the load, after
    rounds and after joins, for fleets of 64 to 256 documents."""
    n_small = 64 + 24 * CASES.index((at_load, shares))
    fleet = make_fleet(n_small, at_load, *shares)
    schedule = traffic.Schedule(dict(MIX, draws_per_request=n_small // 2),
                                n_small, fleet.n_fields, SEED)
    svc = eager(EngineDocSet(backend="rows"))
    try:
        sent: dict = {}
        for round_ in fleet.load_rounds():
            fleetlib.apply_round(svc, round_)
            for d, chs in round_.items():
                sent[d] = list(chs)
        rng = np.random.default_rng(SEED)
        sample = [fleet.small[i] for i in rng.choice(
            n_small, 12, replace=False)] + ["heavy00"]
        hold(svc, sent, sample)
        for r in range(5):
            round_ = fleet.request_changes(schedule.request(r))
            fleetlib.apply_round(svc, round_)
            for d, chs in round_.items():
                sent[d].extend(chs)
        held = sorted(d for d, keys in fleet.conflicted.items() if keys)
        hold(svc, sent, list(dict.fromkeys(
            sample + held[:6] + sorted(fleet.joined)[:6])))
        # the fleet's own account of its conflicts is the reference's
        assert held == sorted(
            d for d in fleet.small if reference.state(sent[d])["conflicts"])
        if shares[0] and at_load != (1, 1):
            assert held
        if shares[1] >= 0.5 and at_load != (8, 8):
            assert fleet.joined      # (at 0.01 a small fleet may see none)
        rset = svc._resident
        assert rset.cap_actors == 8     # the heavy document's eight writers
        for i, d in enumerate(rset.doc_ids):
            assert rset.tables[i].actors == sorted(
                {c.actor for c in sent[d]})
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# the layout: whose lane a registration rewrites


def set_change(actor, seq, deps, key, value):
    return Change(actor, seq, deps, [Op("set", ROOT_ID, key=key, value=value)])


def frame(round_: dict):
    """{doc id: [Change]} as the service's round frame."""
    return round_from_parts({d: [changes_to_columns(chs)]
                             for d, chs in round_.items()})


def primed(n_docs=300):
    """An eager rows engine of `n_docs` documents over three 128-lane
    blocks, each written by two actors of its own (the first document by
    four: the actor axis is four wide), reconciled once: the device copy
    is current and the hash vector stays beside it."""
    ids = [f"d{i:03d}" for i in range(n_docs)]
    rset = ResidentRowsDocSet(ids)
    logs = {d: [set_change(f"{d}-m", 1, {}, "k", i),
                set_change(f"{d}-p", 1, {f"{d}-m": 1}, "k", i + 1),
                set_change(f"{d}-m", 2, {f"{d}-p": 1}, "j", i + 2)]
            for i, d in enumerate(ids)}
    logs["d000"] += [set_change("d000-x", 1, {"d000-m": 2}, "j", 0),
                     set_change("d000-y", 1, {"d000-x": 1}, "j", 1)]
    rset.apply_round_frames([frame(logs)])
    rset.hashes()
    return rset, logs


def test_a_join_rewrites_its_lane_alone():
    rset, logs = primed()
    rset.apply_round_frames([frame({"d007": [
        set_change("d007-m", 3, {}, "k", 70)]})])    # a block round
    rset.hashes()
    assert rset._dev_current and rset._h_prev is not None
    i = rset.doc_index["d140"]
    before = {"rows": rset.rows_host.copy(), "dev": rset.rows_dev,
              "h_prev": rset._h_prev, "epoch": rset.hash_epoch,
              "mirror": rset._hash_mirror.copy()}
    rset._refresh_admission_cache()
    cache = rset._clock_cache.copy()
    c0 = metrics.snapshot()
    # an id that sorts before both writers: every rank of the lane moves
    rset._register_doc_actors({i: {"d140-a"}})
    others = np.arange(rset.n_pad) != i
    assert np.array_equal(rset.rows_host[:, others],
                          before["rows"][:, others])
    assert (rset.rows_host[:, i] != before["rows"][:, i]).any()
    assert rset.rows_dev is before["dev"] and rset._dev_current
    assert rset._h_prev is before["h_prev"]
    assert rset._doc_dirty == {i}
    assert np.array_equal(rset._clock_cache[others[:len(cache)]],
                          cache[others[:len(cache)]])
    assert rset._clock_cache[i].tolist()[:3] == [0] + cache[i].tolist()[:2]
    trips = np.concatenate(rset._lane_trips)
    assert set(trips[:, 1].tolist()) == {i}
    c1 = metrics.snapshot()
    assert c1.get("rows_actor_joins", 0) - c0.get("rows_actor_joins", 0) == 1
    assert c1.get("rows_actor_remap_lanes", 0) \
        - c0.get("rows_actor_remap_lanes", 0) == 1
    # the joiner's first change rides the block route: no upload of the
    # mirror, the lane's cells go with the round's scatter
    uploads = []
    to_dev = rset._to_dev
    rset._to_dev = lambda arr: uploads.append(np.shape(arr)) or to_dev(arr)
    join = set_change("d140-a", 1, {"d140-m": 2}, "k", 999)
    logs["d140"].append(join)
    rset.apply_round_frames([frame({"d140": [join]})])
    got = rset.hashes()
    assert rset.rows_host.shape not in uploads
    assert np.array_equal(np.asarray(rset.rows_dev), rset.rows_host)
    want = oracle_hashes([logs[d] for d in ("d139", "d140", "d141")])
    assert [int(got[rset.doc_index[d]])
            for d in ("d139", "d140", "d141")] == want
    assert np.array_equal(np.delete(got, i),
                          np.delete(before["mirror"][:len(got)], i))


def test_a_ninth_device_grows_the_actor_axis_once_for_all():
    ids = [f"d{i:02d}" for i in range(40)]
    rset = ResidentRowsDocSet(ids)
    logs = {d: [set_change(f"{d}-w0", 1, {}, "k", i)]
            for i, d in enumerate(ids)}
    logs["d30"] += [set_change("d30-w0", s, {}, "k", s) for s in range(2, 12)]
    for k in range(1, 8):
        logs["d05"].append(set_change(f"d05-w{k}", 1,
                                      {f"d05-w{k - 1}": 1}, "k", k))
    rset.apply_round_frames([frame(logs)])
    assert rset.cap_actors == 8
    before = rset.hashes()
    grown = []
    grow = rset._grow
    rset._grow = lambda **caps: grown.append(caps) or grow(**caps)
    ninth = set_change("d05-w8", 1, {"d05-w7": 1}, "j", 9)
    logs["d05"].append(ninth)
    rset.apply_round_frames([frame({"d05": [ninth]})])
    assert grown == [{"cap_actors": 16}] and rset.cap_actors == 16
    after = rset.hashes()
    i = rset.doc_index["d05"]
    assert np.array_equal(np.delete(after, i), np.delete(before, i))
    assert [int(h) for h in after] == oracle_hashes([logs[d] for d in ids])


def test_documents_that_share_no_actor_keep_the_axis_at_their_own_widest():
    rset = ResidentRowsDocSet(["x", "y", "z"])
    rset.apply_round_frames([frame({
        "x": [set_change("a", 1, {}, "k", 1),
              set_change("b", 1, {"a": 1}, "k", 2)],
        "y": [set_change("c", 1, {}, "k", 1),
              set_change("d", 1, {"c": 1}, "k", 2)],
        "z": [set_change("e", 1, {}, "k", 1)]})])
    assert rset.cap_actors == 2      # five actor ids, two a document
    assert rset.dims()[:3] == (8, 2, 8)
    assert [t.actors for t in rset.tables] == [["a", "b"], ["c", "d"], ["e"]]


@pytest.mark.parametrize("engine", ["rows", "resident"])
def test_rank_order_is_actor_id_order_inside_a_document(engine):
    """Two documents whose actors interleave in the instance's order
    (a1 < b1 < c1 < d1): ranks follow each document's own list, the
    higher id wins a concurrent key, and a late joiner that sorts first
    moves the ranks of its document alone."""
    logs = {
        "p": [set_change("a1", 1, {}, "k", "from-a1"),
              set_change("c1", 1, {}, "k", "from-c1")],
        "q": [set_change("d1", 1, {}, "k", "from-d1"),
              set_change("b1", 1, {}, "k", "from-b1")]}
    late = set_change("0z", 1, {"a1": 1, "c1": 1}, "j", "late")
    if engine == "rows":
        rset = ResidentRowsDocSet(["p", "q"])
        rset.reserve(actors=3)     # the widest document will have three
        rset.apply_round_frames([frame(logs)])
        b = rset._bases()
        assert rset.rows_host[b["act"]:b["act"] + 2, 0].tolist() == [0, 1]
        assert rset.rows_host[b["act"]:b["act"] + 2, 1].tolist() == [1, 0]
        q_lane = rset.rows_host[:, 1].copy()
        rset.apply_round_frames([frame({"p": [late]})])
        assert rset.rows_host[b["act"]:b["act"] + 3, 0].tolist() == [1, 2, 0]
        assert np.array_equal(rset.rows_host[:, 1], q_lane)
    else:
        rset = ResidentDocSet(["p", "q"])
        rset.reserve(actors=3)
        rset.apply_changes(logs)
        assert rset.materialize("p")["data"]["k"] == "from-c1"
        assert rset.materialize("q")["data"]["k"] == "from-d1"
        assert np.asarray(rset.state["actor"])[:, :2].tolist() \
            == [[0, 1], [1, 0]]
        rset.apply_changes({"p": [late]})
        assert np.asarray(rset.state["actor"])[0, :3].tolist() == [1, 2, 0]
        assert np.asarray(rset.state["actor"])[1, :2].tolist() == [1, 0]
        got = rset.materialize("p")
        assert got["data"] == {"k": "from-c1", "j": "late"}
        assert got["conflicts"] == {"k": {"a1": "from-a1"}}
    logs["p"].append(late)
    assert [t.actors for t in rset.tables] == [["0z", "a1", "c1"],
                                               ["b1", "d1"]]
    assert rset.cap_actors == 4      # pad of the widest document's three
    assert [int(h) for h in rset.hashes()] == oracle_hashes(
        [logs["p"], logs["q"]])
    assert [int(h) for h in rset.hashes()] == [
        reference.state_hash(logs["p"]), reference.state_hash(logs["q"])]


def test_four_shards_hash_as_one_node():
    n_small = 96
    schedule = traffic.Schedule(dict(MIX, draws_per_request=48), n_small,
                                len(fleetlib.SMALL_KEYS), SEED)
    hashes = []
    first = make_fleet(n_small, (2, 4), 0.10, 0.05)
    for make in (lambda: EngineDocSet(backend="rows"),
                 lambda: ShardedEngineDocSet(n_shards=4,
                                             devices=jax.devices()[:4])):
        # the structured documents' object ids are not seeded: one load
        fleet = devices.Fleet(first.spec, SEED, first=first.first)
        svc = eager(make())
        try:
            sent: dict = {}
            for round_ in fleet.load_rounds():
                fleetlib.apply_round(svc, round_)
                sent.update({d: list(chs) for d, chs in round_.items()})
            for r in range(4):
                round_ = fleet.request_changes(schedule.request(r))
                fleetlib.apply_round(svc, round_)
                for d, chs in round_.items():
                    sent[d].extend(chs)
            hashes.append(svc.hashes())
        finally:
            svc.close()
    one, four = hashes
    assert one == four
    small = [d for d in sent if reference.covers(sent[d])]
    assert len(small) == n_small + 1
    assert all(one[d] == reference.state_hash(sent[d]) for d in small)
