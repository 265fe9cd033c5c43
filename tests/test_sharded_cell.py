"""The sharded node under its cell's traffic (`fleet10k-4shard.storm`), at a
small fleet on four of conftest's virtual devices: held to the benchmark's
plain reference (benchmarks/reference.py) after load and storm rounds under
`batch()`, to the single node (sharding changes nothing a client can see),
to the guarantee that a round is acknowledged only after every shard has
flushed, and to the fan-out's own phase, histogram and counters
(sync/sharded_service.py) with the benchmark's readers that read them.
"""

import json
import os
import sys
import zlib

import pytest

import jax

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
for _p in (os.path.join(BENCH, "tests"), BENCH, os.path.dirname(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import check  # noqa: E402
import fleet as fleetlib  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import tracefile  # noqa: E402
import traffic  # noqa: E402
from test_benchmark import TINY_FLEET, TINY_MIX, _rewrite  # noqa: E402

from automerge_tpu.core.change import Change, Op  # noqa: E402
from automerge_tpu.core.ids import ROOT_ID  # noqa: E402
from automerge_tpu.engine import dispatch  # noqa: E402
from automerge_tpu.sync.service import EngineDocSet  # noqa: E402
from automerge_tpu.sync.sharded_service import ShardedEngineDocSet  # noqa: E402
from automerge_tpu.utils import metrics  # noqa: E402

CELL = "fleet10k-4shard.storm"
SEED = 2**31 + 29
N_SHARDS = 4
ROUNDS = 6
SPEC = fleetlib.FleetSpec(**TINY_FLEET)    # the benchmark's own tiny fleet
NEW_METRICS = ("shard_fanout_share", "shard_flush_concurrency",
               "shard_docs_skew", "pod_idle_share", "chip_busy_balance")
NEW_READERS = ("counter_ratio_since", "trace_pod_idle", "trace_busy_balance")


def eager(svc):
    """The road the chip takes: reconcile at the flush, not at the read."""
    for s in getattr(svc, "shards", [svc]):
        s._lazy_resolved = True
        s._resident.lazy_dispatch = False
    return svc


def sharded():
    return eager(ShardedEngineDocSet(n_shards=N_SHARDS,
                                     devices=jax.devices()[:N_SHARDS]))


def shard_no(doc_id: str) -> int:
    return zlib.crc32(doc_id.encode()) % N_SHARDS


def drive(svc, fleet, schedule, after_first=None) -> list:
    """The cell's load and ROUNDS storm rounds, each under one `batch()`;
    returns [(a round's ops, the rise of the service's ingested ops on the
    batch's return)]. `after_first(svc)` runs once the structured
    documents are loaded, as the harness reads them there."""
    again = fleetlib.Fleet(fleet.spec, small=fleet.small,
                           structured=fleet.structured)
    fleetlib.apply_round(svc, fleet.first)
    if after_first is not None:
        after_first(svc)
    for round_ in fleetlib.small_load_rounds(again, SEED):
        fleetlib.apply_round(svc, round_)
    risen = []
    for r in range(ROUNDS):
        round_ = fleetlib.request_changes(again, schedule.request(r))
        before = fleetlib.ops_ingested(svc)
        fleetlib.apply_round(svc, round_)
        risen.append((len(round_), fleetlib.ops_ingested(svc) - before))
    return risen


@pytest.fixture(scope="module")
def driven():
    """The sharded service after the load and the rounds, with what the
    comparison reads from it and the plain reference's verdict."""
    keys = ("dispatch_fixed_s", "h2d_call_s", "d2h_call_s")
    saved = {k: dispatch._LINK[k] for k in keys}
    dispatch.calibrate(dispatch_fixed_s=1e-5, h2d_call_s=1e-6,
                       d2h_call_s=1e-5)
    fleet = fleetlib.make_fleet(SPEC, SEED)
    mix = dict(fleetlib.load_json("traffic", "storm"), **TINY_MIX["storm"])
    schedule = traffic.Schedule(mix, len(fleet.small),
                                len(fleetlib.SMALL_KEYS), SEED)
    svc = sharded()
    try:
        untouched = {}
        risen = drive(svc, fleet, schedule, lambda svc: untouched.update(
            check.read_untouched(svc, fleet)))
        read = check.read_program(svc, fleet, SEED, 32)
        sent, origin = fleetlib.replay(fleet, SEED, schedule, range(ROUNDS))
        verdict = check.decide(read, fleet, sent, origin, untouched, [], 0)
        yield {"svc": svc, "fleet": fleet, "schedule": schedule,
               "risen": risen, "read": read, "verdict": verdict}
    finally:
        svc.close()
        dispatch.calibrate(**saved)


@pytest.fixture
def small():
    """A fresh sharded service of 40 one-op documents, counters at 0."""
    svc = sharded()
    svc.seqs = {}
    with svc.batch():
        for d in range(40):
            svc.apply_changes(f"d{d}", change(svc, d))
    metrics.reset()
    yield svc
    svc.close()


def change(svc, doc: int) -> list:
    seq = svc.seqs[doc] = svc.seqs.get(doc, 0) + 1
    return [Change(actor="storm", seq=seq, deps={},
                   ops=[Op("set", ROOT_ID, key="n", value=seq * 1000 + doc)])]


# ---------------------------------------------------------------------------
# the sharded service against the plain reference and the single node


@pytest.mark.parametrize("number", sorted(check.LIMITS))
def test_sharded_node_is_held_to_the_plain_reference(driven, number):
    """Every number `correct` compares, at its limit of 0: every map
    document's hash, the sampled states, every acknowledged change served
    back, the structured documents unmoved."""
    sizes = driven["verdict"]["sizes"]
    assert sizes["hashes"] == SPEC.n_small + SPEC.n_heavy
    assert sizes["states"] >= 32 and sizes["untouched"] == 5
    assert sizes["changes"] > SPEC.n_small // 10
    assert driven["verdict"]["compared"][number] == {"value": 0, "limit": 0}


def test_every_shard_holds_documents_and_takes_traffic(driven):
    svc, fleet = driven["svc"], driven["fleet"]
    held = [len(s.doc_ids) for s in svc.shards]
    assert sum(held) == len(fleet.doc_ids) and min(held) > 30, held
    for d in fleet.doc_ids:
        assert d in svc.shards[shard_no(d)].doc_ids


def test_round_is_acknowledged_after_every_shard_has_flushed(driven):
    """On return of `batch()` the shards' summed `sync_ops_ingested` has
    risen by the round's ops: no shard's flush is left for later."""
    assert len(driven["risen"]) == ROUNDS
    for ops, risen in driven["risen"]:
        assert ops > 30 and risen == ops


def test_single_node_gives_the_same_hashes_and_states(driven):
    """Sharding changes where a document lives and nothing a client can
    see: the same load and rounds through `EngineDocSet`."""
    one = eager(EngineDocSet(backend="rows"))
    try:
        drive(one, driven["fleet"], driven["schedule"])
        assert one.hashes() == driven["read"]["hashes"]
        for d, state in driven["read"]["states"].items():
            assert one.materialize(d) == state, d
    finally:
        one.close()


def test_a_shard_left_out_comes_out_not_correct(driven):
    """The comparison sees a shard whose share never arrived: the hashes
    of one shard's documents as they were before the rounds."""
    fleet, read = driven["fleet"], driven["read"]
    sent, origin = fleetlib.replay(fleet, SEED, driven["schedule"],
                                   range(ROUNDS))
    loaded, _ = fleetlib.replay(fleet, SEED, driven["schedule"], [])
    stale = dict(read["hashes"])
    for d in fleet.small:
        if shard_no(d) == N_SHARDS - 1:
            stale[d] = reference.state_hash(loaded[d])
    verdict = check.decide(dict(read, hashes=stale), fleet, sent, origin,
                           {}, [], 0)
    assert verdict["correct"] is False
    assert verdict["compared"]["hashes_wrong"]["value"] > 10


# ---------------------------------------------------------------------------
# the fan-out's phase, histogram and counters


def phase_count(name: str) -> int:
    perf = metrics.snapshot().get("perf") or {}
    return perf.get("phases", {}).get(name, {}).get("count", 0)


def test_one_fanout_a_batch_none_per_apply_changes(small):
    with small.batch():
        for d in range(30):
            small.apply_changes(f"d{d}", change(small, d))
    got = fleetlib.counters()
    assert got["sync_shard_fanout_seconds_count"] == 1
    assert got["sync_shard_fanout_rounds"] == 1
    assert phase_count("shard_fanout") == 1
    # one admission a batch, the body's (one a call before PR 40)
    assert phase_count("admit") == 1
    assert 0 < got["phase.shard_fanout"] == pytest.approx(
        got["sync_shard_fanout_seconds_sum"], rel=0.2)


@pytest.mark.parametrize("docs", [
    tuple(range(30)), (0,), (), tuple(range(0, 40, 3))])
def test_fanout_counters_follow_crc32(small, docs):
    """Shards that had work and the round's documents, over all shards and
    on the fullest, are what the round's ids give under crc32 mod 4."""
    with small.batch():
        for d in docs:
            small.apply_changes(f"d{d}", change(small, d))
    by_shard = [0] * N_SHARDS
    for d in docs:
        by_shard[shard_no(f"d{d}")] += 1
    got = fleetlib.counters()
    assert got["sync_shard_fanout_rounds"] == 1
    assert got["sync_shard_round_docs"] == len(docs)
    assert got["sync_shard_round_docs_fullest"] == max(by_shard)
    assert got.get("sync_rounds_flushed", 0) == sum(
        1 for n in by_shard if n)
    span = [s for s in metrics.recent_spans()
            if s["name"] == "sync_request"][-1]
    assert span["tags"] == {"docs": len(docs), "ops": len(docs),
                            "shards": sum(1 for n in by_shard if n)}


def test_nested_batches_are_one_fanout_and_flush_is_one_too(small):
    with small.batch():
        with small.batch():
            small.apply_changes("d1", change(small, 1))
        assert fleetlib.counters().get("sync_shard_fanout_rounds", 0) == 0
        small.apply_changes("d2", change(small, 2))
    got = fleetlib.counters()
    assert got["sync_shard_fanout_rounds"] == 1
    assert got["sync_shard_round_docs"] == 2
    small.flush()                   # the other way into a fan-out
    got = fleetlib.counters()
    assert got["sync_shard_fanout_rounds"] == 2
    assert got["sync_shard_fanout_seconds_count"] == 2
    assert phase_count("shard_fanout") == 1


def test_fanout_is_counted_when_a_shard_raises(small):
    """Every shard flushes even if one raises, the error propagates, and
    the fan-out is still one observation."""
    sick = small.shards[shard_no("d0")]
    sick._resident._poison(RuntimeError("injected"))
    with pytest.raises(RuntimeError):
        with small.batch():
            for d in range(12):
                small.apply_changes(f"d{d}", change(small, d))
    got = fleetlib.counters()
    assert got["sync_shard_fanout_rounds"] == 1
    assert got["sync_shard_fanout_seconds_count"] == 1
    healthy = sum(1 for k in range(N_SHARDS) if small.shards[k] is not sick
                  and any(shard_no(f"d{d}") == k for d in range(12)))
    assert got["sync_rounds_flushed"] >= healthy


def test_shard_flush_concurrency_reads_one_today(small):
    """The shards flush one after another: their flushes' sum is all but
    the whole fan-out. The best of three windows, since a preempted thread
    leaves time between two flushes."""
    reader = run.load_by_path("readers", "counter_ratio_since")
    args = fleetlib.load_json("metrics", "shard_flush_concurrency")["args"]
    reads = []
    for _ in range(3):
        before = fleetlib.counters()
        for _ in range(4):
            with small.batch():
                for d in range(40):
                    small.apply_changes(f"d{d}", change(small, d))
        reads.append(reader.read(args, {
            "delta": fleetlib.counter_delta(before, fleetlib.counters())}))
    assert all(0.3 < r <= 1.0 for r in reads), reads
    assert max(reads) >= 0.9, reads


# ---------------------------------------------------------------------------
# the readers and the files


def _ctx(delta=None, busy=None, window_s=2.0, chips=4):
    trace = None if busy is None else {
        "window_s": window_s, "busy_by_chip": dict(enumerate(busy)),
        "busy_s": sum(busy) / len(busy) if busy else 0.0}
    return {"window_s": window_s, "delta": delta or {}, "trace": trace,
            "chips": chips, "device_kind": "TPU v5 lite"}


FANOUT = {"sync_shard_fanout_rounds": 10, "phase.shard_fanout": 1.5,
          "sync_shard_fanout_seconds_sum": 1.5,
          "sync_round_seconds_sum": 1.44, "sync_shard_round_docs": 12300,
          "sync_shard_round_docs_fullest": 3260}
# what a program from before this PR counts in a window
OLDER = {"sync_request_count": 10, "sync_round_seconds_sum": 1.44,
         "phase.pack": 0.9}


@pytest.mark.parametrize("metric,ctx,want", [
    ("shard_fanout_share", _ctx(FANOUT), 75.0),
    ("shard_fanout_share", _ctx(OLDER), 0.0),
    ("shard_fanout_share", _ctx({"sync_shard_fanout_rounds": 10}), None),
    ("shard_flush_concurrency", _ctx(FANOUT), 0.96),
    ("shard_flush_concurrency", _ctx(OLDER), 0.0),
    ("shard_flush_concurrency", _ctx({"sync_shard_fanout_rounds": 3}), None),
    ("shard_docs_skew", _ctx(FANOUT), 4 * 3260 / 12300),
    ("shard_docs_skew", _ctx(OLDER), 0.0),
    ("shard_docs_skew", _ctx({"sync_shard_fanout_rounds": 3}), None),
    ("pod_idle_share", _ctx(busy=[0.02, 0.01, 0.01, 0.0]), 99.5),
    ("pod_idle_share", _ctx(busy=[0.02, 0.01]), 99.625),
    ("pod_idle_share", _ctx(busy=[0.5], chips=1), 75.0),
    ("pod_idle_share", _ctx(busy=[]), None),
    ("pod_idle_share", _ctx(), None),
    ("chip_busy_balance", _ctx(busy=[0.02, 0.016, 0.018, 0.019]), 80.0),
    ("chip_busy_balance", _ctx(busy=[0.02, 0.016, 0.018]), 0.0),
    ("chip_busy_balance", _ctx(busy=[0.02, 0.016, 0.018, 0.0]), 0.0),
    ("chip_busy_balance", _ctx(busy=[0.0, 0.0, 0.0, 0.0]), 0.0),
    ("chip_busy_balance", _ctx(busy=[0.02, 0.5, 0.4, 0.3, 0.25]), 50.0),
    ("chip_busy_balance", _ctx(), None),
])
def test_new_reader_on_a_hand_made_context(metric, ctx, want):
    """A number where there is something to read; 0.0 ("absent") on a
    program without the new counters, so that the parent's traced run of
    the cell ends with a result; nothing where the metric fell silent."""
    m = fleetlib.load_json("metrics", metric)
    got = run.load_by_path("readers", m["reader"]).read(m["args"], ctx)
    assert got == (want if want is None else pytest.approx(want))


def test_trace_readers_on_the_recorded_v5e_slice():
    """One device plane: as the cell's one chip the pod's idle share is the
    busiest chip's; as one of four the other three count as idle and the
    balance reads 0."""
    with open(os.path.join(BENCH, "tests", "fixtures",
                           "v5e_storm_slice.json"), encoding="utf-8") as f:
        data = json.load(f)
    idle = run.load_by_path("readers", "trace_pod_idle")
    balance = run.load_by_path("readers", "trace_busy_balance")
    busiest = run.load_by_path("readers", "trace_idle")
    one = {"trace": tracefile.reduce(data, 1), "chips": 1}
    assert 0.0 < idle.read({}, one) == pytest.approx(busiest.read({}, one))
    assert idle.read({}, one) < 100.0
    assert balance.read({}, one) == 100.0
    four = {"trace": tracefile.reduce(data, 4), "chips": 4}
    assert 100.0 - idle.read({}, four) == pytest.approx(
        (100.0 - idle.read({}, one)) / 4)
    assert balance.read({}, four) == 0.0


def test_new_files_are_found_for_the_cell():
    found = {m["name"]: m for m in run.cell_metrics(CELL)}
    assert set(NEW_METRICS) <= set(found) and len(found) == 22
    for name in NEW_METRICS:
        m = found[name]
        assert m["workloads"] == [CELL]
        assert callable(run.load_by_path("readers", m["reader"]).read)
    assert {found[n]["reader"] for n in NEW_METRICS} == set(NEW_READERS) | {
        "phase_share_since"}
    for other in ("fleet10k.storm", "fleet10k.edits"):
        assert not set(NEW_METRICS) & {
            m["name"] for m in run.cell_metrics(other)}
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"),
              encoding="utf-8") as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell and cell[0]["chips"] == 4
    assert cell[0]["config"] == "fleet10k-4shard"
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m["workloads"]}
    assert listed == set(found)


def test_traced_tiny_run_of_the_cell_reads_the_host_side_metrics(
        tmp_path, cpu_link):
    """`run_cell` on the cell at the tiny fleet, traced: the three metrics
    of the program's own counters read; the two of the device trace find no
    device plane in a CPU trace."""
    import gc
    import shutil
    root = str(tmp_path / "benchmarks")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    _rewrite(os.path.join(root, "configs", "fleet10k-4shard.json"),
             fleet=TINY_FLEET)
    _rewrite(os.path.join(root, "traffic", "storm.json"), **TINY_MIX["storm"])
    gc.collect()
    gc.freeze()         # no full collection inside the 0.3 s window
    try:
        res = run.run_cell(
            CELL, SEED, 0.3, 1, jax.devices(), root=root, steer=eager,
            max_requests=10_000,
            may_miss=("megakernel_roofline", "device_idle_share",
                      "pod_idle_share", "chip_busy_balance"))
    finally:
        gc.unfreeze()
    assert res["correct"] is True and res["failed"] == 0
    got = {n: row["value"] for n, row in res["metrics"].items()}
    assert 20.0 < got["shard_fanout_share"] < 100.0
    assert 0.3 < got["shard_flush_concurrency"] <= 1.0
    assert 1.0 <= got["shard_docs_skew"] < 2.5
    # reported, and 0 here: the router fuses the tiny fleet's rounds, so
    # no lane is gathered on either side (tests/test_apply_blocks.py
    # holds the gather itself); the phase around it is still read
    assert got["resident_gather_share"] == 100.0 - got["fused_round_share"]
    assert 0.0 < got["pack_share"] < 50.0
    # every shard's round was made in one pass from the batch's changes
    assert got["direct_frame_share"] == 100.0
    assert "pod_idle_share" not in got and "chip_busy_balance" not in got
