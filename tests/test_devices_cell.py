"""The cells this PR adds, `fleet10k-devices.storm` and `fleet10k.edits-16`,
through the benchmark's own harness at a tiny fleet on the CPU: a run is
correct; the check's five controls (the reference in the program's place
with one guarantee broken, `lowest_actor_wins` among them) each come out
not correct; a fleet kind whose `replay` forgets a join reads
`changes_unserved` > 0; the traced run reads the four new per-layer
metrics; the fleet kind makes its changes again from the seed and its own
account of conflicts is the reference's. The writers driver holds its
guarantee a request: the control `ack_before_flush` reads every one.
"""

import json
import os
import shutil
import sys

import pytest

import jax

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
for _p in (os.path.join(BENCH, "tests"), BENCH, os.path.dirname(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import fleet as fleetlib  # noqa: E402
import prove  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402
from test_benchmark import (DEVICE_METRICS, TINY_FLEET, TINY_MIX,  # noqa: E402,F401
                            _rewrite, cpu_link, eager)

CELL = "fleet10k-devices.storm"
WRITERS_CELL = "fleet10k.edits-16"
SEED = 2**31 + 38
NEW_METRICS = ("batched_admission_share", "general_admit_share",
               "lanes_per_actor_join", "actor_register_mean_ms")


@pytest.fixture
def tiny(tmp_path):
    """The benchmark's data files at a size a test can hold, as its own
    tests cut them; joins at a fifth of the changes, so that twelve
    requests hold dozens."""
    root = str(tmp_path / "benchmarks")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    for name in os.listdir(os.path.join(root, "configs")):
        _rewrite(os.path.join(root, "configs", name), fleet=TINY_FLEET)
    _rewrite(os.path.join(root, "configs", "fleet10k-devices.json"),
             writers={"join_share": 0.2})
    _rewrite(os.path.join(root, "traffic", "storm.json"), **TINY_MIX["storm"])
    _rewrite(os.path.join(root, "traffic", "edits-16.json"),
             warmup_requests=32)
    return root


def run_tiny(root, cell=CELL, trace=0, steer=eager, max_requests=12):
    return run.run_cell(cell, SEED, 0.3 if trace else 30.0, trace,
                        jax.devices(), root=root, steer=steer,
                        max_requests=10_000 if trace else max_requests,
                        may_miss=DEVICE_METRICS)


def test_a_tiny_run_of_the_devices_cell_is_correct(tiny, cpu_link, capsys):
    res = run_tiny(tiny)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 12
    assert all(row["value"] == row["limit"] == 0
               for row in res["compared"].values())
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    load = next(ln for ln in lines if ln["stage"] == "load")
    assert load["dims"][0][1] == 8                  # eight writers a heavy
    assert load["devices_min_median_max"][0] >= 2
    assert load["two_headed"] > 0 and load["conflicted"] > 0
    window = next(ln for ln in lines if ln["stage"] == "window")
    assert window["dims_before"] == window["dims_after"]
    sizes = next(ln for ln in lines if ln["stage"] == "check")["compared"]
    # the sample of states is widened by both kinds of document
    assert sizes["states_conflicted"] >= 16 and sizes["states_joined"] >= 16


def test_the_traced_run_reads_the_new_metrics(tiny, cpu_link, monkeypatch):
    # a trace directory of its own: another worker's traced run of the
    # benchmark's tests would share the checkout's
    monkeypatch.setattr(run, "TRACE_DIR", os.path.join(tiny, ".bench_trace"))
    res = run_tiny(tiny, trace=1)
    assert res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NEW_METRICS) <= set(got)
    assert got["batched_admission_share"] == 0.0     # every round falls back
    assert got["lanes_per_actor_join"] == 1.0        # a join, its lane
    assert 0 < got["general_admit_share"] < 60
    assert got["actor_register_mean_ms"] > 0
    # the accepted storm metrics hold for the cell by its mix
    assert {"flush_mean_ms", "encode_share", "resident_gather_share",
            "compiles_in_window"} <= set(got)


def _controls():
    config = fleetlib.load_json("configs", "fleet10k-devices")
    return sorted(run.seam(config, "check", "checks", run.check).CONTROLS)


def test_the_check_declares_five_controls():
    assert _controls() == sorted(["ack_before_flush", "lose_acknowledged",
                                  "stale_hash", "first_writer_wins",
                                  "lowest_actor_wins"])


@pytest.mark.parametrize("control", _controls())
def test_a_control_comes_out_not_correct(tiny, cpu_link, control):
    config = fleetlib.load_json("configs", "fleet10k-devices", tiny)
    make = run.seam(config, "check", "checks", run.check, tiny) \
        .CONTROLS[control]

    def stand_in(svc):
        svc.close()
        return make()
    res = run_tiny(tiny, steer=stand_in, max_requests=40)
    assert res["correct"] is False
    bad = {k for k, row in res["compared"].items()
           if row["value"] > row["limit"]}
    want = {"ack_before_flush": {"acks_before_flush"},
            "lose_acknowledged": {"changes_unserved", "hashes_wrong"},
            "stale_hash": {"hashes_wrong"},
            "first_writer_wins": {"hashes_wrong", "states_wrong"},
            # the hash sums over every survivor whoever wins: states alone
            "lowest_actor_wins": {"states_wrong"}}[control]
    assert want <= bad, (control, res["compared"])
    if control == "lowest_actor_wins":
        assert bad == {"states_wrong"}


def test_prove_runs_the_five_controls_of_the_cell(tiny, cpu_link, capsys):
    rc = prove.main(["--workload", CELL, "--seeds", str(SEED), "--seconds",
                     "30", "--control", "1"], root=tiny,
                    devices=jax.devices(), max_requests=40)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert rc == 0 and len(lines) == 5
    assert all(ln["correct"] is False and ln["as_it_has_to"] for ln in lines)


FORGETS = '''
"""The fleet kind `devices` whose replay forgets the first join of each
document: the device's changes come back under another writer's name."""
import os
import run
base = run.load_by_path("fleets", "devices", os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


class Forgetful(base.Fleet):
    forgets = False

    def _write(self, d, ops, u_writer, u_conc, u_join):
        if self.forgets and self.loaded and d not in self.joined \\
                and u_join < self.spec.join_share:
            self.joined.add(d)
            u_join = 1.0
        return super()._write(d, ops, u_writer, u_conc, u_join)

    def replay(self, schedule, numbers):
        type(self).forgets = True
        try:
            return super().replay(schedule, numbers)
        finally:
            type(self).forgets = False


def make(config, seed):
    return Forgetful(base.Spec.from_config(config), seed)
'''


def test_a_replay_that_forgets_a_join_reads_changes_unserved(tiny, cpu_link):
    with open(os.path.join(tiny, "fleets", "forgetful.py"), "w",
              encoding="utf-8") as f:
        f.write(FORGETS)
    _rewrite(os.path.join(tiny, "configs", "fleet10k-devices.json"),
             fleet_kind="forgetful")
    res = run_tiny(tiny, max_requests=40)
    assert res["correct"] is False
    assert res["compared"]["changes_unserved"]["value"] > 0
    assert res["compared"]["hashes_wrong"]["value"] > 0
    assert res["compared"]["requests_raised"]["value"] == 0


@pytest.mark.parametrize("seed", [7, 2**31 + 5, 2**32 + 11])
def test_the_fleet_kind_makes_its_changes_again(seed):
    config = fleetlib.load_json("configs", "fleet10k-devices")
    config["fleet"] = dict(TINY_FLEET)
    config["writers"]["join_share"] = 0.1
    kind = run.seam(config, "fleet_kind", "fleets", fleetlib)
    fleet = kind.make(config, seed)
    mix = dict(fleetlib.load_json("traffic", "storm"), **TINY_MIX["storm"])
    schedule = traffic.make(mix, fleet, seed)
    sent: dict = {}
    for round_ in fleet.load_rounds():
        sent.update({d: list(chs) for d, chs in round_.items()})
    for r in range(8):
        for d, chs in fleet.request_changes(schedule.request(r)).items():
            sent[d].extend(chs)
    again, origin = fleet.replay(schedule, range(8))

    def plain(log):
        return [(c.actor, c.seq, dict(c.deps),
                 [(o.action, o.key, o.value) for o in c.ops]) for c in log]
    assert {d: plain(v) for d, v in again.items()} \
        == {d: plain(v) for d, v in sent.items()}
    assert set(origin.values()) == set(range(8))
    # ids of their own: 32 hex digits, shared by no two documents
    owners: dict = {}
    for d, log in sent.items():
        for c in log:
            assert len(c.actor) == 32 and int(c.actor, 16) >= 0
            assert owners.setdefault(c.actor, d) == d
    # every change's deps were there before it, and the fleet's account of
    # conflicts and heads is the reference's
    for d in fleet.small:
        assert len(reference.causal_order(sent[d])) == len(sent[d])
        held = set(reference.state(sent[d])["conflicts"])
        assert held == set(fleet.conflicted.get(d, ()))
    assert any(fleet.conflicted.values()) and fleet.joined
    assert max(len(doc.devices) for doc in fleet.docs.values()) <= 8


# ---------------------------------------------------------------------------
# fleet10k.edits-16: sixteen writers


def test_a_tiny_run_of_the_writers_cell_is_correct(tiny, cpu_link, capsys):
    res = run_tiny(tiny, cell=WRITERS_CELL, max_requests=160)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 160
    assert res["compared"]["acks_before_flush"]["value"] == 0
    window = next(json.loads(ln) for ln in capsys.readouterr().out
                  .splitlines() if '"window"' in ln)
    # sixteen callers: the flusher commits them in groups
    assert window["rounds_flushed"] < window["ops_ingested"] == 160


def test_the_writers_cell_reports_changes_per_flush(tiny, cpu_link):
    names = {m["name"] for m in run.cell_metrics(WRITERS_CELL, tiny)}
    assert "changes_per_flush" in names and "commit_wait_mean_ms" in names
    assert not names & set(NEW_METRICS)
    for cell in ("fleet10k.storm", "fleet10k.edits", "fleet10k.mixed",
                 "fleet10k-4shard.storm"):
        assert not {m["name"] for m in run.cell_metrics(cell, tiny)} \
            & (set(NEW_METRICS) | {"changes_per_flush"})


def test_the_writers_schedule_gives_each_writer_documents_of_its_own():
    config = fleetlib.load_json("configs", "fleet10k")
    config["fleet"] = dict(TINY_FLEET)
    fleet = fleetlib.make(config, SEED)
    mix = fleetlib.load_json("traffic", "edits-16")
    schedule = run.load_by_path("schedules", "writers").make(
        mix, fleet, SEED)
    assert schedule.callers == 16 and not schedule.batch(5)
    for r in range(0, 640):
        docs, fields, values = schedule.request(r)
        assert len(docs) == len(fields) == len(values) == 1
        assert docs[0] % 16 == r % 16 == schedule.writer(r)
    a, b = schedule.request(77), schedule.request(77)
    assert [x.tolist() for x in a] == [x.tolist() for x in b]
    # the hot set moves: a writer's first rank is another document later
    hot = {int(schedule._own[3][(0 + 37 * k) % len(schedule._own[3])])
           for k in range(5)}
    assert len(hot) == 5


def test_the_writers_driver_reads_every_early_acknowledgement(tiny, cpu_link):
    def stand_in(svc):
        svc.close()
        return reference.RefService("ack_before_flush")
    res = run_tiny(tiny, cell=WRITERS_CELL, steer=stand_in, max_requests=96)
    assert res["correct"] is False
    assert res["compared"]["acks_before_flush"]["value"] == 96
    assert res["compared"]["hashes_wrong"]["value"] == 0


def test_the_writers_driver_holds_the_guarantee_by_the_documents_own_count(
        tiny, cpu_link):
    """A service that acknowledges before its flush while OTHER writers'
    flushes lift the global count of ingested ops: only the document's own
    count tells."""
    import threading
    from automerge_tpu.sync.service import EngineDocSet

    class Early(EngineDocSet):
        """Every eighth single ingest returns at once and is applied by a
        thread of its own a little later."""
        _n = 0
        _late: list = []

        def apply_changes(self, doc_id, changes):
            if threading.current_thread().name.startswith("writer-"):
                type(self)._n += 1
                if type(self)._n % 8 == 0:
                    t = threading.Timer(0.05, super().apply_changes,
                                        (doc_id, changes))
                    type(self)._late.append(t)
                    t.start()
                    return None
            return super().apply_changes(doc_id, changes)

        def hashes(self):
            for t in type(self)._late:
                t.join()
            return super().hashes()

    def stand_in(svc):
        svc.close()
        early = Early(backend="rows")
        eager(early)
        return early
    res = run_tiny(tiny, cell=WRITERS_CELL, steer=stand_in, max_requests=160)
    early = res["compared"]["acks_before_flush"]["value"]
    # (a writer's next change can arrive before its late one is applied
    # and wait for it in the causal queue: that one is early too)
    assert 160 // 8 - 2 <= early < 160 // 2
    assert res["correct"] is False
    assert res["compared"]["hashes_wrong"]["value"] == 0
