"""The benchmark's own tests: the harness at a tiny fleet on the CPU, the
data files, the schedule, the trace reducer on a recorded slice of a v5e
trace, and the proof that `correct` can fail: the control (the reference in
the program's place with one guarantee broken) and the timed path broken
underneath. No threads; nothing here waits on the clock.

The CPU reaches the road the chip takes only where a test steers there
(`eager`, `cpu_link`), as tests/test_chip_smoke_stages.py does.
"""

import functools
import hashlib
import json
import os
import re
import shutil

import pytest

import jax

import check
import fleet as fleetlib
import peaks
import reference
import run
import tracefile
import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
FIXTURES = os.path.join(BENCH, "tests", "fixtures")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_./%-]{1,16}$")
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
TINY_FLEET = dict(n_small=200, n_heavy=2, heavy_ops=40, n_list=2, n_text=2,
                  n_move=1, load_batch=100, history_changes_max=2,
                  history_cap=64)
TINY_MIX = {"storm": dict(draws_per_request=80, warmup_requests=3),
            "edits": dict(warmup_requests=3),
            # two cycles: the second round comes after single edits, as
            # every round of a window does
            "mixed": dict(warmup_requests=18)}
ACCEPTED = ("fleet10k.storm", "fleet10k.edits", "fleet10k-4shard.storm")
DEVICE_METRICS = ("megakernel_roofline", "apply_final_roofline",
                  "device_idle_share")


@pytest.fixture
def cpu_link():
    """CPU-scale link constants, so that the round router prices a
    64-document round as the chip prices the full one."""
    from automerge_tpu.engine import dispatch
    keys = ("dispatch_fixed_s", "h2d_call_s", "d2h_call_s")
    saved = {k: dispatch._LINK[k] for k in keys}
    dispatch.calibrate(dispatch_fixed_s=1e-5, h2d_call_s=1e-6,
                       d2h_call_s=1e-5)
    yield
    dispatch.calibrate(**saved)


def eager(svc):
    for s in getattr(svc, "shards", [svc]):
        s._lazy_resolved = True
        s._resident.lazy_dispatch = False


def _rewrite(path, **changes):
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    for k, v in changes.items():
        if isinstance(v, dict) and isinstance(data.get(k), dict):
            data[k].update(v)
        else:
            data[k] = v
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f)


@pytest.fixture
def tiny(tmp_path):
    """A copy of the benchmark's data files at a size a test can hold."""
    root = str(tmp_path / "benchmarks")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    for name in os.listdir(os.path.join(root, "configs")):
        _rewrite(os.path.join(root, "configs", name), fleet=TINY_FLEET)
    for name, changes in TINY_MIX.items():
        _rewrite(os.path.join(root, "traffic", name + ".json"), **changes)
    return root


def run_tiny(root, cell="fleet10k.storm", trace=0, steer=eager, seed=2**31 + 7):
    """Twelve requests untraced; traced, as many as 0.15 s hold, since the
    traced slice is placed by the window's seconds."""
    return run.run_cell(cell, seed, 0.15 if trace else 30.0, trace,
                        jax.devices(), root=root, steer=steer,
                        max_requests=10_000 if trace else 12,
                        may_miss=DEVICE_METRICS)


# ---------------------------------------------------------------------------
# the harness


@pytest.mark.parametrize("cell", ["fleet10k.storm", "fleet10k.edits",
                                  "fleet10k.mixed"])
def test_result_line_has_the_contracts_keys(tiny, cpu_link, capsys, cell):
    res = run_tiny(tiny, cell=cell)
    assert list(res) == RESULT_KEYS + ["compared"]
    assert res["correct"] is True
    assert res["attempted"] == 12 and res["failed"] == 0
    assert sorted(res["metrics"]) == ["ack_p50_ms", "ack_p95_ms",
                                      "ops_per_s", "setup_s"]
    for m in res["metrics"].values():
        assert m["value"] > 0 and UNIT.match(m["unit"])
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(res["device"])
    assert all(row["value"] <= row["limit"] == 0
               for row in res["compared"].values())
    stages = [json.loads(ln)["stage"]
              for ln in capsys.readouterr().out.splitlines()]
    assert stages == ["fleet", "load", "warmup", "window", "check", "samples"]


def test_traced_run_reports_the_per_layer_metrics(tiny, cpu_link, capsys):
    res = run_tiny(tiny, trace=1)
    assert list(res) == RESULT_KEYS + ["breakdown", "compared"]
    assert res["correct"] is True
    # the host-side readers find their counters; the device readers find no
    # device plane in a CPU trace and return nothing, never 0
    assert {"flush_mean_ms", "fused_round_share", "pack_share",
            "readback_share", "compiles_in_window"} <= set(res["metrics"])
    assert "megakernel_roofline" not in res["metrics"]
    assert "device_idle_share" not in res["metrics"]
    assert res["metrics"]["fused_round_share"]["value"] == 100.0
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    assert {"busy_s", "window_s"} <= set(res["device"])
    window = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
              if '"window"' in ln][0]
    assert window["dims_before"] == window["dims_after"]
    assert window["megabatch_rounds"] == window["requests"] \
        == res["attempted"] > 0


def test_edits_cell_takes_the_resident_route(tiny, cpu_link, capsys):
    """One change a request, each its own flush: no fused round, the
    fleet's rows on the device, every acknowledgement after its flush."""
    res = run_tiny(tiny, cell="fleet10k.edits", trace=1)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0
    assert "fused_round_share" not in res["metrics"]
    assert res["metrics"]["flush_mean_ms"]["value"] > 0
    assert res["metrics"]["dispatch_share"]["value"] > 0
    window = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
              if '"window"' in ln][0]
    assert window["megabatch_rounds"] == 0
    assert window["rounds_flushed"] == window["ops_ingested"] \
        == window["ops"] == res["attempted"]


def test_mixed_cell_takes_both_routes_in_one_window(tiny, cpu_link, capsys):
    """Cycles of nine: a round under `batch()`, then eight single edits,
    each its own flush. The cell reads the round route's metrics and the
    resident route's from the accepted files, with no twin."""
    res = run_tiny(tiny, cell="fleet10k.mixed", trace=1)
    assert res["correct"] is True, res["compared"]
    window = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
              if '"window"' in ln][0]
    first = TINY_MIX["mixed"]["warmup_requests"] + 1
    rounds = sum(1 for r in range(first, first + res["attempted"])
                 if r % 9 == 0)
    assert res["attempted"] >= 18 and rounds >= 2
    assert window["rounds_flushed"] == res["attempted"]
    assert window["ops"] == window["ops_ingested"] \
        > res["attempted"] - rounds + 30 * rounds
    assert window["dims_before"] == window["dims_after"]
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    # the first edit after a round reconciles the whole buffer, the seven
    # behind it one block each
    assert res["metrics"]["block_apply_share"]["value"] == pytest.approx(
        100 * 7 / 9, abs=6)
    assert res["metrics"]["direct_frame_share"]["value"] == pytest.approx(
        100 * rounds / res["attempted"])
    assert {"commit_wait_mean_ms", "pack_share", "readback_share",
            "route_share", "fused_round_share"} <= set(res["metrics"])


def test_a_declared_metric_that_reads_nothing_ends_the_run(tiny, cpu_link):
    # a CPU trace has no device plane: the device readers read nothing
    with pytest.raises(run.RunFailed, match="megakernel_roofline"):
        run.run_cell("fleet10k.storm", 5, 0.15, 1, jax.devices(), root=tiny,
                     steer=eager, max_requests=10_000)


def test_sharded_kind_on_four_virtual_devices(tiny, cpu_link):
    assert len(jax.devices()) >= 4
    res = run_tiny(tiny, cell="fleet10k-4shard.storm")
    assert res["correct"] is True and res["attempted"] == 12


def test_main_prints_no_result_without_a_chip(capsys):
    assert run.main(["--workload", "fleet10k.storm", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 2
    cap = capsys.readouterr()
    assert cap.out == "" and "needs a TPU" in cap.err


def test_warmup_that_leaves_a_program_uncompiled_fails_loudly(tiny, cpu_link):
    # every document is as young as the others, and the probe request
    # touches nearly all of them where a warm-up request touches some
    # fifty: a new lane count, which the megakernel has to compile
    _rewrite(os.path.join(tiny, "configs", "fleet10k.json"),
             fleet={"history_changes_max": 0})

    def steer(svc):
        eager(svc)
        real = traffic.Schedule.request

        def request(self, r):
            self.draws = 5000 if r == TINY_MIX["storm"]["warmup_requests"] \
                else TINY_MIX["storm"]["draws_per_request"]
            return real(self, r)
        traffic.Schedule.request = request
        steer.undo = lambda: setattr(traffic.Schedule, "request", real)
    try:
        with pytest.raises(run.RunFailed, match="uncompiled"):
            run_tiny(tiny, steer=steer)
    finally:
        steer.undo()


# ---------------------------------------------------------------------------
# correct can fail: the control, and the timed path broken underneath


@pytest.mark.parametrize("cell", ["fleet10k.storm", "fleet10k.edits",
                                  "fleet10k.mixed"])
@pytest.mark.parametrize("broken", list(check.CONTROLS))
def test_control_comes_out_not_correct(tiny, broken, cell):
    def steer(svc):
        svc.close()
        return reference.RefService(broken, every=7)
    res = run_tiny(tiny, cell=cell, steer=steer)
    assert res["correct"] is False
    failing = {k for k, row in res["compared"].items()
               if row["value"] > row["limit"]}
    assert failing & {"ack_before_flush": {"acks_before_flush"},
                      "lose_acknowledged": {"changes_unserved"},
                      "stale_hash": {"hashes_wrong"},
                      "first_writer_wins": {"hashes_wrong", "states_wrong"}
                      }[broken]
    if broken == "ack_before_flush":
        # every answer is right in the end: only the order was wrong
        assert failing == {"acks_before_flush"}
        assert res["compared"]["acks_before_flush"]["value"] == 12


def test_reference_in_the_programs_place_is_correct(tiny):
    def steer(svc):
        svc.close()
        return reference.RefService("none")
    assert run_tiny(tiny, steer=steer)["correct"] is True


class Faulty:
    """The service with one fault planted at its entry or where an answer
    is produced; everything else goes through to the real service."""

    def __init__(self, svc, fault):
        self._svc, self._fault, self._calls = svc, fault, 0

    def __getattr__(self, name):
        return getattr(self._svc, name)

    def batch(self):
        return self._svc.batch()

    def apply_changes(self, doc_id, changes):
        self._calls += 1
        small = doc_id.startswith("doc")
        if self._fault == "state_unchanged" and small and len(changes) == 1:
            return None          # the step returns its state as it was
        if self._fault == "half_left_out" and small and self._calls % 2 \
                and len(changes) == 1:
            return None          # half of the batch never reaches the engine
        if self._fault == "shard_left_out" and small \
                and self._svc.shard_of(doc_id) is self._svc.shards[-1] \
                and len(changes) == 1:
            return None          # one chip's share is never exchanged
        if self._fault == "value_altered" and small and self._calls % 50 == 0:
            from automerge_tpu.core.change import Change, Op
            c = changes[0]
            changes = [Change(c.actor, c.seq, c.deps, [
                Op(o.action, o.obj, key=o.key, value=-1) for o in c.ops])]
        return self._svc.apply_changes(doc_id, changes)

    def hashes(self):
        out = dict(self._svc.hashes())
        if self._fault == "hash_altered":
            d = sorted(k for k in out if k.startswith("doc"))[3]
            out[d] ^= 1          # one answer altered where it is read
        return out


@pytest.mark.parametrize("fault,cell,number", [
    ("state_unchanged", "fleet10k.storm", "changes_unserved"),
    ("half_left_out", "fleet10k.storm", "hashes_wrong"),
    ("value_altered", "fleet10k.storm", "hashes_wrong"),
    ("hash_altered", "fleet10k.storm", "hashes_wrong"),
    ("shard_left_out", "fleet10k-4shard.storm", "hashes_wrong"),
    ("state_unchanged", "fleet10k.mixed", "changes_unserved"),
    ("half_left_out", "fleet10k.mixed", "hashes_wrong"),
    ("value_altered", "fleet10k.mixed", "hashes_wrong"),
    ("hash_altered", "fleet10k.mixed", "hashes_wrong"),
])
def test_a_fault_under_the_timed_path_comes_out_not_correct(
        tiny, cpu_link, fault, cell, number):
    def steer(svc):
        eager(svc)
        return Faulty(svc, fault)
    res = run_tiny(tiny, cell=cell, steer=steer)
    assert res["correct"] is False
    assert res["compared"][number]["value"] > 0
    if fault in ("state_unchanged", "half_left_out", "shard_left_out"):
        assert res["failed"] > 0      # requests whose changes were lost


def test_a_request_that_raises_is_counted_failed(tiny, cpu_link):
    class Raises(Faulty):
        def apply_changes(self, doc_id, changes):
            self._calls += 1
            if self._calls == 600:    # the sixth request or so of the window
                raise RuntimeError("the engine refused")
            return self._svc.apply_changes(doc_id, changes)

    def steer(svc):
        eager(svc)
        return Raises(svc, None)
    res = run_tiny(tiny, steer=steer)
    assert res["correct"] is False
    assert res["compared"]["requests_raised"]["value"] == 1
    assert res["failed"] >= 1 and res["attempted"] < 12


# ---------------------------------------------------------------------------
# the reference


def test_reference_follows_automerge_on_concurrent_writes():
    from automerge_tpu.core.change import Change, Op
    root = reference.ROOT_ID
    log = [Change("a", 1, {}, [Op("set", root, key="k", value=1)]),
           Change("b", 1, {}, [Op("set", root, key="k", value=2)]),
           Change("a", 2, {}, [Op("set", root, key="j", value="x")]),
           Change("b", 2, {"a": 2}, [Op("del", root, key="j")])]
    assert reference.state(log) == {"data": {"k": 2},
                                    "conflicts": {"k": {"a": 1}}}
    assert reference.state_hash(log) == reference.state_hash(log[::-1])
    assert reference.state_hash(log) != reference.state_hash(log[:2] + [
        Change("a", 2, {}, [Op("set", root, key="j", value="y")])])


# ---------------------------------------------------------------------------
# the schedule


def test_schedule_is_a_pure_function_of_the_seed():
    mix = fleetlib.load_json("traffic", "storm")
    a = traffic.Schedule(mix, 10_000, 7, 2**31 + 11)
    b = traffic.Schedule(mix, 10_000, 7, 2**31 + 11)
    c = traffic.Schedule(mix, 10_000, 7, 12)
    for r in (0, 50):
        assert [x.tolist() for x in a.request(r)] == \
            [x.tolist() for x in b.request(r)]
    assert a.request(50)[0].tolist() != c.request(50)[0].tolist()
    assert a.request(50)[0].tolist() != a.request(51)[0].tolist()
    # the warm-up's requests span the sizes a window's requests come to
    warm = [len(a.request(r)[0]) for r in range(mix["warmup_requests"])]
    later = [len(a.request(r)[0]) for r in range(100, 400)]
    assert min(warm) < min(later) - 20 and max(later) + 20 < max(warm)
    sizes = []
    for r in (100, 105, 999):
        docs, fields, values = a.request(r)
        assert len(docs) == len(set(docs.tolist())) == len(fields) \
            == len(values) <= mix["draws_per_request"]
        assert 0 <= fields.min() and fields.max() < 7
        sizes.append(len(docs))
    # a request's size is what its draws give: it varies
    assert len(set(sizes)) > 1 and min(sizes) > 1000


def test_schedule_follows_ycsbs_zipfian():
    """Constant 0.99 with replacement: the hottest rank takes about a
    tenth of the draws over 10,000 records, and the hot set moves."""
    import numpy as np
    mix = dict(fleetlib.load_json("traffic", "edits"),
               draws_per_request=1, hot_set_stride=0)
    assert mix["zipfian_constant"] == 0.99
    s = traffic.Schedule(mix, 10_000, 7, 3)
    hits = np.zeros(10_000, int)
    for r in range(4000):
        hits[s.request(r)[0]] += 1
    w = np.arange(1, 10_001) ** -0.99
    assert hits.max() / 4000 == pytest.approx(w[0] / w.sum(), rel=0.15)
    moving = traffic.Schedule(dict(mix, hot_set_stride=37), 10_000, 7, 3)
    hits = np.zeros(10_000, int)
    for r in range(4000):
        hits[moving.request(r)[0]] += 1
    assert hits.max() <= 8


def test_history_stays_under_the_cap_and_varies_as_drawn():
    """Loaded depths are drawn for each document, 3 to 129 ops; 600 storm
    requests, twice what a window holds, leave every document under the
    resident history cap."""
    import numpy as np
    mix = fleetlib.load_json("traffic", "storm")
    spec = fleetlib.FleetSpec.from_config(
        fleetlib.load_json("configs", "fleet10k"))
    fleet = fleetlib.make_fleet(spec, 4)
    n = sum(len(chs) for round_ in fleetlib.small_load_rounds(fleet, 4)
            for chs in round_.values())
    loaded = np.array([fleet.depth[d] for d in fleet.small])
    assert loaded.min() == 3 and loaded.max() == 3 + 7 * 18
    assert len(set(loaded.tolist())) == 19
    assert n == pytest.approx(10_000 * 10, rel=0.03)
    s = traffic.Schedule(mix, spec.n_small, 7, 4)
    for r in range(600):
        loaded[s.request(r)[0]] += 1
    assert loaded.max() < spec.history_cap / 2


def test_replay_makes_the_same_changes_again():
    spec = fleetlib.FleetSpec(**TINY_FLEET)
    mix = dict(fleetlib.load_json("traffic", "storm"),
               **TINY_MIX["storm"])
    fleet = fleetlib.make_fleet(spec, 2**31 + 5)
    sent = {d: list(chs) for d, chs in fleet.first.items()}
    for round_ in fleetlib.small_load_rounds(fleet, 2**31 + 5):
        sent.update(round_)
    s = traffic.Schedule(mix, spec.n_small, 7, 2**31 + 5)
    for r in range(6):
        for d, chs in fleetlib.request_changes(fleet, s.request(r)).items():
            sent[d].extend(chs)
    again, origin = fleetlib.replay(fleet, 2**31 + 5, s, range(6))

    def plain(log):
        return [(c.actor, c.seq, check._ops(c)) for c in log]
    assert {d: plain(v) for d, v in again.items()} == \
        {d: plain(v) for d, v in sent.items()}
    assert set(origin.values()) == set(range(6))


# ---------------------------------------------------------------------------
# the data files


def _files(kind):
    d = os.path.join(BENCH, kind)
    return {fn[:-5]: fleetlib.load_json(kind, fn[:-5])
            for fn in sorted(os.listdir(d)) if fn.endswith(".json")}


def test_data_files_load_and_their_names_are_names():
    for kind in ("configs", "traffic", "workloads", "metrics"):
        for name, data in _files(kind).items():
            assert NAME.match(name), (kind, name)
            assert data.get("name", name) == name
    for m in _files("metrics").values():
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(
            BENCH, "readers", m["reader"] + ".py"))
    for mix in _files("traffic").values():
        assert os.path.exists(os.path.join(
            BENCH, "drivers", mix["driver"] + ".py"))
    for cfg in _files("configs").values():
        fleetlib.FleetSpec.from_config(cfg)
        assert len(cfg["source"]) <= 200 and cfg["guarantees"]


def test_benchmark_json_agrees_with_the_files():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    assert list(bench) == ["command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"]
    cells, configs, metrics = (_files(k) for k in
                               ("workloads", "configs", "metrics"))
    listed = {w["name"]: w for w in bench["workloads"]}
    assert set(listed) <= set(cells)
    for name, w in listed.items():
        assert w == {k: cells[name][k] for k in
                     ("name", "config", "traffic", "chips", "why")}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in bench["configs"]:
        cfg = configs[c["name"]]
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        assert c["source"] == cfg["source"] and c["reduced"] == cfg["reduced"]
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and all(0.01 <= m["bound"] <= 0.25
                                    for m in e2e.values())
    # a metric's file says which cells report it by what a cell is; the
    # index lists them by name: the rule's expansion over the listed cells
    reports = {name: {m["name"] for m in run.cell_metrics(name)}
               for name in listed}
    assert sorted(m["name"] for m in bench["per_layer"]) == sorted(
        set().union(*reports.values()))
    for m in bench["per_layer"]:
        f = metrics[m["name"]]
        assert m == {k: f[k] for k in ("name", "unit", "better", "source",
                                       "layer", "moves")} | {"workloads": [
                         w for w in listed if m["name"] in reports[w]]}
        assert m["moves"] in e2e and m["workloads"]
    assert all(reports.values())


# what each accepted cell reported at PR 32, the parent of the PR that made
# the metric files say their cells by mix and service kind
ROUND_METRICS = ["device_wait_share", "direct_frame_share",
                 "fused_round_share", "megakernel_roofline", "pack_share",
                 "readback_share", "resident_gather_share", "route_share"]
EDIT_METRICS = ["apply_final_roofline", "block_apply_share",
                "commit_wait_mean_ms"]
SHARD_METRICS = ["chip_busy_balance", "pod_idle_share", "shard_docs_skew",
                 "shard_fanout_share", "shard_flush_concurrency"]
EVERY_METRICS = ["admit_share", "commit_share", "compiles_in_window",
                 "device_idle_share", "dispatch_share", "encode_share",
                 "flush_mean_ms", "publish_share", "upload_share"]


@pytest.mark.parametrize("cell,names", [
    ("fleet10k.storm", EVERY_METRICS + ROUND_METRICS),
    ("fleet10k.edits", EVERY_METRICS + EDIT_METRICS),
    ("fleet10k-4shard.storm", EVERY_METRICS + ROUND_METRICS + SHARD_METRICS),
    # the one-chip metrics of both accepted mixes, with no twin file
    ("fleet10k.mixed", EVERY_METRICS + ROUND_METRICS + EDIT_METRICS),
])
def test_a_cell_reports_the_metrics_its_mix_and_service_give(cell, names):
    assert [m["name"] for m in run.cell_metrics(cell)] == sorted(names)


def test_metric_rule_by_mix_service_and_name():
    every = dict(name="m")
    assert run.metric_holds(every, "c", {"x"}, "single")
    named = dict(every, workloads=["c"])
    assert run.metric_holds(named, "c", {"x"}, "single")
    assert not run.metric_holds(named, "d", {"x"}, "single")
    ruled = dict(every, mixes=["storm"], services=["sharded"])
    assert run.metric_holds(ruled, "c", {"mixed", "storm"}, "sharded")
    assert not run.metric_holds(ruled, "c", {"mixed", "storm"}, "single")
    assert not run.metric_holds(ruled, "c", {"edits"}, "sharded")
    assert run.metric_holds(dict(every, mixes=["edits"]), "c", {"edits"},
                            "anything")


# ---------------------------------------------------------------------------
# the accepted cells do not move


UUID = re.compile(r"[0-9a-f]{8}-[0-9a-f]{4}-[0-9a-f]{4}-[0-9a-f]{4}-"
                  r"[0-9a-f]{12}")


class Digest:
    """sha256 over changes in the order they are made: `(actor, seq, deps,
    ops)` of each. Object ids that are not seeded (uuid4, in the list and
    text documents' load) are renamed by order of first appearance."""

    def __init__(self):
        self.h = hashlib.sha256()
        self.names = {reference.ROOT_ID: reference.ROOT_ID}

    def _s(self, v):
        if isinstance(v, str):
            return UUID.sub(lambda m: self.names.setdefault(
                m.group(0), f"obj{len(self.names)}"), v)
        return v

    def change(self, c):
        return (c.actor, c.seq, sorted(c.deps.items()),
                [(o.action, self._s(o.obj), self._s(o.key), self._s(o.value),
                  o.elem) for o in c.ops])

    def round(self, tag, round_):
        self.h.update(repr((tag, [(d, [self.change(c) for c in chs])
                                  for d, chs in round_.items()])).encode())


# computed on the tree of PR 32 (bd44103) with the calls run.py and
# drivers/rounds.py made there: FleetSpec.from_config + make_fleet,
# traffic.Schedule, fleet.first + small_load_rounds, request_changes,
# fleet.replay; the full 10,044-document fleet
PINNED = {
    ("fleet10k", "storm", 7):
        "7d10ad28276f47ec9f9702b9d19096bbf9c96b022cea57191119a5d151898e03",
    ("fleet10k", "storm", 2**31 + 11):
        "aaec14dc86e6943d0d9b6f10e8100b71a0cc43335becf4fd998f74c6be99872d",
    ("fleet10k", "storm", 123456789):
        "1ab56e28c529273b18809789c001ff8765daf637cd05f532c18aadb5eb572f39",
    ("fleet10k", "edits", 7):
        "aceb3cdfe05adacbabdc644e15841e8abcbc346001147b85c5a4c1080a8dc8e2",
    ("fleet10k", "edits", 2**31 + 11):
        "fc06009b7b1987a9862133f041a95be7e0a5b565163f4275a7e9f4275835b920",
    ("fleet10k", "edits", 123456789):
        "cb4b16c7ce791cfacd4425399f395c4f0d4bd453fca56d0305702fa5fb13eece",
    ("fleet10k-4shard", "storm", 7):
        "7d10ad28276f47ec9f9702b9d19096bbf9c96b022cea57191119a5d151898e03",
    ("fleet10k-4shard", "storm", 2**31 + 11):
        "aaec14dc86e6943d0d9b6f10e8100b71a0cc43335becf4fd998f74c6be99872d",
    ("fleet10k-4shard", "storm", 123456789):
        "1ab56e28c529273b18809789c001ff8765daf637cd05f532c18aadb5eb572f39",
}


@functools.lru_cache(maxsize=None)
def _digest_of(config_json: str, mix_json: str, seed: int) -> str:
    """Keyed by what the files say, so that the sharded configuration,
    whose fleet is fleet10k's number for number, is made once a seed."""
    config, mix = json.loads(config_json), json.loads(mix_json)
    fleet = run.seam(config, "fleet_kind", "fleets", fleetlib).make(
        config, seed)
    schedule = run.seam(mix, "schedule", "schedules", traffic).make(
        mix, fleet, seed, BENCH)
    dg = Digest()
    for round_ in fleet.load_rounds():
        dg.round("load", round_)
    for r in range(41):
        assert schedule.batch(r) is mix["batch"]
        dg.round(r, fleet.request_changes(schedule.request(r)))
    sent, origin = fleet.replay(schedule, range(41))
    dg.round("sent", sent)
    dg.h.update(repr(sorted(origin.items())).encode())
    return dg.h.hexdigest()


@pytest.mark.parametrize("config_name,mix_name,seed", list(PINNED))
def test_accepted_cells_send_what_they_sent_at_pr_32(config_name, mix_name,
                                                     seed):
    """The load rounds, requests 0..40 and the replay of those requests,
    through the calls run.py and the driver make now, give the digest the
    parent's calls gave: the same fleet, the same requests, the same
    replay from the same seed."""
    config = fleetlib.load_json("configs", config_name)
    mix = fleetlib.load_json("traffic", mix_name)
    assert not {"fleet_kind", "check"} & set(config) and "schedule" not in mix
    # of a configuration, only its fleet's numbers reach the fleet
    fleetlib.FleetSpec.from_config(config)
    assert _digest_of(json.dumps({"fleet": config["fleet"]}, sort_keys=True),
                      json.dumps(mix, sort_keys=True),
                      seed) == PINNED[config_name, mix_name, seed]


# ---------------------------------------------------------------------------
# the cycle schedule and the mix built on it


def test_cycle_schedule_is_built_from_the_accepted_mixes():
    mix = fleetlib.load_json("traffic", "mixed")
    fleet = fleetlib.Fleet(None, small=[f"d{i}" for i in range(10_000)])
    cycle = run.load_by_path("schedules", mix["schedule"]).make(
        mix, fleet, 2**31 + 11, BENCH)
    again = run.load_by_path("schedules", mix["schedule"]).make(
        mix, fleet, 2**31 + 11, BENCH)
    storm = traffic.Schedule(fleetlib.load_json("traffic", "storm"),
                             10_000, 7, 2**31 + 11)
    edits = traffic.Schedule(fleetlib.load_json("traffic", "edits"),
                             10_000, 7, 2**31 + 11)
    n_warm = mix["warmup_requests"]
    assert n_warm == 72 and n_warm % 9 == 0 and mix["hot_set_stride"] == 37
    for r in (0, 9, 71, 72, 73, 80, 81, 900, 907):
        drawn = cycle.request(r)
        assert [x.tolist() for x in drawn] == \
            [x.tolist() for x in again.request(r)]
        assert cycle.batch(r) is (r % 9 == 0)
        if r >= n_warm:
            # past the warm-up a request is its part's own request r
            part = storm if r % 9 == 0 else edits
            assert [x.tolist() for x in drawn] == \
                [x.tolist() for x in part.request(r)]
        assert len(drawn[0]) == 1 if r % 9 else len(drawn[0]) > 1000
    # the eight warm-up rounds span the sizes a window's rounds come to,
    # as the storm mix's eight warm-up requests do
    warm = [len(cycle.request(r)[0]) for r in range(0, n_warm, 9)]
    assert len(warm) == 8 and warm[0] == min(warm) and warm[-1] == max(warm)
    later = [len(cycle.request(r)[0]) for r in range(90, 1890, 9)]
    assert min(warm) < min(later) - 20 and max(later) + 20 < max(warm)
    with pytest.raises(ValueError, match="whole number of cycles"):
        run.load_by_path("schedules", "cycle").make(
            dict(mix, warmup_requests=70), fleet, 1, BENCH)


def test_mixed_history_stays_under_the_cap():
    """1,700 requests, more than a window holds (about 1,500), leave every
    document under half the resident history cap."""
    import numpy as np
    mix = fleetlib.load_json("traffic", "mixed")
    config = fleetlib.load_json("configs", "fleet10k")
    fleet = fleetlib.make(config, 4)
    for _ in fleet.load_rounds():
        pass
    loaded = np.array([fleet.depth[d] for d in fleet.small])
    cycle = run.load_by_path("schedules", "cycle").make(mix, fleet, 4, BENCH)
    for r in range(1700):
        loaded[cycle.request(r)[0]] += 1
    assert loaded.max() < fleet.spec.history_cap / 2


# ---------------------------------------------------------------------------
# the control at any load, and the prover


def test_stale_hash_control_passes_over_a_flush_of_new_documents(tiny):
    """A fleet loaded in ten flushes: the seventh holds new documents only,
    none with a hash to leave stale. The control goes on and is read not
    correct by the window's flushes."""
    _rewrite(os.path.join(tiny, "configs", "fleet10k.json"),
             fleet={"load_batch": 23})
    flushes = []

    def steer(svc):
        svc.close()
        ref = reference.RefService("stale_hash")
        real = ref._flush

        def flush():
            if ref._dirty:
                flushes.append(len(ref._stale))
            real()
        ref._flush = flush
        return ref
    res = run_tiny(tiny, steer=steer)
    # the load is ten flushes, 1 + 9 rounds; the seventh left nothing stale
    assert flushes[:10] == [0] * 10 and flushes[-1] >= 1
    assert res["correct"] is False
    assert res["compared"]["hashes_wrong"]["value"] > 0


def test_prove_runs_the_controls_the_check_declares(tiny, cpu_link, capsys):
    import prove
    argv = ["--workload", "fleet10k.mixed", "--seeds", "5", "--seconds", "30"]
    kw = dict(root=tiny, devices=jax.devices(), max_requests=12)
    assert prove.main(argv + ["--control", "1"], **kw) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln["control"] for ln in lines] == list(check.CONTROLS)
    assert all(ln["correct"] is False and ln["as_it_has_to"] for ln in lines)
    assert prove.main(argv, steer=eager, **kw) == 0
    line, = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert line["correct"] is True and line["control"] is None
    assert set(line["compared"].values()) == {0}


# ---------------------------------------------------------------------------
# later PRs add files and edit none


def test_new_files_are_found_and_run(tiny, cpu_link, capsys):
    shutil.copy(os.path.join(tiny, "configs", "fleet10k.json"),
                os.path.join(tiny, "configs", "fleet-b.json"))
    _rewrite(os.path.join(tiny, "configs", "fleet-b.json"), name="fleet-b",
             fleet={"n_small": 150})
    shutil.copy(os.path.join(tiny, "traffic", "storm.json"),
                os.path.join(tiny, "traffic", "drizzle.json"))
    _rewrite(os.path.join(tiny, "traffic", "drizzle.json"),
             driver="rounds2", draws_per_request=40)
    shutil.copy(os.path.join(tiny, "drivers", "rounds.py"),
                os.path.join(tiny, "drivers", "rounds2.py"))
    with open(os.path.join(tiny, "workloads", "fleet-b.drizzle.json"), "w",
              encoding="utf-8") as f:
        json.dump({"name": "fleet-b.drizzle", "config": "fleet-b",
                   "traffic": "drizzle", "chips": 1, "why": "a new cell"}, f)
    with open(os.path.join(tiny, "readers", "twice.py"), "w",
              encoding="utf-8") as f:
        f.write("def read(args, ctx):\n"
                "    return 2 * ctx['delta'].get(args['counter'], 0)\n")
    with open(os.path.join(tiny, "metrics", "ops_twice.json"), "w",
              encoding="utf-8") as f:
        json.dump({"name": "ops_twice", "unit": "ops", "better": "higher",
                   "source": "program_counter", "layer": "round router",
                   "moves": "ops_per_s", "workloads": ["fleet-b.drizzle"],
                   "reader": "twice",
                   "args": {"counter": "sync_ops_ingested"}}, f)
    res = run_tiny(tiny, cell="fleet-b.drizzle", trace=1)
    assert res["correct"] is True
    window = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
              if '"window"' in ln][0]
    assert res["metrics"]["ops_twice"]["value"] == 2 * window["ops"] > 0
    # the old cell does not report the new cell's metric
    assert "ops_twice" not in [m["name"] for m in run.cell_metrics(
        "fleet10k.storm", tiny)]


TWO_ACTOR_FLEET = '''"""Fleet kind `two_actor`: the maps fleet, written by two actors
concurrently. A request gives each drawn document two changes to the same
field, one by `storm` and one by `zed`, neither of which has seen the
other: the field holds zed's value and storm's as its conflict."""
import fleet as fleetlib


class Fleet(fleetlib.Fleet):
    dims_fixed = False       # reported, and the run goes on

    def request_changes(self, drawn):
        from automerge_tpu.core.change import Change, Op
        from automerge_tpu.core.ids import ROOT_ID
        out = {}
        for i, f, v in zip(*(x.tolist() for x in drawn)):
            d, key = self.small[i], fleetlib.SMALL_KEYS[f]
            self.zed[d] = self.zed.get(d, 0) + 1
            out[d] = fleetlib.storm_change(
                self, d, (Op("set", ROOT_ID, key=key, value=v),)) + [
                Change("zed", self.zed[d], {},
                       [Op("set", ROOT_ID, key=key, value=-v)])]
        return out

    @staticmethod
    def request_ops(round_):
        return 2 * len(round_)

    def load_line(self):
        return {"writers": ["storm", "zed"]}

    def replay(self, schedule, numbers):
        again = Fleet(self.spec, small=self.small,
                      structured=self.structured, seed=self.seed)
        again.zed = {}
        sent = {d: list(chs) for d, chs in self.first.items()}
        for round_ in fleetlib.small_load_rounds(again, self.seed):
            sent.update(round_)
        origin = {}
        for r in numbers:
            for d, chs in again.request_changes(schedule.request(r)).items():
                sent[d].extend(chs)
                origin[(d, chs[0].seq)] = r
        return sent, origin


def make(config, seed):
    made = fleetlib.make(dict(config, fleet={
        k: v for k, v in config["fleet"].items() if k != "writers"}), seed)
    fleet = Fleet(made.spec, small=made.small, structured=made.structured,
                  seqs=made.seqs, first=made.first, seed=made.seed)
    fleet.zed = {}
    return fleet
'''

ALTERNATE_SCHEDULE = '''"""Schedule `alternate`: even requests are rounds of the mix's draws under
one batch(), odd ones a single draw, a bare apply_changes."""
import traffic


class Alternate(traffic.Schedule):
    def batch(self, r):
        return r % 2 == 0

    def request(self, r):
        return self.drawn(r, self.draws if r % 2 == 0 else 1)


def make(mix, fleet, seed, root):
    return Alternate(mix, len(fleet.small), fleet.n_fields, seed)
'''

CONFLICTS_CHECK = '''"""Check `conflicts`: the accepted comparison and one number more,
`conflicts_lost`, by a plain reference of its own for what this fleet
writes: of two concurrent sets the higher actor's is the value, the other
its conflict."""
import check
import reference

read_untouched, read_program = check.read_untouched, check.read_program
FALLBACK_COUNTERS = check.FALLBACK_COUNTERS
CONTROLS = {"first_writer_wins":
            lambda: reference.RefService("first_writer_wins")}


def expected(log):
    """{key: (value, {loser: value})} of the fields the last concurrent
    pair of each key wrote; the changes of a pair follow one another."""
    out = {}
    for a, b in zip(log, log[1:]):
        if (a.actor, b.actor) == ("storm", "zed"):
            out[a.ops[0].key] = (b.ops[0].value, {"storm": a.ops[0].value})
    return out


def decide(read, fleet, sent, origin, untouched_before, requests, fallbacks):
    verdict = check.decide(read, fleet, sent, origin, untouched_before,
                           requests, fallbacks)
    lost = 0
    for d, got in read["states"].items():
        for key, (value, losers) in expected(sent.get(d, ())).items():
            if not isinstance(got, dict) or got["data"].get(key) != value \
                    or got["conflicts"].get(key) != losers:
                lost += 1
    verdict["compared"]["conflicts_lost"] = {"value": lost, "limit": 0}
    verdict["correct"] = verdict["correct"] and lost == 0
    verdict["sizes"]["pairs"] = sum(
        len(expected(sent.get(d, ()))) for d in read["states"])
    return verdict
'''


def _cell_of_its_own_seams(tiny):
    """A configuration that names its fleet kind and its check, a mix that
    names its schedule, a cell of the two: new files in a copy, no edit."""
    for kind, name, text in (("fleets", "two_actor", TWO_ACTOR_FLEET),
                             ("schedules", "alternate", ALTERNATE_SCHEDULE),
                             ("checks", "conflicts", CONFLICTS_CHECK)):
        os.makedirs(os.path.join(tiny, kind), exist_ok=True)
        with open(os.path.join(tiny, kind, name + ".py"), "w",
                  encoding="utf-8") as f:
            f.write(text)
    shutil.copy(os.path.join(tiny, "configs", "fleet10k.json"),
                os.path.join(tiny, "configs", "fleet-2w.json"))
    _rewrite(os.path.join(tiny, "configs", "fleet-2w.json"), name="fleet-2w",
             fleet_kind="two_actor", check="conflicts", fleet={"writers": 2})
    shutil.copy(os.path.join(tiny, "traffic", "storm.json"),
                os.path.join(tiny, "traffic", "pairs.json"))
    _rewrite(os.path.join(tiny, "traffic", "pairs.json"),
             schedule="alternate", reports_as=["storm"], warmup_requests=4)
    with open(os.path.join(tiny, "workloads", "fleet-2w.pairs.json"), "w",
              encoding="utf-8") as f:
        json.dump({"name": "fleet-2w.pairs", "config": "fleet-2w",
                   "traffic": "pairs", "chips": 1, "why": "a new cell"}, f)
    return "fleet-2w.pairs"


def test_a_cell_of_its_own_fleet_schedule_and_check_runs(tiny, cpu_link,
                                                         capsys):
    cell = _cell_of_its_own_seams(tiny)
    res = run_tiny(tiny, cell=cell)
    assert res["correct"] is True, res["compared"]
    assert list(res["compared"])[-1] == "conflicts_lost"
    assert all(row["value"] == 0 == row["limit"]
               for row in res["compared"].values())
    stages = {json.loads(ln)["stage"]: json.loads(ln)
              for ln in capsys.readouterr().out.splitlines()}
    assert stages["load"]["writers"] == ["storm", "zed"]
    assert "small_depth_min_median_max" not in stages["load"]
    # six rounds and six single edits, two ops a document
    assert res["attempted"] == 12
    assert stages["window"]["ops"] == stages["window"]["ops_ingested"] > 12
    assert stages["window"]["rounds_flushed"] == 12
    assert stages["check"]["compared"]["pairs"] > 0
    # the new mix takes the accepted round metrics as data
    assert "megakernel_roofline" in [m["name"] for m in run.cell_metrics(
        cell, tiny)]


def test_a_cell_of_its_own_seams_with_a_guarantee_broken(tiny, cpu_link):
    cell = _cell_of_its_own_seams(tiny)
    controls = run.load_by_path("checks", "conflicts", tiny).CONTROLS
    assert list(controls) == ["first_writer_wins"]

    def steer(svc):
        svc.close()
        return controls["first_writer_wins"]()
    res = run_tiny(tiny, cell=cell, steer=steer)
    assert res["correct"] is False
    assert res["compared"]["conflicts_lost"]["value"] > 0
    # the fleet's own fault: zed's half of every pair never reaches the engine

    class OneWriter(Faulty):
        def apply_changes(self, doc_id, changes):
            return self._svc.apply_changes(
                doc_id, [c for c in changes if c.actor != "zed"])

    def steer(svc):
        eager(svc)
        return OneWriter(svc, None)
    res = run_tiny(tiny, cell=cell, steer=steer)
    assert res["correct"] is False
    assert res["compared"]["conflicts_lost"]["value"] > 0
    assert res["compared"]["changes_unserved"]["value"] > 0


def test_a_fleet_whose_dims_may_move_has_both_reported(tiny, cpu_link, capsys):
    """The default ends a run whose resident dims moved in the window; a
    fleet kind that says its documents arrive there has both reported and
    the run goes on."""
    cell = _cell_of_its_own_seams(tiny)
    real = fleetlib.resident_dims
    calls = []

    def moving(svc):
        calls.append(1)
        return real(svc) + [[len(calls)]]
    fleetlib.resident_dims = moving
    try:
        res = run_tiny(tiny, cell=cell)
        window = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
                  if '"window"' in ln][0]
        assert res["correct"] is True
        assert window["dims_before"] != window["dims_after"]
        with pytest.raises(run.RunFailed, match="dims changed"):
            run_tiny(tiny, cell="fleet10k.storm")
    finally:
        fleetlib.resident_dims = real


# ---------------------------------------------------------------------------
# peaks, bytes and the trace reducer


def test_bytes_function_by_hand():
    # the whole resident buffer of the 10K fleet: dims (512, 4, 64) as the
    # issue sized it, (512, 4, 32) as the chip run loads it
    assert peaks.rows_count(512, 4, 64) == 6468
    assert peaks.rows_hash_min_bytes(6468, 10112) == \
        4 * 6468 * 10112 + 4 * 10112 == 261_658_112
    assert peaks.rows_count(512, 4, 32) == 6308
    assert peaks.rows_hash_min_bytes(6308, 10112) == 255_186_432
    # the recorded slice's shape: dims (256, 4, 0) over 768 lanes
    assert peaks.rows_count(256, 4, 0) == 3076
    assert peaks.rows_hash_min_bytes(3076, 768) == 9_452_544
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peak("cpu")


def test_reducer_on_a_recorded_slice_of_a_v5e_trace():
    """0.7 s of the traced slice of `fleet10k.storm` on one TPU v5e (my
    chip run, PR 26): five turns of the driver's loop, five megakernel
    calls over `s32[3076,768]`."""
    with open(os.path.join(FIXTURES, "v5e_storm_slice.json"),
              encoding="utf-8") as f:
        data = json.load(f)
    r = tracefile.reduce(data, 1)
    assert r["planes"] == ["/device:TPU:0", "/host:metadata", "/host:CPU"]
    assert r["window_s"] == pytest.approx(0.740798929, rel=1e-9)
    assert r["busy_by_chip"] == {"0": pytest.approx(0.003949811, rel=1e-9)}
    assert r["busy_s"] == pytest.approx(0.003949811, rel=1e-9)
    (name, k), = [(n, k) for n, k in r["events"].items()
                  if n.startswith("%reconcile_rows_hash")]
    assert "custom-call(s32[3076,768]" in name and k["calls"] == 5
    assert k["device_s"] == pytest.approx(0.003948542, rel=1e-9)
    ctx = {"trace": r, "device_kind": "TPU v5 lite"}
    args = fleetlib.load_json("metrics", "megakernel_roofline")["args"]
    roofline = run.load_by_path("readers", "trace_roofline").read(args, ctx)
    assert roofline == pytest.approx(
        100 * (47_262_720 / 819e9) / 0.003948542, rel=1e-9)
    assert roofline == pytest.approx(1.4615, abs=1e-3)
    idle = run.load_by_path("readers", "trace_idle").read({}, ctx)
    assert idle == pytest.approx(100 * (1 - 0.003949811 / 0.740798929))
    b = tracefile.breakdown(r)
    assert b["device_ops"][0] == ["reconcile_rows_hash s32[3076,768]",
                                  pytest.approx(0.003948542)]
    assert [g[0] for g in b["idle_gaps"][:3]] == [
        "bench_loop", "rows_round_apply", "sync_round_flush"]
    assert sum(g[1] for g in r["gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    # a kernel the slice does not hold, or a trace with no device: nothing
    assert run.load_by_path("readers", "trace_roofline").read(
        dict(args, event="^%no_such_kernel"), ctx) is None
    with pytest.raises(ValueError, match="no shape"):
        run.load_by_path("readers", "trace_roofline").read(
            dict(args, shape=r"f32\\[(\\d+)\\]"), ctx)
    empty = tracefile.reduce({"planes": []}, 1)
    assert run.load_by_path("readers", "trace_idle").read(
        {}, {"trace": empty}) is None


def test_union_of_intervals():
    s, merged = tracefile.union_s([(0, 10), (5, 20), (30, 40), (32, 35)])
    assert s == pytest.approx(30e-9) and merged == [[0, 20], [30, 40]]
