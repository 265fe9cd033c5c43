"""bench_history.jsonl + the perf regression gate
(automerge_tpu/perf/history.py and the `python -m automerge_tpu.perf`
CLI contract). Pure host tests — no jax dispatch work."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from automerge_tpu.perf import history

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _rec(value, backend="cpu", source="test", compiles=None, configs=None):
    out = {"schema": 1, "at": 0.0, "source": source, "backend": backend,
           "value": value, "unit": "ops/sec", "vs_baseline": 1.0,
           "configs": configs or {}}
    if compiles is not None:
        out["perf"] = {"compiles_total": compiles, "kernels": {}}
    return out


def _frec(value, hashes_s, backend="cpu", source="test"):
    out = _rec(value, backend=backend, source=source)
    if hashes_s is not None:
        out["fleet"] = {"fleet_hashes_s": hashes_s,
                        "fleet_hashes_clean_shards": 8,
                        "fleet_hashes_dirty_shards": 0}
    return out


def _write(path, records):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


# -- ledger -----------------------------------------------------------------


def test_append_load_roundtrip_tolerates_torn_tail(tmp_path):
    p = str(tmp_path / "h.jsonl")
    history.append(_rec(100), p)
    history.append(_rec(110), p)
    with open(p, "a") as f:
        f.write('{"torn": ')        # a killed run's partial line
    recs = history.load(p)
    assert [r["value"] for r in recs] == [100, 110]


def test_backfill_from_bench_captures(tmp_path):
    """`BENCH_r0*.json` driver captures beside the ledger seed it: captures
    with a parsed final record become history records (backend-labeled,
    in filename order), crashed rounds are skipped. The captures are
    written here — the committed ones are history, not test fixtures."""
    import json

    def capture(name, parsed):
        (tmp_path / name).write_text(json.dumps(
            {"n": 1, "cmd": "python bench.py", "rc": 0, "tail": "",
             "parsed": parsed}))

    capture("BENCH_r01.json", {
        "metric": "ops", "value": 5000, "unit": "ops/sec",
        "vs_baseline": 1.5, "backend": "cpu", "configs": {"1": 1.2}})
    capture("BENCH_r02.json", {
        "metric": "ops", "value": 9000, "unit": "ops/sec",
        "vs_baseline": 2.5, "backend": "tpu",
        "configs": {"1": {"speedup": 1.4}, "5": {"speedup": 9.0}}})
    capture("BENCH_r03.json", None)                  # a crashed round
    (tmp_path / "BENCH_r04.json").write_text("{torn")   # a torn file

    recs = history.backfill_records(str(tmp_path))
    assert [r["value"] for r in recs] == [5000, 9000]
    assert [r["source"] for r in recs] == ["backfill:BENCH_r01.json",
                                           "backfill:BENCH_r02.json"]
    assert [r["backend"] for r in recs] == ["cpu", "tpu"]
    # per-config speedups normalize to dicts for both record shapes
    assert all(isinstance(v, dict)
               for r in recs for v in r["configs"].values())

    p = str(tmp_path / "h.jsonl")
    n = history.ensure_backfilled(str(tmp_path), p)
    assert n == len(recs) == len(history.load(p))
    # a second call never rewrites existing history
    assert history.ensure_backfilled(str(tmp_path), p) == 0


def test_record_from_bench_aggregates_compile_counts():
    rec = {"backend": "cpu", "value": 5000, "unit": "ops/sec",
           "vs_baseline": 2.0,
           "configs": {
               "1": {"speedup": 1.2, "engine_ops_per_s": 900,
                     "metrics": {"perf": {"kernels": {
                         "apply_final": {"dispatches": 4, "compiles": 2},
                         "scan_rounds": {"dispatches": 1, "compiles": 1},
                     }}}},
               "5": {"speedup": 2.0, "engine_ops_per_s": 5000,
                     "metrics": {"perf": {"kernels": {
                         "apply_final": {"dispatches": 2, "compiles": 1},
                     }}}}}}
    out = history.record_from_bench(rec)
    assert out["value"] == 5000 and out["backend"] == "cpu"
    assert out["configs"]["5"]["engine_ops_per_s"] == 5000
    assert out["perf"]["compiles_total"] == 4
    assert out["perf"]["kernels"] == {"apply_final": 3, "scan_rounds": 1}


# -- the gate ---------------------------------------------------------------


def test_check_empty_history_skips_cleanly(tmp_path):
    rc, lines = history.check(path=str(tmp_path / "missing.jsonl"))
    assert rc == 0
    assert any("SKIP" in ln for ln in lines)


def test_check_identical_rerun_passes(tmp_path):
    p = str(tmp_path / "h.jsonl")
    _write(p, [_rec(1000, compiles=10), _rec(1000, compiles=10),
               _rec(1000, compiles=10, source="rerun")])
    rc, lines = history.check(path=p)
    assert rc == 0, lines


def test_check_flags_2x_throughput_regression(tmp_path):
    p = str(tmp_path / "h.jsonl")
    _write(p, [_rec(1000), _rec(1050), _rec(500, source="regressed")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("REGRESSION" in ln for ln in lines)


def test_check_flags_compile_count_growth(tmp_path):
    p = str(tmp_path / "h.jsonl")
    _write(p, [_rec(1000, compiles=10), _rec(1000, compiles=10),
               _rec(1000, compiles=40, source="retrace-storm")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("COMPILE GROWTH" in ln for ln in lines)


def test_check_flags_hash_read_cost_growth(tmp_path):
    """The convergence-read gate (r6): a clean-fleet hashes() read that
    regresses back toward O(fleet) — well past the rolling median plus
    the absolute slack — fails the check."""
    p = str(tmp_path / "h.jsonl")
    _write(p, [_frec(1000, 0.02), _frec(1000, 0.03),
               _frec(1000, 6.5, source="o-fleet-regression")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("HASH-READ GROWTH" in ln for ln in lines)


def test_check_hash_gate_passes_within_slack(tmp_path):
    """Sub-second jitter on a milliseconds-scale read must not trip the
    gate (absolute slack): 20ms -> 120ms is noise, not a regression."""
    p = str(tmp_path / "h.jsonl")
    _write(p, [_frec(1000, 0.02), _frec(1000, 0.03),
               _frec(1000, 0.12, source="jittery-rerun")])
    rc, lines = history.check(path=p)
    assert rc == 0, lines


def test_check_hash_gate_skips_when_history_lacks_fleet(tmp_path):
    """Skip-clean semantics, both directions: a record WITH the fleet
    section judged against history WITHOUT it (and vice versa) is
    informational, never a failure."""
    p = str(tmp_path / "h.jsonl")
    _write(p, [_rec(1000), _rec(1000),
               _frec(1000, 5.0, source="first-with-fleet")])
    rc, lines = history.check(path=p)
    assert rc == 0
    assert any("comparison starts next run" in ln for ln in lines)
    _write(p, [_frec(1000, 0.02), _rec(1000, source="no-fleet-run")])
    rc, lines = history.check(path=p)
    assert rc == 0, lines


def test_hash_gate_runs_even_when_throughput_gate_skips(tmp_path):
    """The convergence-read gate has its own comparison pool (config 8
    carries its own numbers): a run whose headline config changed — so
    the throughput gate skips — must still be judged on fleet_hashes_s."""
    p = str(tmp_path / "h.jsonl")
    priors = [dict(_frec(1000, 0.02), headline_config="5"),
              dict(_frec(1000, 0.03), headline_config="5")]
    cur = dict(_frec(900, 8.0, source="headline-fellback"),
               headline_config="1")
    _write(p, priors + [cur])
    rc, lines = history.check(path=p)
    assert any("SKIP throughput" in ln for ln in lines)
    assert rc == 1, lines
    assert any("HASH-READ GROWTH" in ln for ln in lines)


def test_hash_gate_window_not_consumed_by_fleetless_runs(tmp_path):
    """Filter-then-window: runs without config 8 in between must not push
    the comparable fleet records out of the gate's window."""
    p = str(tmp_path / "h.jsonl")
    recs = [_frec(1000, 0.02)] + [_rec(1000) for _ in range(10)] \
        + [_frec(1000, 9.0, source="regressed-after-gap")]
    _write(p, recs)
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("HASH-READ GROWTH" in ln for ln in lines)


def test_check_hash_gate_is_backend_scoped(tmp_path):
    """A CPU run's hash read is never judged against TPU history."""
    p = str(tmp_path / "h.jsonl")
    _write(p, [_frec(1000, 0.001, backend="tpu"),
               _frec(1000, 0.001, backend="tpu"),
               _frec(1000, 0.5, backend="cpu", source="cpu-read")])
    rc, lines = history.check(path=p)
    assert rc == 0, lines


def test_record_from_bench_extracts_fleet_section():
    rec = {"value": 14000, "backend": "cpu", "configs": {
        "8": {"engine_ops_per_s": 14000, "fleet_hashes_s": 0.02,
              "fleet_hashes_first_s": 21.0,
              "fleet_hashes_clean_shards": 8,
              "fleet_hashes_dirty_shards": 0,
              "round_cost_scaling": 1.05, "round_max_s": 0.4,
              "round_max_cause": "GC"}}}
    out = history.record_from_bench(rec)
    assert out["fleet"] == {
        "fleet_hashes_s": 0.02, "fleet_hashes_first_s": 21.0,
        "fleet_hashes_clean_shards": 8, "fleet_hashes_dirty_shards": 0,
        "round_cost_scaling": 1.05, "round_max_s": 0.4}
    # compact/driver records without config-8 detail: no fleet section
    assert "fleet" not in history.record_from_bench(
        {"value": 100, "configs": {"8": 1.5}})


def test_check_never_compares_across_hosts(tmp_path):
    """Host-scoping rule (r6): a host-stamped record is judged only
    against same-host-class records — raw ops/sec differs ~10x between a
    small container and a big runner on identical code. Un-stamped
    (pre-r6 backfill) records fall out of a stamped record's pool."""
    p = str(tmp_path / "h.jsonl")
    big = dict(_rec(10_000_000), host={"cpus": 32, "machine": "x86_64"})
    unstamped = _rec(12_000_000)
    small = dict(_rec(1_000_000, source="small-box"),
                 host={"cpus": 2, "machine": "x86_64"})
    _write(p, [big, unstamped, small])
    rc, lines = history.check(path=p)
    assert rc == 0
    assert any("SKIP" in ln for ln in lines)
    # same-host history DOES gate
    small2 = dict(_rec(400_000, source="small-box-regressed"),
                  host={"cpus": 2, "machine": "x86_64"})
    _write(p, [small, dict(small), small2])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("REGRESSION" in ln for ln in lines)
    # an UN-stamped current record keeps the old pan-host behavior
    _write(p, [_rec(1000), _rec(1000), _rec(980, source="ok-rerun")])
    rc, lines = history.check(path=p)
    assert rc == 0, lines


def test_record_from_bench_stamps_host():
    out = history.record_from_bench({"value": 100, "configs": {}})
    assert out["host"]["cpus"] >= 1
    assert isinstance(out["host"]["machine"], str)


def test_check_never_compares_across_backends(tmp_path):
    """Backend-labeling rule: a CPU fallback run is judged only against
    CPU history — TPU numbers are an order of magnitude apart and would
    make the gate either blind or permanently red."""
    p = str(tmp_path / "h.jsonl")
    _write(p, [_rec(100000, backend="tpu"), _rec(120000, backend="tpu"),
               _rec(1000, backend="cpu", source="cpu-fallback")])
    rc, lines = history.check(path=p)
    assert rc == 0
    assert any("SKIP" in ln for ln in lines)


def test_check_never_compares_across_headline_configs(tmp_path):
    """A partial run (--config 1) falls back to a different headline
    config than a full run; judging its value against full-run history
    would be a guaranteed false alarm."""
    p = str(tmp_path / "h.jsonl")
    full = history.record_from_bench(
        {"backend": "cpu", "value": 14000000,
         "configs": {"1": 1.2, "5": 90.0}})
    partial = history.record_from_bench(
        {"backend": "cpu", "value": 47000, "configs": {"1": 1.1}},
        source="partial")
    assert full["headline_config"] == "5"
    assert partial["headline_config"] == "1"
    _write(p, [full, full, partial])
    rc, lines = history.check(path=p)
    assert rc == 0
    assert any("SKIP" in ln for ln in lines)


def test_check_explicit_record_against_whole_file(tmp_path):
    p = str(tmp_path / "h.jsonl")
    _write(p, [_rec(1000), _rec(1000)])
    rc, _ = history.check(path=p, record=_rec(980, source="candidate"))
    assert rc == 0
    rc, _ = history.check(path=p, record=_rec(400, source="candidate"))
    assert rc == 1


# -- CLI contract -----------------------------------------------------------


def _cli(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "automerge_tpu.perf", *args],
        capture_output=True, text=True, cwd=str(ROOT), env=env,
        timeout=120)


def test_cli_check_exit_codes(tmp_path):
    p = str(tmp_path / "h.jsonl")
    _write(p, [_rec(1000), _rec(1000), _rec(1000, source="rerun")])
    out = _cli("check", "--history", p, "--no-backfill")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "PERFCHECK OK" in out.stdout

    _write(p, [_rec(1000), _rec(1000), _rec(500, source="regressed")])
    out = _cli("check", "--history", p, "--no-backfill")
    assert out.returncode == 1
    assert "PERFCHECK FAIL" in out.stdout

    out = _cli("check", "--history", str(tmp_path / "none.jsonl"),
               "--no-backfill")
    assert out.returncode == 0
    assert "SKIP" in out.stdout


def test_cli_check_backfills_missing_history(tmp_path):
    p = str(tmp_path / "h.jsonl")
    out = _cli("check", "--history", p)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "backfilled" in out.stdout
    assert len(history.load(p)) >= 3


def test_cli_report_renders_trajectory(tmp_path):
    p = str(tmp_path / "h.jsonl")
    _write(p, [_rec(1000, source="one"), _rec(2000, source="two")])
    out = _cli("report", "--history", p, "--no-backfill")
    assert out.returncode == 0
    assert "bench history — 2 records" in out.stdout
    assert "one" in out.stdout and "two" in out.stdout


def test_cli_rejects_unknown_command():
    out = _cli("frobnicate")
    assert out.returncode == 2


# -- r8 gates: bulk text merge (config 10) + keystroke flatness (config 7) --


def _mrec(value, merge_ops, source="test", host=None):
    out = _rec(value, source=source,
               configs={"10": {"merge_ops_per_s": merge_ops,
                               "merge_speedup_vs_perop": 3.0,
                               "merge_speedup_vs_replay": 40.0}})
    if host is not None:
        out["host"] = host
    return out


def test_merge_gate_passes_on_steady_throughput(tmp_path):
    p = str(tmp_path / "h.jsonl")
    _write(p, [_mrec(1000, 9000), _mrec(1000, 9500),
               _mrec(1000, 9200, source="rerun")])
    rc, lines = history.check(path=p)
    assert rc == 0, lines
    assert any("text bulk merge" in ln and "OK" in ln for ln in lines)


def test_merge_gate_flags_regression(tmp_path):
    p = str(tmp_path / "h.jsonl")
    _write(p, [_mrec(1000, 9000), _mrec(1000, 9500),
               _mrec(1000, 3000, source="regressed")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("MERGE REGRESSION" in ln for ln in lines)


def test_merge_gate_first_run_and_absent_config_skip_cleanly(tmp_path):
    p = str(tmp_path / "h.jsonl")
    # no prior config-10 history: informational line, rc 0
    _write(p, [_rec(1000), _mrec(1000, 9000, source="first")])
    rc, lines = history.check(path=p)
    assert rc == 0, lines
    assert any("comparison starts next run" in ln
               for ln in lines if "merge" in ln)
    # run without config 10 against merge-carrying history: no gate line
    _write(p, [_mrec(1000, 9000), _rec(1000, source="no-cfg10")])
    rc, lines = history.check(path=p)
    assert rc == 0, lines
    assert not any("text bulk merge" in ln for ln in lines)


def test_merge_gate_is_host_scoped(tmp_path):
    """A big-host record must not set the bar for a small-host run."""
    p = str(tmp_path / "h.jsonl")
    big = {"cpus": 32, "machine": "x86_64"}
    small = {"cpus": 2, "machine": "x86_64"}
    _write(p, [_mrec(1000, 90000, host=big), _mrec(1000, 90000, host=big),
               _mrec(1000, 9000, source="small-host", host=small)])
    rc, lines = history.check(path=p)
    assert rc == 0, lines   # no same-host history -> skip, not fail


def test_flatness_gate_ok_and_ceiling(tmp_path):
    p = str(tmp_path / "h.jsonl")

    def frec(flat, source="test"):
        return _rec(1000, source=source,
                    configs={"7": {"keystroke_flatness": flat,
                                   "ms_per_keystroke": 0.3}})

    _write(p, [frec(1.0), frec(1.1, source="ok")])
    rc, lines = history.check(path=p)
    assert rc == 0, lines
    assert any("keystroke flatness" in ln and "OK" in ln for ln in lines)

    _write(p, [frec(1.0), frec(1.8, source="regressed")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("FLATNESS REGRESSION" in ln for ln in lines)

    # records without config 7 never produce the line
    _write(p, [frec(1.0), _rec(1000, source="no-cfg7")])
    rc, lines = history.check(path=p)
    assert rc == 0
    assert not any("keystroke flatness" in ln for ln in lines)


def test_norm_configs_carries_span_plane_fields():
    rec = {"backend": "cpu", "value": 10, "configs": {
        "7": {"speedup": 1.1, "ms_per_keystroke": 0.31,
              "keystroke_flatness": 1.05},
        "10": {"speedup": 40.0, "merge_ops_per_s": 9100,
               "merge_speedup_vs_perop": 3.1,
               "merge_speedup_vs_replay": 41.5,
               "span_merge_s": 1.2, "perop_merge_s": 3.8}}}
    out = history.record_from_bench(rec)
    assert out["configs"]["7"]["keystroke_flatness"] == 1.05
    assert out["configs"]["7"]["ms_per_keystroke"] == 0.31
    assert out["configs"]["10"]["merge_ops_per_s"] == 9100
    assert out["configs"]["10"]["merge_speedup_vs_perop"] == 3.1
    assert out["configs"]["10"]["span_merge_s"] == 1.2


def test_ledger_gate_budget_ok_over_and_absent(tmp_path):
    """Config-12 doc-ledger duty-cycle gate (LEDGER_BUDGET_PCT): absolute
    budget like the scrape gate — over fails, under passes, runs without
    config 12 skip cleanly."""
    p = str(tmp_path / "h.jsonl")

    def lrec(pct, source="test"):
        return _rec(1000, source=source,
                    configs={"12": {"ledger_overhead_pct": pct,
                                    "redundancy_ratio": 1.8,
                                    "redundancy_floor": 1.0,
                                    "doc_lag_p99_s": 0.09,
                                    "explain_attributed": 1}})

    _write(p, [lrec(0.5), lrec(0.9, source="ok")])
    rc, lines = history.check(path=p)
    assert rc == 0, lines
    assert any("doc-ledger duty cycle" in ln and "OK" in ln
               for ln in lines)
    assert any("mesh redundancy x1.8" in ln and "floor 1.0" in ln
               for ln in lines)
    assert any("explain attribution OK" in ln for ln in lines)

    _write(p, [lrec(0.5), lrec(3.7, source="heavy")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("LEDGER OVER BUDGET" in ln for ln in lines)

    _write(p, [lrec(0.5), _rec(1000, source="no-cfg12")])
    rc, lines = history.check(path=p)
    assert rc == 0
    assert not any("doc-ledger" in ln for ln in lines)


def test_norm_configs_carries_doc_obs_fields():
    rec = {"backend": "cpu", "value": 10, "configs": {
        "12": {"doc_lag_p50_s": 0.0, "doc_lag_p99_s": 0.09,
               "doc_lag_max_s": 0.13, "redundancy_ratio": 1.85,
               "redundancy_floor": 1.0, "ledger_overhead_pct": 0.56,
               "explain_attributed": 1, "mesh_nodes": 4,
               "redundancy_note": "dropped (string, non-numeric keys "
                                  "only ride the detail sidecar)"}}}
    out = history.record_from_bench(rec)
    c12 = out["configs"]["12"]
    assert c12["doc_lag_p99_s"] == 0.09
    assert c12["redundancy_ratio"] == 1.85
    assert c12["redundancy_floor"] == 1.0
    assert c12["ledger_overhead_pct"] == 0.56
    assert c12["explain_attributed"] == 1
    assert c12["mesh_nodes"] == 4


def test_sub_relay_gates_ok_over_and_absent(tmp_path):
    """Config-13 partial-replication gates: growth exponent, bytes/sub
    ceiling vs the flat baseline, relay redundancy, subscribed-doc SLO,
    backfill — all absolute; runs without config 13 skip cleanly."""
    p = str(tmp_path / "h.jsonl")

    def srec(exp=0.74, frac=0.18, red=0.0, p99=0.07, bf=1,
             source="test"):
        return _rec(1000, source=source,
                    configs={"13": {"fanout_growth_exponent": exp,
                                    "fanout_vs_mesh_fraction": frac,
                                    "sub_redundancy_ratio": red,
                                    "sub_converge_p99_s": p99,
                                    "sub_backfill_ok": bf}})

    _write(p, [srec(), srec(source="ok")])
    rc, lines = history.check(path=p)
    assert rc == 0, lines
    assert any("relay fan-out growth" in ln and "OK" in ln
               for ln in lines)
    assert any("bytes/subscriber vs flat baseline" in ln and "OK" in ln
               for ln in lines)
    assert any("relay redundancy ratio" in ln and "OK" in ln
               for ln in lines)
    assert any("subscribed-doc converge p99" in ln and "OK" in ln
               for ln in lines)
    assert any("late-subscribe backfill: OK" in ln for ln in lines)

    _write(p, [srec(), srec(exp=1.02, source="linear")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("FAN-OUT NOT SUBLINEAR" in ln for ln in lines)

    _write(p, [srec(), srec(frac=0.8, source="fat")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("FAN-OUT OVER MESH CEILING" in ln for ln in lines)

    _write(p, [srec(), srec(red=1.5, source="dup")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("RELAY REDUNDANCY OVER BUDGET" in ln for ln in lines)

    _write(p, [srec(), srec(p99=3.0, source="slow")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("SUBSCRIBED-DOC SLO BREACH" in ln for ln in lines)

    _write(p, [srec(), srec(bf=0, source="nofill")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("late-subscribe backfill: MISS" in ln for ln in lines)

    _write(p, [srec(), _rec(1000, source="no-cfg13")])
    rc, lines = history.check(path=p)
    assert rc == 0
    assert not any("relay fan-out" in ln for ln in lines)


def test_norm_configs_carries_sub_relay_fields():
    rec = {"backend": "cpu", "value": 10, "configs": {
        "13": {"fanout_bytes_per_sub": 6662.5,
               "mesh_bytes_per_sub": 36847.0,
               "fanout_vs_mesh_fraction": 0.18,
               "fanout_growth_exponent": 0.735,
               "sub_redundancy_ratio": 0.0,
               "sub_converge_p99_s": 0.066,
               "sub_slo_bound_s": 2.0,
               "sub_backfill_ok": 1,
               "backfill": {"dropped": "(dict fields only ride the "
                                       "detail sidecar)"}}}}
    out = history.record_from_bench(rec)
    c13 = out["configs"]["13"]
    assert c13["fanout_growth_exponent"] == 0.735
    assert c13["fanout_vs_mesh_fraction"] == 0.18
    assert c13["mesh_bytes_per_sub"] == 36847.0
    assert c13["sub_redundancy_ratio"] == 0.0
    assert c13["sub_converge_p99_s"] == 0.066
    assert c13["sub_backfill_ok"] == 1
    assert "backfill" not in c13


def test_remed_gates_ok_over_and_absent(tmp_path):
    """Config-14 remediation gates: MTTR budget, recovered-class floor,
    steady-state duty cycle, dry-run cleanliness — all absolute, each
    judged independently; runs without config 14 skip cleanly."""
    p = str(tmp_path / "h.jsonl")

    def rrec(mttr=6.2, classes=4, ovh=0.4, dry=1, source="test"):
        return _rec(1000, source=source,
                    configs={"14": {"mttr_max_s": mttr,
                                    "fault_classes_injected": 4,
                                    "fault_classes_recovered": classes,
                                    "remed_overhead_pct": ovh,
                                    "remed_dry_run_clean": dry}})

    _write(p, [rrec(), rrec(source="ok")])
    rc, lines = history.check(path=p)
    assert rc == 0, lines
    assert any("remediation MTTR" in ln and "OK" in ln for ln in lines)
    assert any("remediation classes recovered: 4/4" in ln and "OK" in ln
               for ln in lines)
    assert any("remediation duty cycle" in ln and "OK" in ln
               for ln in lines)
    assert any("remediation dry-run: OK" in ln for ln in lines)

    _write(p, [rrec(), rrec(mttr=45.0, source="slow-heal")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("MTTR OVER BUDGET" in ln for ln in lines)

    _write(p, [rrec(), rrec(classes=2, source="half-healed")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("TOO FEW CLASSES RECOVERED" in ln for ln in lines)

    _write(p, [rrec(), rrec(ovh=3.1, source="heavy")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("REMEDIATION OVER BUDGET" in ln for ln in lines)

    _write(p, [rrec(), rrec(dry=0, source="wet-run")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("EXECUTED SOMETHING" in ln for ln in lines)

    # a record missing only the MTTR must not vacate the other gates
    bad = rrec(ovh=3.1, source="partial")
    del bad["configs"]["14"]["mttr_max_s"]
    _write(p, [rrec(), bad])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("REMEDIATION OVER BUDGET" in ln for ln in lines)

    _write(p, [rrec(), _rec(1000, source="no-cfg14")])
    rc, lines = history.check(path=p)
    assert rc == 0
    assert not any("remediation" in ln for ln in lines)


def test_norm_configs_carries_remed_fields():
    rec = {"backend": "cpu", "value": 10, "configs": {
        "14": {"mttr_max_s": 6.2, "mttr_mean_s": 4.1,
               "mttr_budget_s": 30.0,
               "fault_classes_injected": 4,
               "fault_classes_recovered": 4,
               "remed_overhead_pct": 0.4,
               "remed_tick_p50_s": 0.0016,
               "remed_dry_run_clean": 1,
               "remed_actions_total": 2,
               "reconnects_total": 3,
               "faults": {"dropped": "(dict fields ride the detail "
                                     "sidecar only)"}}}}
    out = history.record_from_bench(rec)
    c14 = out["configs"]["14"]
    assert c14["mttr_max_s"] == 6.2
    assert c14["mttr_budget_s"] == 30.0
    assert c14["fault_classes_recovered"] == 4
    assert c14["remed_overhead_pct"] == 0.4
    assert c14["remed_dry_run_clean"] == 1
    assert c14["reconnects_total"] == 3
    assert "faults" not in c14


def test_move_gates_ok_over_and_absent(tmp_path):
    """Config-16 move-plane gates: atom-vs-emulation byte ratios (wire +
    archive), batched-resolution direction, kernel/pallas parity and the
    two-replica storm verdict — all absolute, each judged independently;
    runs without config 16 skip cleanly."""
    p = str(tmp_path / "h.jsonl")

    def mrec(wire=6.7, arch=6.9, spd=196.0, kpar=1, ppar=1, conv=1,
             source="test"):
        return _rec(1000, source=source,
                    configs={"16": {"move_wire_ratio_x": wire,
                                    "move_archive_ratio_x": arch,
                                    "move_resolve_speedup_x": spd,
                                    "move_storm_moves": 1536,
                                    "move_kernel_parity": kpar,
                                    "move_pallas_parity": ppar,
                                    "move_storm_converged": conv}})

    _write(p, [mrec(), mrec(source="ok")])
    rc, lines = history.check(path=p)
    assert rc == 0, lines
    assert any("move-as-atom wire-frame" in ln and "OK" in ln
               for ln in lines)
    assert any("move-as-atom archived-log" in ln and "OK" in ln
               for ln in lines)
    assert any("batched move resolution" in ln and "OK" in ln
               for ln in lines)
    assert any("move host/XLA parity: OK" in ln for ln in lines)
    assert any("move pallas parity: OK" in ln for ln in lines)
    assert any("move two-replica storm convergence: OK" in ln
               for ln in lines)

    _write(p, [mrec(), mrec(wire=3.0, source="fat-wire")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("MOVE NOT BEATING DELETE+REINSERT" in ln for ln in lines)

    _write(p, [mrec(), mrec(spd=0.8, source="slow-batch")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("BATCHED RESOLUTION NOT FASTER" in ln for ln in lines)

    _write(p, [mrec(), mrec(ppar=0, source="diverged")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("move pallas parity: FAILED" in ln for ln in lines)

    # a record missing only the wire ratio must not vacate the others
    bad = mrec(conv=0, source="partial")
    del bad["configs"]["16"]["move_wire_ratio_x"]
    _write(p, [mrec(), bad])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("move two-replica storm convergence: FAILED" in ln
               for ln in lines)

    _write(p, [mrec(), _rec(1000, source="no-cfg16")])
    rc, lines = history.check(path=p)
    assert rc == 0
    assert not any("move" in ln for ln in lines)


def test_norm_configs_carries_move_fields():
    rec = {"backend": "cpu", "value": 10, "configs": {
        "16": {"move_wire_ratio_x": 6.73, "move_archive_ratio_x": 6.93,
               "move_atom_ops_per_s": 2287.8,
               "reorder_ops_per_s": 3594.8,
               "move_resolve_speedup_x": 196.03,
               "move_batch_resolve_s": 0.058,
               "move_perop_resolve_s": 11.35,
               "move_storm_moves": 1536,
               "move_cycles_dropped": 2,
               "move_kernel_parity": True,
               "move_pallas_parity": True,
               "move_storm_converged": True,
               "protocol": "(string fields ride the detail sidecar)"}}}
    out = history.record_from_bench(rec)
    c16 = out["configs"]["16"]
    assert c16["move_wire_ratio_x"] == 6.73
    assert c16["move_archive_ratio_x"] == 6.93
    assert c16["move_resolve_speedup_x"] == 196.03
    assert c16["move_storm_moves"] == 1536
    assert c16["move_kernel_parity"] is True
    assert c16["move_storm_converged"] is True
    assert "protocol" not in c16  # prose rides the detail sidecar only


def test_trace_gates_ok_over_and_absent(tmp_path):
    """Config-19 trace-plane gates: duty-cycle budget, sampled-trace
    completeness floor, stage-sum-vs-e2e reconciliation bound and the
    unset-path parity verdict — all absolute, each judged
    independently; runs without config 19 skip cleanly."""
    p = str(tmp_path / "h.jsonl")

    def trec(duty=0.1, comp=100.0, serr=2.3, par=1, source="test"):
        return _rec(1000, source=source,
                    configs={"19": {"trace_ledger_overhead_pct": duty,
                                    "trace_completeness_pct": comp,
                                    "trace_stage_sum_err_pct": serr,
                                    "trace_disabled_parity": par,
                                    "trace_crit_p50_s": 0.12,
                                    "trace_crit_p99_s": 1.18,
                                    "trace_stitched": 47}})

    _write(p, [trec(), trec(source="ok")])
    rc, lines = history.check(path=p)
    assert rc == 0, lines
    assert any("trace-plane duty cycle" in ln and "OK" in ln
               for ln in lines)
    assert any("trace completeness" in ln and "OK" in ln for ln in lines)
    assert any("trace stage-sum vs e2e lag" in ln and "OK" in ln
               for ln in lines)
    assert any("trace-plane unset-path parity: OK" in ln for ln in lines)
    assert any("trace critical-path baseline" in ln
               and "47 stitched across the wire" in ln for ln in lines)

    _write(p, [trec(), trec(duty=3.4, source="heavy")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("TRACE PLANE OVER BUDGET" in ln for ln in lines)

    _write(p, [trec(), trec(comp=91.0, source="leaky")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("SAMPLED TRACES LOST MID-LIFECYCLE" in ln for ln in lines)

    _write(p, [trec(), trec(serr=11.5, source="gappy")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("STAGES DO NOT RECONCILE WITH E2E LAG" in ln
               for ln in lines)

    _write(p, [trec(), trec(par=0, source="tainted")])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("trace-plane unset-path parity: DIVERGED" in ln
               for ln in lines)

    # a record missing only the duty figure must not vacate the others
    bad = trec(comp=91.0, source="partial")
    del bad["configs"]["19"]["trace_ledger_overhead_pct"]
    _write(p, [trec(), bad])
    rc, lines = history.check(path=p)
    assert rc == 1
    assert any("SAMPLED TRACES LOST MID-LIFECYCLE" in ln for ln in lines)

    _write(p, [trec(), _rec(1000, source="no-cfg19")])
    rc, lines = history.check(path=p)
    assert rc == 0
    assert not any("trace" in ln for ln in lines)


def test_norm_configs_carries_trace_fields():
    rec = {"backend": "cpu", "value": 10, "configs": {
        "19": {"trace_sampled": 51, "trace_completed": 51,
               "trace_stitched": 47,
               "trace_completeness_pct": 100.0,
               "trace_stage_sum_err_pct": 2.5,
               "trace_ledger_overhead_pct": 0.074,
               "trace_disabled_parity": 1,
               "trace_crit_p50_s": 0.118, "trace_crit_p99_s": 1.183,
               "trace_stages": {"dropped": "(dict fields ride the "
                                           "detail sidecar only)"}}}}
    out = history.record_from_bench(rec)
    c19 = out["configs"]["19"]
    assert c19["trace_sampled"] == 51
    assert c19["trace_stitched"] == 47
    assert c19["trace_completeness_pct"] == 100.0
    assert c19["trace_stage_sum_err_pct"] == 2.5
    assert c19["trace_ledger_overhead_pct"] == 0.074
    assert c19["trace_disabled_parity"] == 1
    assert c19["trace_crit_p99_s"] == 1.183
    assert "trace_stages" not in c19
