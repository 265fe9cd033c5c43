"""chip_smoke.py's stages at a small size on the CPU, so the smoke cannot rot
between chip runs. The chip takes the eager road (every flush dispatches) and
prices its routes with the product's link constants; the CPU tests reach
that road only where a test steers there, so the steering is here and not in
an option of the program: the eager pin (as bench config 20 does) and
CPU-scale link constants (conftest's `cpu_link`, as tests/test_megabatch.py
uses), which let a 64-document storm round ride the fused route the 10K
fleet takes on the chip.
"""

import importlib.util
import json
import pathlib
import sys

import pytest

import jax

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", str(ROOT / "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    # the smoke's checks read the process's totals (no fallback to host
    # truth "for the whole run", compile seconds of the load stage); a test
    # that ran before it in this worker may have left a rebuild on them, or
    # compiled the same shapes (the benchmark's tiny fleet is the smoke's)
    from automerge_tpu.utils import metrics
    metrics.reset()
    jax.clear_caches()
    return mod


def small_spec(smoke):
    return smoke.FleetSpec(n_small=64, n_heavy=2, heavy_ops=40, n_list=2,
                           n_text=2, n_move=1, load_batch=32, rounds=2,
                           draws_per_round=40, sample=16)


def eager(svc):
    for s in getattr(svc, "shards", [svc]):
        s._lazy_resolved = True
        s._resident.lazy_dispatch = False
    return svc


def stage_lines(capsys):
    return {r["stage"]: r for r in map(json.loads, (
        ln for ln in capsys.readouterr().out.splitlines()
        if ln.startswith("{"))) if "stage" in r}


def test_one_chip_stages(smoke, cpu_link, capsys):
    spec = small_spec(smoke)
    out = smoke.run_one_chip(spec, 3, eager(smoke.new_service()), None)
    lines = stage_lines(capsys)
    assert list(lines) == ["fleet", "load", "rounds", "peer", "hashes",
                           "parity", "kernels"]
    assert all(r["ok"] for r in lines.values())
    n_docs = spec.n_small + spec.n_heavy + spec.n_list + spec.n_text \
        + spec.n_move
    assert out["load"]["docs"] == n_docs
    assert out["parity"]["hash_equal"] == n_docs
    assert out["parity"]["oracle_equal"] >= spec.sample
    assert out["parity"]["rows_dispatch_failed"] == 0
    # the routes the chip takes: fused storm rounds, single apply_final
    rounds = lines["rounds"]
    assert rounds["plan_round_routes"][:spec.rounds] == \
        ["megabatch"] * spec.rounds
    assert rounds["dispatched"]["apply_final"] >= rounds["single_ingests"]
    assert lines["peer"]["sync_text_batches_merged"] >= 1
    assert lines["peer"]["peer_equal"] == lines["peer"]["subscribed"]
    # compile seconds come from the perfscope listener
    assert lines["load"]["compiles"]["reconcile_rows_hash"]["compile_s"] > 0


def test_four_chip_stages(smoke, cpu_link, capsys):
    devices = jax.devices()[:4]
    assert len(devices) == 4, "the conftest gives the CPU eight devices"
    spec = small_spec(smoke)
    out = smoke.run_four_chips(
        spec, 3, eager(smoke.new_sharded_service(devices)), devices)
    lines = stage_lines(capsys)
    assert list(lines) == ["fleet", "sharded_service", "sharded_mesh"]
    shards = out["sharded_service"]["shards"]
    assert [s["device"] for s in shards] == [str(d) for d in devices]
    for s in shards:
        assert s["rows_dev"] == s["outputs"] == [s["device"]]
    assert out["sharded_service"]["hash_equal"] == \
        out["sharded_service"]["docs_hashed"]
    assert out["sharded_mesh"]["outputs_on"] == \
        sorted(str(d) for d in devices)
    assert len(out["sharded_mesh"]["programs_equal"]) == 4


def test_device_stage_refuses_the_cpu(smoke):
    with pytest.raises(RuntimeError, match="needs a TPU"):
        smoke.stage_device(1)


def test_main_fails_at_stage_device_without_a_chip(smoke, capsys):
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert e.value.code == 1
    out = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert out == [{"stage": "device", "ok": False,
                    "error": out[0]["error"], "seconds": out[0]["seconds"]}]
    assert "needs a TPU" in out[0]["error"]


def test_a_failing_stage_names_itself_and_ends_the_run(smoke, capsys):
    def boom():
        raise ValueError("kernel refused")
    with pytest.raises(SystemExit) as e:
        smoke.run_stage("rounds", boom)
    assert e.value.code == 1
    cap = capsys.readouterr()
    rec = json.loads(cap.out.splitlines()[-1])
    assert rec["stage"] == "rounds" and rec["ok"] is False
    assert "kernel refused" in rec["error"]
    assert "Traceback" in cap.err


def test_rounds_check_refuses_a_run_without_device_work(smoke):
    before = smoke._counters()
    with pytest.raises(AssertionError, match="no device dispatch"):
        smoke.check_rounds(before, True)


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    from automerge_tpu.utils import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    # set: JAX reads the variable itself and the program sets nothing
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    assert compile_cache.configure() == str(tmp_path / "c")
    assert calls == []
    # unset: one fixed directory inside the checkout, the same every time
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR",
                        str(tmp_path / ".jax_cache"))
    assert compile_cache.configure() == compile_cache.configure() \
        == str(tmp_path / ".jax_cache")
    assert ("jax_compilation_cache_dir", str(tmp_path / ".jax_cache")) \
        in calls
    assert compile_cache.entries(str(tmp_path / ".jax_cache")) == 0
    (tmp_path / ".jax_cache" / "jit_f-abc-cache").write_bytes(b"x")
    assert compile_cache.entries(str(tmp_path / ".jax_cache")) == 1


def test_compile_cache_default_is_inside_the_checkout():
    from automerge_tpu.utils import compile_cache
    assert pathlib.Path(compile_cache.DEFAULT_DIR) == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
