"""Performance plane (utils/perfscope.py): compile telemetry, phase
attribution, memory gauges, and their embedding in snapshots and
flight-recorder post-mortems. CPU-only — compile events fire identically
on every backend (the jax.monitoring listener is backend-agnostic)."""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import automerge_tpu as am
from automerge_tpu import metrics
from automerge_tpu.utils import flightrec, perfscope


@pytest.fixture(autouse=True)
def _fresh_metrics():
    metrics.reset()
    yield
    metrics.reset()


def _fresh_kernel(scale):
    """A jitted fn whose compile cache starts empty (fresh closure per
    call, so prior tests cannot have warmed it)."""
    @jax.jit
    def k(x):
        return (x * scale + 1).sum()
    return k


# -- compile telemetry ------------------------------------------------------


def test_dispatch_compile_telemetry_rows():
    k = _fresh_kernel(3)
    metrics.dispatch_jit("pf_toy", k, jnp.arange(8))      # compile
    metrics.dispatch_jit("pf_toy", k, jnp.arange(8))      # cache hit
    metrics.dispatch_jit("pf_toy", k, jnp.arange(16))     # retrace
    snap = metrics.snapshot()
    assert snap["engine_kernels_dispatched{kernel=pf_toy}"] == 3
    # exact: the cached dispatch must NOT count as a retrace
    assert snap["engine_kernels_retraced{kernel=pf_toy}"] == 2
    row = snap["perf"]["kernels"]["pf_toy"]
    assert row["dispatches"] == 3 and row["compiles"] == 2
    assert row["compile_s"] > 0
    # the one-time XLA analysis: cost + memory rows, plus gauges
    assert row["cost"]["flops"] > 0
    assert row["cost"]["bytes_accessed"] > 0
    assert row["memory"]["argument"] > 0
    assert snap["engine_kernel_flops{kernel=pf_toy}"] > 0
    assert snap["engine_kernel_hbm_bytes{kernel=pf_toy,section=argument}"] > 0
    assert snap["engine_kernel_compile{kernel=pf_toy}_s"] > 0


def test_dispatch_attribution_is_thread_exact():
    """The r5-era cache-size delta misattributed concurrent dispatches;
    the listener attributes through a per-thread marker stack, so two
    threads compiling different kernels at once each get exactly their
    own retraces."""
    n_shapes = 4
    kernels = {"pf_a": _fresh_kernel(5), "pf_b": _fresh_kernel(7)}
    barrier = threading.Barrier(len(kernels))
    errs = []

    def worker(name, fn):
        try:
            barrier.wait()
            for s in range(n_shapes):
                metrics.dispatch_jit(name, fn, jnp.arange(8 + s))
                metrics.dispatch_jit(name, fn, jnp.arange(8 + s))  # hit
        except Exception as e:                # surfaces on the main thread
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(n, f))
               for n, f in kernels.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    snap = metrics.snapshot()
    for name in kernels:
        assert snap[f"engine_kernels_dispatched{{kernel={name}}}"] \
            == 2 * n_shapes
        assert snap[f"engine_kernels_retraced{{kernel={name}}}"] == n_shapes
        assert snap["perf"]["kernels"][name]["compiles"] == n_shapes


def test_non_jit_callable_degrades_gracefully():
    out = metrics.dispatch_jit("pf_plain", lambda x: x + 1, 41)
    assert out == 42
    snap = metrics.snapshot()
    assert snap["engine_kernels_dispatched{kernel=pf_plain}"] == 1
    assert "engine_kernels_retraced{kernel=pf_plain}" not in snap


def test_perf_section_resets_with_metrics():
    k = _fresh_kernel(11)
    metrics.dispatch_jit("pf_reset", k, jnp.arange(4))
    assert "perf" in metrics.snapshot()
    from automerge_tpu.utils.gcpause import gc_paused
    with gc_paused():       # a collection in between would be counted
        metrics.reset()
        assert metrics.snapshot() == {}
    # a post-reset dispatch still gets its cached analysis rows (the jit
    # cache survives reset; re-lowering+compiling per bench config would
    # double compile cost for nothing)
    metrics.dispatch_jit("pf_reset", k, jnp.arange(4))    # cache hit
    row = metrics.snapshot()["perf"]["kernels"]["pf_reset"]
    assert row["dispatches"] == 1 and row["compiles"] == 0
    assert "cost" in row and "memory" in row


# -- phase attribution ------------------------------------------------------


def test_phase_rollup_accumulates():
    with perfscope.phase("pack"):
        pass
    with perfscope.phase("pack"):
        with perfscope.phase("readback"):
            pass
    phases = metrics.snapshot()["perf"]["phases"]
    assert phases["pack"]["count"] == 2
    assert phases["readback"]["count"] == 1
    assert phases["pack"]["s"] >= 0


def test_phased_decorator():
    @perfscope.phased("sync_wire")
    def encode(x):
        return x * 2

    assert encode(3) == 6
    assert metrics.snapshot()["perf"]["phases"]["sync_wire"]["count"] == 1


# -- the real engine path (the acceptance-criteria shape) -------------------


def _tiny_rows_engine(n_docs=6):
    from automerge_tpu.engine.resident_rows import ResidentRowsDocSet

    doc_ids = [f"d{i}" for i in range(n_docs)]
    changes = {}
    for i, d in enumerate(doc_ids):
        s = am.change(am.init("A"), lambda doc, i=i: doc.__setitem__("n", i))
        changes[d] = s._doc.opset.get_missing_changes({})
    rset = ResidentRowsDocSet(doc_ids)
    rset.apply_rounds([changes])
    return rset


def test_every_dispatched_kernel_has_perf_rows():
    """The acceptance criterion: every kernel dispatched in a CPU run has
    compile-count, cost, and memory rows in metrics.snapshot()["perf"]."""
    rset = _tiny_rows_engine()
    rset.hashes()
    snap = metrics.snapshot()
    dispatched = {k.split("{kernel=")[1].rstrip("}")
                  for k in snap
                  if k.startswith("engine_kernels_dispatched{")}
    assert dispatched, "the rows engine dispatched nothing?"
    perf_kernels = snap["perf"]["kernels"]
    for kernel in dispatched:
        row = perf_kernels.get(kernel)
        assert row is not None, f"no perf row for dispatched {kernel!r}"
        assert row["dispatches"] >= 1
        assert "compiles" in row
        assert "cost" in row, f"{kernel!r} has no XLA cost analysis"
        assert "memory" in row, f"{kernel!r} has no XLA memory analysis"


def test_phases_cover_the_engine_round():
    from automerge_tpu.core.change import Change, Op
    from automerge_tpu.core.ids import ROOT_ID
    from automerge_tpu.sync.frames import encode_round_frame

    rset = _tiny_rows_engine()
    rset.hashes()
    # a round frame through the served path's engine entry: decode, encode
    # and commit on the host, the triplets' upload, the dispatch; the read
    # then waits for the device inside its readback
    rset.apply_round_frames([encode_round_frame({
        "d0": [Change(actor="B", seq=1, deps={},
                      ops=[Op("set", ROOT_ID, key="m", value=7)])]})])
    rset.hashes()
    phases = metrics.snapshot()["perf"]["phases"]
    for name in ("dispatch", "readback", "host_materialize", "sync_wire",
                 "encode", "commit", "upload", "device_wait"):
        assert phases[name]["count"] >= 1, (name, phases)
    assert phases["device_wait"]["s"] <= phases["readback"]["s"]
    assert set(phases) <= set(perfscope.PHASES)


def test_phase_totals_merge_threads_and_survive_their_exit():
    """A phase exit takes no lock: each thread accumulates into a dict of
    its own, and the readers merge them, the exited threads' too."""
    import threading

    def work():
        for _ in range(100):
            with perfscope.phase("pack"):
                pass

    for _ in range(3):      # three generations of short-lived threads
        ts = [threading.Thread(target=work) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
            assert not t.is_alive()
    with perfscope.phase("pack"):
        pass
    assert metrics.snapshot()["perf"]["phases"]["pack"]["count"] == 1201
    assert perfscope.phase_totals()["pack"] > 0
    # exited threads are folded away as later ones register, not kept
    # one dict each: at most the last generation is still listed
    assert sum(not s.thread.is_alive()
               for s in perfscope._store.slots) <= 4
    metrics.reset()
    assert "pack" not in perfscope.phase_totals()
    with perfscope.phase("pack"):       # this thread's dict was cleared in
        pass                            # place and still counts
    assert metrics.snapshot()["perf"]["phases"]["pack"]["count"] == 1


def test_a_reset_forgets_threads_that_exited_before_any_read():
    """An exited thread's slot is folded into the retired totals by the
    next reader; a reset that is that reader folds it before it clears."""
    import threading

    def work():
        with perfscope.phase("pack"):
            pass

    metrics.reset()
    t = threading.Thread(target=work)
    t.start()
    t.join()
    metrics.reset()
    assert "pack" not in perfscope.phase_totals()


def test_a_phase_is_a_profiler_annotation_of_its_own_name():
    import jax.profiler
    assert isinstance(perfscope.phase("pack"), jax.profiler.TraceAnnotation)


# -- memory gauges + flight-recorder embedding ------------------------------


def test_memory_gauges_present():
    rset = _tiny_rows_engine()
    rset.hashes()
    snap = metrics.snapshot()
    assert snap["rows_resident_bytes"] == rset.resident_bytes() > 0
    assert snap["obs_live_arrays_peak_bytes"] \
        >= snap["obs_live_arrays_bytes"] >= 0
    mem = snap["perf"]["memory"]
    assert mem["live_array_peak_bytes"] >= mem["live_array_bytes"]


def test_flightrec_dump_embeds_perf_plane(tmp_path):
    rset = _tiny_rows_engine()
    rset.hashes()
    path = flightrec.dump("perfscope-test", path=str(tmp_path / "dump.json"))
    assert path is not None
    doc = json.loads(open(path).read())
    m = doc["metrics"]
    assert "perf" in m and "kernels" in m["perf"]
    assert m["rows_resident_bytes"] > 0
    # the post-mortem carries the same compile telemetry the snapshot does
    assert any(v.get("dispatches", 0) >= 1
               for v in m["perf"]["kernels"].values())


def test_queue_bytes_gauge_tracks_causal_queue():
    # a change whose dependency never arrives parks in the causal queue
    from automerge_tpu.core.change import Change, Op
    from automerge_tpu.core.ids import ROOT_ID

    doc = am.init("X")
    orphan = Change(actor="Y", seq=2, deps={}, ops=[
        Op("set", ROOT_ID, key="k", value=1)])
    am.apply_changes(doc, [orphan])
    snap = metrics.snapshot()
    assert snap["core_queue_depth"] >= 1
    assert snap["core_queue_bytes"] > 0
