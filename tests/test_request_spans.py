"""One phase substrate on the profiler's clock (utils/perfscope.py +
utils/metrics.py): every layer boundary of a served request is a phase,
the phases partition the blocking thread's time, each holds a profiler
annotation of its own name, and all spans of one request share a trace id,
across the flusher thread too. On the CPU, with the service steered onto
the road the chip takes (eager dispatch) as tests/test_chip_smoke_stages.py
does."""

import os
import sys

import pytest

import jax

from automerge_tpu.core.change import Change, Op
from automerge_tpu.core.ids import ROOT_ID
from automerge_tpu.sync.service import EngineDocSet
from automerge_tpu.sync.sharded_service import ShardedEngineDocSet
from automerge_tpu.utils import metrics, perfscope

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
for _p in (os.path.join(BENCH, "tests"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# the benchmark's own: a copy of its data files at a tiny size, the link
# prices at which a tiny round takes the chip's route, and the steering of
# a service onto the chip's eager dispatch
from test_benchmark import cpu_link, eager, run_tiny, tiny  # noqa: E402,F401
from test_benchmark_suite import (  # noqa: E402,F401
    _no_full_collection_inside_a_window as quiet_collector)

N_DOCS = 2000
ROUND_DOCS = 800        # a minority of the fleet: the lane or fused route
FLUSH_PHASES = ("encode", "commit", "route", "pack", "upload", "dispatch",
                "readback", "publish")
PHASE_NAMES = set(FLUSH_PHASES) | {"admit", "commit_wait", "device_wait"}
ROUNDS = "test_storm_rounds"    # the test's own annotation around them
REPEATS = 3     # of a traced round: a preempted thread leaves time unnamed


def change(svc, doc: int):
    """The next one-op change of document `doc` by the actor `storm`."""
    seq = svc.seqs[doc] = svc.seqs.get(doc, 0) + 1
    return [Change(actor="storm", seq=seq, deps={},
                   ops=[Op("set", ROOT_ID, key="n", value=seq * 1000 + doc)])]


def edit(svc, doc: int):
    """A single ingest: its own request and its own flush."""
    svc.apply_changes(f"d{doc}", change(svc, doc))


def storm_round(svc, n_docs: int = ROUND_DOCS):
    with svc.batch():
        for d in range(n_docs):
            svc.apply_changes(f"d{d}", change(svc, d))


def load(svc):
    eager(svc)
    svc.seqs = {}
    storm_round(svc, N_DOCS)
    svc.hashes()
    return svc


SERVICES = {
    "epoch": lambda: EngineDocSet(backend="rows", ingest_mode="epoch"),
    "locked": lambda: EngineDocSet(backend="rows", ingest_mode="locked"),
    "sharded": lambda: ShardedEngineDocSet(n_shards=2),
}


@pytest.fixture(params=sorted(SERVICES))
def svc(request):
    s = load(SERVICES[request.param]())
    s.kind = request.param
    metrics.reset()
    yield s
    s.close()


def phase_counts() -> dict:
    return {n: row["count"] for n, row in
            (metrics.snapshot().get("perf") or {}).get("phases", {}).items()}


def test_every_phase_is_entered(svc):
    storm_round(svc)
    got = phase_counts()
    for name in ("admit",) + FLUSH_PHASES + ("device_wait",):
        assert got.get(name, 0) >= 1, (name, got)
    # one entry a batch: its body is the round's admission, and a call
    # inside it enters no phase (ROUND_DOCS of them before PR 40)
    assert got["admit"] == 1
    assert "commit_wait" not in got         # a batch parks on no ticket

    metrics.reset()
    edit(svc, 7)
    svc.hashes()                            # the read waits for the device
    got = phase_counts()
    want = ["admit", "encode", "commit", "upload", "dispatch", "publish",
            "readback", "device_wait"]
    if svc.kind != "locked":
        want.append("commit_wait")          # the caller parked on a ticket
    for name in want:
        assert got.get(name, 0) >= 1, (name, got)
    assert got["admit"] == 2                # wire columns, then the append


def request_traces() -> dict:
    """{trace id: [span names]} of the ring, for the traces that hold a
    `sync_request`; plus the flush spans that belong to none."""
    by_tid: dict = {}
    for s in metrics.recent_spans():
        by_tid.setdefault(s["trace_id"], []).append(s)
    roots = {tid: spans for tid, spans in by_tid.items()
             if any(s["name"] == "sync_request" for s in spans)}
    stray = [s for tid, spans in by_tid.items() if tid not in roots
             for s in spans
             if s["name"] in ("sync_round_flush", "rows_round_apply")]
    return roots, stray


def test_spans_of_a_request_share_its_trace_id(svc):
    storm_round(svc)
    for k in range(5):
        edit(svc, k)
    roots, stray = request_traces()
    assert len(roots) == 6 and not stray, (sorted(roots), stray)
    n_flushes = 2 if svc.kind == "sharded" else 1
    for spans in roots.values():
        names = [s["name"] for s in spans]
        assert names.count("sync_request") == 1
        root = next(s for s in spans if s["name"] == "sync_request")
        batch = root["tags"]["docs"] > 1
        want = ({"docs": ROUND_DOCS, "ops": ROUND_DOCS}
                if batch else {"docs": 1, "ops": 1})
        if batch and svc.kind == "sharded":
            want["shards"] = 2              # both had work (PR 29)
        assert root["tags"] == want
        assert names.count("sync_round_flush") == (n_flushes if batch else 1)
        assert names.count("rows_round_apply") == (n_flushes if batch else 1)
        if svc.kind != "locked" and not batch:
            # the flusher thread did the work, under the caller's id
            flush = next(s for s in spans if s["name"] == "sync_round_flush")
            assert flush["thread"] != root["thread"]
            assert flush["parent_span_id"] == root["span_id"]
            assert flush["tags"]["riders"] == 1
        if svc.kind == "sharded":
            assert all(s["labels"].get("shard") is not None for s in spans
                       if s["name"] == "sync_round_flush")


def test_pipelined_ingress_joins_the_callers_trace():
    """apply_columns_async returns before the flush, so it opens no root;
    the ticket still carries the caller's context to the flusher."""
    from automerge_tpu.native.wire import changes_to_columns
    svc = EngineDocSet(backend="rows", ingest_mode="epoch")
    try:
        svc.seqs = {}
        edit(svc, 0)
        metrics.reset()
        with metrics.trace("sync_msg_serve") as outer:
            svc.apply_columns_async(
                "d0", changes_to_columns(change(svc, 0))).wait()
        flush = [s for s in metrics.recent_spans()
                 if s["name"] == "sync_round_flush"]
        assert [s["trace_id"] for s in flush] == [outer.trace_id]
        assert not [s for s in metrics.recent_spans()
                    if s["name"] == "sync_request"]
    finally:
        svc.close()


_FORK = """
import os
from automerge_tpu.utils import metrics
with metrics.trace("a") as mine:
    pass
r, w = os.pipe()
if os.fork() == 0:
    with metrics.trace("a") as s:
        pass
    os.write(w, f"{s.trace_id} {s.span_id}".encode())
    os._exit(0)
os.wait()
with metrics.trace("a") as after:
    pass
print(mine.trace_id, mine.span_id, after.trace_id, after.span_id,
      os.read(r, 99).decode())
"""


def test_a_forked_child_draws_ids_of_its_own():
    """Ids are a prefix drawn once a process plus a counter: a child
    forked after the import would repeat its parent's, in one merged
    timeline, if it kept the prefix and the counter it inherited."""
    import subprocess
    out = subprocess.run(
        [sys.executable, "-c", _FORK], check=True, capture_output=True,
        text=True, cwd=os.path.dirname(BENCH),
        env=dict(os.environ, JAX_PLATFORMS="cpu")).stdout.split()
    tid, sid, tid2, sid2, child_tid, child_sid = out
    assert (len(tid), len(sid)) == (24, 16)     # 8 and 4 random bytes
    assert tid[:16] == tid2[:16] and tid != tid2    # one prefix a process
    assert sid[:8] == sid2[:8] == tid[:8]
    assert child_tid[:16] != tid[:16] and child_sid[:8] != sid[:8]


# -- the phases on the profiler's clock -------------------------------------


@pytest.fixture(scope="module")
def host_lines(tmp_path_factory):
    """One short profiler capture of a storm round, five single ingests and
    a hash read on an epoch-mode service, then a round on the sharded one:
    [[name, start_ns, duration_ns]] for each thread line of the host
    plane (threads may share a line's name)."""
    import tracefile

    one = load(SERVICES["epoch"]())
    many = load(SERVICES["sharded"]())
    for warm in (one, many):                # compiles stay out of the trace
        storm_round(warm)
        edit(warm, 1)
        warm.hashes()
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        for _ in range(REPEATS):
            with jax.profiler.TraceAnnotation(ROUNDS):
                storm_round(one)
        for k in range(5):
            edit(one, k)
        one.hashes()
        for _ in range(REPEATS):
            with jax.profiler.TraceAnnotation(ROUNDS):
                storm_round(many)
    finally:
        jax.profiler.stop_trace()
        one.close()
        many.close()
    data = tracefile.from_xplane(tracefile.newest_xplane(trace_dir))
    lines = []
    for pl in data["planes"]:
        if not pl["name"].startswith("/host:"):
            continue
        for ln in pl["lines"]:
            events = [ev[:3] for ev in ln["events"]
                      if ev[0] in PHASE_NAMES or ev[0] == ROUNDS
                      or ev[0].startswith(("sync_", "rows_"))]
            if events:
                lines.append(events)
    return lines


def test_a_phase_holds_an_annotation_of_its_own_name(host_lines):
    seen = {ev[0] for events in host_lines for ev in events}
    assert PHASE_NAMES <= seen, PHASE_NAMES - seen
    # the metrics.trace parents are there under the name their timer has
    assert any(n.startswith("sync_request") for n in seen)
    assert any(n.startswith("sync_round_flush") for n in seen)
    assert "rows_round_apply" in seen


def test_phases_are_a_partition_of_the_flushing_thread(host_lines):
    """No phase nests in another but `device_wait` in `readback`, and
    inside the `sync_round_flush` of a storm round the phases leave at most
    a tenth of the time without a name. (A single ingest's flush is 1.5 ms
    here, a fifth of it the fixed cost around a dispatch that no phase
    claims: held to a half.) The named share is the best of each kind of
    flush: beside five other test workers a thread is preempted between
    two phases now and then, which only ever takes from it."""
    rounds = [(s, s + d) for events in host_lines for n, s, d in events
              if n == ROUNDS]
    named: dict = {}        # {(span name with labels, in a round): [share]}
    for events in host_lines:
        phases = sorted((ev for ev in events if ev[0] in PHASE_NAMES),
                        key=lambda ev: (ev[1], -ev[2]))
        open_until = []         # [(name, end)] of the phases still open
        for name, start, dur in phases:
            open_until = [(n, e) for n, e in open_until if e > start]
            for outer, _end in open_until:
                assert (outer, name) == ("readback", "device_wait"), (
                    f"{name} nests in {outer}")
            open_until.append((name, start + dur))
        for fname, fstart, fdur in events:
            if not fname.startswith("sync_round_flush"):
                continue
            inside = sum(d for n, s, d in phases if n != "device_wait"
                         and s >= fstart and s + d <= fstart + fdur)
            in_round = any(a <= fstart < b for a, b in rounds)
            named.setdefault((fname, in_round), []).append(inside / fdur)
    # the storm rounds and five single ingests on the single service, the
    # rounds over two shards on the other
    assert sorted(len(v) for v in named.values()) == [REPEATS] * 3 + [5]
    for (fname, in_round), shares in named.items():
        assert max(shares) >= (0.9 if in_round else 0.5), (fname, shares)


def test_a_storm_flush_leaves_two_milliseconds_unnamed_at_most(host_lines):
    """The same partition in milliseconds: what no phase claims of a storm
    round's `sync_round_flush` (guards, spans, ledger scopes, the glue
    between the engine's phases) is a fixed cost of about a millisecond
    and a half here, whatever the phases around it take. A share moves
    when the named work shrinks; this bound moves only when unnamed work
    grows. The least of each kind of flush, for the reason above."""
    rounds = [(s, s + d) for events in host_lines for n, s, d in events
              if n == ROUNDS]
    unnamed: dict = {}      # {span name with labels: [ms in no phase]}
    for events in host_lines:
        for fname, fstart, fdur in events:
            if not (fname.startswith("sync_round_flush")
                    and any(a <= fstart < b for a, b in rounds)):
                continue
            inside = sum(d for n, s, d in events if n in PHASE_NAMES
                         and n != "device_wait"
                         and s >= fstart and s + d <= fstart + fdur)
            unnamed.setdefault(fname, []).append((fdur - inside) / 1e6)
    assert sorted(len(v) for v in unnamed.values()) == [REPEATS] * 3
    for fname, ms in unnamed.items():
        assert 0.0 < min(ms) <= 2.0, (fname, ms)


# -- counts where the work happens ------------------------------------------


@pytest.mark.parametrize("mode", ["epoch", "locked"])
def test_no_ack_before_the_flush_is_counted(mode, monkeypatch):
    """The service counts a round's ops before it releases the riders: the
    benchmark's driver, reading `sync_ops_ingested` right after each return
    with no grace read, finds every request of 50 single ingests flushed."""
    import fleet as fleetlib
    import run
    import traffic

    rounds = run.load_by_path("drivers", "rounds")
    monkeypatch.setattr(rounds, "GRACE_READS", 0)
    config = fleetlib.load_json("configs", "fleet10k")
    config["fleet"].update(n_small=60, n_heavy=1, heavy_ops=20, n_list=1,
                           n_text=1, n_move=1, load_batch=30,
                           history_changes_max=1, history_cap=128)
    mix = fleetlib.load_json("traffic", "edits")
    fleet = fleetlib.make_fleet(fleetlib.FleetSpec.from_config(config), 11)
    schedule = traffic.Schedule(mix, len(fleet.small),
                                len(fleetlib.SMALL_KEYS), 11)
    svc = EngineDocSet(backend="rows", ingest_mode=mode)
    try:
        fleetlib.apply_round(svc, fleet.first)
        for round_ in fleetlib.small_load_rounds(fleet, 11):
            fleetlib.apply_round(svc, round_)
        window = rounds.run(svc, fleet, schedule, first=0, max_requests=50)
    finally:
        svc.close()
    reqs = window["requests"]
    assert len(reqs) == 50 and all(q.error is None for q in reqs)
    assert sum(not q.flushed for q in reqs) == 0    # acks_before_flush


# -- the per-layer metrics that read the phases -----------------------------

NEW_METRICS = {
    "fleet10k.storm": {"admit_share", "encode_share", "commit_share",
                       "route_share", "upload_share", "device_wait_share",
                       "publish_share", "pack_share"},
    "fleet10k.edits": {"admit_share", "encode_share", "commit_share",
                       "upload_share", "publish_share",
                       "commit_wait_mean_ms", "block_apply_share"},
}


@pytest.mark.parametrize("cell", sorted(NEW_METRICS))
def test_the_new_metrics_read_a_value_in_a_tiny_traced_run(
        tiny, cpu_link, quiet_collector, cell):      # noqa: F811
    res = run_tiny(tiny, cell=cell, trace=1)
    assert res["correct"] is True
    got = {n: row["value"] for n, row in res["metrics"].items()}
    assert NEW_METRICS[cell] <= set(got), NEW_METRICS[cell] - set(got)
    assert all(got[n] > 0 for n in NEW_METRICS[cell]), got
    if cell == "fleet10k.storm":
        # reported, and 0 here: the router fuses the tiny fleet's rounds
        # (`fused_round_share` 100 %), so no lane is gathered on either
        # side; tests/test_apply_blocks.py holds the gather itself
        assert got["resident_gather_share"] == 100.0 - got[
            "fused_round_share"]
        # every round's frame was one pass over the batch's changes
        assert got["direct_frame_share"] == 100.0
    else:
        # a single ingest is converted at its call: not listed, not read
        assert "direct_frame_share" not in got
    shares = [got[n] for n in got if n.endswith("_share")
              and n not in ("fused_round_share", "block_apply_share",
                            "resident_gather_share", "device_wait_share",
                            "direct_frame_share")]
    assert sum(shares) <= 102.0, got      # a partition: nothing twice


def test_a_phase_metric_reads_zero_on_an_older_program_and_never_falls_silent():
    """benchmarks/readers/phase_share_since.py: a program from before the
    phases (the parent commit, measured with these files laid over it)
    spends no time in them and its traced run still gives a result; a
    program that has them and never enters one ends the run."""
    import run
    reader = run.load_by_path("readers", "phase_share_since")
    args = {"phase": "admit", "since": "sync_request_count"}
    older = {"window_s": 2.0, "delta": {"phase.pack": 1.0}}
    assert reader.read(args, older) == 0.0
    silent = {"window_s": 2.0, "delta": {"sync_request_count": 9}}
    assert reader.read(args, silent) is None
    live = {"window_s": 2.0,
            "delta": {"sync_request_count": 9, "phase.admit": 0.5}}
    assert reader.read(args, live) == 25.0
