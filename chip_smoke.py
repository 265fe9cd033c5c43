"""The served sync path, end to end, on the TPU: the quickest proof that the
system still starts on the chip.

    python chip_smoke.py              # one chip: the served path
    python chip_smoke.py --chips 4    # four chips: the sharded paths only

One chip: a 10,000-document `EngineDocSet(backend="rows")` (the fleet of
BASELINE.json config 5 / bench config 20: small map documents, eight heavy
400-op documents that set the fleet's caps, list, text and move documents)
is loaded and written to through `batch()` rounds (the megabatch route) and
single ingests (the `apply_final` route), serves one plain interpretive
`DocSet` peer over `TcpSyncServer` / `TcpSyncClient`, and is then checked
on results: every document's resident hash against `batchdoc.apply_batch`
(the XLA program) on the same change log, a seeded sample against the
interpretive oracle, the peer's documents against the server's, and every
acknowledged change against `missing_changes(doc, {})`.

Every stage prints one JSON line as it ends. A stage that raises prints its
traceback and its name, and the script exits 1. The last line of a passing
run is `{"ok": true, "device": {...}}` with the device as JAX reports it.
Without a TPU the script fails at stage `device`; nothing here passes
`interpret=` or picks a platform. Stage seconds are for orientation, never
a speed. The stages are plain functions so that
tests/test_chip_smoke_stages.py can call them at a small size on the CPU.
"""

from __future__ import annotations

import argparse
import json
import random
import sys
import time
import traceback
from dataclasses import dataclass, field

# ops per reference bucket: `apply_batch` pads every document of a batch to
# the largest, so the small documents are never reconciled with the heavy
# ones (10,000 x 512^2 pairwise joins is a memory fault, not a check). A
# bucket goes through in chunks of like-sized documents: the chip's compiler
# takes 1 s for a 1,024-document batch and 20 s for a 10,000-document one.
_REF_LADDER = (64, 512, 4096)
_REF_CHUNK = 1024


# ---------------------------------------------------------------------------
# stage plumbing


def emit(rec: dict) -> None:
    print(json.dumps(rec, sort_keys=True, default=str), flush=True)


def run_stage(name: str, fn, *args):
    """Run one stage, print its line, return its result. A stage that raises
    ends the run: the traceback goes to stderr, the stage's name to stdout."""
    t0 = time.perf_counter()
    before = _counters()
    try:
        out = fn(*args)
    except BaseException as e:
        traceback.print_exc()
        emit({"stage": name, "ok": False, "error": repr(e)[:400],
              "seconds": round(time.perf_counter() - t0, 2)})
        raise SystemExit(1)
    rec = {"stage": name, "ok": True,
           "seconds": round(time.perf_counter() - t0, 2)}
    rec.update(_counter_delta(before, _counters()))
    info = out if isinstance(out, dict) else {}
    rec.update({k: v for k, v in info.items() if not k.startswith("_")})
    emit(rec)
    return out


_WATCHED = ("engine_megabatch_rounds", "engine_megabatch_fallbacks",
            "engine_megabatch_docs", "sync_text_batches_merged",
            "rows_dispatch_failed", "rows_log_rebuilt",
            "rows_engine_poisoned", "sync_rounds_flushed",
            "sync_ops_ingested", "sync_msgs_sent")


def _counters() -> dict:
    """The counters that prove the device did the work, read from the
    package's metrics registry (empty before the package is imported)."""
    if "automerge_tpu" not in sys.modules:
        return {}
    from automerge_tpu.utils import metrics
    snap = metrics.snapshot()
    out = {"dispatched": {}, "compiles": {}}
    for k, v in snap.items():
        if k.startswith("engine_kernels_dispatched{kernel="):
            out["dispatched"][k[len("engine_kernels_dispatched{kernel="):-1]] = v
        elif k in _WATCHED:
            out[k] = v
    for k, row in ((snap.get("perf") or {}).get("kernels") or {}).items():
        if row.get("compiles"):
            out["compiles"][k] = (row["compiles"], row["compile_s"])
    return out


def _counter_delta(a: dict, b: dict) -> dict:
    out: dict = {}
    disp = {k: v - a.get("dispatched", {}).get(k, 0)
            for k, v in b.get("dispatched", {}).items()}
    disp = {k: v for k, v in disp.items() if v}
    if disp:
        out["dispatched"] = disp
    comp = {}
    for k, (n, s) in b.get("compiles", {}).items():
        n0, s0 = a.get("compiles", {}).get(k, (0, 0.0))
        if n - n0:
            comp[k] = {"compiles": n - n0, "compile_s": round(s - s0, 2)}
    if comp:
        out["compiles"] = comp
    for k in _WATCHED:
        if b.get(k, 0) - a.get(k, 0):
            out[k] = b.get(k, 0) - a.get(k, 0)
    return out


def _wait(what: str, cond, timeout_s: float = 300.0) -> None:
    deadline = time.monotonic() + timeout_s
    while not cond():
        if time.monotonic() > deadline:
            raise TimeoutError(f"{what}: not reached in {timeout_s:.0f}s")
        time.sleep(0.02)


# ---------------------------------------------------------------------------
# the fleet, made from a seed


@dataclass
class FleetSpec:
    n_small: int = 10_000
    n_heavy: int = 8
    heavy_ops: int = 400
    n_list: int = 16
    n_text: int = 16
    n_move: int = 4
    load_batch: int = 2_000       # small documents per load round
    rounds: int = 3               # coalesced storm rounds of small docs
    draws_per_round: int = 3_000  # zipf draws; ~1K distinct dirty documents
    zipf_s: float = 1.1
    burst_chars: int = 32         # text burst: two ops a character
    sample: int = 256             # documents held to the oracle, at least


@dataclass
class Fleet:
    spec: FleetSpec
    rng: random.Random
    small: list = field(default_factory=list)       # doc ids
    structured: list = field(default_factory=list)  # heavy/list/text/move
    replicas: dict = field(default_factory=dict)    # doc id -> frontend doc
    seqs: dict = field(default_factory=dict)        # storm seq per doc
    acked: dict = field(default_factory=dict)       # doc id -> [Change]

    def kind(self, prefix: str) -> list:
        """The structured documents of one kind: heavy, list, text, move."""
        return [d for d in self.structured if d.startswith(prefix)]

    def ack(self, doc_id: str, changes) -> None:
        """The service acknowledged these changes (its call returned)."""
        self.acked.setdefault(doc_id, []).extend(changes)

    @property
    def doc_ids(self) -> list:
        return self.structured + self.small


def _storm_change(fleet: Fleet, doc_id: str, ops) -> list:
    from automerge_tpu.core.change import Change
    fleet.seqs[doc_id] = fleet.seqs.get(doc_id, 0) + 1
    return [Change("storm", fleet.seqs[doc_id], {}, list(ops))]


def _edit(fleet: Fleet, doc_id: str, fn) -> list:
    """One frontend change by the document's own local replica."""
    import automerge_tpu as am
    old = fleet.replicas[doc_id]
    new = am.change(old, fn)
    fleet.replicas[doc_id] = new
    return new._doc.opset.get_missing_changes(old._doc.opset.clock)


def _move_doc_base() -> list:
    """A small board: six maps under the root and one six-element list."""
    from automerge_tpu.core.change import Change, Op
    from automerge_tpu.core.ids import ROOT_ID
    ops = []
    for i in range(6):
        ops.append(Op("makeMap", f"f{i}"))
        ops.append(Op("link", ROOT_ID, key=f"k{i}", value=f"f{i}"))
    ops.append(Op("makeList", "L"))
    ops.append(Op("link", ROOT_ID, key="L", value="L"))
    prev = "_head"
    for e in range(1, 7):
        ops.append(Op("ins", "L", key=prev, elem=e))
        ops.append(Op("set", "L", key=f"A:{e}", value=f"v{e}"))
        prev = f"A:{e}"
    return [Change("A", 1, {}, ops)]


def make_fleet(spec: FleetSpec, seed: int) -> tuple:
    """The seeded fleet and its load rounds: a list of {doc_id: [Change]},
    structured documents first (they set the resident caps), then the small
    map documents in equal rounds."""
    import automerge_tpu as am
    from automerge_tpu.core.change import Change, Op
    from automerge_tpu.core.ids import ROOT_ID

    rng = random.Random(seed)
    fleet = Fleet(spec, rng)
    first: dict = {}
    for h in range(spec.n_heavy):
        first[f"heavy{h:02d}"] = [Change("storm", 1, {}, [
            Op("set", ROOT_ID, key=f"k{j}", value=rng.randrange(1 << 20))
            for j in range(spec.heavy_ops)])]
        fleet.seqs[f"heavy{h:02d}"] = 1
    for i in range(spec.n_list):
        a = am.change(am.init("A"), lambda d: d.__setitem__(
            "xs", [rng.randrange(100) for _ in range(12)]))
        b = am.merge(am.init("B"), a)
        a = am.change(a, lambda d: d["xs"].insert_at(rng.randrange(12), -1))
        b = am.change(b, lambda d: d["xs"].delete_at(rng.randrange(12)))
        a = am.merge(a, b)
        fleet.replicas[f"list{i:02d}"] = a
        first[f"list{i:02d}"] = a._doc.opset.get_missing_changes({})
    for i in range(spec.n_text):
        a = am.change(am.init("A"), lambda d: d.__setitem__("t", am.Text()))
        a = am.change(a, lambda d: d["t"].insert_at(
            0, *(chr(97 + rng.randrange(26)) for _ in range(24))))
        b = am.merge(am.init("B"), a)
        a = am.change(a, lambda d: d["t"].insert_at(rng.randrange(24), "A"))
        b = am.change(b, lambda d: d["t"].insert_at(rng.randrange(24), *"bb"))
        b = am.change(b, lambda d: d["t"].delete_at(rng.randrange(20), 2))
        a = am.merge(a, b)
        fleet.replicas[f"text{i:02d}"] = a
        first[f"text{i:02d}"] = a._doc.opset.get_missing_changes({})
    for i in range(spec.n_move):
        # B reparents f1 under f0 and reorders the list now; the storm
        # writer's conflicting moves (a cycle) arrive in a later round
        first[f"move{i:02d}"] = _move_doc_base() + [
            Change("B", 1, {"A": 1}, [Op("move", "f1", key="in", value="f0")]),
            Change("B", 2, {"B": 1}, [Op("move", "L", key="_head",
                                         value="A:4", elem=9)])]
    fleet.structured = list(first)
    rounds = [first]
    fleet.small = [f"doc{i:05d}" for i in range(spec.n_small)]
    for lo in range(0, spec.n_small, spec.load_batch):
        rounds.append({
            d: _storm_change(fleet, d, [
                Op("set", ROOT_ID, key="title", value=f"t{rng.randrange(999)}"),
                Op("set", ROOT_ID, key="n", value=rng.randrange(1 << 16)),
                Op("set", ROOT_ID, key="done", value=bool(rng.randrange(2)))])
            for d in fleet.small[lo:lo + spec.load_batch]})
    return fleet, rounds


# ---------------------------------------------------------------------------
# one chip: the served path


def stage_device(n_chips: int) -> dict:
    """First touch of JAX: a TPU or fail at once, before any import of the
    package can touch the backend; then the compile cache, before the first
    compile."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX reports {devs[0].platform!r} "
            f"({devs[0].device_kind})")
    if len(devs) < n_chips:
        raise RuntimeError(f"--chips {n_chips} needs {n_chips} TPU devices; "
                           f"JAX reports {len(devs)}")
    from automerge_tpu.utils import compile_cache
    cache_dir = compile_cache.configure()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "jax": jax.__version__,
            "cache_dir": cache_dir,
            "cache_entries_at_start": compile_cache.entries(cache_dir)}


def new_service():
    from automerge_tpu.sync.service import EngineDocSet
    return EngineDocSet(backend="rows")


def apply_round(svc, fleet: Fleet, round_: dict) -> None:
    """One coalesced round through the service's own batching; the changes
    count as acknowledged once the batch has flushed."""
    with svc.batch():
        for doc_id, changes in round_.items():
            svc.apply_changes(doc_id, changes)
    for doc_id, changes in round_.items():
        fleet.ack(doc_id, changes)


def stage_load(svc, fleet: Fleet, rounds: list) -> dict:
    from automerge_tpu import native
    from automerge_tpu.native import delta as native_delta
    for r in rounds:
        apply_round(svc, fleet, r)
    rset = _engines(svc)[0]
    return {"docs": len(svc.doc_ids), "load_rounds": len(rounds),
            "dims": list(rset.dims()), "n_pad": rset.n_pad,
            "native_wire": native.native_available(),
            "native_wire_error": native.native_error(),
            "native_delta": rset._native is not None,
            "native_delta_error": native_delta.native_delta_error()}


def _engines(svc) -> list:
    shards = getattr(svc, "shards", None)
    return [s._resident for s in shards] if shards else [svc._resident]


def stage_rounds(svc, fleet: Fleet, native_delta: bool = True) -> dict:
    """Coalesced zipf storm rounds (the megabatch route), then single
    ingests (the `apply_final` route)."""
    from automerge_tpu.core.change import Change, Op
    from automerge_tpu.core.ids import ROOT_ID

    spec, rng = fleet.spec, fleet.rng
    counters_before = _counters()
    n = len(fleet.small)
    cum, acc = [], 0.0
    for k in range(n):
        acc += 1.0 / (k + 1) ** spec.zipf_s
        cum.append(acc)
    routes, dirty_counts = [], []

    def routed_round(round_: dict) -> None:
        before = _counters().get("engine_megabatch_rounds", 0)
        apply_round(svc, fleet, round_)
        fused = _counters().get("engine_megabatch_rounds", 0) > before
        routes.append("megabatch" if fused else "per_doc")
        dirty_counts.append(len(round_))

    for r in range(spec.rounds):
        picks = sorted(set(rng.choices(range(n), cum_weights=cum,
                                       k=spec.draws_per_round)))
        routed_round({fleet.small[p]: _storm_change(fleet, fleet.small[p], [
            Op("set", ROOT_ID, key=f"f{r % 4}", value=r)]) for p in picks})
    # one round of the structured documents alone (its buckets span the
    # shape ladder, so the router may well price it per document): list and
    # text edits by their replicas, the storm writer's conflicting moves
    round_ = {}
    for d in fleet.kind("list"):
        round_[d] = _edit(fleet, d, lambda x: x["xs"].insert_at(0, 99))
    for d in fleet.kind("text"):
        round_[d] = _edit(fleet, d, lambda x: x["t"].insert_at(
            len(x["t"]), "!"))
    for d in fleet.kind("move"):
        round_[d] = [
            Change("storm", 1, {"A": 1}, [
                Op("move", "f0", key="in", value="f1")]),
            Change("storm", 2, {"storm": 1}, [
                Op("move", "L", key="A:6", value="A:4", elem=9)])]
        fleet.seqs[d] = 2
    routed_round(round_)
    # single ingests, outside any batch: one small, one list, one text
    singles = [fleet.small[0], *fleet.kind("list")[:1],
               *fleet.kind("text")[:1]]
    for d in singles:
        if d in fleet.replicas:
            key = "xs" if d.startswith("list") else "t"
            chs = _edit(fleet, d, lambda x: x[key].insert_at(0, "s"))
        else:
            chs = _storm_change(fleet, d, [
                Op("set", ROOT_ID, key="single", value=1)])
        svc.apply_changes(d, chs)
        fleet.ack(d, chs)
    check_rounds(counters_before, native_delta)
    return {"plan_round_routes": routes, "dirty_per_round": dirty_counts,
            "single_ingests": len(singles)}


def check_rounds(before: dict, native_delta: bool) -> None:
    """The `rounds` stage drove the device, by the route the service takes
    with its native encoder (without it every flush is a `scan_rounds`)."""
    d = _counter_delta(before, _counters())
    disp = d.get("dispatched", {})
    if not sum(disp.values()):
        raise AssertionError("the rounds stage made no device dispatch")
    if native_delta:
        if not d.get("engine_megabatch_rounds"):
            raise AssertionError(f"no megabatch round was fused: {d}")
        if not disp.get("apply_final"):
            raise AssertionError(f"no apply_final dispatch: {d}")
    elif not disp.get("scan_rounds"):
        raise AssertionError(f"no scan_rounds dispatch: {d}")


def stage_peer(svc, fleet: Fleet) -> dict:
    """One plain interpretive DocSet on a TcpSyncClient, in this process:
    subscribes, receives, sees a text burst, writes back, converges."""
    import automerge_tpu as am
    from automerge_tpu.engine.batchdoc import oracle_state
    from automerge_tpu.sync.docset import DocSet
    from automerge_tpu.sync.tcp import TcpSyncClient, TcpSyncServer, sync_lock

    spec, rng = fleet.spec, fleet.rng
    want = list(fleet.structured) + rng.sample(
        fleet.small, min(len(fleet.small), spec.sample))
    peer = DocSet()
    server = TcpSyncServer(svc, wire="columnar").start()
    client = TcpSyncClient(peer, server.host, server.port, wire="columnar")
    try:
        # the subscription leaves before this side starts answering the
        # server's adverts, so the server reads it first and frames only
        # the subscribed documents
        client.peer.connection.subscribe(docs=want)
        client.start()

        def caught_up(docs):
            for d in docs:
                got = peer.get_doc(d)
                if got is None or dict(got._doc.opset.clock) != dict(
                        svc.clock_of(d)):
                    return False
            return True

        _wait("peer receives its subscription", lambda: caught_up(want))
        # a pure-text burst on the server: the peer's span plane engages
        burst_doc = fleet.kind("text")[0]
        merged0 = _counters().get("sync_text_batches_merged", 0)
        chs = _edit(fleet, burst_doc, lambda x: x["t"].insert_at(
            3, *(chr(65 + rng.randrange(26))
                 for _ in range(spec.burst_chars))))
        burst_ops = sum(len(c.ops) for c in chs)
        svc.apply_changes(burst_doc, chs)
        fleet.ack(burst_doc, chs)
        _wait("peer receives the text burst", lambda: caught_up([burst_doc]))
        if _counters().get("sync_text_batches_merged", 0) <= merged0:
            raise AssertionError(
                f"a {burst_ops}-op text burst did not engage the span plane")
        # the peer writes back, under one pinned actor (the fleet's actor
        # set is a resident cap)
        wrote = [want[len(fleet.structured)], burst_doc,
                 fleet.kind("list")[0]]
        for d in wrote:
            with sync_lock(peer):
                mine = am.merge(am.init("peer"), peer.get_doc(d))
                if d.startswith("text"):
                    mine = am.change(mine, lambda x: x["t"].insert_at(0, "P"))
                elif d.startswith("list"):
                    mine = am.change(mine, lambda x: x["xs"].insert_at(1, 7))
                else:
                    mine = am.change(mine, lambda x: x.__setitem__(
                        "peer", "was here"))
                peer.set_doc(d, mine)
        _wait("server admits the peer's writes", lambda: all(
            svc.clock_of(d).get("peer", 0) >= 1 for d in wrote))
        for d in wrote:
            fleet.ack(d, [c for c in peer.get_doc(d)._doc.opset
                          .get_missing_changes({}) if c.actor == "peer"])
        _wait("peer and server converge", lambda: caught_up(want))
        diverged = [d for d in want
                    if oracle_state(peer.get_doc(d)) != svc.materialize(d)]
        if diverged:
            raise AssertionError(
                f"{len(diverged)} peer documents differ from the server's, "
                f"first {diverged[:5]}")
    finally:
        client.close()
        server.close()
    return {"subscribed": len(want), "peer_docs": len(peer.doc_ids),
            "peer_equal": len(want), "burst_ops": burst_ops,
            "peer_writes": len(wrote)}


def stage_hashes(svc, fleet: Fleet) -> dict:
    import numpy as np
    hashes = svc.hashes()
    missing = [d for d in fleet.doc_ids if d not in hashes]
    if missing or len(hashes) < len(fleet.doc_ids):
        raise AssertionError(f"{len(missing)} documents have no hash")
    engines = _engines(svc)
    return {"docs_hashed": len(hashes),
            "resident_bytes": sum(e.resident_bytes() for e in engines),
            "dims": [list(e.dims()) for e in engines],
            "_hashes": {d: np.uint32(h) for d, h in hashes.items()}}


def check_no_fallback() -> dict:
    """A kernel the chip refuses is acknowledged from host truth round
    after round (the product's failure contract); the smoke must not pass
    on that. Totals for the whole run."""
    c = _counters()
    out = {k: c.get(k, 0) for k in ("rows_dispatch_failed",
                                    "rows_log_rebuilt",
                                    "rows_engine_poisoned")}
    if any(out.values()):
        raise AssertionError(f"the engine fell back to host truth: {out}")
    out["engine_megabatch_fallbacks"] = c.get("engine_megabatch_fallbacks", 0)
    return out


def reference_hashes(logs: dict) -> tuple:
    """`apply_batch` (the XLA program) over each document's change log from
    scratch, in size buckets. Returns ({doc: uint32 hash}, {doc: (encoding,
    bucket outputs, row)} for decoding sampled documents, {bucket: docs})."""
    import numpy as np
    from automerge_tpu.engine.batchdoc import apply_batch

    buckets: dict = {}
    size = {}
    for d, chs in logs.items():
        size[d] = (sum(len(c.ops) for c in chs), len(chs))
        has_list = any(op.action in ("makeList", "makeText")
                       for c in chs for op in c.ops)
        cap = next((c for c in _REF_LADDER if size[d][0] <= c), size[d][0])
        buckets.setdefault((cap, has_list), []).append(d)
    actors = sorted({c.actor for chs in logs.values() for c in chs})
    hashes, where = {}, {}
    for key in sorted(buckets):
        docs = sorted(buckets[key], key=size.__getitem__)
        for lo in range(0, len(docs), _REF_CHUNK):
            chunk = docs[lo:lo + _REF_CHUNK]
            encs, _arrays, out = apply_batch([logs[d] for d in chunk],
                                             actors=actors)
            h = np.asarray(out["hash"])
            for i, d in enumerate(chunk):
                hashes[d] = np.uint32(h[i])
                where[d] = (encs[i], out, i)
    return hashes, where, {f"{k[0]}{'L' if k[1] else ''}": len(v)
                           for k, v in buckets.items()}


def stage_parity(svc, fleet: Fleet, resident: dict) -> dict:
    """Results, not timings: (d) acknowledged changes are served back,
    (a) resident hashes equal the XLA from-scratch reference fleet-wide,
    (b) a seeded sample equals the interpretive oracle."""
    import numpy as np
    import automerge_tpu as am
    from automerge_tpu.engine.batchdoc import decode_doc, oracle_state
    from automerge_tpu.frontend.materialize import apply_changes_to_doc

    logs = {d: list(svc.missing_changes(d, {})) for d in fleet.doc_ids}
    lost = [d for d, sent in fleet.acked.items()
            if {(c.actor, c.seq) for c in sent}
            - {(c.actor, c.seq) for c in logs[d]}]
    if lost:
        raise AssertionError(f"acknowledged changes missing from "
                             f"missing_changes(doc, {{}}): {lost[:5]}")
    ref, where, bucket_sizes = reference_hashes(logs)
    bad = [d for d in fleet.doc_ids if ref[d] != resident[d]]
    if bad:
        raise AssertionError(
            f"{len(bad)} of {len(ref)} resident hashes differ from the "
            f"apply_batch reference, first {bad[:5]}")
    extra = max(fleet.spec.sample - len(fleet.structured), 0)
    sample = list(fleet.structured) + fleet.rng.sample(
        fleet.small, min(extra, len(fleet.small)))
    host_out: dict = {}
    for d in sample:
        # the oracle replays what this script sent, not what the service
        # kept
        doc = am.init("oracle")
        doc = apply_changes_to_doc(doc, doc._doc.opset, fleet.acked[d],
                                   incremental=False)
        want = oracle_state(doc)
        if svc.materialize(d) != want:
            raise AssertionError(f"materialize({d}) differs from the "
                                 f"interpretive oracle")
        if d.startswith("move"):
            continue   # decode_doc renders no move winners (host plane)
        enc, out, i = where[d]
        if id(out) not in host_out:
            host_out[id(out)] = {k: np.asarray(v) for k, v in out.items()}
        got = decode_doc(enc, {k: v[i] for k, v in host_out[id(out)].items()})
        if got != want:
            raise AssertionError(f"decoded device state of {d} differs "
                                 f"from the interpretive oracle")
    acked = sum(len(v) for v in fleet.acked.values())
    return {"hash_equal": len(ref), "oracle_equal": len(sample),
            "acked_changes_served": acked,
            "reference_buckets": bucket_sizes, **check_no_fallback()}


def stage_kernel_families(fleet: Fleet) -> dict:
    """The span and move families on the device: each router's verdict, and
    the XLA and Pallas programs against the numpy one at D > 1. The Pallas
    kernels are routed nowhere; this is their only run on the chip."""
    import numpy as np
    from automerge_tpu.core.moves import MoveProblem
    from automerge_tpu.engine import dispatch
    from automerge_tpu.engine.move_kernels import (pack_moves, resolve_moves,
                                                   resolve_moves_host,
                                                   resolve_moves_pallas)
    from automerge_tpu.engine.pack import pack_spans
    from automerge_tpu.engine.span_kernels import (merge_spans,
                                                   merge_spans_host,
                                                   sort_spans,
                                                   span_rank_hash_pallas)
    from automerge_tpu.utils import metrics

    rng = fleet.rng
    # span tables of the shape the text documents' merges produce: a base
    # split into regions, concurrent bursts in its gaps
    tables = []
    for _ in range(8):
        n_base = rng.randrange(4, 12)
        rows = [(1, 10 * i, rng.randrange(0, 9), 2 * i, 0, 0, i)
                for i in range(n_base)]
        for j in range(rng.randrange(2, 8)):
            rows.append((2 + j % 2, 1000 + 40 * j, fleet.spec.burst_chars,
                         2 * rng.randrange(-1, n_base) + 1,
                         1000 + 40 * j, 1 + j % 2, 0))
        tables.append(rows)
    spans = pack_spans(tables)
    host = merge_spans_host(spans)
    dev = metrics.dispatch_jit("merge_spans", merge_spans, spans)
    span_plan, routed = dispatch.merge_spans_adaptive(tables)
    sorted_spans, _ = sort_spans(spans)
    _, p_hash, p_total = metrics.dispatch_jit(
        "span_rank_hash_pallas", span_rank_hash_pallas, sorted_spans)
    for name, got in (("routed", routed["hash"]), ("xla", dev["hash"]),
                      ("pallas", p_hash)):
        if not np.array_equal(np.asarray(got), host["hash"]):
            raise AssertionError(f"span merge: {name} hash != numpy")
    if not np.array_equal(np.asarray(p_total), host["total"]):
        raise AssertionError("span merge: pallas totals != numpy")
    # move realms with one reparent cycle each, as the move documents hold
    problems = []
    for k in range(8):
        p = MoveProblem()
        n = 12 + k
        for i in range(n):
            p.slot(i)
            p.base[i] = i - 1 if i else -1
        p.cands[3] = [(9, 1, 7, None)]
        p.cands[7] = [(8, 0, 3, None)]
        p.moved = [3, 7]
        problems.append(p)
    packed = pack_moves(problems)
    host = resolve_moves_host(packed)
    xla = metrics.dispatch_jit("resolve_moves", resolve_moves,
                               packed["nodes"], packed["cands"])
    move_plan, routed = dispatch.resolve_moves_adaptive(packed)
    pls = resolve_moves_pallas(packed)
    for name, got in (("routed", routed), ("xla", xla), ("pallas", pls)):
        for key in ("ptr", "hash"):
            if not np.array_equal(np.asarray(got[key]), host[key]):
                raise AssertionError(f"move resolve: {name} {key} != numpy")
    if not int(host["dropped"].sum()):
        raise AssertionError("the move realms' cycles were never dropped")
    return {"plan_spans": span_plan.backend, "span_shape": list(spans.shape),
            "plan_moves": move_plan.backend,
            "move_shape": list(packed["nodes"].shape)}


def stage_cache(cache_dir: str) -> dict:
    from automerge_tpu.utils import compile_cache
    n = compile_cache.entries(cache_dir)
    if n < 1:
        raise AssertionError(f"compile cache {cache_dir} holds no entries")
    return {"cache_dir": cache_dir, "cache_entries": n}


def run_one_chip(spec: FleetSpec, seed: int, svc, cache_dir: str | None):
    """Stages `load` .. `cache` on a service the caller made (and may have
    steered). Returns the stage records for the caller's own checks."""
    fleet, rounds = run_stage("fleet", lambda: make_fleet(spec, seed))
    try:
        load = run_stage("load", stage_load, svc, fleet, rounds)
        run_stage("rounds", stage_rounds, svc, fleet, load["native_delta"])
        run_stage("peer", stage_peer, svc, fleet)
        hashed = run_stage("hashes", stage_hashes, svc, fleet)
        parity = run_stage("parity", stage_parity, svc, fleet,
                           hashed["_hashes"])
        run_stage("kernels", stage_kernel_families, fleet)
    finally:
        svc.close()
    if cache_dir is not None:
        run_stage("cache", stage_cache, cache_dir)
    return {"load": load, "parity": parity}


# ---------------------------------------------------------------------------
# four chips: the sharded paths and what they are compared with


def new_sharded_service(devices):
    from automerge_tpu.sync.sharded_service import ShardedEngineDocSet
    return ShardedEngineDocSet(n_shards=len(devices), devices=list(devices))


def stage_sharded_service(svc, fleet: Fleet, rounds: list) -> dict:
    """The fleet through a ShardedEngineDocSet, one shard a device: load,
    storm rounds and single ingests, the fleet's hashes against the
    one-device XLA reference, and where each shard's buffers live."""
    from automerge_tpu.core.change import Op
    from automerge_tpu.core.ids import ROOT_ID

    for r in rounds:
        apply_round(svc, fleet, r)
    routes = stage_rounds(svc, fleet)["plan_round_routes"]
    placement = []
    for k, shard in enumerate(svc.shards):
        # a single ingest leaves the shard's resident buffer and its
        # flush-time hashes on the shard's device, unread
        d = next(d for d in fleet.small if svc.shard_of(d) is shard)
        chs = _storm_change(fleet, d, [Op("set", ROOT_ID, key="shard",
                                          value=k)])
        svc.apply_changes(d, chs)
        fleet.ack(d, chs)
        rset = shard._resident
        want = rset.device
        got = {"rows_dev": sorted(str(x) for x in rset.rows_dev.devices()),
               "outputs": sorted(str(x) for x in
                                 rset._hash_handle.devices())}
        if any(v != [str(want)] for v in got.values()):
            raise AssertionError(f"shard {k} pinned to {want} holds {got}")
        placement.append({"shard": k, "device": str(want),
                          "docs": len(rset.doc_ids), **got})
    hashed = stage_hashes(svc, fleet)
    parity = stage_parity(svc, fleet, hashed["_hashes"])
    return {"shards": placement, "plan_round_routes": routes,
            "docs_hashed": hashed["docs_hashed"],
            "hash_equal": parity["hash_equal"],
            "oracle_equal": parity["oracle_equal"]}


def stage_sharded_mesh(svc, fleet: Fleet, devices) -> dict:
    """The mesh programs over a slice of the same fleet: the sharded XLA
    reconcile, the sharded megakernel (wide and byte wire) and the clock
    union, each against its one-device program."""
    import numpy as np
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from automerge_tpu.engine.batchdoc import apply_batch
    from automerge_tpu.parallel import global_clock_union
    from automerge_tpu.parallel.mesh import (DOCS_AXIS,
                                             reconcile_rows_sharded,
                                             reconcile_rows_sharded_bytes,
                                             reconcile_sharded)

    mesh = Mesh(np.array(list(devices)), (DOCS_AXIS,))
    n = len(devices)
    # one 128-lane block a device; the heavy documents pad every document
    # of this batch to 512 ops, so it stays small
    docs = fleet.structured + fleet.small[
        :max(128 * n - len(fleet.structured), n)]
    logs = [list(svc.missing_changes(d, {})) for d in docs]
    _encs, _arr, ref_out = apply_batch(logs)
    ref = np.asarray(ref_out["hash"]).astype(np.uint32)
    _e, out, n_real = reconcile_sharded(logs, mesh)
    placed = sorted(str(d) for d in out["hash"].devices())
    if len(placed) != n:
        raise AssertionError(f"sharded outputs live on {placed}")
    got = {"reconcile_sharded": np.asarray(out["hash"])[:n_real],
           "reconcile_rows_sharded": reconcile_rows_sharded(logs, mesh)[0],
           "reconcile_rows_sharded_bytes":
               reconcile_rows_sharded_bytes(logs, mesh)[0]}
    # the megakernel as one program on one device: the same entry point
    # over a mesh of one
    one = Mesh(np.array(list(devices)[:1]), (DOCS_AXIS,))
    got["reconcile_rows_sharded (one device)"] = \
        reconcile_rows_sharded(logs, one)[0]
    for name, h in got.items():
        if not np.array_equal(np.asarray(h).astype(np.uint32), ref):
            raise AssertionError(f"{name} != apply_batch on one device")
    clocks = np.zeros((len(docs) - len(docs) % n, 2), np.int32)
    clocks[:, 0] = np.arange(len(clocks))
    clocks[:, 1] = len(clocks) - np.arange(len(clocks))
    union = np.asarray(global_clock_union(jax.device_put(
        clocks, NamedSharding(mesh, P(DOCS_AXIS))), mesh))
    if union.tolist() != clocks.max(axis=0).tolist():
        raise AssertionError(f"clock union {union.tolist()}")
    return {"mesh_devices": [str(d) for d in devices], "docs": len(docs),
            "outputs_on": placed,
            "programs_equal": sorted(got), "clock_union": union.tolist()}


def run_four_chips(spec: FleetSpec, seed: int, svc, devices) -> dict:
    fleet, rounds = run_stage("fleet", lambda: make_fleet(spec, seed))
    try:
        sharded = run_stage("sharded_service", stage_sharded_service, svc,
                            fleet, rounds)
        mesh = run_stage("sharded_mesh", stage_sharded_mesh, svc, fleet,
                         devices)
    finally:
        svc.close()
    return {"sharded_service": sharded, "sharded_mesh": mesh}


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the sharded paths and their one-device "
                         "comparison, and no other stage")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = run_stage("device", stage_device, args.chips)
    import jax
    if args.chips == 4:
        devices = jax.devices()[:4]
        run_four_chips(FleetSpec(), args.seed, new_sharded_service(devices),
                       devices)
    else:
        run_one_chip(FleetSpec(), args.seed, new_service(), dev["cache_dir"])
    emit({"ok": True, "device": {"platform": dev["platform"],
                                 "kind": dev["kind"], "count": dev["count"]}})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
