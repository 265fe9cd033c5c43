"""ShardedEngineDocSet: one sync-node surface over K engine shards.

The rows engine bounds its per-instance working set by the megakernel's
VMEM envelope and rejects batches that would blow it with the advice
"shard this DocSet across more rows instances" (resident_rows.py budget
prechecks). This module productizes that advice: documents are
partitioned across K independent `EngineDocSet` shards by a stable hash
of the doc id, every Connection-facing read/write routes to the owning
shard, and `batch()` coalesces a burst into one round PER SHARD, flushed
at its exit one shard after another on the caller's thread (the fan-out:
phase `shard_fanout`, histogram `sync_shard_fanout_seconds`) — on a
multi-chip host each shard's dispatch binds to its own device, making this
the single-process analog of the mesh-sharded DocSet (parallel/mesh.py)
for the streaming service posture.

Duck-typing contract: same surface Connection consumes from EngineDocSet
(doc_ids, get_doc, add_doc, apply_changes, apply_columns,
register_handler/unregister_handler), plus the engine reads
(hashes, materialize, clock_of, missing_changes, flush, batch).
"""

from __future__ import annotations

import contextlib
import os
import threading
import time as _time
import zlib
from typing import Callable

from ..utils import flightrec, metrics, perfscope
from .service import EngineDocSet, request_span

# Stall-watchdog budget for the hash fan-out (the r5 config-8 hang site:
# `sharded_service.hashes → service.hashes → resident_rows.hashes` sat on a
# readback barrier past a 3-minute timeout with no diagnosis). When a hash
# read overruns this many seconds, one WARNING line with every thread's
# active span stack is logged; 0 disables. Overridable per deployment.
STALL_WATCHDOG_S = float(os.environ.get("AMTPU_STALL_WATCHDOG_S", "120"))


class ShardedEngineDocSet:
    #: transports may apply without holding their doc_set-wide lock
    #: (see EngineDocSet.concurrent_ingest; routing adds no shared state
    #: beyond the stable crc32 hash)
    concurrent_ingest = True

    def __init__(self, n_shards: int = 2, doc_ids: list[str] | None = None,
                 backend: str = "rows", devices=None,
                 log_archive_dir: str | None = None,
                 log_horizon_changes: int | None = None,
                 ingest_mode: str | None = None):
        """devices: optional list of jax devices; shards bind round-robin
        so K shards drive K chips from one process (each shard's uploads
        and dispatches are pinned via the engine's `device` attribute —
        engine/resident_rows._to_dev). None = backend default device.

        log_archive_dir/log_horizon_changes thread the log-horizon layer
        to every shard (shard k archives under <dir>/shard<k>; routing is
        stable, so a doc's archive stays with its shard across restarts)."""
        if n_shards < 1:
            raise ValueError("n_shards must be >= 1")
        self.n_shards = n_shards
        self.shards = [
            EngineDocSet(backend=backend,
                         device=(devices[k % len(devices)]
                                 if devices else None),
                         log_archive_dir=(None if log_archive_dir is None
                                          else f"{log_archive_dir}/shard{k}"),
                         log_horizon_changes=log_horizon_changes,
                         ingest_mode=ingest_mode)
            for k in range(n_shards)]
        for k, s in enumerate(self.shards):
            s._shard = str(k)   # per-shard metric series (sync_round_flush…)
            # per-shard lock-contention series (bounded: one per shard),
            # so the lockprof plane separates a hot shard from the rest;
            # each shard's lazy flusher thread picks up the shard label
            # at spawn time (amtpu-flusher-<k>)
            s._lock.rename(f"service_shard{k}")
        # monotonic hash fan-out counter: tagged onto the fan-out span and
        # the flight-recorder progress events, so a post-mortem names which
        # round stalled and how far the fan-out got before stalling
        self._hash_round = 0
        # per-shard dirty epochs (the incremental convergence plane): each
        # entry caches (engine hash epoch, per-doc hash dict) from that
        # shard's last read. hashes() fans out ONLY to shards whose state
        # moved since (hashes_dirty_since) and serves the rest from the
        # cache, so a clean-fleet read touches no engine at all. Guarded
        # by _hash_cache_lock (reads can race ingress threads).
        self._hash_cache: list[tuple[int, dict] | None] = [None] * n_shards
        self._hash_cache_lock = threading.Lock()
        # clean/dirty split of the most recent fan-out (bench/ops surface;
        # also exported as the sync_hashes_{clean,dirty}_shards gauges)
        self.last_hashes_clean_shards = 0
        self.last_hashes_dirty_shards = n_shards
        for d in doc_ids or []:
            self.add_doc(d)

    # -- routing ------------------------------------------------------------

    def shard_of(self, doc_id: str) -> EngineDocSet:
        """Stable assignment: crc32 of the id mod K (deterministic across
        processes and restarts; no coordination state to persist)."""
        return self.shards[zlib.crc32(doc_id.encode()) % self.n_shards]

    # -- registry surface ----------------------------------------------------

    @property
    def doc_ids(self) -> list[str]:
        return [d for s in self.shards for d in s.doc_ids]

    def get_doc(self, doc_id: str):
        return self.shard_of(doc_id).get_doc(doc_id)

    def add_doc(self, doc_id: str):
        return self.shard_of(doc_id).add_doc(doc_id)

    def register_handler(self, handler: Callable) -> None:
        for s in self.shards:
            s.register_handler(handler)

    def unregister_handler(self, handler: Callable) -> None:
        for s in self.shards:
            s.unregister_handler(handler)

    # -- ingress -------------------------------------------------------------

    def apply_changes(self, doc_id: str, changes):
        return self.shard_of(doc_id).apply_changes(doc_id, changes)

    def apply_columns(self, doc_id: str, cols):
        return self.shard_of(doc_id).apply_columns(doc_id, cols)

    def apply_columns_async(self, doc_id: str, cols):
        """Pipelined admission routed to the owning shard (see
        EngineDocSet.apply_columns_async); per-shard flushers drain
        concurrently, so a streaming writer saturates K shards."""
        return self.shard_of(doc_id).apply_columns_async(doc_id, cols)

    def archive_logs(self, doc_ids: list[str] | None = None) -> dict[str, int]:
        """Per-doc archived counts across shards (log-horizon layer)."""
        out: dict[str, int] = {}
        if doc_ids is None:
            for s in self.shards:
                out.update(s.archive_logs())
        else:
            for d in doc_ids:
                out.update(self.shard_of(d).archive_logs([d]))
        return out

    def close(self) -> None:
        """Flush buffered ingress and stop (join) every shard's flusher
        thread — deterministic teardown for tests and restarts."""
        for s in self.shards:
            s.close()

    def flush(self) -> None:
        """Flush every shard even if one raises (shards are independent;
        batch() has the same semantics via ExitStack): the first error
        propagates after all shards have drained. Counted as one fan-out,
        as a batch() exit is; the shards' pending documents are a peek
        (no shard lock is held here)."""
        first: BaseException | None = None
        t0 = _time.perf_counter()
        docs = [len(s._pending) for s in self.shards]
        for s in self.shards:
            try:
                s.flush()
            except BaseException as e:
                first = first or e
        self._fanout_done(t0, docs)
        if first is not None:
            raise first

    def _fanout_done(self, t0: float, docs: list[int]) -> None:
        """The counters of one fan-out: `docs` are the documents each
        shard had pending when it began at `t0`."""
        metrics.observe("sync_shard_fanout_seconds",
                        _time.perf_counter() - t0)
        metrics.bump("sync_shard_fanout_rounds")
        metrics.bump("sync_shard_round_docs", sum(docs))
        metrics.bump("sync_shard_round_docs_fullest", max(docs))

    def batch(self):
        """Coalesce a burst into one round a shard. At the exit every
        shard flushes what it has pending, one shard after another (the
        last shard first) on the caller's thread; every shard's lock is
        held from the entry until the last flush has returned, and only
        then is the burst acknowledged. Every shard flushes even if one
        raises. The exit of the outermost batch is one fan-out: phase
        `shard_fanout` from the end of the caller's body to the last
        shard's return, around the shards' own phases."""
        @contextlib.contextmanager
        def _cm():
            # one root for the fleet-wide request: the shards' batches
            # open none of their own inside it, and their flushes keep
            # the shard= label under its trace id. `fanout` is entered
            # at the end of the body and unwinds after `stack` has left
            # (and so flushed) the shards.
            with request_span(None) as span, \
                    contextlib.ExitStack() as fanout, \
                    contextlib.ExitStack() as stack, \
                    contextlib.ExitStack() as admit:
                for s in self.shards:
                    stack.enter_context(s.batch())
                outermost = self.shards[0]._batch_depth == 1
                if span is not None:
                    # the body is the request's admission, one `admit`
                    # (the shards' batches open none inside the request)
                    admit.enter_context(perfscope.phase("admit"))
                try:
                    yield self
                finally:
                    if span is not None or outermost:
                        sizes = [s._pending_size() for s in self.shards]
                        docs = [d for d, _ in sizes]
                    admit.close()
                    if span is not None:
                        span.tags = {"docs": sum(docs),
                                     "ops": sum(o for _, o in sizes),
                                     "shards": sum(1 for d in docs if d)}
                    if outermost:
                        fanout.callback(self._fanout_done,
                                        _time.perf_counter(), docs)
                        fanout.enter_context(perfscope.phase("shard_fanout"))
        return _cm()

    # -- protocol / engine reads ---------------------------------------------

    def clock_of(self, doc_id: str):
        return self.shard_of(doc_id).clock_of(doc_id)

    def missing_changes(self, doc_id: str, clock, drain: bool = True):
        return self.shard_of(doc_id).missing_changes(doc_id, clock,
                                                     drain=drain)

    def hashes(self) -> dict[str, int]:
        """Fleet convergence read, O(dirty shards) not O(fleet): shards
        untouched since their last read serve straight from the per-shard
        hash cache (validated by the engine's hash epoch — zero engine
        work, zero locks beyond the epoch check); dirty shards are read
        CONCURRENTLY (dispatch all, then barrier) instead of serially, so
        the wall cost is the slowest dirty shard, and each shard's own
        read is O(its dirty docs) via the engine's lane-partial
        reconcile. This is the r5 config-8 fix: the 100K-doc fleet's
        180s+ serial full-fleet reconcile becomes a sub-second cache read
        when nothing changed."""
        self._hash_round += 1
        rnd = self._hash_round
        with self._hash_cache_lock:
            cache = list(self._hash_cache)
        clean: list[int] = []
        dirty: list[int] = []
        results: dict[int, tuple[dict, int]] = {}
        failures: list[tuple[int, BaseException]] = []

        def _read(k: int) -> None:
            # per-shard progress breadcrumbs: if the fan-out stalls, the
            # flight-recorder dump shows exactly how many shards answered
            # before the stall — the diagnosis the r5 config-8 hang never
            # produced
            flightrec.record("hash_shard", shard=str(k), round=rnd)
            try:
                results[k] = self.shards[k].hashes_snapshot()
            except BaseException as e:  # re-raised on the calling thread
                failures.append((k, e))

        # The epoch classification takes each shard's engine lock, so it
        # runs INSIDE the watchdog too: a shard wedged by a hung apply
        # must produce the watchdog diagnosis + flightrec breadcrumb, not
        # a silent pre-fan-out block.
        with metrics.watchdog("sync_hashes_fanout", STALL_WATCHDOG_S,
                              tags={"round": rnd}), \
                perfscope.phase("fleet_hashes"):
            for k, s in enumerate(self.shards):
                flightrec.record("hash_epoch_check", shard=str(k),
                                 round=rnd)
                c = cache[k]
                if c is not None and not s.hashes_dirty_since(c[0]):
                    clean.append(k)
                else:
                    dirty.append(k)
            if len(dirty) <= 1:
                for k in dirty:
                    _read(k)
            else:
                threads = [threading.Thread(
                    target=_read, args=(k,),
                    name=f"amtpu-hashfan-{k}", daemon=False)
                    for k in dirty]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
        with self._hash_cache_lock:
            for k, (d, ep) in results.items():
                self._hash_cache[k] = (ep, d)
        if failures:
            raise failures[0][1]
        self.last_hashes_clean_shards = len(clean)
        self.last_hashes_dirty_shards = len(dirty)
        metrics.gauge("sync_hashes_clean_shards", len(clean))
        metrics.gauge("sync_hashes_dirty_shards", len(dirty))
        out: dict[str, int] = {}
        for k in clean:
            out.update(cache[k][1])
        for k, (d, _ep) in results.items():
            out.update(d)
        flightrec.record("hash_fanout_done", round=rnd,
                         shards=self.n_shards, docs=len(out),
                         clean=len(clean), dirty=len(dirty))
        return out

    def hashes_for(self, doc_ids) -> dict[str, int]:
        """Partial convergence read routed per shard: each owning shard
        reconciles only its requested ∩ dirty docs (EngineDocSet
        .hashes_for); untouched shards are never contacted."""
        by_shard: dict[int, list[str]] = {}
        for d in doc_ids:
            by_shard.setdefault(
                zlib.crc32(d.encode()) % self.n_shards, []).append(d)
        out: dict[str, int] = {}
        for k, ds in sorted(by_shard.items()):
            out.update(self.shards[k].hashes_for(ds))
        return out

    def materialize(self, doc_id: str):
        return self.shard_of(doc_id).materialize(doc_id)

    # -- convergence audit surface (sync/audit.py) ---------------------------

    def audit_state(self) -> dict[str, dict]:
        """Per-shard audit digests across all K shards — the auditor
        compares these shard-by-shard and bisects only mismatched shards
        to the doc level."""
        out: dict[str, dict] = {}
        for s in self.shards:
            out.update(s.audit_state())
        return out

    def audit_shard_state(self, shard: str) -> dict:
        """Doc-level hashes + clock frontiers for one shard."""
        return self.shards[int(shard)].audit_shard_state(shard)
