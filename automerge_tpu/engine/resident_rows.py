"""Device-resident DocSet state in the megakernel's docs-minor row layout.

`resident.py` keeps docs-major columnar tables and re-runs the multi-op XLA
reconcile per sync round — one dispatch per round. On hardware where each
dispatch carries a large fixed cost (see INTERNALS.md §4) a streaming sync
service wants the opposite shape: state held as the single [ROWS, D_pad]
int32 buffer that `pallas_kernels.reconcile_rows_hash` consumes natively,
deltas applied as point scatters, and MANY rounds processed in ONE dispatch
(`lax.scan` over stacked per-round scatter triplets, reconciling after each
round). Per round the device work is one scatter + one fused kernel; the
host keeps an authoritative numpy mirror, so structural events (capacity
growth) rebuild host-side and re-upload once. A device that joins a document
rewrites that document's lane alone (_adopt_doc_actors): the actor axis is a
document's own (resident.py), and the cells that moved ride the next
scatter.

Causal admission, interning, and LWW actor ranking reuse the host machinery
of `resident.ResidentDocSet` (the reference semantics live in
op_set.js:254-270 and op_set.js:201). List order is maintained host-side via
the native RGA linearizer and shipped as position rows, exactly like the
from-scratch batch path.
"""

from __future__ import annotations

import contextlib
import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from . import dispatchledger
from .encode import A_MAKE_LIST, A_MAKE_TEXT, _pad_to
# CompactionAnchorError is re-exported: callers catch it beside the errors
# below
from .lists import CompactionAnchorError, ListStore, anchor_pins  # noqa: F401
from .resident import ResidentDocSet
from . import dispatch as round_dispatch
from .pack import LANE, pad_to_lanes
from .pallas_kernels import (gather_lanes, host_block_extents, join_steps,
                             lane_gather_plan, reconcile_rows_hash)
from ..utils import flightrec, metrics, perfscope

# lanes one _put_lanes call carries: one program shape for every count
LANE_PUT = 32


class DeviceDispatchError(RuntimeError):
    """The device dispatch of an already-admitted batch failed. Host truth — change_log, per-doc clocks, and the
    rows_host mirror (kept current by _cols_triplets BEFORE dispatch) — is
    fully consistent; only the device buffer is suspect, and the engine has
    marked itself dirty so the next dispatch re-uploads the mirror.

    ``admission_complete`` tells the caller whether the whole batch made it
    into host truth. True (dispatch guard): every change in the batch was
    admitted, queued, or dropped as a duplicate — nothing to retry. False
    (mid-admission rebuild): the unprocessed suffix of the batch is in
    neither the rebuilt log nor the queue — the caller should replay the
    batch; the (actor, seq) admission dedup drops the already-admitted
    prefix idempotently, so the retry admits exactly the missing
    remainder."""

    def __init__(self, msg: str, *, admission_complete: bool = False):
        super().__init__(msg)
        self.admission_complete = admission_complete


class RowsBudgetError(RuntimeError):
    """The batch would grow the resident rows state past the megakernel's
    VMEM budget. Recoverable: the instance is untouched — compact the
    long-lived docs (ResidentRowsDocSet.compact, engine/compaction.py) to
    reclaim dominated/tombstoned slots and retry, or shard the DocSet. The
    sync service compacts the documents a round takes past the caps
    before it grows them (dispatch_round_frames' `compactor`), so it
    raises this only where compaction could not make room: a floor an
    idle peer holds down, or a document whose live state is that large.
    `doc_ids` names the documents the round would take past the current
    caps, where the round's precheck knows them: the round without them
    fits, and the service refuses them alone."""

    def __init__(self, msg: str, *, doc_ids=()):
        super().__init__(msg)
        self.doc_ids = tuple(doc_ids)


def _budget_error(cap_ops: int, actors: int, elem_slots: int,
                  doc_ids=()) -> RowsBudgetError:
    return RowsBudgetError(
        f"this batch could grow the resident rows state past the "
        f"megakernel VMEM budget (ops<={cap_ops}, actors={actors}, "
        f"elem slots<={elem_slots}); compact the long-lived docs "
        f"(ResidentRowsDocSet.compact) or shard this DocSet across "
        f"more rows instances", doc_ids=doc_ids)


class ResidentRowsDocSet(ResidentDocSet):
    """Resident DocSet whose device state IS the megakernel row buffer."""

    def __init__(self, doc_ids, actors: list[str] = (),  # noqa: B006
                 native: bool | None = None):
        self._rows_ready = False
        # One delta encoder per instance (same rule as the base class): when
        # the native C++ encoder is available, ALL ingress routes through it
        # (Change rounds are converted to columns first), so its interning
        # tables stay authoritative; otherwise the Python _encode_delta path
        # runs. Mixing encoders on one instance would desync interning state.
        super().__init__(doc_ids, native=native)
        self.n_pad = pad_to_lanes(max(len(self.doc_ids), 1))
        # every list's host state: its insert log, slots and ghosts
        # (engine/lists.py)
        self.lists = ListStore(len(self.doc_ids))
        # last compaction floor per doc_id (rebuild-from-log re-compacts
        # with these so a rebuilt long-lived doc fits the budget again)
        self.compaction_floors: dict[str, dict[str, int]] = {}
        # Pin every upload/dispatch of this instance to one jax device
        # (None = backend default). A ShardedEngineDocSet assigns its
        # shards round-robin over jax.devices() so K shards drive K chips
        # from one process (sync/sharded_service.py).
        self.device = None
        # True = apply_round_frames skips the device dispatch: the host
        # mirror is the complete post-round truth, so upload + reconcile
        # defer to the next hash read. The right posture on backends with
        # no link to amortize (CPU): per-flush reconcile would do O(state)
        # work per round where admission is O(changes). On TPU the async
        # pipelined dispatch is strictly better — leave False there.
        self.lazy_dispatch = False
        # per-doc admitted change log (for materialization/debugging)
        self.change_log: list[list] = [[] for _ in self.doc_ids]
        # log-horizon layer (sync/logarchive.py): per-doc clock below which
        # the admitted prefix has been moved to the archive; the in-RAM
        # change_log holds only the tail above it. Empty dict = no horizon.
        self.log_horizon: list[dict] = [{} for _ in self.doc_ids]
        self.log_archive = None   # LogArchive, injected by the service
        # SnapshotStore (sync/snapshots.py), injected by the service:
        # compacted doc-state images beside the full-fidelity archive —
        # rebuild-from-log replays a snapshot-booted doc from its image
        # when the archive does not hold its history
        self.snapshot_store = None
        # bumped by _rebuild_from_log: lets the service's admission
        # detection use cheap log-length compares except across a rebuild
        # (which restores the archived prefix into the RAM log)
        self._rebuild_gen = 0
        if len(actors) > self.cap_actors:
            # the writers a document is expected to have: sizing the actor
            # axis now avoids a re-layout when the widest document gets them
            self.cap_actors = _pad_to(len(actors), 2)
            self._fit_ahash()
        # instance-wide interning of actor names, in arrival order: an id
        # means identity alone. _doc_gids [cap_docs, cap_actors] holds each
        # document's actors' ids in RANK order (-1 past its count), so a
        # round's (document, actor) -> rank lookups are one compare
        self._gid: dict[str, int] = {}
        self._doc_gids = np.full((self.cap_docs, self.cap_actors), -1,
                                 np.int64)
        # cells a lane rewrite changed while the device copy was current:
        # [k, 3] (row, lane, value) parts the next scatter carries
        self._lane_trips: list[np.ndarray] = []
        self._gid_memo: dict = {}     # id(frame columns) -> (them, ids)
        # (lane width, n_pad, dims) the lane route has run at (_warm_lanes)
        self._lanes_warm: set = set()
        # mirror shapes whose lanes' put has run (_warm_put)
        self._puts_warm: set = set()
        # (mirror shape, triplet pad) the round's scatter has run at
        # (_scatter_round, _warm_scatter)
        self._scatters_warm: set = set()
        self._rows_ready = True
        self._alloc_rows()
        self.rows_dev = None
        self._dirty = True
        # Device hashes of the last dispatch (full fleet, unread while the
        # pipeline is async). The incremental hash plane sits on top: the
        # base class's _hash_mirror/_doc_dirty/hash_epoch (resident.py)
        # track which LANES changed since the last readback, so hashes()/
        # hashes_for() reconcile only dirty lanes (narrow [ROWS, k_pad]
        # gather + the same fused kernel) and a clean read is free.
        self._hash_handle = None
        # The last all-lane hash vector a kernel produced from rows_dev,
        # kept on the device (readers consume _hash_handle; this stays).
        # Valid exactly while rows_dev is: _apply_final patches the dirty
        # 128-lane blocks' hashes into it instead of rehashing the fleet.
        self._h_prev = None
        # The one unsettled round: (lanes, h) of a round whose reconcile
        # was dispatched and whose hashes are not in the mirror yet
        # (lanes None: every lane). Its lanes stay in _doc_dirty until
        # _settle reads h back, so dropping the record is always safe;
        # whatever dirties a lane or loses the copy drops it
        # (_mark_hash_dirty, _drop_copy), every hash read settles it.
        self._unsettled = None
        # dense admission cache (vectorized round-frame fast path): per-doc
        # clock rows + single-head frontier summary. Rebuilt lazily from the
        # authoritative DocTables dicts for docs in _cache_dirty.
        self._clock_cache: np.ndarray | None = None
        self._fsize = None
        self._hrank = None
        self._hseq = None
        self._cache_dirty = set(range(len(self.doc_ids)))

    # ------------------------------------------------------------------
    # row layout

    def _bases(self):
        from .pack import row_bases
        return row_bases(self.cap_ops, self.cap_actors,
                         self.cap_lists * self.cap_elems)

    def dims(self) -> tuple:
        from .encode import A_DEL, A_SET
        return (self.cap_ops, self.cap_actors,
                self.cap_lists * self.cap_elems, int(A_SET), int(A_DEL))

    @property
    def _dev_current(self) -> bool:
        """The device copy of the rows exists and equals the host mirror
        (every re-layout of the mirror sets _dirty; every site that loses
        the buffer drops it)."""
        return self.rows_dev is not None and not self._dirty

    def _alloc_rows(self):
        b = self._bases()
        self.rows_host = np.zeros((b["rows"], self.n_pad), dtype=np.int32)
        self.rows_host[b["ac"]:b["ac"] + self.cap_ops] = -1
        self.rows_host[b["fid"]:b["fid"] + self.cap_ops] = -1
        le = self.cap_lists * self.cap_elems
        self.rows_host[b["if"]:b["if"] + le] = -1
        self.rows_host[b["io"]:b["io"] + le] = -1
        # elem_list is a static pattern (owning-list row per slot) shared by
        # every doc; it never needs scattering.
        self.rows_host[b["il"]:b["il"] + le] = np.repeat(
            np.arange(self.cap_lists, dtype=np.int32),
            self.cap_elems)[:, None]
        self._refill_actor_hash_band()

    def _refill_actor_hash_band(self) -> None:
        """Rewrite the ah band (rank -> actor CONTENT hash, a column a
        lane in the document's own rank basis) from the host table
        (_ahash). Called after alloc and any re-layout; a registration
        writes its own lane (_adopt_doc_actors). The state hash mixes
        these values, never ranks (kernels.state_hash)."""
        b = self._bases()
        n = min(self._ahash.shape[0], self.n_pad)
        self.rows_host[b["ah"]:b["ah"] + self.cap_actors, :n] = \
            self._ahash[:n].T

    # the docs-major device state of the base class is never built
    def _alloc(self):
        self.state = {}

    def add_docs(self, new_ids: list[str]) -> None:
        """Grow the document (lane) axis of the rows mirror — a sync
        service auto-creates docs the way DocSet.apply_changes does
        (doc_set.js:24-29). Padded lanes are valid empty documents."""
        from .resident import DocTables

        fresh = [d for d in new_ids if d not in self.doc_index]
        if not fresh:
            return
        old_cap_docs = self.cap_docs
        first_new = len(self.doc_ids)
        self.lists.add_docs(len(fresh))
        for d in fresh:
            self.doc_index[d] = len(self.doc_ids)
            self.doc_ids.append(d)
            self.tables.append(DocTables())
            self.change_log.append([])
            self.log_horizon.append({})
        # fresh lanes need one reconcile for their empty-doc hash;
        # existing lanes stay clean
        self._mark_hash_dirty(range(first_new, len(self.doc_ids)))
        n = len(self.doc_ids)
        if n > self.cap_docs:
            k = _pad_to(n, 8) - self.cap_docs
            self.cap_docs += k
            self.op_count = np.concatenate(
                [self.op_count, np.zeros(k, np.int64)])
            self.change_count = np.concatenate(
                [self.change_count, np.zeros(k, np.int64)])
            self._fit_ahash()
            self._doc_gids = np.pad(self._doc_gids, ((0, k), (0, 0)),
                                    constant_values=-1)
        new_pad = pad_to_lanes(n)
        if new_pad > self.n_pad:
            b = self._bases()
            grown = np.zeros((b["rows"], new_pad), np.int32)
            grown[:, :self.n_pad] = self.rows_host
            cols = slice(self.n_pad, new_pad)
            I = self.cap_ops
            le = self.cap_lists * self.cap_elems
            for g in ("ac", "fid"):
                grown[b[g]:b[g] + I, cols] = -1
            for g in ("if", "io"):
                grown[b[g]:b[g] + le, cols] = -1
            grown[b["il"]:b["il"] + le, cols] = np.repeat(
                np.arange(self.cap_lists, dtype=np.int32),
                self.cap_elems)[:, None]
            self.rows_host = grown
            self.n_pad = new_pad
            self.rows_dev = None
            self._dirty = True
            self._h_prev = None
            self._lane_trips.clear()
        # admission cache: fresh lanes are valid empty docs (zero clock,
        # empty frontier) — grow the cache arrays in place rather than
        # dropping them, or one-doc-at-a-time ingress of N new docs would
        # pay N full O(docs) rebuilds
        if self._clock_cache is not None and self.cap_docs > old_cap_docs:
            k = self.cap_docs - old_cap_docs
            self._clock_cache = np.pad(self._clock_cache, ((0, k), (0, 0)))
            self._fsize = np.pad(self._fsize, (0, k))
            self._hrank = np.pad(self._hrank, (0, k), constant_values=-1)
            self._hseq = np.pad(self._hseq, (0, k))

    def _grow(self, **caps):
        """Re-layout the host mirror for new capacities; device re-uploads."""
        if not getattr(self, "_rows_ready", False):
            for k, v in caps.items():
                setattr(self, k, v)
            return
        old_b = self._bases()
        old = self.rows_host
        A_old = self.cap_actors
        old_caps = dict(I=self.cap_ops, C=self.cap_changes, A=self.cap_actors,
                        L=self.cap_lists, E=self.cap_elems)
        for k, v in caps.items():
            setattr(self, k, v)
        self._fit_ahash()
        if self.cap_actors > A_old:
            self._doc_gids = np.pad(
                self._doc_gids, ((0, 0), (0, self.cap_actors - A_old)),
                constant_values=-1)
        b = self._bases()
        self._alloc_rows()
        new = self.rows_host
        I0, A0 = old_caps["I"], old_caps["A"]
        L0, E0 = old_caps["L"], old_caps["E"]
        for g in ("om", "ac", "fid", "act", "seq", "chg", "fh", "vh"):
            new[b[g]:b[g] + I0] = old[old_b[g]:old_b[g] + I0]
        # clock_op bands re-stride from (A0, I0) to (A, I)
        co = old[old_b["co"]:old_b["co"] + A0 * I0].reshape(A0, I0, -1)
        new[b["co"]:b["co"] + self.cap_actors * self.cap_ops] \
            .reshape(self.cap_actors, self.cap_ops, -1)[:A0, :I0] = co
        for g in ("im", "if", "ip", "io"):
            src = old[old_b[g]:old_b[g] + L0 * E0].reshape(L0, E0, -1)
            new[b[g]:b[g] + self.cap_lists * self.cap_elems] \
                .reshape(self.cap_lists, self.cap_elems, -1)[:L0, :E0] = src
        # il is static, and the ah band comes from the host table: both
        # are re-filled by _alloc_rows for the new strides
        self._dirty = True
        self._h_prev = None
        self._lane_trips.clear()
        metrics.bump("rows_caps_grown")
        # re-layout preserves hashes but rewrites every lane: conservative
        self._mark_all_hash_dirty()

    # _register_actors/_register_actors_cols/_register_doc_actors are
    # inherited from the base class; only the sink differs
    # (_adopt_doc_actors: a lane of the host mirror vs device state).
    class _StaleView:
        """Read-through guard left in place of a fast-path-stale table's
        clock/frontier dict: ANY read materializes the real dicts first
        (via _sync_stale_table), so external readers — e.g. a sync service
        advertising clocks — can never observe stale values, and writes
        through a stale reference fail loudly (no __setitem__)."""

        __slots__ = ("_owner", "_t", "_attr")

        def __init__(self, owner, t, attr):
            self._owner = owner
            self._t = t
            self._attr = attr

        def _m(self) -> dict:
            self._owner._sync_stale_table(self._t)
            real = getattr(self._t, self._attr)
            if real is self:  # cache unavailable: invariant broken
                raise RuntimeError("stale table could not materialize")
            return real

        def get(self, k, d=None):
            return self._m().get(k, d)

        def __getitem__(self, k):
            return self._m()[k]

        def __contains__(self, k):
            return k in self._m()

        def __iter__(self):
            return iter(self._m())

        def __len__(self):
            return len(self._m())

        def __eq__(self, other):
            return self._m() == other

        def __bool__(self):
            return bool(self._m())

        def items(self):
            return self._m().items()

        def keys(self):
            return self._m().keys()

        def values(self):
            return self._m().values()

        def __repr__(self):
            return repr(self._m())

    def _mirror_stats(self, bd, docs) -> None:
        """Mirror the native encoder's per-doc list/elem stats into the
        host tables (shared by the batched and per-round encode paths)."""
        touched = np.unique(docs)
        if len(touched) and len(bd.stats):
            sub = bd.stats[touched[touched < len(bd.stats)]]
            if len(sub):
                self._lists_hi = max(self._lists_hi, int(sub[:, 0].max()))
                self._elems_hi = max(self._elems_hi, int(sub[:, 1].max()))
        for i in touched:
            if i < len(bd.stats):
                t = self.tables[i]
                t.n_lists = int(bd.stats[i, 0])
                t.max_elems = int(bd.stats[i, 1])

    def _queued_mask(self) -> np.ndarray | None:
        """Boolean [cap_docs] mask of docs with queued changes, or None."""
        if not self._queued_docs:
            return None
        qf = np.zeros(self.cap_docs, bool)
        qf[np.fromiter(self._queued_docs, np.int64,
                       len(self._queued_docs))] = True
        return qf

    def sync_tables(self) -> None:
        """Materialize every fast-path-stale table's clock/frontier dicts
        from the dense cache. The vectorized admission path leaves table
        dicts stale (the cache is the authority); internal readers sync
        per-table on touch, external readers of `tables[i].clock` /
        `.frontier` call this first."""
        if getattr(self, "_stale_tables", False):
            for t in self.tables:
                self._sync_stale_table(t)
            self._stale_tables = False

    def _sync_stale_table(self, t) -> None:
        """Materialize a fast-path-stale table's clock/frontier dicts from
        the dense cache (the authority while the doc rode the vectorized
        admission path). Must run before any dict reader touches the table:
        slow-path _admit, cache rebuild, actor remap."""
        i = t._stale_idx
        if i is None:
            return
        cc = self._clock_cache
        if cc is None:
            # the only cache-invalidation sites materialize stale tables
            # first (_adopt_doc_actors, _refresh_admission_cache)
            raise RuntimeError("stale table with no clock cache")
        actors = t.actors
        t.clock = {actors[r]: int(v)
                   for r, v in enumerate(cc[i].tolist())
                   if v and r < len(actors)}
        if self._fsize[i] == 1 and self._hrank[i] >= 0:
            t.frontier = {actors[int(self._hrank[i])]:
                          int(self._hseq[i])}
        elif isinstance(t.frontier, self._StaleView):
            raise RuntimeError("stale table frontier not single-head")
        t._stale_idx = None

    def _admit(self, t, incoming):
        self._sync_stale_table(t)
        return super()._admit(t, incoming)

    def _adopt_doc_actors(self, plans: dict) -> None:
        """Host-mirror sink of a registration ({doc index: its new sorted
        actor list}): each document rewrites ITS lane and nothing else —
        its act row through the document's permutation, its co bands, its
        ah column, its lists' ranks, its row of the admission cache and
        its lazy memos. The lane's hash goes dirty; the other lanes, the
        device copy and _h_prev stay as they are: where the copy is
        current the cells that changed are pended as triplets
        (_lane_trips) and the round's scatter carries them."""
        b = self._bases()
        I, A = self.cap_ops, self.cap_actors
        joins = lanes = 0
        for i, actors in plans.items():
            t = self.tables[i]
            n_old = len(t.actors)
            if n_old:
                # the stale view and the lazy memos read the cache in the
                # document's OLD rank basis: materialize them first
                self._sync_stale_table(t)
                for key in t.state_clocks:
                    self._memo_dict(t, key)
                joins += len(actors) - n_old
                lanes += 1
            perm = self._set_doc_actors(i, actors)
            self._doc_gids[i, :len(actors)] = [
                self._gid.setdefault(a, len(self._gid)) for a in actors]
            # the lane's rank-bearing cells: its ah column and, where a
            # rank moved, the act row and co bands of the ops it holds (a
            # lane is a strided column of the mirror: touch no more)
            at = [b["ah"] + np.arange(A)]
            moved = n_old and (perm != np.arange(n_old)).any()
            if moved:
                ops = np.arange(int(self.op_count[i]))
                at += [b["act"] + ops] + [b["co"] + r * I + ops
                                          for r in range(len(actors))]
            at = np.concatenate(at)
            was = self.rows_host[at, i]
            now = was.copy()
            now[:A] = self._ahash[i]
            if moved:
                k = len(ops)
                now[A:A + k] = perm[np.clip(was[A:A + k], 0, n_old - 1)]
                co = was[A + k:].reshape(len(actors), k)
                now[A + k:] = 0
                now[A + k:].reshape(len(actors), k)[perm] = co[:n_old]
                self.lists.remap_ranks(i, perm, n_old)
                if self._clock_cache is not None \
                        and self._clock_cache.shape[1] == A:
                    # (another width: _refresh_admission_cache rebuilds)
                    row = self._clock_cache[i]
                    seen = row[:n_old].copy()
                    row[:] = 0
                    row[perm] = seen
                    if self._hrank[i] >= 0:
                        self._hrank[i] = perm[self._hrank[i]]
            diff = now != was
            self.rows_host[at[diff], i] = now[diff]
            if self._dev_current and diff.any():
                self._lane_trips.append(np.stack(
                    [at[diff], np.full(int(diff.sum()), i), now[diff]],
                    axis=1).astype(np.int32))
        self._mark_hash_dirty(plans)
        if joins:
            metrics.bump("rows_actor_joins", joins)
            metrics.bump("rows_actor_remap_lanes", lanes)

    def _take_lane_trips(self) -> list:
        """The pended lane rewrites, handed to the scatter that carries
        them (before the round's own triplets: a later write wins)."""
        trips, self._lane_trips = self._lane_trips, []
        return trips

    # ------------------------------------------------------------------
    # delta encoding to scatter triplets

    def _reserve_for(self, rounds, compactor=None) -> None:
        """Upper-bound capacity growth so row offsets stay fixed across the
        whole micro-batch. Counts submitted changes PLUS every change still
        buffered in the per-doc causal queues — a delta in this batch can
        release queued changes from earlier calls, so admitted counts are
        bounded by (queued + submitted), not by this batch alone. A
        `compactor` compacts the documents past the caps first, as
        _precheck_round_frames does."""
        need_ops = self.op_count.copy()
        need_ch = self.change_count.copy()
        n_elems = np.zeros(self.cap_docs, np.int64)
        n_lists = np.zeros(self.cap_docs, np.int64)
        new_fids = {}

        def count(i, c):
            need_ch[i] += 1
            need_ops[i] += len(c.ops)
            # every op can mint at most one new field id (assigns on
            # fresh keys, inserts minting their element's fid)
            new_fids[i] = new_fids.get(i, 0) + len(c.ops)
            for op in c.ops:
                if op.action == "ins":
                    n_elems[i] += 1
                elif op.action in ("makeList", "makeText"):
                    n_lists[i] += 1
            self.check_ingress_anchors(i, [c])

        for i, t in enumerate(self.tables):
            for p in t.queue:  # _Pending records; rows path payloads are Changes
                count(i, p.payload)
        for r in rounds:
            for doc_id, changes in r.items():
                i = self.doc_index[doc_id]
                for c in changes:
                    count(i, c)
        if compactor is not None:
            # the round-frame precheck's rule (_precheck_round_frames):
            # the documents past the caps compact first
            over = self._over_caps(need_ops, n_elems, n_lists)
            if len(over):
                self._compact_over(over, compactor)
                return self._reserve_for(rounds)
        if need_ch.max(initial=0) > self.cap_changes:
            # change ids live in the rows themselves (clock_op replaced the
            # per-change clock bands), so growing the change cap never
            # re-layouts the buffer.
            self.cap_changes = _pad_to(int(need_ch.max()))
        need_fids = max((len(self.tables[i].fields) + n
                         for i, n in new_fids.items()), default=0)
        if need_fids > self.cap_fids:
            # field ids live in the rows themselves and the blocked kernel
            # joins on fid equality directly, so the field count is
            # unbounded: growing this bookkeeping cap costs nothing.
            self.cap_fids = _pad_to(need_fids)
        # budget-check the PROSPECTIVE caps before _grow re-lays the buffer:
        # a rejected batch must leave the instance fully usable
        grow = {k: v for k, v in self._fit_or_raise(
            need_ops, n_elems, n_lists).items() if v > getattr(self, k)}
        if grow:
            self._grow(**grow)

    def _check_rows_budget(self, cap_ops: int | None = None,
                           le: int | None = None) -> None:
        from .pack import rows_dims_fit
        cap_ops = self.cap_ops if cap_ops is None else cap_ops
        le = self.cap_lists * self.cap_elems if le is None else le
        if not rows_dims_fit(cap_ops, self.cap_actors, le):
            raise _budget_error(cap_ops, self.cap_actors, le)

    def _elem_trips(self, ins) -> list:
        """A round's inserts `ins` (ListStore.append) appended to their
        lists, as scatter-triplet parts (rows, docs, values): their
        im/if/io cells, then the positions of every list they touched,
        placed where the store says so, else re-linearized."""
        t_elem = time.perf_counter()
        b, E = self._bases(), self.cap_elems
        lid, io, lists, ok = self.lists.append(ins)
        ins = ins.astype(np.int64)
        le = ins[:, 1] * E + ins[:, 2]
        parts = [(b[g] + le, ins[:, 0], v) for g, v in (
            ("im", np.ones(len(ins), np.int64)), ("if", ins[:, 6]),
            ("io", io))]
        pos = []
        if ok.any():
            row_ok = ok[lid]
            # the placed lists' indices among themselves
            sub = np.cumsum(ok) - 1
            pos.append(self.lists.placed(
                self.rows_host, b["ip"], E, lists[ok], sub[lid[row_ok]],
                ins[row_ok, 5]))
        if not ok.all():
            pos.append(self.lists.linearized(lists[~ok, :2], b["ip"], E))
        n_placed = int(ok.sum())
        metrics.bump("rows_elem_lists_placed", n_placed)
        metrics.bump("rows_elem_lists_relinearized", len(ok) - n_placed)
        metrics.bump("rows_elem_pos_rows_shipped",
                     sum(len(prow) for _, prow, _ in pos))
        metrics.observe("rows_elem_admit_seconds",
                        time.perf_counter() - t_elem)
        return parts + [(prow, pdoc, pval) for pdoc, prow, pval in pos]

    def _round_triplets(self, changes_by_doc) -> np.ndarray:
        """Encode one round into (P, 3) int32 scatter triplets
        (row, doc, value) and apply them to the host mirror."""
        b = self._bases()
        I = self.cap_ops
        rows, docs, vals = [], [], []
        ins = []

        def put(r, d, v):
            rows.append(r); docs.append(d); vals.append(int(v))

        for doc_id, changes in changes_by_doc.items():
            i = self.doc_index[doc_id]
            delta = self._encode_delta(i, changes)
            self.change_log[i].extend(delta.changes)
            s0 = int(self.op_count[i])
            c0 = int(self.change_count[i])
            for k, (code, fid, arank, seq, chg, _value, fh, vh) in enumerate(
                    delta.ops):
                s = s0 + k
                put(b["om"] + s, i, 1)
                put(b["ac"] + s, i, code)
                put(b["fid"] + s, i, fid)
                put(b["act"] + s, i, arank)
                put(b["seq"] + s, i, seq)
                put(b["chg"] + s, i, chg)
                put(b["fh"] + s, i, fh)
                put(b["vh"] + s, i, vh)
                # the op's own change-clock row, scattered into the
                # actor-major clock_op bands
                row = delta.clocks[chg - c0]
                for a in np.nonzero(row)[0]:
                    put(b["co"] + int(a) * I + s, i, row[a])
            objects = self.tables[i].objects
            for (lrow, oi, objhash) in delta.new_lists:
                self.lists.add(i, lrow, oi, objhash, objects[oi][0])
            ins += [(i, *r) for r in delta.ins]
            self.op_count[i] += len(delta.ops)
            self.change_count[i] += len(delta.clocks)

        parts = [(np.asarray(rows, np.int64), np.asarray(docs, np.int64),
                  np.asarray(vals, np.int64))]
        if ins:
            parts += self._elem_trips(np.asarray(ins, np.int64))
        trips = np.stack([np.concatenate(c) for c in zip(*parts)],
                         axis=1).astype(np.int32)
        # mirror update
        self.rows_host[trips[:, 0], trips[:, 1]] = trips[:, 2]
        return trips

    # ------------------------------------------------------------------
    # failure recovery (ADVICE r3): every apply path runs
    #   precheck -> admission (change_log/clock dicts) -> mirror scatter
    #   (rows_host) -> device dispatch
    # and each stage can fail with host state progressively ahead of the
    # device. The guards keep the instance consistent at every boundary.

    @contextlib.contextmanager
    def _dispatch_guard(self):
        """Wrap the device dispatch/readback. Host truth — change_log,
        clocks, and the rows_host mirror — is already fully updated when
        the dispatch runs, so the cheap recovery is: drop the (possibly
        donated-away) device buffer, mark dirty so the next dispatch
        re-uploads the mirror, and raise the typed error so the sync
        service knows the admission SUCCEEDED and must not be replayed."""
        try:
            yield
        except Exception as e:
            self._drop_copy()
            metrics.bump("rows_dispatch_failed")
            raise DeviceDispatchError(str(e), admission_complete=True) from e

    @contextlib.contextmanager
    def _admission_guard(self):
        """Wrap the admission + mirror-scatter region. A failure midway
        (encoder error, grow/copy MemoryError, the defensive budget check)
        can leave change_log/clocks ahead of the rows_host mirror AND an
        unprocessed suffix of the batch in neither log nor queue. If
        anything was admitted, rebuild row state from the authoritative
        log and raise the typed error with admission_complete=False: the
        caller should replay the whole batch — the (actor, seq) dedup
        drops the already-admitted prefix idempotently, so the retry
        admits exactly the lost remainder. If nothing was admitted, the
        original error propagates and the caller may safely retry."""
        with perfscope.phase("encode"):     # one length a document
            log_lens = list(map(len, self.change_log))
        try:
            yield
        except DeviceDispatchError:
            raise  # dispatch guard already recovered; admission stands
        except Exception as e:
            if any(len(log) != n
                   for log, n in zip(self.change_log, log_lens)):
                if getattr(self, "_rebuilding", False):
                    # a rebuild replay must not trigger a nested rebuild
                    # (the failure is deterministic) — poison and fail fast
                    self._poison(e)
                    raise
                metrics.bump("rows_log_rebuilt")
                self._rebuild_from_log()
                raise DeviceDispatchError(
                    str(e), admission_complete=False) from e
            raise

    def _poison(self, cause) -> None:
        self._poisoned = (f"resident row state no longer reflects the "
                          f"admitted change log ({cause!r}); rebuild the "
                          f"node from its durable log")
        metrics.bump("rows_engine_poisoned")

    def _check_poisoned(self) -> None:
        msg = getattr(self, "_poisoned", None)
        if msg:
            raise RuntimeError(msg)

    def archive_log_prefix(self, doc_id: str,
                           floor: dict[str, int]) -> int:
        """Log-horizon layer: move the causally-stable prefix of one doc's
        admitted log (every change with seq <= floor[actor]) out of RAM
        into self.log_archive, advancing self.log_horizon. The floor must
        be a causal-stability floor (service._compaction_floor_locked):
        such floors are transitive clocks, so the prefix is causally
        closed and archive-then-tail replay order is always valid.
        Returns the number of changes archived (0 when no archive is
        attached or nothing is below the floor)."""
        from .resident import AdmittedRef

        if self.log_archive is None or not floor:
            return 0
        i = self.doc_index[doc_id]
        hz = self.log_horizon[i]
        if not any(s > hz.get(a, 0) for a, s in floor.items()):
            # floor has not advanced past the horizon (e.g. a lagging peer
            # pins it): nothing below it is still in RAM — skip the O(log)
            # scan the auto-trigger would otherwise pay on every flush
            return 0
        keep, move = [], []
        for c in self.change_log[i]:
            (move if c.seq <= floor.get(c.actor, 0) else keep).append(c)
        if not move:
            return 0
        self.log_archive.append(
            doc_id, [c.change() if isinstance(c, AdmittedRef) else c
                     for c in move])
        self.change_log[i] = keep
        hz = self.log_horizon[i]
        for a, s in floor.items():
            if s > hz.get(a, 0):
                hz[a] = int(s)
        metrics.bump("rows_horizon_truncated")
        return len(move)

    @staticmethod
    def _archive_covers_floor(archived, floor: dict[str, int]) -> bool:
        """True when the archived changes include each floor actor's
        history FROM SEQ 1 — i.e. the archive holds the doc's full
        prefix, not just a post-bootstrap tail. A wire-snapshot-booted
        replica that later archives its own tail has a NON-empty
        archive that still does not cover the compacted prefix; replay
        paths must route through the image for such docs (per-actor
        seqs are dense from 1 and archive_log_prefix moves contiguous
        prefixes, so min-seq == 1 is the coverage witness)."""
        if not floor:
            return True
        mins: dict[str, int] = {}
        for c in archived:
            if c.actor in floor and c.seq < mins.get(c.actor, 1 << 62):
                mins[c.actor] = c.seq
        return all(mins.get(a) == 1 for a in floor)

    def seed_clock(self, doc_id: str, clock: dict[str, int],
                   head_closures: dict | None = None) -> None:
        """Snapshot-bootstrap seeding (sync/snapshots.py): after a doc's
        compacted (renumbered) snapshot frame admitted through the
        ordinary ingress, raise the doc's clock to the ORIGINAL covered
        clock so the suffix — archive tail or live sync — admits with
        its original seqs and below-clock redeliveries drop
        idempotently. `head_closures` (per-actor transitive clocks of
        the covered heads, the engine's state_clocks convention of
        excluding the own coordinate) are memoized so `causal_floor`
        and later slow-path clock rows can expand references to the
        seeded heads; `snap_floor` arms the post-seed clock-row clamp
        (resident.DocTables.snap_floor)."""
        i = self.doc_index[doc_id]
        t = self.tables[i]
        self._sync_stale_table(t)
        self._register_doc_actors({i: set(clock)})
        heads = head_closures or {}
        for a, s in clock.items():
            if s > t.clock.get(a, 0):
                t.clock[a] = int(s)
            t.state_clocks[(a, int(s))] = dict(heads.get(a) or {})
        # frontier := the seeded heads not covered by another head's
        # closure (the pruned maximal set the reference keeps as deps)
        t.frontier = {
            a: int(s) for a, s in clock.items()
            if not any(o != a and (heads.get(o) or {}).get(a, 0) >= s
                       for o in clock)}
        t.snap_floor = {a: int(s) for a, s in clock.items()}
        self._cache_dirty.add(i)
        metrics.bump("sync_bootstrap_docs")

    def _rebuild_from_log(self) -> None:
        """Disaster recovery: reconstruct the whole instance from the
        admitted change log (the authoritative record) plus any causally-
        buffered queue payloads, then adopt the fresh state in place. A
        device outage during the rebuild is fine — the fresh instance's
        own dispatch guard leaves it host-consistent and dirty, and the
        next read re-uploads its mirror. If the replay fails for any OTHER
        reason (the original failure was deterministic, e.g. the batch
        genuinely exceeds capacity), the instance is poisoned: serving
        reads would silently drop admitted changes, so every later
        apply/read raises loudly instead.

        With a log horizon the RAM log is only the tail: the archived
        prefix is cold-read back and replayed first (it is causally closed
        below the floor). The rebuilt instance holds the FULL log in RAM
        again with an empty horizon — the service's next threshold pass
        re-archives; the archive's (actor, seq) read-dedup makes the
        resulting re-append harmless."""
        from .resident import AdmittedRef

        docs = list(self.doc_ids)
        round_: dict[str, list] = {}
        snap_replay: dict[str, object] = {}
        for i, d in enumerate(docs):
            chs = []
            snap_floor = getattr(self.tables[i], "snap_floor", None)
            if self.log_archive is not None and self.log_horizon[i]:
                archived = self.log_archive.read(d)
                if snap_floor and not self._archive_covers_floor(
                        archived, snap_floor):
                    # the local archive holds only this replica's
                    # post-bootstrap tail — the prefix lives in the
                    # image; keep the archived tail for the round
                    chs.extend(c for c in archived
                               if c.seq > snap_floor.get(c.actor, 0))
                else:
                    chs.extend(archived)
                    snap_floor = None   # full prefix on disk: no image
            if snap_floor:
                # snapshot-booted doc whose archive (if any) lacks the
                # compacted prefix: the image is the only durable copy
                # — replay it (and re-seed) before the tail. Losing it
                # poisons the rebuild (serving a tail-only doc as truth
                # would be silent divergence).
                img = (self.snapshot_store.load(d)
                       if self.snapshot_store is not None else None)
                if img is None:
                    e = RuntimeError(
                        f"rebuild of snapshot-booted doc {d!r}: no "
                        "archived prefix and no local snapshot image")
                    self._poison(e)
                    raise e
                snap_replay[d] = img
            chs.extend(c.change() if isinstance(c, AdmittedRef) else c
                       for c in self.change_log[i])
            for p in self.tables[i].queue:
                pay = p.payload
                chs.append(AdmittedRef(*pay).change()
                           if isinstance(pay, tuple) else pay)
            if chs:
                round_[d] = chs
        fresh = ResidentRowsDocSet(docs, native=self._native is not None)
        fresh.reserve(actors=self.cap_actors)   # one layout for the replay
        fresh.log_archive = self.log_archive
        fresh.snapshot_store = self.snapshot_store
        fresh.compaction_floors = dict(self.compaction_floors)
        fresh.device = self.device
        fresh.lazy_dispatch = self.lazy_dispatch
        fresh._rebuilding = True
        try:
            for d, img in snap_replay.items():
                fresh.apply_rounds([{d: img.columns().to_changes()}])
                fresh.seed_clock(d, img.clock, img.heads)
                i2 = fresh.doc_index[d]
                # the image is the doc's below-horizon truth, not a
                # re-servable log prefix (renumbered seqs)
                fresh.change_log[i2] = []
                fresh.log_horizon[i2] = dict(img.clock)
            if round_:
                try:
                    fresh.apply_rounds([round_])
                except RowsBudgetError:
                    # a compacted long-lived doc's full log exceeds the
                    # budget by design — replay in chunks, re-compacting
                    # with the stored floors between them
                    self._replay_chunked(fresh, round_)
        except DeviceDispatchError:
            pass
        except Exception as e:
            self._poison(e)
            raise
        fresh._rebuilding = False
        gen = getattr(self, "_rebuild_gen", 0)
        # the hash epoch must stay monotonic ACROSS the rebuild: a sync
        # layer holding a pre-rebuild epoch must see every post-rebuild
        # read as dirty (the fresh instance restarts its counter at 0)
        epoch = max(self.hash_epoch, fresh.hash_epoch) + 1
        self.__dict__.clear()
        self.__dict__.update(fresh.__dict__)
        self._rebuild_gen = gen + 1
        self.hash_epoch = epoch

    def _replay_chunked(self, fresh: "ResidentRowsDocSet", round_: dict,
                        chunk: int = 256) -> None:
        """Budget-safe rebuild replay: admit the log in per-doc chunks,
        compacting to the last-known floors between chunks so the rebuilt
        row state converges to the same compacted footprint the original
        instance carried. Anchors referenced by the not-yet-replayed tail
        are pinned — the log legitimately inserts after elements whose
        tombstones are below the stored floor (they were ghosted only
        AFTER those inserts admitted in the original instance)."""
        pos = {d: 0 for d in round_}
        while True:
            part = {d: chs[pos[d]:pos[d] + chunk]
                    for d, chs in round_.items() if pos[d] < len(chs)}
            if not part:
                return
            try:
                fresh.apply_rounds([part])
            except RowsBudgetError:
                # a stored-empty floor ({}) means "nothing reclaimable"
                # (peer-vetoed) and must be honored as-is — only docs with
                # NO stored floor fall back to their own replayed clock
                floors = {d: (self.compaction_floors[d]
                              if d in self.compaction_floors
                              else dict(
                                  fresh.tables[fresh.doc_index[d]].clock))
                          for d in fresh.doc_ids}
                fresh.compact(floors, anchor_pins(
                    {d: [chs[pos[d]:]] for d, chs in round_.items()}))
                fresh.apply_rounds([part])
            for d, chs in part.items():
                pos[d] += len(chs)

    # ------------------------------------------------------------------
    # device path

    def apply_rounds(self, rounds, interpret: bool | None = None,
                     compactor=None):
        """Apply a micro-batch of sync rounds in ONE device dispatch.

        rounds: list of {doc_id: [Change]} — applied in order, reconciling
        after each. Returns np.ndarray [len(rounds), n_docs] uint32 state
        hashes (one row per round). `compactor`: as dispatch_round_frames'
        (the Python encoder's served path).

        Actor ranks are the sorted-string ranks of the WHOLE micro-batch's
        actor universe (all rounds are registered before any is encoded, so
        the scan runs as one device dispatch over fixed-shape rows).
        Consequence: the hash reported for an intermediate round k is
        computed under ranks that may include actors first appearing in
        rounds > k, so it is only comparable to hashes produced under the
        same final actor universe (e.g. other rows of this same call, or a
        `hashes()` call after the batch). The FINAL round's hash always
        equals the canonical post-batch hash.
        """
        self._check_poisoned()
        if self._native is not None:
            from ..native.wire import changes_to_columns
            return self.apply_rounds_cols(
                [{d: changes_to_columns(chs) for d, chs in r.items()}
                 for r in rounds], interpret)
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        for r in rounds:
            self._register_actors(r)
        self._reserve_for(rounds, compactor)
        with self._admission_guard():
            pre_rows = self.rows_host.copy() \
                if not self._dev_current else None
            trip_list = [self._round_triplets(r) for r in rounds]
            with self._dispatch_guard():
                return self._dispatch_rounds(trip_list, pre_rows, interpret)

    def apply_rounds_cols(self, rounds, interpret: bool | None = None):
        """Columnar-native variant of apply_rounds: each round maps doc_id ->
        WireColumns (a decoded wire frame). Ingress is frame bytes -> native
        C++ delta encoder -> vectorized numpy triplet assembly -> one scan
        dispatch; no per-op Python anywhere on the path (the round's causal
        admission and clock rows stay per-CHANGE Python, as in the base
        class's apply_columns). Same return and actor-universe semantics as
        apply_rounds."""
        self._check_poisoned()
        if self._native is None:
            return self.apply_rounds(
                [{d: c.to_changes() for d, c in r.items()} for r in rounds],
                interpret)
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        for r in rounds:
            self._register_actors_cols(r)
        # Reject an oversized batch BEFORE admission mutates any state
        # (seen-sets, clocks, change logs, C++ tables); afterwards the
        # instance could no longer retry the same changes.
        self._precheck_rows_budget_cols(rounds)
        with self._admission_guard():
            encoded = [self._native_encode_round(r) for r in rounds]
            self._grow_for_rounds(encoded)
            pre_rows = self.rows_host.copy() \
                if not self._dev_current else None
            trip_list = [self._cols_triplets(e) for e in encoded]
            with self._dispatch_guard():
                return self._dispatch_rounds(trip_list, pre_rows, interpret)

    def _to_dev(self, arr):
        """Upload pinned to this instance's device (None = default)."""
        with perfscope.phase("upload"):
            if self.device is not None:
                return jax.device_put(arr, self.device)
            return jnp.asarray(arr)

    @staticmethod
    def _to_host(handle) -> np.ndarray:
        """Read a device array back, inside the caller's `readback`
        phase: the wait for the device (`device_wait`) and then the
        copy. One synchronisation point, as np.asarray alone was."""
        with perfscope.phase("device_wait"):
            handle.block_until_ready()
        return np.asarray(handle)

    def _mark_trips_dirty(self, trip_list) -> set:
        """Hash invalidation for the lanes a batch of scatter triplets
        touches (BEFORE the dispatch: a failed dispatch leaves host truth
        updated, so these lanes must re-reconcile either way). Returns
        the touched lane set (the dispatch ledger's docs-served count)."""
        touched = {int(d) for t in trip_list for d in np.unique(t[:, 1])}
        if touched:
            self._mark_hash_dirty(touched)
        return touched

    def _dispatch_rounds(self, trip_list, pre_rows, interpret):
        if pre_rows is None and self._lane_trips and trip_list:
            # the copy is current: the first round's scatter carries the
            # lanes a registration rewrote (last write wins on a cell)
            merged, n = self._merged_trips(
                self._take_lane_trips() + trip_list[:1])
            trip_list = [merged[:n]] + trip_list[1:]
        p = _pad_to(max((len(t) for t in trip_list), default=1), 8)
        oob = self._bases()["rows"]  # out-of-range row => dropped by scatter
        stacked = np.full((len(trip_list), p, 3), 0, dtype=np.int32)
        for k, t in enumerate(trip_list):
            stacked[k, :len(t)] = t
            stacked[k, len(t):, 0] = oob
        touched = self._mark_trips_dirty(trip_list)
        if pre_rows is not None:
            self.rows_dev = self._to_dev(pre_rows)
            self._dirty = False
        stacked_dev = self._to_dev(stacked)
        with dispatchledger.call_scope(
                "rows_scan", backend="device", docs=len(touched),
                axes={"docs": (len(self.doc_ids), self.n_pad),
                      "rounds": (len(trip_list), len(trip_list)),
                      "trips": (max((len(t) for t in trip_list),
                                    default=1), p)}):
            self.rows_dev, hashes = metrics.dispatch_jit(
                "scan_rounds", _scan_rounds,
                self.rows_dev, stacked_dev, self.dims(), interpret)
        self._hash_handle = None
        self._h_prev = None
        with perfscope.phase("readback"):
            vals = self._to_host(hashes)
        # the FINAL round's row is the canonical post-batch hash table:
        # adopt it so the next hashes() read is free (flush-time capture)
        self._adopt_full_hashes(vals[-1])
        return vals[:, :len(self.doc_ids)]

    # ------------------------------------------------------------------
    # native columnar ingress

    def check_ingress_anchors(self, i: int, part, lo: int = 0,
                              hi: int | None = None) -> None:
        """ListStore.check: refuse an insert of document i anchored at a
        ghost of its list, before admission (CompactionAnchorError)."""
        self.lists.check(i, self.doc_ids[i], part, lo, hi)

    def _precheck_rows_budget_cols(self, rounds) -> None:
        """Upper-bound VMEM-budget check from the submitted columns plus the
        causal queues, BEFORE any admission runs (the cols analog of
        _reserve_for's ordering). Conservative: duplicates and non-admitted
        changes are counted as if applied; the exact post-encode check in
        _grow_for_rounds still runs."""
        from ..storage import _ACTION_IDX
        ins_code = _ACTION_IDX["ins"]
        list_idxs = (_ACTION_IDX["makeList"], _ACTION_IDX["makeText"])

        need_ops = self.op_count.copy()
        n_elems = np.zeros(self.cap_docs, np.int64)
        n_lists = np.zeros(self.cap_docs, np.int64)

        def count(i, cols, j):
            o0, o1 = int(cols.op_off[j]), int(cols.op_off[j + 1])
            need_ops[i] += o1 - o0
            acts = np.asarray(cols.op_action[o0:o1])
            n_elems[i] += int((acts == ins_code).sum())
            n_lists[i] += int(np.isin(acts, list_idxs).sum())
            self.check_ingress_anchors(i, cols, o0, o1)

        for i, t in enumerate(self.tables):
            for p in t.queue:  # native instances queue (cols, j) payloads
                count(i, *p.payload)
        for r in rounds:
            for doc_id, cols in r.items():
                i = self.doc_index[doc_id]
                for j in range(cols.n_changes):
                    count(i, cols, j)
        self._fit_or_raise(need_ops, n_elems, n_lists)

    def _native_encode_round(self, cols_by_doc):
        """Causal admission (Python, per change) + ONE native batch encode
        for the round (shared protocol in the base class). Returns the
        native BatchDelta plus the admission-aligned clock matrix, or None
        if nothing was admitted."""
        from .resident import AdmittedRef

        clock_rows = []

        def on_admitted(i, t, ready):
            self.change_log[i].extend(
                AdmittedRef(*p.payload) for p in ready)
            for p in ready:
                clock_rows.append(self._clock_row(t, p.actor, p.seq, p.deps))

        bd, adm_doc, cidxs = self._native_ingest_round(cols_by_doc,
                                                       on_admitted)
        if bd is None:
            return None
        return {
            "bd": bd,
            "clock_mat": np.stack(clock_rows),
            "adm_doc": np.asarray(adm_doc, np.int64),
            "adm_cidx": np.asarray(cidxs, np.int64),
        }

    def _grow_for_rounds(self, encoded) -> None:
        """Exact capacity growth from the already-encoded rounds (the native
        encoder reports precisely which op/elem/list slots each round fills,
        so no estimation is needed)."""
        need_ops = self.op_count.copy()
        for enc in encoded:
            if enc is None:
                continue
            doc = enc["bd"].op_rows[:, 0]
            if len(doc):
                ids, cnts = np.unique(doc, return_counts=True)
                need_ops[ids] += cnts
        grow = {}
        if need_ops.max(initial=0) > self.cap_ops:
            grow["cap_ops"] = _pad_to(int(need_ops.max()))
        if self._lists_hi > self.cap_lists:
            grow["cap_lists"] = _pad_to(self._lists_hi, 1)
        if self._elems_hi > self.cap_elems:
            grow["cap_elems"] = _pad_to(self._elems_hi)
        self._check_rows_budget(
            grow.get("cap_ops", self.cap_ops),
            grow.get("cap_lists", self.cap_lists)
            * grow.get("cap_elems", self.cap_elems))
        if grow:
            self._grow(**grow)
        if self._changes_hi > self.cap_changes:
            self.cap_changes = _pad_to(self._changes_hi)

    def _cols_triplets(self, enc) -> np.ndarray:
        """Vectorized scatter-triplet assembly from one round's BatchDelta
        (the numpy replacement for _round_triplets' per-op Python loop)."""
        if enc is None:
            return np.zeros((0, 3), np.int32)
        b = self._bases()
        I = self.cap_ops
        bd = enc["bd"]
        parts = []          # (rows, docs, values)

        op = bd.op_rows.astype(np.int64)
        if len(op):
            doc = op[:, 0]
            # rows are doc-grouped in admission order: within-group index
            # via each row's group start
            starts = np.searchsorted(doc, doc, side="left")
            slot = self.op_count[doc] + (np.arange(len(op)) - starts)
            for g, v in (("om", np.ones(len(op), np.int64)), ("ac", op[:, 1]),
                         ("fid", op[:, 2]), ("act", op[:, 3]),
                         ("seq", op[:, 4]), ("chg", op[:, 5]),
                         ("fh", op[:, 7]), ("vh", op[:, 8])):
                parts.append((b[g] + slot, doc, v))
            # per-op change-clock rows into the actor-major clock_op bands;
            # (doc, cidx) keys are ascending in both arrays, so the op ->
            # admitted-change join is one searchsorted
            key_adm = enc["adm_doc"] * (1 << 32) + enc["adm_cidx"]
            key_op = doc * (1 << 32) + op[:, 5]
            ai = np.searchsorted(key_adm, key_op)
            cmat = enc["clock_mat"][ai]                      # [k, A]
            oi, a = np.nonzero(cmat)
            parts.append((b["co"] + a * I + slot[oi], doc[oi], cmat[oi, a]))
            ids, cnts = np.unique(doc, return_counts=True)
            self.op_count[ids] += cnts
        ids, cnts = np.unique(enc["adm_doc"], return_counts=True)
        self.change_count[ids] += cnts

        # a new list's object id: the new objects of list kinds, in the
        # order the encoder made them (and their newlist rows)
        oids = [name for (_d, name, kind) in bd.new_objects
                if kind in (A_MAKE_LIST, A_MAKE_TEXT)]
        for (d, lrow, oi, objhash), oid in zip(bd.newlist_rows.tolist(),
                                               oids):
            self.lists.add(d, lrow, oi, objhash, oid)
        if len(bd.ins_rows):
            parts += self._elem_trips(bd.ins_rows)

        if not parts:
            return np.zeros((0, 3), np.int32)
        trips = np.stack([np.concatenate(c) for c in zip(*parts)],
                         axis=1).astype(np.int32)
        self.rows_host[trips[:, 0], trips[:, 1]] = trips[:, 2]
        return trips

    # ------------------------------------------------------------------
    # round-frame ingress: the streaming sync service's hot path

    def apply_round_frames(self, frames, interpret: bool | None = None):
        """Apply a micro-batch of sync rounds shipped as ROUND FRAMES:
        the dispatch half and the collect half, one after the other.
        Returns the device array of the post-batch per-doc hashes, padded
        to n_pad (slice [:len(doc_ids)] after np.asarray): the handle the
        one-program routes leave on the device (`blocks`, `whole` with
        readback False: not read back, the next hashes() consumes it), a
        copy of the host hash mirror after a route that reads back; None
        under `deferred`."""
        with metrics.trace("rows_round_apply"):
            h, read_back = self._dispatch_round_frames(frames, interpret)
            if not read_back:
                return h
            if self._unsettled is not None:
                self._collect_round(interpret)
            n = len(self.doc_ids)
            out = np.zeros(self.n_pad, np.uint32)
            out[:n] = self._hash_mirror[:n]
            return self._to_dev(out)

    def dispatch_round_frames(self, frames, interpret: bool | None = None,
                              compactor=None) -> None:
        """The dispatch half alone, for a caller with host work of its
        own that needs no hash (the sync service's tail): everything up to
        and including the dispatch of the round's reconcile. The caller
        owes one collect_round() before it lets anyone read the round as
        flushed; the engine stays sound if it never comes (the round's
        lanes stay dirty, and every entry that reads a hash or dirties a
        lane settles or drops the unsettled round first). `compactor`
        (doc ids -> (floors, pins)) lets the precheck compact the
        documents the round takes past the caps (_precheck_round_frames);
        without one the round grows the caps or raises RowsBudgetError."""
        with metrics.trace("rows_round_apply"):
            self._dispatch_round_frames(frames, interpret, compactor)

    def collect_round(self, interpret: bool | None = None) -> None:
        """The collect half of dispatch_round_frames: the round's hashes
        read into the host mirror (`readback`, the one wait for the
        device), its lanes clean, the device copy primed after a host
        gather, and lanes left dirty from outside the round routed as a
        read. A no-op where the round left nothing unsettled (the fused
        route reads its buckets back in the dispatch; `blocks`, `whole`
        with readback False and `deferred` read nothing back). A device
        failure that surfaces here is the dispatch guard's: the copy
        dropped, the lanes dirty, DeviceDispatchError with
        admission_complete=True."""
        if self._unsettled is not None:
            self._collect_round(interpret)

    def _collect_round(self, interpret) -> None:
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        with self._dispatch_guard():
            # settles, then routes what is dirty from outside the round
            # (a failed dispatch, a deferred read) as a read; the steady
            # round leaves none
            self._refresh_hash_mirror(None, interpret)

    def _dispatch_round_frames(self, frames, interpret, compactor=None):
        """The dispatch half of a round (sync/frames.py AMR1: one columnar
        frame per round covering every document touched that round):
        decode, registration, precheck, admission and the native delta
        encode (`encode`); the triplets committed to the host mirror and
        their lanes marked dirty (`commit`); where dispatch.scatters_first
        says so, the round's scatter sent to the device; then the router,
        once (`route`), and its route executed up to and including the
        dispatch of the reconcile (_dispatch_final). Hashes are read back
        here only by the fused route (a bucket at a time); `lanes` and
        read-back `whole` leave their vector as the unsettled round for
        the collect half, the one-program routes leave theirs on the
        device as the pending handle.

        Returns (handle, read_back): the device hash array of every lane
        where the route leaves one on the device (else None), and whether
        the route is one whose hashes reach the host mirror with the
        collect (False: `deferred` and the one-program routes).

        frames: list of round-frame bytes (or decoded RoundColumns).
        Documents must already exist in this set.
        """
        from ..sync.frames import RoundColumns, decode_round_frame

        self._check_poisoned()
        rounds = [f if isinstance(f, RoundColumns) else decode_round_frame(f)
                  for f in frames]
        if self._native is None:
            # Python-encoder fallback: same semantics, per-doc Change path.
            h = self.apply_rounds([rc.to_dict() for rc in rounds], interpret,
                                  compactor)
            return self._to_dev(h[-1] if len(h) else
                                self.hashes(interpret=interpret)), False
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        # Nothing on this path creates reference cycles, but its allocation
        # bursts (admitted refs, delta rows) trigger generational GC scans
        # over the whole service heap — measured at ~2/3 of the ingress cost
        # on a 2K-doc node (same pathology core/bulkload.py documents).
        from ..utils.gcpause import gc_paused
        self._gid_memo.clear()
        with gc_paused():
            with perfscope.phase("encode"):
                t0 = time.perf_counter()
                for rc in rounds:
                    self._register_round_actors(rc)
                metrics.observe("rows_actor_register_seconds",
                                time.perf_counter() - t0)
            self._precheck_round_frames(rounds, compactor)
            # steady-state fast path: ONE vectorized admission + native
            # encode for the whole micro-batch; falls back to per-round
            # encode (full protocol handling) when any change breaks the
            # per-doc in-order chain shape
            with self._admission_guard():
                with perfscope.phase("encode"):
                    enc_all = self._encode_rounds_batched(rounds)
                    if enc_all is not None:
                        metrics.bump("rows_rounds_batched", len(rounds))
                        encoded = [enc_all]
                    else:
                        encoded = [self._encode_round_frame(rc)
                                   for rc in rounds]
                    admitted = [e for e in encoded if e is not None]
                    metrics.bump("rows_changes_admitted", sum(
                        len(e["adm_doc"]) for e in admitted))
                    metrics.bump("rows_changes_admitted_general", sum(
                        e.get("n_general", 0) for e in admitted))
                with perfscope.phase("commit"):
                    self._grow_for_rounds(encoded)
                    trip_list = self._take_lane_trips() + [
                        self._cols_triplets(e) for e in encoded]
                    touched = sorted(self._mark_trips_dirty(trip_list))
                    round_docs = len({d for rc in rounds
                                      for d in rc.doc_ids})
                with self._dispatch_guard():
                    if round_dispatch.scatters_first(
                            self, touched, round_docs):
                        # the chip scatters while the router reads the
                        # host mirror
                        self._scatter_round(trip_list, len(touched))
                        trip_list = None
                    # routed AFTER the trips commit: the fused route's
                    # bucket shapes see this round's ops
                    route = round_dispatch.reconcile_route(
                        self, touched, round_docs)
                    h = self._dispatch_final(trip_list, route, interpret)
                    if self._dev_current and not self.lazy_dispatch:
                        self._warm_put()
                    return h, route.readback and route.kind != "deferred"

    def _frame_gids(self, cols) -> np.ndarray:
        """The instance's id of each name in a frame's actor table; a
        name never met gets the next id. Kept for the frame while its
        round is applied (an id never changes)."""
        memo = self._gid_memo.get(id(cols))
        if memo is None:
            gid = self._gid
            memo = self._gid_memo[id(cols)] = (cols, np.fromiter(
                (gid.setdefault(a, len(gid)) for a in cols.actors),
                np.int64, len(cols.actors)))
        return memo[1]

    def _ranks_in(self, docs: np.ndarray, gids: np.ndarray) -> np.ndarray:
        """Rank of actor gids[k] in document docs[k], a position in the
        document's own sorted list; -1 where it has not written there."""
        hit = self._doc_gids[docs] == gids[:, None]
        return np.where(hit.any(axis=1), hit.argmax(axis=1), -1)

    def _register_round_actors(self, rc) -> None:
        """Register the frame's writers with the documents they write:
        one compare finds the (document, actor) pairs not met yet, and
        only those are walked."""
        cols = rc.cols
        if not cols.n_changes:
            return
        doc_of_k = np.fromiter((self.doc_index[d] for d in rc.doc_ids),
                               np.int64, len(rc.doc_ids))
        chg_doc = np.repeat(doc_of_k, np.diff(
            np.asarray(rc.change_off, np.int64)))
        chg_actor = np.asarray(cols.change_actor, np.int64)
        new = np.nonzero(self._ranks_in(
            chg_doc, self._frame_gids(cols)[chg_actor]) < 0)[0]
        if len(new):
            names: dict[int, set] = {}
            for i, k in zip(chg_doc[new].tolist(), chg_actor[new].tolist()):
                names.setdefault(i, set()).add(cols.actors[k])
            self._register_doc_actors(names)

    def _precheck_round_frames(self, rounds, compactor=None) -> None:
        """Vectorized VMEM-budget precheck for round frames (the analog of
        _precheck_rows_budget_cols, one numpy pass per round instead of
        per-change slicing), plus the ghost-anchor reject for compacted
        docs. With a `compactor` the documents the round would take past
        the current caps are compacted first, each to its own floor
        (_compact_over); only what still passes the caps grows them, and
        only where a kernel takes the grown dims: else RowsBudgetError."""
        with perfscope.phase("encode"):
            need = self._round_needs(rounds)
            over = self._over_caps(*need) if compactor is not None else ()
        if len(over):
            self._compact_over(over, compactor)
            with perfscope.phase("encode"):
                need = self._round_needs(rounds)
        self._fit_or_raise(*need)

    def _round_needs(self, rounds):
        """Each document's op rows, inserts and new lists once the round
        and the causal queues are admitted, as [cap_docs] arrays (an upper
        bound: duplicates count as applied). An insert of the round
        anchored at a compacted element raises CompactionAnchorError."""
        from ..storage import _ACTION_IDX
        ins_code = _ACTION_IDX["ins"]
        l1, l2 = _ACTION_IDX["makeList"], _ACTION_IDX["makeText"]

        need_ops = self.op_count.copy()
        n_elems = np.zeros(self.cap_docs, np.int64)
        n_lists = np.zeros(self.cap_docs, np.int64)
        for i in list(getattr(self, "_queued_docs", ())):
            t = self.tables[i]
            for p in t.queue:
                cols, j = p.payload
                o0, o1 = int(cols.op_off[j]), int(cols.op_off[j + 1])
                need_ops[i] += o1 - o0
                acts = np.asarray(cols.op_action[o0:o1])
                n_elems[i] += int((acts == ins_code).sum())
                n_lists[i] += int(((acts == l1) | (acts == l2)).sum())
        ghosted = self.lists.ghosted
        for rc in rounds:
            cols = rc.cols
            doc_idx = np.fromiter((self.doc_index[d] for d in rc.doc_ids),
                                  np.int64, len(rc.doc_ids))
            off = np.asarray(rc.change_off, np.int64)
            op_off = np.asarray(cols.op_off, np.int64)
            ops_per_doc = op_off[off[1:]] - op_off[off[:-1]]
            np.add.at(need_ops, doc_idx, ops_per_doc)
            acts = np.asarray(cols.op_action)
            is_ins = acts == ins_code
            is_list = (acts == l1) | (acts == l2)
            if is_ins.any() or is_list.any():
                op_doc = np.repeat(doc_idx, ops_per_doc)
                np.add.at(n_elems, op_doc, is_ins)
                np.add.at(n_lists, op_doc, is_list)
            if ghosted and is_ins.any():
                for k, i in enumerate(doc_idx.tolist()):
                    if i in ghosted:
                        self.check_ingress_anchors(
                            i, cols, int(op_off[off[k]]),
                            int(op_off[off[k + 1]]))
        return need_ops, n_elems, n_lists

    def _over_caps(self, need_ops, n_elems, n_lists,
                   fresh: bool = False) -> np.ndarray:
        """Documents the round would take past the current op or element
        caps: with history alone, the ones compaction can make room in (a
        fresh document has nothing to reclaim); with `fresh` every one,
        and those past the list cap too."""
        over = need_ops > self.cap_ops
        el = np.flatnonzero(n_elems)
        if len(el):
            max_elems = np.fromiter((self.tables[i].max_elems for i in el),
                                    np.int64, len(el))
            over[el[max_elems + n_elems[el] > self.cap_elems]] = True
        if fresh:
            ls = np.flatnonzero(n_lists)
            n_now = np.fromiter((self.tables[i].n_lists for i in ls),
                                np.int64, len(ls))
            over[ls[n_now + n_lists[ls] > self.cap_lists]] = True
        over = np.flatnonzero(over[:len(self.doc_ids)])
        return over if fresh else over[self.op_count[over] > 0]

    def _compact_over(self, over, compactor) -> None:
        """Compact the documents `over` (indices), each to the floor the
        `compactor` gives it: compactor(doc ids) -> (floors, pins), the
        sync service's clock floors and the anchors of its pending round.
        Where the device copy is current the rewritten lanes are written
        into it (compaction.compact, _put_lanes)."""
        docs = [self.doc_ids[i] for i in over.tolist()]
        t0 = time.perf_counter()
        with perfscope.phase("compact"):
            floors, pins = compactor(docs)
            stats = self.compact(floors, pins)
        metrics.observe("rows_compact_seconds", time.perf_counter() - t0)
        metrics.bump("rows_compact_docs", len(docs))
        ops0 = sum(s["ops_before"] for s in stats.values())
        metrics.bump("rows_compact_ops_before", ops0)
        metrics.bump("rows_compact_ops_reclaimed",
                     ops0 - sum(s["ops_after"] for s in stats.values()))
        metrics.bump("rows_compact_slots_reclaimed", sum(
            s["elems_before"] - s["elems_after"] for s in stats.values()))

    def _fit_or_raise(self, need_ops, n_elems, n_lists) -> dict:
        """The caps the round needs, {"cap_ops", "cap_elems", "cap_lists"};
        RowsBudgetError where they are dims no kernel takes, naming the
        documents past the current caps (the round without them fits)."""
        cap_ops = max(self.cap_ops, _pad_to(int(need_ops.max(initial=1))))
        # a document's lists after the round: its own largest and count
        # now, and at most what its ops add
        tables = self.tables
        cap_elems = max(self.cap_elems, _pad_to(max(
            (tables[i].max_elems + int(n_elems[i])
             for i in np.flatnonzero(n_elems)), default=0)))
        cap_lists = max(self.cap_lists, _pad_to(max(
            (tables[i].n_lists + int(n_lists[i])
             for i in np.flatnonzero(n_lists)), default=0), 1))
        from .pack import rows_dims_fit
        if not rows_dims_fit(cap_ops, self.cap_actors,
                             cap_lists * cap_elems):
            over = self._over_caps(need_ops, n_elems, n_lists, fresh=True)
            raise _budget_error(cap_ops, self.cap_actors,
                                cap_lists * cap_elems,
                                [self.doc_ids[i] for i in over.tolist()])
        return {"cap_ops": cap_ops, "cap_elems": cap_elems,
                "cap_lists": cap_lists}

    def _refresh_admission_cache(self) -> None:
        """Rebuild the dense clock/frontier cache rows for stale docs. The
        DocTables dicts stay authoritative; the cache exists so a round's
        admission checks run as a handful of numpy gathers."""
        D, A = self.cap_docs, self.cap_actors
        if self._clock_cache is None \
                or self._clock_cache.shape != (D, A):
            # full rebuild reads every table's dicts: materialize any
            # fast-path-stale tables from the OLD cache before zeroing it
            self.sync_tables()
            self._clock_cache = np.zeros((D, A), np.int64)
            self._fsize = np.zeros(D, np.int64)
            self._hrank = np.full(D, -1, np.int64)
            self._hseq = np.zeros(D, np.int64)
            dirty = range(len(self.doc_ids))
        elif self._cache_dirty:
            dirty = self._cache_dirty
        else:
            return
        cc, fs, hr, hs = (self._clock_cache, self._fsize,
                          self._hrank, self._hseq)
        for i in dirty:
            t = self.tables[i]
            if t._stale_idx is not None:
                # fast-path-stale AND dirtied: the dicts must be current
                # before this rebuild reads them
                self._sync_stale_table(t)
            rank_of = t.actor_rank
            row = cc[i]
            row[:] = 0
            for a, s in t.clock.items():
                row[rank_of[a]] = s
            f = t.frontier
            fs[i] = len(f)
            if len(f) == 1:
                (a, s), = f.items()
                hr[i] = rank_of[a]
                hs[i] = s
        self._cache_dirty = set()

    def _encode_rounds_batched(self, rounds):
        """Whole-micro-batch vectorized admission (the streaming steady
        state): every change in every round rides a per-doc SAME-ACTOR
        in-order chain — one peer's consecutive edits per document —
        whose frontier is one head. ONE change concurrent with another
        device's last write, or one document with two heads, returns
        None for the whole round: the benchmark's `fleet10k-devices.storm`
        takes the per-round fallback every round (`rows_rounds_batched`
        does not move; `rows_changes_admitted_general` counts what its
        general path took), the other cells never. One
        classification over the concatenated frame columns, one batched
        clock-row construction, ONE native encode call for all rounds;
        per-change Python shrinks to the state-clock memo + change-log
        appends. Returns the merged enc dict, or None when any change
        breaks the chain shape (caller falls back to per-round encode,
        which handles every protocol case)."""
        from .resident import AdmittedRef

        rcs = [rc for rc in rounds if rc.cols.n_changes]
        if not rcs:
            return None
        self._refresh_admission_cache()

        doc_l, j_l, rnd_l, arank_l, seq_l = [], [], [], [], []
        dep_rank_l, dep_seq_l, dep_chg_l = [], [], []
        off = 0
        for r, rc in enumerate(rcs):
            cols = rc.cols
            n_k = len(rc.doc_ids)
            ch_off = np.asarray(rc.change_off, np.int64)
            ch_per_k = np.diff(ch_off)
            if (ch_per_k > 1).any():
                return None  # multi-change docs: per-round path
            sel = ch_per_k == 1
            docs_r = np.fromiter((self.doc_index[d] for d in rc.doc_ids),
                                 np.int64, n_k)[sel]
            js_r = ch_off[:-1][sel]
            gids = self._frame_gids(cols)
            arank_r = self._ranks_in(
                docs_r, gids[np.asarray(cols.change_actor, np.int64)[js_r]])
            seq_r = np.asarray(cols.change_seq, np.int64)[js_r]
            doc_l.append(docs_r)
            j_l.append(js_r)
            rnd_l.append(np.full(len(js_r), r, np.int64))
            arank_l.append(arank_r)
            seq_l.append(seq_r)
            deps_off = np.asarray(cols.deps_off, np.int64)
            dep_cnt = np.diff(deps_off)
            if dep_cnt.any():
                # change index within frame == admitted position (1/doc)
                dep_chg_frame = np.repeat(np.arange(cols.n_changes), dep_cnt)
                pos_of_j = np.full(cols.n_changes, -1, np.int64)
                pos_of_j[js_r] = off + np.arange(len(js_r))
                dep_pos = pos_of_j[dep_chg_frame]
                if (dep_pos < 0).any():
                    return None  # dep rows of unadmitted changes: fallback
                # a dep's rank in the document of the change that names it
                dep_rank_l.append(self._ranks_in(
                    docs_r[dep_pos - off],
                    gids[np.asarray(cols.deps_actor, np.int64)]))
                dep_seq_l.append(np.asarray(cols.deps_seq, np.int64))
                dep_chg_l.append(dep_pos)
            off += len(js_r)

        doc_all = np.concatenate(doc_l)
        n = len(doc_all)
        if n == 0:
            return None
        j_all = np.concatenate(j_l)
        rnd_all = np.concatenate(rnd_l)
        arank_all = np.concatenate(arank_l)
        seq_all = np.concatenate(seq_l)
        if (arank_all < 0).any():
            return None
        qf = self._queued_mask()
        if qf is not None and qf[doc_all].any():
            return None

        order = np.lexsort((rnd_all, doc_all))
        d = doc_all[order]
        a = arank_all[order]
        s = seq_all[order]
        starts = np.searchsorted(d, d, side="left")
        is_first = starts == np.arange(n)
        cc, fs_, hr_, hs_ = (self._clock_cache, self._fsize,
                             self._hrank, self._hseq)
        # single-actor chain, consecutive seqs from the pre-batch clock
        if (a != a[starts]).any():
            return None
        base = cc[d[starts], a[starts]]
        if not (s == base + 1 + (np.arange(n) - starts)).all():
            return None
        # frontier coverage for chain firsts (deps checked below)
        own = (a == hr_[d]) & (s - 1 >= hs_[d])
        cov = np.zeros(n, np.int64)
        deps_ok = True
        if dep_chg_l:
            dep_chg = np.concatenate(dep_chg_l)
            dep_rank = np.concatenate(dep_rank_l)
            dep_seq = np.concatenate(dep_seq_l)
            # map dep rows into ordered space
            inv = np.empty(n, np.int64)
            inv[order] = np.arange(n)
            dep_pos = inv[dep_chg]
            dep_doc = d[dep_pos]
            safe_rank = np.maximum(dep_rank, 0)
            sat_pre = (dep_rank >= 0) & (cc[dep_doc, safe_rank] >= dep_seq)
            sat_chain = (dep_rank == a[dep_pos]) & (dep_seq < s[dep_pos])
            bad = np.zeros(n, np.int64)
            np.add.at(bad, dep_pos, ~(sat_pre | sat_chain))
            deps_ok = not bad.any()
            np.add.at(cov, dep_pos,
                      (dep_rank == hr_[dep_doc]) & (dep_seq >= hs_[dep_doc]))
        if not deps_ok:
            return None
        fsz = fs_[d]
        first_ok = (~is_first) | (fsz == 0) | ((fsz == 1) & ((cov > 0) | own))
        if not first_ok.all():
            return None

        # ---- admitted: batched bookkeeping ----
        # pre-change clock rows: pre-batch row with own entry = seq-1
        cmat = cc[d].astype(np.int32)
        cmat[np.arange(n), a] = (s - 1).astype(np.int32)
        # cache update from each chain's last change
        last = np.ones(n, bool)
        last[:-1] = d[1:] != d[:-1]
        cc[d[last], a[last]] = s[last]
        fs_[d[last]] = 1
        hr_[d[last]] = a[last]
        hs_[d[last]] = s[last]

        j_ord = j_all[order]
        rnd_ord = rnd_all[order]
        cidx = np.empty(n, np.int64)
        tables = self.tables
        change_log = self.change_log
        cols_of = [rc.cols for rc in rcs]
        for pos, (i, j, r, ar, s_) in enumerate(zip(
                d.tolist(), j_ord.tolist(), rnd_ord.tolist(),
                a.tolist(), s.tolist())):
            t = tables[i]
            t.state_clocks[(t.actors[ar], s_)] = (cmat, pos)
            change_log[i].append(AdmittedRef(cols_of[r], j))
            cidx[pos] = t.n_changes
            t.n_changes += 1
            if t.n_changes > self._changes_hi:
                self._changes_hi = t.n_changes
            if t._stale_idx is None:
                t._stale_idx = i
                t.clock = self._StaleView(self, t, "clock")
                t.frontier = self._StaleView(self, t, "frontier")
        self._stale_tables = True

        self._native.ensure_docs(len(self.doc_ids))
        self._native.begin()
        self._native.apply_frames([c.frame_bytes for c in cols_of],
                                  rnd_ord, j_ord, d, a, s, cidx)
        bd = self._native.finish()
        self._mirror_stats(bd, d)
        return {"bd": bd, "clock_mat": cmat, "adm_doc": d,
                "adm_cidx": cidx}

    def _encode_round_frame(self, rc):
        """Admission + clock rows for one round frame, then ONE batched
        native encode over the shared embedded AMW1 frame.

        The hot case — in-order delivery of one change per doc whose
        declared deps cover the doc's dependency frontier — is classified
        VECTORIZED against the dense clock/frontier cache: its transitive
        clock IS the doc's current clock (one gather for the whole round),
        no closure walk, no _Pending allocation, no per-change deps dict.
        Anything else (gaps, dups, queued docs, multi-change docs, partial
        frontiers) falls back per-doc to the general _admit / _clock_row
        machinery, unchanged."""
        from ..native.delta import frame_bytes_of
        from .resident import AdmittedRef, _Pending

        cols = rc.cols
        n_ch = cols.n_changes
        if n_ch == 0:
            return None
        self._refresh_admission_cache()
        actors = cols.actors

        n_k = len(rc.doc_ids)
        doc_of_k = np.fromiter((self.doc_index[d] for d in rc.doc_ids),
                               np.int64, n_k)
        ch_off = np.asarray(rc.change_off, np.int64)
        ch_per_k = np.diff(ch_off)
        chg_doc = np.repeat(doc_of_k, ch_per_k)
        chg_k = np.repeat(np.arange(n_k), ch_per_k)
        # A rank is the actor's position in the change's OWN document.
        # The frame's actor table may intern actors that only appear in
        # deps and have not written the document yet (their changes
        # haven't arrived). -1 marks them; any dep on such an actor is
        # unsatisfied, which routes the change to the slow path to queue.
        gids = self._frame_gids(cols)
        arank = self._ranks_in(
            chg_doc, gids[np.asarray(cols.change_actor, np.int64)])
        seq = np.asarray(cols.change_seq, np.int64)

        cc, fs_, hr_, hs_ = (self._clock_cache, self._fsize,
                             self._hrank, self._hseq)
        # in-order next change per actor
        ok = seq == cc[chg_doc, arank] + 1
        # every declared dep satisfied; frontier head covered by a dep
        deps_off = np.asarray(cols.deps_off, np.int64)
        dep_cnt = np.diff(deps_off)
        cov = np.zeros(n_ch, np.int64)
        if dep_cnt.any():
            dep_chg = np.repeat(np.arange(n_ch), dep_cnt)
            dep_doc = chg_doc[dep_chg]
            dep_rank = self._ranks_in(
                dep_doc, gids[np.asarray(cols.deps_actor, np.int64)])
            dep_seq = np.asarray(cols.deps_seq, np.int64)
            safe_rank = np.maximum(dep_rank, 0)
            bad = np.zeros(n_ch, np.int64)
            np.add.at(bad, dep_chg,
                      (dep_rank < 0) | (cc[dep_doc, safe_rank] < dep_seq))
            ok &= bad == 0
            np.add.at(cov, dep_chg,
                      (dep_rank == hr_[dep_doc]) & (dep_seq >= hs_[dep_doc]))
        own = (arank == hr_[chg_doc]) & (seq - 1 >= hs_[chg_doc])
        fsz = fs_[chg_doc]
        ok &= (fsz == 0) | ((fsz == 1) & ((cov > 0) | own))
        qflag = self._queued_mask()
        if qflag is not None:
            ok &= ~qflag[chg_doc]
        # multi-change docs would need sequential cache updates: slow path
        ok &= np.repeat(ch_per_k == 1, ch_per_k)
        k_bad = np.zeros(n_k, np.int64)
        np.add.at(k_bad, chg_k, ~ok)

        order = sorted(range(n_k), key=lambda k: doc_of_k[k])
        # fast docs: exactly one change this round and it passed every
        # check (empty docs are no-ops; multi-change docs went slow above)
        fast_in_order = [k for k in order
                        if ch_per_k[k] == 1 and not k_bad[k]]
        fast_js = ch_off[fast_in_order]
        fast_docs = doc_of_k[fast_in_order]
        # clock rows = clock BEFORE each fast change (doc-disjoint, so one
        # gather), then one batched cache update
        cmat_fast = cc[fast_docs]
        cc[fast_docs, arank[fast_js]] = seq[fast_js]
        fs_[fast_docs] = 1
        hr_[fast_docs] = arank[fast_js]
        hs_[fast_docs] = seq[fast_js]

        # fast bookkeeping, vectorized: the admitted-metadata columns are
        # sliced straight from the frame vectors; the per-doc dict state
        # (clock/frontier/seen) is NOT updated — the dense cache is the
        # authority for these docs until _sync_stale_table materializes it
        # back (slow-path touch or actor remap; see _admit override). What
        # stays per-doc: the state-clock memo (read by _clock_row for
        # later slow changes), the change log, and the change counter.
        n_fast = len(fast_in_order)
        cidx_fast = np.empty(n_fast, np.int64)
        ca_list = np.asarray(cols.change_actor)[fast_js].tolist()
        seq_list = seq[fast_js].tolist()
        tables = self.tables
        change_log = self.change_log
        for pos, (i, j, ca, s) in enumerate(zip(
                fast_docs.tolist(), fast_js.tolist(), ca_list, seq_list)):
            t = tables[i]
            t.state_clocks[(actors[ca], s)] = (cmat_fast, pos)
            change_log[i].append(AdmittedRef(cols, j))
            cidx_fast[pos] = t.n_changes
            t.n_changes += 1
            if t.n_changes > self._changes_hi:
                self._changes_hi = t.n_changes
            if t._stale_idx is None:
                t._stale_idx = i
                t.clock = self._StaleView(self, t, "clock")
                t.frontier = self._StaleView(self, t, "frontier")
        if n_fast:
            self._stale_tables = True

        frames: list[bytes] = [cols.frame_bytes]
        frame_of: dict[int, int] = {id(cols): 0}
        adm_frame: list[int] = []
        adm_idx: list[int] = []
        adm_doc: list[int] = []
        aranks: list[int] = []
        seqs: list[int] = []
        cidxs: list[int] = []
        clock_rows: list[np.ndarray] = []

        queued = self._queued_docs
        change_actor = cols.change_actor
        for k in order:
            if not ch_per_k[k] or (ch_per_k[k] == 1 and not k_bad[k]):
                continue
            i = int(doc_of_k[k])
            t = self.tables[i]
            log = self.change_log[i]
            # slow path: full causal admission, change by change (may also
            # release changes queued earlier, possibly from OTHER frames)
            for j in range(int(ch_off[k]), int(ch_off[k + 1])):
                actor = actors[int(change_actor[j])]
                s = int(seq[j])
                ready = self._admit(t, [_Pending(actor, s,
                                                 cols.deps_at(j), (cols, j))])
                if t.queue:
                    queued.add(i)
                else:
                    queued.discard(i)
                for p in ready:
                    pc, pj = p.payload
                    if id(pc) not in frame_of:
                        frame_of[id(pc)] = len(frames)
                        frames.append(frame_bytes_of(pc))
                    clock_rows.append(
                        self._clock_row(t, p.actor, p.seq, p.deps))
                    log.append(AdmittedRef(pc, pj))
                    adm_frame.append(frame_of[id(pc)])
                    adm_idx.append(pj)
                    adm_doc.append(i)
                    aranks.append(t.actor_rank[p.actor])
                    seqs.append(p.seq)
                    cidxs.append(t.n_changes)
                    t.n_changes += 1
                    if t.n_changes > self._changes_hi:
                        self._changes_hi = t.n_changes
            self._cache_dirty.add(i)

        n_adm = n_fast + len(adm_doc)
        if not n_adm:
            return None

        # merge fast (vectors) + slow (lists) into (doc, cidx)-ascending
        # admitted columns — the order both the native encoder's doc-grouped
        # output rows and the triplet join's searchsorted key require
        A_cap = cc.shape[1]
        if adm_doc:
            m_frame = np.concatenate([np.zeros(n_fast, np.int64),
                                      np.asarray(adm_frame, np.int64)])
            m_idx = np.concatenate([fast_js, np.asarray(adm_idx, np.int64)])
            m_doc = np.concatenate([fast_docs,
                                    np.asarray(adm_doc, np.int64)])
            m_arank = np.concatenate([arank[fast_js],
                                      np.asarray(aranks, np.int64)])
            m_seq = np.concatenate([seq[fast_js],
                                    np.asarray(seqs, np.int64)])
            m_cidx = np.concatenate([cidx_fast,
                                     np.asarray(cidxs, np.int64)])
            m_clock = np.zeros((n_adm, A_cap), np.int32)
            m_clock[:n_fast] = cmat_fast
            for r, row in enumerate(clock_rows):
                m_clock[n_fast + r, :len(row)] = row
            perm2 = np.lexsort((m_cidx, m_doc))
            m_frame, m_idx, m_doc = (m_frame[perm2], m_idx[perm2],
                                     m_doc[perm2])
            m_arank, m_seq, m_cidx = (m_arank[perm2], m_seq[perm2],
                                      m_cidx[perm2])
            m_clock = m_clock[perm2]
        else:
            m_frame = np.zeros(n_fast, np.int64)
            m_idx, m_doc = fast_js, fast_docs
            m_arank, m_seq, m_cidx = arank[fast_js], seq[fast_js], cidx_fast
            m_clock = cmat_fast.astype(np.int32)

        self._native.ensure_docs(len(self.doc_ids))
        self._native.begin()
        self._native.apply_frames(frames, m_frame, m_idx, m_doc,
                                  m_arank, m_seq, m_cidx)
        bd = self._native.finish()
        self._mirror_stats(bd, m_doc)
        return {
            "bd": bd,
            "clock_mat": m_clock,
            "adm_doc": m_doc,
            "adm_cidx": m_cidx,
            "n_general": len(adm_doc),
        }

    def _merged_trips(self, trip_list, least: int = 8):
        """The rounds' scatter triplets as one scatter: merged in round
        order with last-wins dedup (rounds only overwrite each other on
        position rows), padded to a power of two (`least`
        or more) with a row past the buffer, which the scatter drops.
        Returns (the padded [p, 3] int32 array, the count before
        padding)."""
        parts = [t for t in trip_list if len(t)]
        if parts:
            trips = np.concatenate(parts)
            key = trips[:, 0].astype(np.int64) * self.n_pad + trips[:, 1]
            # np.unique keeps the FIRST occurrence per key of the
            # reversed array == the LAST write in round order
            _, first = np.unique(key[::-1], return_index=True)
            trips = trips[len(trips) - 1 - first]
        else:
            trips = np.zeros((0, 3), np.int32)
        n = len(trips)
        p = _pad_to(max(n, 1), least)
        padded = np.zeros((p, 3), dtype=np.int32)
        padded[:n] = trips
        padded[n:, 0] = self._bases()["rows"]
        return padded, n

    def _scatter_round(self, trip_list, n_lanes: int) -> None:
        """The round's triplets into the current device copy, as one
        scatter; what was computed from the copy before is dropped."""
        with perfscope.phase("commit"):
            # a round's count moves with its documents: small
            # rounds share one shape, large ones a power of two
            padded, n_trips = self._merged_trips(trip_list, 1024)
        padded_dev = self._to_dev(padded)
        with dispatchledger.call_scope(
                "rows_scatter", backend="device", docs=n_lanes,
                axes={"trips": (max(n_trips, 1), len(padded))}):
            self.rows_dev = metrics.dispatch_jit(
                "scatter_trips", _scatter_trips, self.rows_dev,
                padded_dev)
        self._hash_handle = self._h_prev = None
        key = (self.rows_host.shape, len(padded))
        if key not in self._scatters_warm:
            # the first round at this pad compiled its scatter: the next
            # pad up is compiled beside it, so that a later round whose
            # count crosses this pad's edge (in a window too) finds it
            self._scatters_warm.add(key)
            self._warm_scatter(2 * len(padded))

    def _warm_scatter(self, p: int) -> None:
        """Run the round's scatter at pad `p`, once a mirror shape, with
        every triplet past the buffer: the scatter drops them all. Counted
        as a kernel of its own (`scatter_trips_warm`): it is no round's."""
        key = (self.rows_host.shape, p)
        if key in self._scatters_warm:
            return
        self._scatters_warm.add(key)
        padded = np.zeros((p, 3), np.int32)
        padded[:, 0] = self._bases()["rows"]
        self.rows_dev = metrics.dispatch_jit(
            "scatter_trips_warm", _scatter_trips, self.rows_dev,
            self._to_dev(padded))

    def _put_lanes(self, idxs) -> None:
        """The mirror's columns of lanes `idxs` into the current device
        copy, where a lane was rewritten in place (compaction): uploaded
        LANE_PUT at a time, the last lane repeated into the padding, and
        written on the device column by column (_put_cols), one program
        whatever the count. Hashes are the caller's: a compaction moves
        none, and marks its lanes dirty for the kernel's check. Cells of
        these lanes pended for the next scatter (a registration's) predate
        the rewrite: the columns carry their truth, so they are dropped.

        A put that fails leaves the copy behind the mirror (or donated
        away): the copy is dropped, as after any failed dispatch, and the
        error is DeviceDispatchError. A compaction runs in the precheck,
        before anything of the round is admitted, so the caller replays
        the round (admission_complete=False); the next route uploads the
        mirror."""
        if self._lane_trips:
            self._lane_trips = [t[~np.isin(t[:, 1], idxs)]
                                for t in self._lane_trips]
        try:
            for lo in range(0, len(idxs), LANE_PUT):
                part = list(idxs[lo:lo + LANE_PUT])
                sel = np.asarray(part + [part[-1]] * (LANE_PUT - len(part)),
                                 np.int32)
                cols = self._to_dev(
                    np.ascontiguousarray(self.rows_host[:, sel]))
                sel_dev = self._to_dev(sel)
                with dispatchledger.call_scope(
                        "rows_put_lanes", backend="device", docs=len(part),
                        axes={"docs": (len(part), LANE_PUT)}):
                    self.rows_dev = metrics.dispatch_jit(
                        "put_lanes", _put_cols, self.rows_dev, cols, sel_dev)
        except Exception as e:
            self._drop_copy()
            metrics.bump("rows_dispatch_failed")
            raise DeviceDispatchError(
                f"lanes' put failed: {e}", admission_complete=False) from e

    def _dispatch_final(self, trip_list, route, interpret):
        """Execute the route dispatch.reconcile_route gave this round (its
        docstring holds the table), up to the dispatch of its reconcile.
        `trip_list`: the round's triplets, or None where the round has
        scattered them into the copy already (dispatch.scatters_first: a
        round that planned and found the copy current). This function is
        told, it does not look at the copy again. `fused` reads its
        buckets back here; `lanes` and read-back `whole` leave their
        vector as the unsettled round (_settle reads it back). Returns the
        device hash array of every lane, padded to n_pad, where the route
        leaves it on the device (`blocks`, `whole` with readback False);
        else None."""
        if route.kind == "deferred":
            # _cols_triplets already committed the round to the host
            # mirror; the next hash read uploads it and, with the dirty
            # lanes marked, reconciles ONLY this round's docs
            # (O(changes)), not the fleet
            self._drop_copy()
            return None
        if not route.readback:
            return self._apply_final_route(trip_list, route, interpret)
        if trip_list is not None:
            # planned without a current copy: nothing to scatter into
            self._drop_copy()
        if route.kind == "fused":
            self._fuse(route.plan, interpret)
        else:
            self._reconcile(route, interpret, settle=False)
        if self._unsettled is None:
            # read back already (a fused round, a read's fused route):
            # lanes it did not take, and lanes left dirty from outside
            # the round, are routed as a read now; the collect half does
            # the same behind an unsettled round
            self._refresh_hash_mirror(None, interpret)
        return None

    def _fuse(self, plan, interpret):
        """The fused route: the plan's bucketed dispatches out of the host
        mirror (dispatch.apply_round_adaptive). An eager engine then keeps
        the lane route ready for the round a plan declines: where the copy
        is not current it uploads the mirror, as _settle does after a host
        gather; where it is (a load's re-layouts have passed), it runs the
        lane route's two programs once at each lane width it fuses
        (_warm_lanes). Fused buckets follow the documents' sizes; the lane
        route has one shape a width, so a fleet whose documents cross a
        bucket's size, and whose plans then decline, compiles nothing
        there. Returns the round's summary, or None where nothing was
        fused."""
        summary = round_dispatch.apply_round_adaptive(self, plan, interpret)
        if summary is not None and not self.lazy_dispatch:
            if self._dev_current:
                self._warm_lanes(plan.docs, interpret)
            else:
                self._prime()
        return summary

    def _warm_lanes(self, idxs: list[int], interpret) -> None:
        """Gather `idxs` out of the current copy and reconcile them, as
        _reconcile_lanes does, the first time the copy's layout meets
        their lane width; the hashes are not read."""
        k_pad = pad_to_lanes(len(idxs))
        key = (k_pad, self.n_pad, self.dims())
        if key in self._lanes_warm:
            return
        self._lanes_warm.add(key)
        sel = np.asarray(idxs + [idxs[-1]] * (k_pad - len(idxs)), np.int64)
        plan_dev = self._to_dev(lane_gather_plan(sel, self.n_pad))
        sub_dev = metrics.dispatch_jit("gather_lanes", gather_lanes,
                                       self.rows_dev, plan_dev, k_pad,
                                       interpret)
        metrics.dispatch_jit("reconcile_rows_hash", reconcile_rows_hash,
                             sub_dev, self.dims(), interpret)

    def _drop_copy(self) -> None:
        """Forget the device copy and what was computed from it; the next
        route that needs it uploads the host mirror (_prime)."""
        self.rows_dev = None
        self._dirty = True
        self._hash_handle = None
        self._h_prev = None
        self._unsettled = None
        self._lane_trips.clear()

    def _mark_hash_dirty(self, idxs) -> None:
        # behind an unsettled round its vector may predate the write:
        # dropped, the round's lanes stay dirty with these
        self._unsettled = None
        super()._mark_hash_dirty(idxs)

    def _mark_all_hash_dirty(self) -> None:
        self._unsettled = None
        super()._mark_all_hash_dirty()

    def _prime(self) -> None:
        """Upload the host mirror as the device copy."""
        self.rows_dev = self._to_dev(self.rows_host)
        self._dirty = False
        self._h_prev = None
        self._lane_trips.clear()

    def _warm_put(self) -> None:
        """Write lane 0's own column into the current copy (_put_lanes)
        behind the first round that leaves the copy current in its
        layout, so that the program a compaction runs is compiled by the
        first round after a load or a re-layout, and not inside the first
        round that compacts, which comes later and at no round one can
        name."""
        key = self.rows_host.shape
        if key not in self._puts_warm:
            self._puts_warm.add(key)
            self._put_lanes([0])

    def _apply_final_route(self, trip_list, route, interpret):
        """`blocks`, or `whole` for a round that never plans: scatter and
        reconcile in one program (_apply_final). `blocks` patches the
        dirty blocks' hashes into _h_prev; `whole` (re)creates it."""
        with perfscope.phase("commit"):
            padded, n_trips = self._merged_trips(trip_list)
        if not self._dev_current:
            # the mirror holds the round already; its triplets land on
            # equal cells (the scatter is an idempotent set)
            self._prime()
        padded_dev = self._to_dev(padded)
        if route.kind == "blocks":
            nb = len(route.blocks)
            docs_axis = (len(route.lanes), nb * LANE)
            blocks_dev = self._to_dev(np.asarray(route.blocks, np.int32))
            h_prev = self._h_prev
            metrics.bump("rows_apply_block_calls")
        else:
            docs_axis = (len(self.doc_ids), self.n_pad)
            blocks_dev = h_prev = None
        with dispatchledger.call_scope(
                "rows_apply", backend="device", docs=len(route.lanes),
                axes={"docs": docs_axis,
                      "trips": (max(n_trips, 1), len(padded))}):
            self.rows_dev, h = metrics.dispatch_jit(
                "apply_final", _apply_final,
                self.rows_dev, padded_dev, blocks_dev, h_prev,
                self.dims(), interpret)
        self._h_prev = h
        self._hash_handle = h  # polling hashes() between deltas is free
        return h

    @property
    def hashes_clean(self) -> bool:
        """True iff hashes() would serve entirely from the host hash
        mirror: zero dispatches, zero readbacks, no unconsumed flush-time
        device handle."""
        n = len(self.doc_ids)
        return ((n == 0 or (self._hash_mirror is not None
                            and len(self._hash_mirror) >= n))
                and not any(i < n for i in self._doc_dirty)
                and self._hash_handle is None
                and getattr(self, "_poisoned", None) is None)

    def _refresh_hash_mirror(self, want, interpret) -> None:
        """Bring the host hash mirror current for `want` (doc indices;
        None = every doc) by the route dispatch.reconcile_route gives a
        read; no device work where no handle is pending and no lane asked
        for is dirty."""
        n = len(self.doc_ids)
        self._ensure_hash_mirror()
        self._settle()
        if self._lane_trips:
            # a registration outside a round (seed_clock) rewrote lanes
            # and no scatter has carried them: the mirror is the truth
            self._drop_copy()
        if self._hash_handle is not None and not self._dev_current:
            # the handle predates a re-layout/invalidation (add_docs pad
            # growth, _grow, remap): it can never be consumed — drop it,
            # or hashes_clean would stay False forever and the sharded
            # cache would re-read this shard on every fleet read
            self._hash_handle = None
        dirty = sorted(i for i in self._doc_dirty if i < n
                       and (want is None or i in want))
        if dirty or self._hash_handle is not None:
            self._reconcile(
                round_dispatch.reconcile_route(self, dirty), interpret)

    def _reconcile(self, route, interpret, settle: bool = True) -> None:
        """Execute a read's route (for a round: its `lanes` or `whole`,
        with `settle` False: dispatched, and left as the unsettled round
        for the collect half)."""
        if route.kind == "handle":
            return self._read_back_all(self._hash_handle, cached=True)
        if route.kind == "fused":
            if self._fuse(route.plan, interpret) is not None:
                return
            # None: nothing was fused and the lanes are still dirty
            route = round_dispatch.share_route(self, route.lanes)
        if route.kind == "lanes":
            self._reconcile_lanes(route.lanes, interpret)
        else:
            self._reconcile_whole(len(route.lanes), interpret)
        if settle:
            self._settle()

    def _reconcile_whole(self, n_dirty: int, interpret) -> None:
        """Dispatch the reconcile of the whole buffer (one kernel shape
        for the steady fleet), uploading the mirror first where the copy
        is not current; the vector stays as _h_prev, and unsettled until
        _settle reads every lane's hash back."""
        if not self._dev_current:
            self._prime()
        with dispatchledger.call_scope(
                "rows_hash", backend="device", docs=n_dirty,
                axes={"docs": (len(self.doc_ids), self.n_pad)}):
            h = metrics.dispatch_jit(
                "reconcile_rows_hash", reconcile_rows_hash,
                self.rows_dev, self.dims(), interpret)
        self._h_prev = h   # every lane, from the buffer just primed
        self._unsettled = (None, h)

    def _settle(self) -> None:
        """Read the unsettled round's hashes into the host mirror: the
        one wait for the device. Its lanes go clean; after a host gather
        an eager engine then uploads the mirror once, so that the next
        round or read finds the copy (a lazy engine drops it at every
        round: nothing to prime)."""
        if self._unsettled is None:
            return
        # forgotten BEFORE the barrier: a device failure surfaces there,
        # and the lanes stay dirty for the retry
        (idxs, h), self._unsettled = self._unsettled, None
        if idxs is None:
            return self._read_back_all(h, cached=False)
        flightrec.record("rows_hash_readback", docs=len(idxs), cached=False)
        with perfscope.phase("readback"):   # the copy and its write
            vals = self._to_host(h)
            self._ensure_hash_mirror()[np.asarray(idxs, np.int64)] = \
                vals[:len(idxs)]
            self._doc_dirty.difference_update(idxs)
        if not self._dev_current and not self.lazy_dispatch:
            self._prime()

    def _read_back_all(self, h, cached: bool) -> None:
        """One readback of an all-lane hash vector into the mirror."""
        # breadcrumb BEFORE the readback barrier: a device hang surfaces
        # at np.asarray below, and the flight recorder must already show
        # this thread entered the readback
        flightrec.record("rows_hash_readback", docs=len(self.doc_ids),
                         cached=cached)
        with perfscope.phase("readback"):
            vals = self._to_host(h)
            self._adopt_full_hashes(vals)
        self._hash_handle = None   # consumed into the mirror

    def _mega_doc_sizes(self, idxs):
        """Exact per-doc used sizes for megabatch bucket planning, from
        band scans over the selected lanes of the host row mirror: the
        highest op row with op_mask set, and the highest occupied elem
        slot rounded up to whole lists (elem bands subset only at list
        granularity — pack.mega_row_map). Scanning the mirror, not the
        admission bookkeeping, keeps the sizes correct across
        compaction/rebuild. Returns (i_used, l_used) int64 arrays."""
        b = self._bases()
        sel = np.asarray(idxs, np.int64)
        I = self.cap_ops
        om = self.rows_host[b["om"]:b["om"] + I][:, sel] > 0
        i_used = np.where(om.any(axis=0),
                          I - np.argmax(om[::-1], axis=0), 0)
        le = self.cap_lists * self.cap_elems
        if le:
            im = self.rows_host[b["im"]:b["im"] + le][:, sel] > 0
            slot = np.where(im.any(axis=0),
                            le - np.argmax(im[::-1], axis=0), 0)
            l_used = -(-slot // self.cap_elems)
        else:
            l_used = np.zeros(len(sel), np.int64)
        return i_used.astype(np.int64), l_used.astype(np.int64)

    def _reconcile_lanes(self, idxs: list[int], interpret) -> None:
        """Reconcile ONLY the given doc lanes (ascending): gather their
        columns into a narrow [ROWS, k_pad] buffer and run the SAME fused
        kernel on it (dims carry no lane count, so the kernel is reused
        across fleets; k_pad quantizes to the 128 lane width, so
        recompiles are bounded by the dirty-set size distribution, not its
        values). Dispatch + readback cost is O(dirty), independent of
        fleet size — the difference between a convergence read that scales
        and the r5 O(fleet) stall.

        Where the device copy is current the columns are gathered out of
        it, on the device (gather_lanes), and only the lane indices cross
        the link. Where it is not (after add_docs pad growth, _grow, a
        failed dispatch) they are gathered out of the host mirror and
        uploaded. Dispatched, not read back: the vector
        is the unsettled round until _settle."""
        k = len(idxs)
        k_pad = pad_to_lanes(k)
        on_device = self._dev_current
        with perfscope.phase("pack"):
            # padding lanes must be VALID doc columns (a zero column is
            # not: empty lanes carry -1 in the ac/fid/if/io bands); repeat
            # the last dirty lane — its extra hashes are discarded below
            sel = np.asarray(idxs + [idxs[-1]] * (k_pad - k), np.int64)
            # what crosses the link: the gather's plan, or the lanes
            staged = lane_gather_plan(sel, self.n_pad) if on_device \
                else np.ascontiguousarray(self.rows_host[:, sel])
            # the share of the static domination join the kernel still
            # runs, from the host's account of the lanes' extents
            run, full = join_steps(host_block_extents(
                self.op_count[sel], (self._doc_gids[sel] >= 0).sum(axis=1),
                self.dims()), self.dims())
        sub_dev = self._to_dev(staged)
        with dispatchledger.call_scope(
                "rows_hash", backend="device", docs=k,
                axes={"docs": (k, k_pad)}):
            if on_device:
                sub_dev = metrics.dispatch_jit(
                    "gather_lanes", gather_lanes,
                    self.rows_dev, sub_dev, k_pad, interpret)
            h = metrics.dispatch_jit(
                "reconcile_rows_hash", reconcile_rows_hash,
                sub_dev, self.dims(), interpret)
        if on_device:
            metrics.bump("rows_lane_gathers_device")
        else:
            metrics.bump("rows_lane_gathers_host")
        metrics.bump("rows_join_steps_run", run)
        metrics.bump("rows_join_steps_full", full)
        self._unsettled = (idxs, h)

    def hashes(self, interpret: bool | None = None) -> np.ndarray:
        """Current per-doc state hashes from resident state, O(dirty) not
        O(fleet): served from the host hash mirror; only lanes whose rows
        changed since the last read are gathered and reconciled. A clean
        read performs zero dispatches and zero readbacks; a read right
        after a pipelined apply consumes the flush-time device hashes with
        one readback and no reconcile."""
        self._check_poisoned()
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        # The dispatch is async: a device failure during execution often
        # surfaces HERE, at the readback barrier, not at dispatch time. The
        # same recovery applies — the host mirror is authoritative, so drop
        # the buffer, mark dirty, and let the next call re-upload + retry.
        with metrics.trace("rows_hashes"), self._dispatch_guard():
            self._refresh_hash_mirror(None, interpret)
            metrics.gauge("rows_resident_bytes", self.resident_bytes())
            return self._hash_mirror[:len(self.doc_ids)].copy()

    def hashes_for(self, idxs,
                   interpret: bool | None = None) -> np.ndarray:
        """Hashes for a subset of docs (indices into doc_ids) WITHOUT
        reconciling untouched docs: device work is O(requested ∩ dirty).
        Returns uint32 hashes aligned with idxs (the partial convergence
        read the auditor's doc-level bisect uses)."""
        self._check_poisoned()
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        idxs = [int(i) for i in idxs]
        if not idxs:
            return np.zeros(0, np.uint32)
        with metrics.trace("rows_hashes"), self._dispatch_guard():
            self._refresh_hash_mirror(set(idxs), interpret)
            return self._hash_mirror[np.asarray(idxs, np.int64)].copy()

    def resident_bytes(self) -> int:
        """Footprint of this engine's resident state: the host row mirror,
        the device buffer (same layout; an eager engine holds it through
        rounds and single ingests alike, from the first hash refresh after
        whatever dropped it), and the per-doc admission counters; not the
        device hash vector beside the buffer (_h_prev, 4 bytes a lane),
        nor a round's gathered [ROWS, k_pad] lanes, which live for one
        reconcile. The memory gauge (`rows_resident_bytes`) and
        flight-recorder post-mortems carry this number."""
        total = int(self.rows_host.nbytes)
        if self.rows_dev is not None:
            total += int(self.rows_host.nbytes)   # device copy, same layout
        total += int(self.op_count.nbytes) + int(self.change_count.nbytes)
        return total

    def compact(self, floors: dict[str, dict[str, int]],
                pins: dict[str, set] | None = None) -> dict[str, dict]:
        """Causally-stable compaction (engine/compaction.py): reclaim
        dominated op slots and below-floor tombstoned element slots per doc,
        in place, preserving convergence hashes exactly. `floors` maps
        doc_id -> the known-peer clock floor for that doc; `pins` maps
        doc_id -> anchor element ids of known-but-unadmitted changes that
        must keep their slots. Returns per-doc reclaim stats."""
        from .compaction import compact as _compact
        stats = _compact(self, floors, pins)
        # compaction preserves hashes BY DESIGN, but the mirror must not
        # be the thing that hides a compaction bug: every doc whose slots
        # actually moved re-reads through the kernel once
        moved = [self.doc_index[d] for d, s in stats.items()
                 if d in self.doc_index
                 and (s["ops_after"] < s["ops_before"]
                      or s["elems_after"] < s["elems_before"])]
        if moved:
            self._mark_hash_dirty(moved)
        return stats

    def materialize(self, doc_id: str):
        """Snapshot one document by replaying its admitted change log
        through the interpretive frontend (the slow/cold path; the hot path
        is hash-only)."""
        from .. import api
        from ..frontend.materialize import apply_changes_to_doc

        from .resident import AdmittedRef

        i = self.doc_index[doc_id]
        doc = api.init("resident-view")
        changes = []
        arch_tail: list = []
        snap_floor = getattr(self.tables[i], "snap_floor", None)
        if self.log_archive is not None and self.log_horizon[i]:
            # RAM holds only the tail above the log horizon; the replay
            # needs the archived prefix too (cold path, like a fresh peer)
            archived = self.log_archive.read(doc_id)
            if snap_floor and not self._archive_covers_floor(
                    archived, snap_floor):
                # post-bootstrap archival only: the archived changes are
                # TAIL, not prefix — fold them into the tail and route
                # through the image below
                arch_tail = [c for c in archived
                             if c.seq > snap_floor.get(c.actor, 0)]
            else:
                changes.extend(archived)
                snap_floor = None
        tail = arch_tail + [c.change() if isinstance(c, AdmittedRef) else c
                            for c in self.change_log[i]]
        if snap_floor:
            # snapshot-booted doc whose original-numbered prefix exists
            # only as the compacted image: replay image + the tail
            # REBASED onto the renumbered history (snapshots.remap_tail
            # — a monotone per-actor bijection, identical visible state)
            from ..sync.snapshots import remap_tail
            img = (self.snapshot_store.load(doc_id)
                   if self.snapshot_store is not None else None)
            if img is None:
                raise RuntimeError(
                    f"cannot materialize snapshot-booted doc {doc_id!r}: "
                    "no archived prefix and no local snapshot image "
                    "(attach snapshot_dir so wire-received images are "
                    "retained)")
            changes = img.columns().to_changes()
            tail = remap_tail(tail, img.clock, img.kept_seqs)
        changes.extend(tail)
        doc = apply_changes_to_doc(doc, doc._doc.opset, changes,
                                   incremental=False, emit_diffs=False)
        from .batchdoc import oracle_state
        return oracle_state(doc)


@partial(jax.jit, static_argnames=("dims", "interpret"),
         donate_argnums=(0,))
def _apply_final(rows, trips, blocks, h_prev, dims, interpret):
    """Merged-batch apply: one ordered-dedup scatter, then reconcile+hash
    of the lanes it dirtied. `blocks` (int32 [nb]) names the 128-lane
    blocks to reconcile, the unit the kernel reads anyway; their hashes
    are patched into `h_prev`, the all-lane vector the last call on this
    buffer returned (not donated: a caller may still hold it). With
    `blocks` None every block reconciles: the same kernel over the whole
    buffer. Returns (rows, hashes of every lane). Async by design — the
    caller decides when (and whether) to read the hashes back."""
    rows = rows.at[trips[:, 0], trips[:, 1]].set(trips[:, 2], mode="drop")
    # None is no tracer: the branch is on the call's structure
    if blocks is None:   # graftlint: disable=jit-tracer-branch
        return rows, reconcile_rows_hash.__wrapped__(rows, dims, interpret)
    # dynamic_slice, not reshape + take: that form makes XLA copy the
    # whole buffer into another layout on every call
    starts = blocks * LANE
    sub = jnp.concatenate(
        [jax.lax.dynamic_slice(rows, (0, starts[k]), (rows.shape[0], LANE))
         for k in range(blocks.shape[0])], axis=1)
    h_sub = reconcile_rows_hash.__wrapped__(sub, dims, interpret)
    h = h_prev
    for k in range(blocks.shape[0]):
        h = jax.lax.dynamic_update_slice(
            h, h_sub[k * LANE:(k + 1) * LANE], (starts[k],))
    return rows, h


@partial(jax.jit, donate_argnums=(0,))
def _scatter_trips(rows, trips):
    """A round's merged triplets (_merged_trips) into the resident rows,
    donated. Keyed on the triplet pad alone. The reconcile is the
    caller's: a round's lanes are gathered out of the result
    (gather_lanes) for reconcile_rows_hash. No `unique_indices` /
    `indices_are_sorted`, true as both are of merged triplets: with them
    the chip's scatter lost a cell of 12,300 every few rounds."""
    return rows.at[trips[:, 0], trips[:, 1]].set(trips[:, 2], mode="drop")


@partial(jax.jit, donate_argnums=(0,))
def _put_cols(rows, cols, lanes):
    """Columns `cols` [rows, k] into the resident rows, donated, at lanes
    `lanes` [k]: one in-place dynamic_update_slice a column, where a
    scatter over the lane axis would have XLA re-lay the whole buffer.
    A lane named twice carries the same column both times."""
    def put(k, rows):
        col = jax.lax.dynamic_slice_in_dim(cols, k, 1, axis=1)
        return jax.lax.dynamic_update_slice(rows, col, (0, lanes[k]))
    return jax.lax.fori_loop(0, lanes.shape[0], put, rows)


@partial(jax.jit, static_argnames=("dims", "interpret"),
         donate_argnums=(0,))
def _scan_rounds(rows, trips, dims, interpret):
    """lax.scan over rounds: point-scatter the round's triplets, then
    reconcile+hash — one dispatch for the whole micro-batch."""
    def body(st, tr):
        st = st.at[tr[:, 0], tr[:, 1]].set(tr[:, 2], mode="drop")
        h = reconcile_rows_hash.__wrapped__(st, dims, interpret)
        return st, h
    return jax.lax.scan(body, rows, trips)
