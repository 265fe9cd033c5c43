"""Device-resident incremental DocSet state.

The from-scratch batch path (batchdoc.py) re-ships every document's full op
log per reconcile. A syncing service does the opposite: state lives on the
device and only *deltas* cross the host boundary. This module keeps the
columnar op tables resident in device memory and applies incoming change
batches by scattering delta rows at per-document offsets, then re-running the
reconcile kernel over the updated tables.

Key mechanics:
- Interning tables grow in arrival order (canonical ordering cannot be kept
  incrementally); state hashes stay canonical anyway because they mix content
  hashes, not table ids (encode.content_hash).
- The actor axis is a DOCUMENT'S OWN: a rank is a position in the sorted list
  of the actors that have written that document (`DocTables.actors`), so rank
  order is actor-string order inside a document, which is all the LWW
  tie-break asks. `cap_actors` is the widest document's count. When a device
  joins a document, the host computes that document's new ranking and the
  device remaps that document's actor columns and clock matrix
  (`_remap_actors`, one row of the permutation a document).
- Capacities (ops, changes, elements, fids, actors) are padded to powers of
  two and doubled on overflow, bounding recompilation.
- Causality: each document keeps a host-side queue of changes whose
  dependencies are not yet applied (the OpSet queue's analog,
  /root/reference/src/op_set.js:254-270); duplicates are dropped
  idempotently (op_set.js:227-232).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from ..core.change import Change
from ..core.ids import ROOT_ID, HEAD, make_elem_id
from ..utils import flightrec, metrics, perfscope
from .encode import (A_DEL, A_INS, A_LINK, A_MAKE_LIST, A_MAKE_MAP,
                     A_MAKE_TEXT, A_MOVE, A_SET, ASSIGN_CODES, _ACTION_CODE,
                     ValueTable, content_hash, move_loc_key, move_value_key,
                     value_hash_of, _pad_to)
from .kernels import apply_doc

OP_COLS = ("op_mask", "action", "fid", "actor", "seq", "change_idx", "value",
           "fid_hash", "value_hash")


class DocTables:
    """Host-side per-document interning state, arrival-ordered."""

    def __init__(self):
        self.objects: list[tuple[str, int]] = [(ROOT_ID, A_MAKE_MAP)]
        self.obj_index: dict[str, int] = {ROOT_ID: 0}
        self.fields: list[tuple[int, str]] = []
        self.fid_index: dict[tuple[int, str], int] = {}
        self.values = ValueTable()
        self.value_arrival: dict = {}   # key -> arrival id
        self.value_list: list = []
        self.list_rows: dict[int, int] = {}      # obj_idx -> list row
        self.elem_slots: dict[int, dict[str, int]] = {}  # obj_idx -> eid -> slot
        self.state_clocks: dict[tuple[str, int], dict[str, int]] = {}
        # the actors that have written this document, sorted: a rank is a
        # position here (ResidentDocSet._register_doc_actors)
        self.actors: list[str] = []
        self.actor_rank: dict[str, int] = {}
        self.clock: dict[str, int] = {}
        # dependency frontier: the maximal (actor, seq) heads — the same
        # pruned set the reference keeps as opSet.deps (op_set.js:243-249).
        # A change whose declared deps cover this frontier has a transitive
        # clock equal to the doc's full clock (the fast-admission invariant).
        self.frontier: dict[str, int] = {}
        self.seen: set[tuple[str, int]] = set()
        self.queue: list = []  # _Pending records awaiting admission
        # set to the doc index while the vectorized fast path owns this
        # table's clock/frontier truth in the dense cache (resident_rows);
        # _sync_stale_table materializes it back before any dict reader
        self._stale_idx: int | None = None
        self.n_changes = 0
        self.n_ops = 0
        # capacity stats (mirrored by both the Python and native encoders)
        self.n_lists = 0
        self.max_elems = 0
        # snapshot-bootstrap floor (ResidentRowsDocSet.seed_clock): the
        # covered clock of the snapshot this doc was booted from, in
        # ORIGINAL seq numbering. Post-seed clock rows clamp to it —
        # every conforming suffix change covers the snapshot floor (the
        # same contract the compaction floor imposes), and the clamp
        # reconstructs the transitive coverage whose prefix memos the
        # compacted history no longer holds. None = never seeded.
        self.snap_floor: dict[str, int] | None = None

    # arrival-ordered value interning (ValueTable sorts; we can't)
    def value_id(self, value) -> int:
        key = ValueTable._key(value)
        if key not in self.value_arrival:
            self.value_arrival[key] = len(self.value_list)
            self.value_list.append(value)
        return self.value_arrival[key]

    def fid_of(self, obj_idx: int, key: str) -> int:
        fk = (obj_idx, key)
        if fk not in self.fid_index:
            self.fid_index[fk] = len(self.fields)
            self.fields.append(fk)
        return self.fid_index[fk]


class Delta:
    """Delta rows for one document (lists of tuples from the Python encoder
    or numpy row arrays from the native one; stacked later)."""

    def __init__(self):
        self.ops = []        # rows matching OP_COLS[1:]
        self.clocks: list[np.ndarray] = []  # rows [n_actors]
        self.ins = []        # (list_row, slot, elem, actor, parent_slot, fid)
        self.new_lists = []  # (list_row, obj_idx, obj_hash)
        self.changes = []    # admitted changes (Change or AdmittedRef), in order


class _Pending:
    """A change awaiting causal admission: protocol header + payload
    (a Change, or (cols, idx) into a columnar frame)."""
    __slots__ = ("actor", "seq", "deps", "payload")

    def __init__(self, actor: str, seq: int, deps: dict, payload):
        self.actor = actor
        self.seq = seq
        self.deps = deps
        self.payload = payload


class AdmittedRef:
    """Lazy handle to an admitted change living in a columnar frame — lets
    the sync layer log and re-serve changes without materializing per-op
    Python objects unless a lagging peer actually needs them."""
    __slots__ = ("cols", "idx")

    def __init__(self, cols, idx: int):
        self.cols = cols
        self.idx = idx

    @property
    def actor(self) -> str:
        return self.cols.actors[self.cols.change_actor[self.idx]]

    @property
    def seq(self) -> int:
        return int(self.cols.change_seq[self.idx])

    def change(self) -> Change:
        return self.cols.change_at(self.idx)


class ResidentDocSet:
    """A DocSet whose columnar state lives on the device.

    Ingress runs through ONE delta encoder per instance: the native C++ one
    (native/deltaenc.cpp — interning, hashing and row building with no
    per-op Python) when the toolchain is available, else the pure-Python
    `_encode_delta`. Change-object ingress is converted to columns first on
    the native path so the C++ tables stay authoritative; mixing encoders on
    one instance would desynchronize interning state.
    """

    def __init__(self, doc_ids: list[str], native: bool | None = None):
        self.doc_ids = list(doc_ids)
        self.doc_index = {d: i for i, d in enumerate(self.doc_ids)}
        n = len(self.doc_ids)
        self.tables = [DocTables() for _ in range(n)]
        # running fleet-wide maxima of per-doc list/elem stats (values only
        # grow, so the cached max is exact): replaces O(n_docs) generator
        # scans on every streaming round's precheck/grow
        self._lists_hi = 0
        self._elems_hi = 0
        self._changes_hi = 0

        # capacities (powers of two)
        self.cap_ops = 8
        self.cap_changes = 8
        self.cap_lists = 1
        self.cap_elems = 8
        self.cap_actors = 2
        self.cap_fids = 8
        # Doc-axis capacity: exact at construction (a fixed fleet pays no
        # padding), grown with pow2 slack by add_docs so a service
        # auto-creating docs recompiles O(log n) times, not per doc.
        self.cap_docs = max(n, 1)

        self.op_count = np.zeros(self.cap_docs, dtype=np.int64)
        self.change_count = np.zeros(self.cap_docs, dtype=np.int64)
        # [cap_docs, cap_actors]: row i holds the CONTENT hashes of document
        # i's actors in rank order (0 past its count). The state hash mixes
        # these, never a rank (kernels.state_hash)
        self._ahash = np.zeros((self.cap_docs, self.cap_actors), np.int32)
        self._ahash_ver = 0     # bumped by every registration
        # doc indices whose causal queue is non-empty (so budget prechecks
        # scan O(queued) tables, not O(all))
        self._queued_docs: set[int] = set()
        # docs whose dense clock/frontier cache rows (maintained by the
        # rows subclass for vectorized admission) are stale; base-class
        # admission paths just mark, the consumer refreshes lazily
        self._cache_dirty: set[int] = set()

        # Incremental hash plane (the r5 config-8 fix): a host-side mirror
        # of the last per-doc hash readback plus the doc indices whose
        # state changed since. hashes()/hashes_for() reconcile ONLY dirty
        # docs (gathered into a narrow sub-batch) and serve everything
        # else from the mirror, so a clean convergence read costs zero
        # device work. hash_epoch is the monotonic invalidation counter
        # the sync layers key their per-shard caches on: it bumps on
        # EVERY hash-affecting mutation (admission, compaction, rebuild,
        # doc creation, actor remap), never on reads.
        self._hash_mirror: np.ndarray | None = None
        self._doc_dirty: set[int] = set(range(n))
        self.hash_epoch = 0

        self.state: dict[str, jnp.ndarray] = {}
        self._alloc()
        self._out = None
        # diff-emission baseline: what the diff consumer last saw (device
        # refs + host copies of elem vis/ranks); decoupled from _out
        self._diff_prev = None
        self._diff_prev_host = None

        self._native = None
        if native is not False:
            from ..native.delta import NativeDeltaEncoder
            self._native = NativeDeltaEncoder.create()
        if native is True and self._native is None:
            raise RuntimeError("native delta encoder requested but unavailable")

    # ------------------------------------------------------------------
    def _alloc(self):
        n = self.cap_docs
        z = jnp.zeros
        self.state = {
            "op_mask": z((n, self.cap_ops), dtype=bool),
            "action": jnp.full((n, self.cap_ops), -1, dtype=jnp.int32),
            "fid": jnp.full((n, self.cap_ops), -1, dtype=jnp.int32),
            "actor": z((n, self.cap_ops), dtype=jnp.int32),
            "seq": z((n, self.cap_ops), dtype=jnp.int32),
            "change_idx": z((n, self.cap_ops), dtype=jnp.int32),
            "value": jnp.full((n, self.cap_ops), -1, dtype=jnp.int32),
            "fid_hash": z((n, self.cap_ops), dtype=jnp.int32),
            "value_hash": z((n, self.cap_ops), dtype=jnp.int32),
            "clock": z((n, self.cap_changes, self.cap_actors), dtype=jnp.int32),
            "ins_mask": z((n, self.cap_lists, self.cap_elems), dtype=bool),
            "ins_elem": z((n, self.cap_lists, self.cap_elems), dtype=jnp.int32),
            "ins_actor": z((n, self.cap_lists, self.cap_elems), dtype=jnp.int32),
            "ins_parent": jnp.full((n, self.cap_lists, self.cap_elems), -1, dtype=jnp.int32),
            "ins_fid": jnp.full((n, self.cap_lists, self.cap_elems), -1, dtype=jnp.int32),
            "list_obj": jnp.full((n, self.cap_lists), -1, dtype=jnp.int32),
            "list_obj_hash": jnp.full((n, self.cap_lists), -1, dtype=jnp.int32),
        }

    def _grow(self, **caps):
        """Grow capacities; pad resident arrays in place (device-side).
        Padding preserves per-doc hashes, but the mirror goes conservative
        across any re-layout (growth events are rare and amortized)."""
        self._mark_all_hash_dirty()
        old = dict(cap_ops=self.cap_ops, cap_changes=self.cap_changes,
                   cap_lists=self.cap_lists, cap_elems=self.cap_elems,
                   cap_actors=self.cap_actors)
        for k, v in caps.items():
            setattr(self, k, v)
        self._fit_ahash()

        def pad(arr, pads, fill):
            return jnp.pad(arr, pads, constant_values=fill)

        s = self.state
        d_ops = self.cap_ops - old["cap_ops"]
        if d_ops:
            for col in OP_COLS:
                fill = False if col == "op_mask" else (
                    -1 if col in ("action", "fid", "value") else 0)
                s[col] = pad(s[col], ((0, 0), (0, d_ops)), fill)
        d_ch = self.cap_changes - old["cap_changes"]
        d_ac = self.cap_actors - old["cap_actors"]
        if d_ch or d_ac:
            s["clock"] = pad(s["clock"], ((0, 0), (0, d_ch), (0, d_ac)), 0)
        d_l = self.cap_lists - old["cap_lists"]
        d_e = self.cap_elems - old["cap_elems"]
        if d_l or d_e:
            for col, fill in (("ins_mask", False), ("ins_elem", 0),
                              ("ins_actor", 0), ("ins_parent", -1),
                              ("ins_fid", -1)):
                s[col] = pad(s[col], ((0, 0), (0, d_l), (0, d_e)), fill)
            if d_l:
                s["list_obj"] = pad(s["list_obj"], ((0, 0), (0, d_l)), -1)
                s["list_obj_hash"] = pad(s["list_obj_hash"], ((0, 0), (0, d_l)), -1)

    def _fit_ahash(self) -> None:
        """Pad the actor-hash table to the current (cap_docs, cap_actors)."""
        d = self.cap_docs - self._ahash.shape[0]
        a = self.cap_actors - self._ahash.shape[1]
        if d > 0 or a > 0:
            self._ahash = np.pad(self._ahash, ((0, max(d, 0)), (0, max(a, 0))))

    # ------------------------------------------------------------------
    def add_docs(self, new_ids: list[str]) -> None:
        """Grow the document axis (a sync service auto-creates docs the way
        DocSet.apply_changes does, doc_set.js:24-29). Capacity doubles past
        the current cap, so array shapes — and therefore XLA compilations —
        change O(log n) times as docs trickle in; rows between len(doc_ids)
        and cap_docs are valid empty documents."""
        fresh = [d for d in new_ids if d not in self.doc_index]
        if not fresh:
            return
        first_new = len(self.doc_ids)
        for d in fresh:
            self.doc_index[d] = len(self.doc_ids)
            self.doc_ids.append(d)
            self.tables.append(DocTables())
        # fresh docs have no mirror entry yet (their empty-doc hash still
        # needs one reconcile); existing docs stay clean
        self._mark_hash_dirty(range(first_new, len(self.doc_ids)))
        if len(self.doc_ids) <= self.cap_docs:
            self._out = None
            return
        k = _pad_to(len(self.doc_ids), 8) - self.cap_docs
        self.cap_docs += k
        self.op_count = np.concatenate([self.op_count, np.zeros(k, np.int64)])
        self.change_count = np.concatenate([self.change_count,
                                            np.zeros(k, np.int64)])
        self._fit_ahash()
        fills = {"op_mask": False, "action": -1, "fid": -1, "value": -1,
                 "ins_mask": False, "ins_parent": -1, "ins_fid": -1,
                 "list_obj": -1, "list_obj_hash": -1}
        self.state = {
            name: jnp.pad(arr, ((0, k),) + ((0, 0),) * (arr.ndim - 1),
                          constant_values=fills.get(name, 0))
            for name, arr in self.state.items()}
        self._out = None

    # ------------------------------------------------------------------
    def reserve(self, *, ops_per_doc: int | None = None,
                changes_per_doc: int | None = None,
                lists_per_doc: int | None = None,
                elems_per_list: int | None = None,
                actors: int | None = None,
                fids_per_doc: int | None = None) -> None:
        """Pre-size resident capacity so steady-state rounds never regrow.

        Growing any capacity changes the resident array shapes, which forces
        an XLA recompile of the fused scatter+apply on the next dispatch
        (seconds on a TPU, even for small shapes). A long-lived
        sync service should reserve for its expected horizon up front; the
        per-delta arrays are unaffected (their shapes track the delta size).
        """
        grow = {}
        for want, cap_name in ((ops_per_doc, "cap_ops"),
                               (changes_per_doc, "cap_changes"),
                               (elems_per_list, "cap_elems")):
            if want and _pad_to(want) > getattr(self, cap_name):
                grow[cap_name] = _pad_to(want)
        if lists_per_doc and _pad_to(lists_per_doc, 1) > self.cap_lists:
            grow["cap_lists"] = _pad_to(lists_per_doc, 1)
        if actors and _pad_to(actors, 2) > self.cap_actors:
            grow["cap_actors"] = _pad_to(actors, 2)
        if grow:
            self._grow(**grow)
        if fids_per_doc and _pad_to(fids_per_doc) > self.cap_fids:
            self.cap_fids = _pad_to(fids_per_doc)

    # ------------------------------------------------------------------
    def _register_actors(self, changes_by_doc) -> None:
        self._register_doc_actors(
            {self.doc_index[d]: {c.actor for c in changes}
             for d, changes in changes_by_doc.items()})

    def _register_doc_actors(self, names_by_doc: dict) -> None:
        """Register actors with the documents they write: {doc index:
        actor names}. A document's actor list stays sorted, so a rank is
        actor-string order inside the document (the LWW tie-break), and a
        new name rewrites that document's ranks alone
        (_adopt_doc_actors). `cap_actors` follows the widest document; it
        grows here, before any rank moves, and nowhere else."""
        plans: dict[int, list[str]] = {}
        widest = 0
        for i, names in names_by_doc.items():
            rank = self.tables[i].actor_rank
            new = [a for a in names if a not in rank]
            if new:
                plans[i] = sorted(self.tables[i].actors + new)
                widest = max(widest, len(plans[i]))
        if not plans:
            return
        if widest > self.cap_actors:
            self._grow(cap_actors=_pad_to(widest, 2))
        self._adopt_doc_actors(plans)

    def _set_doc_actors(self, i: int, actors: list[str]) -> np.ndarray:
        """Give document i its new sorted actor list. Returns the
        permutation old rank -> new rank (empty for a first registration)."""
        t = self.tables[i]
        old = t.actors
        t.actors = actors
        t.actor_rank = {a: r for r, a in enumerate(actors)}
        self._ahash[i, :len(actors)] = [content_hash(a) for a in actors]
        self._ahash_ver += 1
        return np.fromiter((t.actor_rank[a] for a in old), np.int32, len(old))

    def _adopt_doc_actors(self, plans: dict) -> None:
        """Device sink of a registration: the documents whose ranks moved
        remap their actor columns and clock matrix in one gather."""
        n, A = self.cap_docs, self.cap_actors
        perm = np.tile(np.arange(A, dtype=np.int32), (n, 1))
        inv = perm.copy()
        moved = []
        for i, actors in plans.items():
            p = self._set_doc_actors(i, actors)
            if len(p) and (p != np.arange(len(p))).any():
                # ranks past the old count hold no op yet: any bijection
                rest = np.setdiff1d(np.arange(A, dtype=np.int32), p)
                perm[i] = np.concatenate([p, rest])
                inv[i] = -1
                inv[i, p] = np.arange(len(p), dtype=np.int32)
                moved.append(i)
        if not moved:
            return
        # hash VALUES survive the remap (content hashes, never ranks), but
        # the mirror stays conservative for the documents rewritten
        self._mark_hash_dirty(moved)
        perm_j = jnp.asarray(perm)
        self.state = _remap_actors(self.state, perm_j, jnp.asarray(inv))
        if self._diff_prev is not None:
            # the diff baseline's winner ranks must follow the remap, or
            # every field of a remapped doc would look changed next round
            p, wv, wa, sh, ev, vr = self._diff_prev
            k = wa.shape[0]
            wa = jnp.where(wa >= 0, jnp.take_along_axis(
                perm_j[:k], jnp.clip(wa, 0, A - 1), axis=1), wa)
            self._diff_prev = (p, wv, wa, sh, ev, vr)

    # ------------------------------------------------------------------
    def _admit(self, t: DocTables, incoming: list[_Pending]) -> list[_Pending]:
        """Causal admission fixpoint over the doc's queue + `incoming`
        (op_set.js:254-270 analog); duplicates drop idempotently."""
        pending = list(t.queue)
        for p in incoming:
            key = (p.actor, p.seq)
            # duplicates drop idempotently: either already queued/admitted
            # (seen) or already APPLIED — per-actor seqs are dense and
            # admitted in order, so clock >= seq means applied (this also
            # covers changes fast-admitted by the vectorized path, which
            # updates the dense clock cache without touching `seen`)
            if key in t.seen or t.clock.get(p.actor, 0) >= p.seq:
                continue
            pending.append(p)
            t.seen.add(key)
        ready: list[_Pending] = []
        progress = True
        while progress:
            progress = False
            still = []
            for p in pending:
                deps = dict(p.deps)
                deps[p.actor] = p.seq - 1
                if all(t.clock.get(a, 0) >= s for a, s in deps.items()):
                    ready.append(p)
                    t.clock[p.actor] = max(t.clock.get(p.actor, 0), p.seq)
                    # frontier update (op_set.js:243-249): drop heads the
                    # change declares it has seen, add the change itself
                    drop = [a for a, s in t.frontier.items()
                            if deps.get(a, 0) >= s]
                    for a in drop:
                        del t.frontier[a]
                    t.frontier[p.actor] = p.seq
                    progress = True
                else:
                    still.append(p)
            pending = still
        t.queue = pending
        return ready

    @staticmethod
    def _memo_dict(t: DocTables, key) -> dict | None:
        """The state-clock memo of change `key` as {actor: seq}; a lazy
        dense row (matrix, row index) in the document's rank basis is
        converted where it sits."""
        trans = t.state_clocks.get(key)
        if trans is not None and not isinstance(trans, dict):
            arr, ridx = trans
            trans = t.state_clocks[key] = {
                t.actors[r]: int(v) for r, v in enumerate(
                    arr[ridx][:len(t.actors)].tolist()) if v}
        return trans

    def _clock_row(self, t: DocTables, actor: str, seq: int,
                   deps: dict) -> np.ndarray:
        """Transitive clock row for one admitted change; also advances the
        per-doc state-clock memo and change counter."""
        base = dict(deps)
        base[actor] = seq - 1
        full: dict[str, int] = {}
        for a, s in base.items():
            if s <= 0:
                continue
            trans = t.state_clocks.get((a, s))
            if trans is not None and not isinstance(trans, dict):
                # lazy dense-row memo from the vectorized fast path:
                # (matrix, row_idx) in the document's CURRENT rank basis
                # (converted to dicts when its ranks move, see the rows
                # engine's _adopt_doc_actors)
                trans = self._memo_dict(t, (a, s))
            if trans:
                for a2, s2 in trans.items():
                    if s2 > full.get(a2, 0):
                        full[a2] = s2
            full[a] = s
        if t.snap_floor:
            # snapshot-booted doc: memos for the compacted-away prefix
            # don't exist, but every conforming post-seed change covers
            # the snapshot floor — clamp restores exactly the coverage
            # those memos would have contributed (sync/snapshots.py)
            for a, s in t.snap_floor.items():
                if s > full.get(a, 0):
                    full[a] = s
        t.state_clocks[(actor, seq)] = full
        row = np.zeros(self.cap_actors, dtype=np.int32)
        for a, s in full.items():
            row[t.actor_rank[a]] = s
        return row

    def _encode_delta(self, doc_idx: int, changes: list[Change]) -> Delta:
        """Pure-Python delta encode (the native fallback)."""
        t = self.tables[doc_idx]
        delta = Delta()
        ready = self._admit(t, [
            _Pending(c.actor, c.seq, dict(c.deps), c) for c in changes])
        if t.queue:
            self._queued_docs.add(doc_idx)
        else:
            self._queued_docs.discard(doc_idx)
        self._cache_dirty.add(doc_idx)
        delta.changes = [p.payload for p in ready]
        for p in ready:
            c: Change = p.payload
            delta.clocks.append(self._clock_row(t, c.actor, c.seq, c.deps))
            change_idx = t.n_changes
            t.n_changes += 1
            if t.n_changes > self._changes_hi:
                self._changes_hi = t.n_changes

            arank = t.actor_rank[c.actor]
            for op in c.ops:
                code = _ACTION_CODE[op.action]
                if code in (A_MAKE_MAP, A_MAKE_LIST, A_MAKE_TEXT):
                    if op.obj not in t.obj_index:
                        t.obj_index[op.obj] = len(t.objects)
                        t.objects.append((op.obj, code))
                        if code in (A_MAKE_LIST, A_MAKE_TEXT):
                            oi = t.obj_index[op.obj]
                            row_i = len(t.list_rows)
                            t.list_rows[oi] = row_i
                            t.elem_slots[oi] = {}
                            delta.new_lists.append(
                                (row_i, oi, content_hash(op.obj)))
                    fid = -1
                    value = -1
                    fh = vh = 0
                elif code == A_INS:
                    oi = t.obj_index[op.obj]
                    eid = make_elem_id(c.actor, op.elem)
                    slots = t.elem_slots[oi]
                    if eid not in slots:
                        slot = len(slots)
                        slots[eid] = slot
                        parent_slot = (-1 if op.key == HEAD
                                       else slots[op.key])
                        fid = t.fid_of(oi, eid)
                        delta.ins.append((t.list_rows[oi], slot, op.elem,
                                          arank, parent_slot, fid))
                    fid = -1
                    value = -1
                    fh = vh = 0
                elif code == A_MOVE:
                    # location field on the root object (encode.py's
                    # move_loc_key contract; deltaenc.cpp mirrors it)
                    if op.obj not in t.obj_index:
                        raise KeyError(f"move into unknown object {op.obj}")
                    lockey = move_loc_key(op)
                    fid = t.fid_of(0, lockey)
                    fh = content_hash(f"{ROOT_ID}\x00{lockey}")
                    vkey = move_value_key(op)
                    value = t.value_id(vkey)
                    vh = value_hash_of(vkey)
                else:  # assign
                    oi = t.obj_index[op.obj]
                    fid = t.fid_of(oi, op.key)
                    fh = content_hash(f"{op.obj}\x00{op.key}")
                    if code == A_SET:
                        value = t.value_id(op.value)
                        vh = value_hash_of(op.value)
                    elif code == A_LINK:
                        value = t.value_id(("__link__", op.value))
                        vh = value_hash_of(("__link__", op.value))
                    else:
                        value = -1
                        vh = 0
                delta.ops.append((code, fid, arank, c.seq, change_idx,
                                  value, fh, vh))
                t.n_ops += 1
        t.n_lists = len(t.list_rows)
        if t.elem_slots:
            t.max_elems = max(len(s) for s in t.elem_slots.values())
        if t.n_lists > self._lists_hi:
            self._lists_hi = t.n_lists
        if t.max_elems > self._elems_hi:
            self._elems_hi = t.max_elems
        return delta

    # ------------------------------------------------------------------
    def apply_changes(self, changes_by_doc: dict[str, list[Change]]) -> None:
        """Encode + scatter a delta batch into resident state."""
        if self._native is not None:
            from ..native.wire import changes_to_columns
            self.apply_columns({d: changes_to_columns(chs)
                                for d, chs in changes_by_doc.items()})
            return
        self._register_actors(changes_by_doc)
        flat, meta = self._build_delta_arrays(changes_by_doc)
        self.state = _scatter_delta(self.state, flat, meta)
        self._out = None

    def apply_columns(self, cols_by_doc: dict) -> None:
        """Columnar-frame ingress: encode + scatter without per-op Python
        (native path); falls back through Change objects otherwise."""
        if self._native is None:
            self.apply_changes({d: c.to_changes()
                                for d, c in cols_by_doc.items()})
            return
        self._register_actors_cols(cols_by_doc)
        flat, meta = self._build_delta_arrays_cols(cols_by_doc)
        self.state = _scatter_delta(self.state, flat, meta)
        self._out = None

    def apply_and_reconcile_columns(self, cols_by_doc: dict,
                                    diffs: bool = False):
        """Fused columnar apply + reconcile (one device dispatch); see
        apply_and_reconcile for the diffs=True contract."""
        if self._native is None:
            return self.apply_and_reconcile(
                {d: c.to_changes() for d, c in cols_by_doc.items()},
                diffs=diffs)
        self._register_actors_cols(cols_by_doc)
        flat, meta = self._build_delta_arrays_cols(cols_by_doc)
        return self._apply_flat(flat, meta, diffs)

    def _register_actors_cols(self, cols_by_doc: dict) -> None:
        self._register_doc_actors(
            {self.doc_index[d]: {
                cols.actors[k]
                for k in set(np.asarray(cols.change_actor).tolist())}
             for d, cols in cols_by_doc.items()})

    def _build_delta_arrays(self, changes_by_doc: dict[str, list[Change]]):
        n = self.cap_docs
        deltas = [Delta() for _ in range(n)]
        self._mark_hash_dirty(self.doc_index[d] for d in changes_by_doc)
        self.last_admitted = {}
        for doc_id, changes in changes_by_doc.items():
            i = self.doc_index[doc_id]
            deltas[i] = self._encode_delta(i, changes)
            self.last_admitted[doc_id] = deltas[i].changes
        return self._stack_deltas(deltas)

    def _native_ingest_round(self, cols_by_doc: dict, on_admitted):
        """Shared native-encode round protocol: per-doc causal admission in
        sorted doc order, frame dedup, admitted-metadata assembly, ONE
        batched native call straight from raw AMW1 frame bytes, and the
        capacity-stats mirror. `on_admitted(i, t, ready)` runs per doc with
        its admitted _Pending list for caller-specific bookkeeping (clock
        rows, change logs) before metadata assembly. Returns
        (BatchDelta | None, adm_doc, cidxs) — None when nothing was
        admitted."""
        from ..native.delta import frame_bytes_of

        frames: list[bytes] = []
        frame_of: dict[int, int] = {}
        adm_frame, adm_idx, adm_doc, aranks, seqs, cidxs = [], [], [], [], [], []
        for doc_id in sorted(cols_by_doc, key=lambda d: self.doc_index[d]):
            cols = cols_by_doc[doc_id]
            i = self.doc_index[doc_id]
            t = self.tables[i]
            ready = self._admit(t, [
                _Pending(cols.actors[cols.change_actor[j]],
                         int(cols.change_seq[j]), cols.deps_at(j), (cols, j))
                for j in range(cols.n_changes)])
            if t.queue:
                self._queued_docs.add(i)
            else:
                self._queued_docs.discard(i)
            self._cache_dirty.add(i)
            on_admitted(i, t, ready)
            for p in ready:
                c, j = p.payload
                if id(c) not in frame_of:
                    frame_of[id(c)] = len(frames)
                    frames.append(frame_bytes_of(c))
                adm_frame.append(frame_of[id(c)])
                adm_idx.append(j)
                adm_doc.append(i)
                aranks.append(t.actor_rank[p.actor])
                seqs.append(p.seq)
                cidxs.append(t.n_changes)
                t.n_changes += 1
                if t.n_changes > self._changes_hi:
                    self._changes_hi = t.n_changes
        if not adm_doc:
            return None, adm_doc, cidxs

        self._native.ensure_docs(len(self.doc_ids))
        self._native.begin()
        self._native.apply_frames(frames, adm_frame, adm_idx, adm_doc,
                                  aranks, seqs, cidxs)
        bd = self._native.finish()
        for i in range(min(len(self.tables), len(bd.stats))):
            t = self.tables[i]
            t.n_lists = int(bd.stats[i, 0])
            t.max_elems = int(bd.stats[i, 1])
        if len(bd.stats):
            self._lists_hi = max(self._lists_hi, int(bd.stats[:, 0].max()))
            self._elems_hi = max(self._elems_hi, int(bd.stats[:, 1].max()))
        return bd, adm_doc, cidxs

    def _build_delta_arrays_cols(self, cols_by_doc: dict):
        """Columnar round encode: admission + clock rows in Python (per
        change), ONE batched native call set for all per-op work (interning,
        hashing, row building) across every document in the round. The C++
        side reads the raw AMW1 frame bytes directly — the wire format IS
        the encoder input, so ingest pays no Python-side merge or re-blob."""
        n = self.cap_docs
        deltas = [Delta() for _ in range(n)]
        self._mark_hash_dirty(self.doc_index[d] for d in cols_by_doc)
        self.last_admitted = {}

        def on_admitted(i, t, ready):
            deltas[i].changes = [AdmittedRef(*p.payload) for p in ready]
            self.last_admitted[self.doc_ids[i]] = deltas[i].changes
            for p in ready:
                deltas[i].clocks.append(
                    self._clock_row(t, p.actor, p.seq, p.deps))

        bd, adm_doc, _ = self._native_ingest_round(cols_by_doc, on_admitted)
        if bd is None:
            return self._stack_deltas(deltas)

        # slice doc-grouped rows into per-doc deltas
        for rows, attr in ((bd.op_rows, "ops"), (bd.ins_rows, "ins"),
                           (bd.newlist_rows, "new_lists")):
            if len(rows):
                bounds = np.searchsorted(rows[:, 0], np.arange(n + 1))
                for i in range(n):
                    lo, hi = bounds[i], bounds[i + 1]
                    if hi > lo:
                        setattr(deltas[i], attr, rows[lo:hi, 1:])
        # mirror table additions
        for d, name, kind in bd.new_objects:
            self.tables[d].objects.append((name, kind))
        for d, oi, key in bd.new_fields:
            self.tables[d].fields.append((oi, key))
        for d, v in bd.new_values:
            self.tables[d].value_list.append(v)
        for i in set(adm_doc):
            self.tables[i].n_ops += len(deltas[i].ops)
        return self._stack_deltas(deltas)

    def _stack_deltas(self, deltas: list[Delta]):
        n = self.cap_docs
        # capacity checks (n_lists/max_elems/fields are per-table scalars
        # maintained by both encoders)
        need_ops = int(max((self.op_count[i] + len(d.ops)
                            for i, d in enumerate(deltas)), default=0))
        need_ch = int(max((self.change_count[i] + len(d.clocks)
                           for i, d in enumerate(deltas)), default=0))
        need_lists = max((t.n_lists for t in self.tables), default=0)
        need_elems = max((t.max_elems for t in self.tables), default=0)
        need_fids = max((len(t.fields) for t in self.tables), default=0)
        grow = {}
        if need_ops > self.cap_ops:
            grow["cap_ops"] = _pad_to(need_ops)
        if need_ch > self.cap_changes:
            grow["cap_changes"] = _pad_to(need_ch)
        if need_lists > self.cap_lists:
            grow["cap_lists"] = _pad_to(need_lists, 1)
        if need_elems > self.cap_elems:
            grow["cap_elems"] = _pad_to(need_elems)
        if grow:
            self._grow(**grow)
        if need_fids > self.cap_fids:
            self.cap_fids = _pad_to(need_fids)

        # stack delta arrays
        max_d_ops = _pad_to(max((len(d.ops) for d in deltas), default=1), 1)
        max_d_ch = _pad_to(max((len(d.clocks) for d in deltas), default=1), 1)
        max_d_ins = _pad_to(max((len(d.ins) for d in deltas), default=1), 1)
        max_d_nl = _pad_to(max((len(d.new_lists) for d in deltas), default=1), 1)

        d_ops = np.zeros((n, max_d_ops, 8), dtype=np.int32)
        d_ops_n = np.zeros(n, dtype=np.int32)
        d_clock = np.zeros((n, max_d_ch, self.cap_actors), dtype=np.int32)
        d_ch_n = np.zeros(n, dtype=np.int32)
        d_ins = np.zeros((n, max_d_ins, 6), dtype=np.int32)
        d_ins_n = np.zeros(n, dtype=np.int32)
        d_nl = np.zeros((n, max_d_nl, 3), dtype=np.int32)
        d_nl_n = np.zeros(n, dtype=np.int32)
        offsets_ops = self.op_count.astype(np.int32)
        offsets_ch = self.change_count.astype(np.int32)

        for i, d in enumerate(deltas):
            if len(d.ops):
                d_ops[i, :len(d.ops)] = np.asarray(d.ops, dtype=np.int32)
                d_ops_n[i] = len(d.ops)
            if len(d.clocks):
                d_clock[i, :len(d.clocks)] = np.stack(d.clocks)
                d_ch_n[i] = len(d.clocks)
            if len(d.ins):
                d_ins[i, :len(d.ins)] = np.asarray(d.ins, dtype=np.int32)
                d_ins_n[i] = len(d.ins)
            if len(d.new_lists):
                d_nl[i, :len(d.new_lists)] = np.asarray(d.new_lists,
                                                        dtype=np.int32)
                d_nl_n[i] = len(d.new_lists)
            self.op_count[i] += len(d.ops)
            self.change_count[i] += len(d.clocks)

        # One flat transfer: every host->device call has a fixed cost, so
        # the ten delta arrays ship as a single packed buffer.
        parts = [d_ops, d_ops_n, offsets_ops.astype(np.int32),
                 d_clock, d_ch_n, offsets_ch.astype(np.int32),
                 d_ins, d_ins_n, d_nl, d_nl_n]
        meta = tuple((p.shape, int(np.prod(p.shape))) for p in parts)
        flat = np.concatenate([p.astype(np.int32).ravel() for p in parts])
        with perfscope.phase("upload"):
            return jnp.asarray(flat), meta

    # ------------------------------------------------------------------
    def apply_and_reconcile(self, changes_by_doc: dict[str, list[Change]],
                            diffs: bool = False):
        """Fused delta apply + reconcile: one device dispatch for the whole
        round (scatter, survivor analysis, linearization, hashing), one
        readback for the hashes. This is the hot path of a resident sync
        service — per-round cost is a single host<->device roundtrip plus
        the delta bytes.

        With diffs=True the dispatch also computes changed-field/element
        masks vs the previous round on device, and the return value is
        (hashes, {doc_id: [edit records]}) — reference-shaped diff records
        (op_set.js:105-176) decoded only for the changed entries, so a
        frontend can update a materialized view incrementally
        (engine/diffs.py)."""
        if self._native is not None:
            from ..native.wire import changes_to_columns
            return self.apply_and_reconcile_columns(
                {d: changes_to_columns(chs)
                 for d, chs in changes_by_doc.items()}, diffs=diffs)
        self._register_actors(changes_by_doc)
        flat, meta = self._build_delta_arrays(changes_by_doc)
        return self._apply_flat(flat, meta, diffs)

    def _ensure_actor_hash_state(self):
        """Keep state["actor_hash"] current: [cap_docs, cap_actors], a row
        a document, its actors' CONTENT hashes in its own rank basis
        (kernels.state_hash mixes these, never ranks, so a hash does not
        depend on who else the instance holds). Uploaded from the host
        table (_ahash) only when a registration or a capacity changed it;
        between uploads the array rides the state pytree through the
        donating apply jits (the returned copy is the live one — a side
        cache would hand back a donated/deleted buffer)."""
        key = (self._ahash_ver, self._ahash.shape)
        if self.state.get("actor_hash") is not None \
                and getattr(self, "_actor_hash_key", None) == key:
            return
        self.state["actor_hash"] = jnp.asarray(self._ahash)
        self._actor_hash_key = key

    def _apply_flat(self, flat, meta, diffs: bool):
        self._ensure_actor_hash_state()
        if not diffs:
            with metrics.trace("engine_resident_apply"):
                self.state, out = metrics.dispatch_jit(
                    "scatter_and_apply", _scatter_and_apply,
                    self.state, flat, meta, max_fids=self.cap_fids)
            self._out = out
            vals = np.asarray(out["hash"])[:len(self.doc_ids)]
            self._adopt_full_hashes(vals)   # flush-time capture
            return vals
        prev = self._prev_for_diffs()
        prev_vis_host, prev_rank_host = self._prev_host_for_diffs()
        with metrics.trace("engine_resident_apply"):
            self.state, out, survh, chg_fid, chg_elem = metrics.dispatch_jit(
                "scatter_apply_diff", _scatter_apply_diff,
                self.state, flat, meta, *prev,
                max_fids=self.cap_fids)
        self._out = out
        # the baseline for the NEXT diff round: device refs (no transfer);
        # independent of _out so hash-only rounds / add_docs in between do
        # not reset the consumer's view to empty
        self._diff_prev = (out["present"], out["win_value"],
                           out["win_actor"], survh,
                           out["elem_visible"], out["vis_rank"])
        from .diffs import decode_round_diffs
        records = decode_round_diffs(self, np.asarray(chg_fid),
                                     np.asarray(chg_elem),
                                     prev_vis_host, prev_rank_host)
        vals = np.asarray(out["hash"])[:len(self.doc_ids)]
        self._adopt_full_hashes(vals)   # flush-time capture
        return vals, records

    def _prev_for_diffs(self):
        """The last diff round's converged state padded to current
        capacities (the baseline the device change-detection compares
        against). Before any diff round the baseline is empty: the first
        one then describes building every document from scratch — exactly
        what a frontend needs to seed its mirror. Hash-only rounds between
        diff rounds intentionally leave the baseline where the diff
        consumer last saw it, so their effects are reported on the next
        diff round."""
        n, F = self.cap_docs, self.cap_fids
        L, E = self.cap_lists, self.cap_elems

        def pad(arr, shape, fill):
            arr = jnp.asarray(arr)
            pads = [(0, s - arr.shape[k]) for k, s in enumerate(shape)]
            if any(p[1] for p in pads):
                arr = jnp.pad(arr, pads, constant_values=fill)
            return arr

        if self._diff_prev is None:
            return (jnp.zeros((n, F), bool),
                    jnp.full((n, F), -1, jnp.int32),
                    jnp.full((n, F), -1, jnp.int32),
                    jnp.zeros((n, F), jnp.uint32),
                    jnp.zeros((n, L, E), bool),
                    jnp.full((n, L, E), -1, jnp.int32))
        p, wv, wa, sh, ev, vr = self._diff_prev
        return (pad(p, (n, F), False), pad(wv, (n, F), -1),
                pad(wa, (n, F), -1), pad(sh, (n, F), 0),
                pad(ev, (n, L, E), False), pad(vr, (n, L, E), -1))

    def _prev_host_for_diffs(self):
        """Host copies of the baseline's element visibility/ranks for the
        decode (old indexes of removals) — reused from the previous diff
        round's decode readback, not re-downloaded."""
        n = self.cap_docs
        L, E = self.cap_lists, self.cap_elems
        if self._diff_prev_host is None:
            return (np.zeros((n, L, E), bool),
                    np.full((n, L, E), -1, np.int32))
        vis, rank = self._diff_prev_host
        pads = [(0, n - vis.shape[0]), (0, L - vis.shape[1]),
                (0, E - vis.shape[2])]
        if any(p[1] for p in pads):
            vis = np.pad(vis, pads, constant_values=False)
            rank = np.pad(rank, pads, constant_values=-1)
        return vis, rank

    # -- incremental hash plane (shared vocabulary with the rows engine) ---

    def _mark_hash_dirty(self, idxs) -> None:
        """Record a hash-affecting mutation for specific docs. The epoch
        bumps even when every doc was already dirty — epoch equality is
        the sync layers' "nothing changed since my cached read" test, so
        every mutation must advance it."""
        self._doc_dirty.update(int(i) for i in idxs)
        self.hash_epoch += 1

    def _mark_all_hash_dirty(self) -> None:
        self._doc_dirty.update(range(len(self.doc_ids)))
        self.hash_epoch += 1

    def _ensure_hash_mirror(self) -> np.ndarray:
        n = len(self.doc_ids)
        mirror = self._hash_mirror
        if mirror is None or len(mirror) < n:
            grown = np.zeros(max(self.cap_docs, n), np.uint32)
            if mirror is not None:
                grown[:len(mirror)] = mirror
            self._hash_mirror = mirror = grown
        return mirror

    def _adopt_full_hashes(self, row: np.ndarray) -> None:
        """Adopt a full per-doc hash readback (flush-time capture): the
        mirror becomes current and every doc goes clean."""
        n = len(self.doc_ids)
        self._ensure_hash_mirror()[:n] = np.asarray(row)[:n]
        self._doc_dirty.clear()

    @property
    def hashes_clean(self) -> bool:
        """True iff hashes() would serve entirely from the host mirror
        (zero dispatches, zero device readbacks)."""
        n = len(self.doc_ids)
        return ((n == 0 or (self._hash_mirror is not None
                            and len(self._hash_mirror) >= n))
                and not any(i < n for i in self._doc_dirty))

    def _reconcile_partial(self, idxs: list[int]) -> None:
        """Reconcile ONLY the given docs: gather their rows out of the
        resident state (leading-axis gather per array), run the same
        reconcile kernel on the narrow sub-batch, and scatter the hashes
        into the mirror. Device work is O(len(idxs)), independent of the
        fleet size; the sub-batch doc count pads to a power-of-two-ish
        step so recompiles stay bounded."""
        with metrics.trace("engine_hashes"):
            self._ensure_actor_hash_state()
            k = len(idxs)
            pad = _pad_to(k, 8)
            # padded rows repeat the last dirty doc (any valid doc works;
            # the extra hashes are discarded below)
            sel = jnp.asarray(idxs + [idxs[-1]] * (pad - k), jnp.int32)
            sub = {name: jnp.take(arr, sel, axis=0)
                   for name, arr in self.state.items()}
            out = metrics.dispatch_jit("apply_doc", apply_doc,
                                       sub, self.cap_fids)
            flightrec.record("engine_hash_readback", docs=k)
            with perfscope.phase("readback"):
                vals = np.asarray(out["hash"])
            self._ensure_hash_mirror()[np.asarray(idxs, np.int64)] = \
                vals[:k].astype(np.uint32)
            self._doc_dirty.difference_update(idxs)

    def reconcile(self):
        """Run the reconcile kernel over resident state; returns per-doc
        uint32 hashes (numpy, aligned with doc_ids)."""
        with metrics.trace("engine_hashes"):
            self._ensure_actor_hash_state()
            self._out = metrics.dispatch_jit("apply_doc", apply_doc,
                                             self.state, self.cap_fids)
            # breadcrumb before the readback barrier (see rows engine)
            flightrec.record("engine_hash_readback",
                             docs=len(self.doc_ids))
            metrics.gauge("engine_resident_bytes", self.resident_bytes())
            with perfscope.phase("readback"):
                vals = np.asarray(self._out["hash"])[:len(self.doc_ids)]
            self._adopt_full_hashes(vals)
            return vals

    def resident_bytes(self) -> int:
        """Footprint of the docs-major resident state tables (bytes). Set
        as the `engine_resident_bytes` gauge at each reconcile so flight-
        recorder post-mortems carry the memory picture."""
        total = 0
        for v in self.state.values():
            total += int(getattr(v, "nbytes", 0) or 0)
        return total

    def hashes(self) -> np.ndarray:
        """Per-doc state hashes, O(dirty) not O(fleet): served from the
        host hash mirror; only docs whose state changed since the last
        read are re-reconciled (narrow sub-batch dispatch). A clean read
        performs zero dispatches and zero readbacks; a read after a fused
        apply reuses the flush-time hashes (`self._out`) with one cheap
        readback and no reconcile."""
        n = len(self.doc_ids)
        mirror = self._hash_mirror
        if mirror is not None and len(mirror) >= n \
                and not any(i < n for i in self._doc_dirty):
            return mirror[:n].copy()
        if self._out is not None:
            # flush-time hashes from the last fused apply dispatch cover
            # every doc: one readback, no reconcile
            with perfscope.phase("readback"):
                vals = np.asarray(self._out["hash"])[:n]
            self._adopt_full_hashes(vals)
            return vals.copy()
        dirty = sorted(i for i in self._doc_dirty if i < n)
        if self._hash_mirror is None or 2 * len(dirty) >= n:
            return self.reconcile().copy()
        self._reconcile_partial(dirty)
        return self._hash_mirror[:n].copy()

    def hashes_for(self, idxs) -> np.ndarray:
        """Hashes for a subset of docs (indices into doc_ids) WITHOUT
        reconciling untouched docs: device work is O(requested ∩ dirty).
        Returns uint32 hashes aligned with idxs."""
        idxs = [int(i) for i in idxs]
        if not idxs:
            return np.zeros(0, np.uint32)
        n = len(self.doc_ids)
        if self._out is not None and self._hash_mirror is None:
            # cheaper than a partial dispatch: the fused-apply output
            # already holds every hash
            return self.hashes()[np.asarray(idxs, np.int64)].copy()
        mirror = self._ensure_hash_mirror()
        want = set(idxs)
        dirty = sorted(i for i in self._doc_dirty if i < n and i in want)
        if dirty:
            if self._out is not None:
                with perfscope.phase("readback"):
                    self._adopt_full_hashes(np.asarray(self._out["hash"]))
            else:
                self._reconcile_partial(dirty)
        return mirror[np.asarray(idxs, np.int64)].copy()

    def materialize(self, doc_id: str) -> Any:
        """Decode one document from resident state + reconcile outputs."""
        if self._out is None:
            self.reconcile()
        i = self.doc_index[doc_id]
        t = self.tables[i]
        out = {k: np.asarray(v)[i] for k, v in self._out.items()}
        host = {k: np.asarray(v)[i] for k, v in self.state.items()}

        from .batchdoc import decode_doc

        class _Enc:  # adapter with the DocEncoding fields decode_doc uses
            pass

        enc = _Enc()
        enc.fid = host["fid"]
        enc.actor = host["actor"]
        enc.value = host["value"]
        enc.actors = t.actors
        enc.objects = t.objects
        enc.fields = t.fields
        enc.ins_fid = host["ins_fid"]
        enc.list_obj = host["list_obj"]

        class _VT:
            def __init__(self, values):
                self.values = values
        enc.value_table = _VT(t.value_list)
        return decode_doc(enc, out)


# ---------------------------------------------------------------------------
# jitted state-update kernels

@jax.jit
def _remap_actors(state, perm, inv):
    """Renumber actor ranks after actors joined documents, a row of
    `perm` / `inv` ([docs, actors]) a document (the identity where none
    did): op/ins actor columns map through `perm` (old->new); clock
    columns gather through `inv` (new->old, -1 where no old column
    existed)."""
    out = dict(state)
    hi = perm.shape[1] - 1
    out["actor"] = jnp.where(
        state["op_mask"],
        jnp.take_along_axis(perm, jnp.clip(state["actor"], 0, hi), axis=1),
        state["actor"])
    ins = state["ins_actor"]
    mapped = jnp.take_along_axis(
        perm, jnp.clip(ins, 0, hi).reshape(ins.shape[0], -1), axis=1)
    out["ins_actor"] = jnp.where(state["ins_mask"],
                                 mapped.reshape(ins.shape), ins)
    clock = state["clock"]
    gathered = jnp.take_along_axis(
        clock, jnp.clip(inv, 0, hi)[:, None, :], axis=2)
    out["clock"] = jnp.where(inv[:, None, :] >= 0, gathered, 0)
    return out


def _unpack_delta(flat, meta):
    parts = []
    offset = 0
    for shape, size in meta:
        parts.append(jax.lax.slice(flat, (offset,), (offset + size,))
                     .reshape(shape))
        offset += size
    return parts


@partial(jax.jit, static_argnames=("meta",))
def _scatter_delta(state, flat, meta):
    (d_ops, d_ops_n, off_ops, d_clock, d_ch_n, off_ch,
     d_ins, d_ins_n, d_nl, d_nl_n) = _unpack_delta(flat, meta)
    out = dict(state)
    n, max_d, _ = d_ops.shape
    docs = jnp.arange(n)[:, None]

    # op rows
    j = jnp.arange(max_d)[None, :]
    valid = j < d_ops_n[:, None]
    pos = jnp.where(valid, off_ops[:, None] + j, state["op_mask"].shape[1])
    cols = {"action": 0, "fid": 1, "actor": 2, "seq": 3, "change_idx": 4,
            "value": 5, "fid_hash": 6, "value_hash": 7}
    for name, ci in cols.items():
        out[name] = out[name].at[docs, pos].set(d_ops[:, :, ci], mode="drop")
    out["op_mask"] = out["op_mask"].at[docs, pos].set(valid, mode="drop")

    # clock rows
    _, max_c, _ = d_clock.shape
    jc = jnp.arange(max_c)[None, :]
    validc = jc < d_ch_n[:, None]
    posc = jnp.where(validc, off_ch[:, None] + jc, state["clock"].shape[1])
    out["clock"] = out["clock"].at[docs, posc].set(d_clock, mode="drop")

    # ins rows (explicit (list_row, slot) indices)
    _, max_i, _ = d_ins.shape
    ji = jnp.arange(max_i)[None, :]
    validi = ji < d_ins_n[:, None]
    li = jnp.where(validi, d_ins[:, :, 0], state["ins_mask"].shape[1])
    si = jnp.where(validi, d_ins[:, :, 1], state["ins_mask"].shape[2])
    out["ins_elem"] = out["ins_elem"].at[docs, li, si].set(d_ins[:, :, 2], mode="drop")
    out["ins_actor"] = out["ins_actor"].at[docs, li, si].set(d_ins[:, :, 3], mode="drop")
    out["ins_parent"] = out["ins_parent"].at[docs, li, si].set(d_ins[:, :, 4], mode="drop")
    out["ins_fid"] = out["ins_fid"].at[docs, li, si].set(d_ins[:, :, 5], mode="drop")
    out["ins_mask"] = out["ins_mask"].at[docs, li, si].set(validi, mode="drop")

    # new list rows
    _, max_l, _ = d_nl.shape
    jl = jnp.arange(max_l)[None, :]
    validl = jl < d_nl_n[:, None]
    lrow = jnp.where(validl, d_nl[:, :, 0], state["list_obj"].shape[1])
    out["list_obj"] = out["list_obj"].at[docs, lrow].set(d_nl[:, :, 1], mode="drop")
    out["list_obj_hash"] = out["list_obj_hash"].at[docs, lrow].set(d_nl[:, :, 2], mode="drop")
    return out


@partial(jax.jit, static_argnames=("meta", "max_fids"), donate_argnums=(0,))
def _scatter_and_apply(state, flat, meta, *, max_fids):
    """Fused delta scatter + full reconcile in one device dispatch. The old
    state buffers are donated (updated in place where XLA can)."""
    new_state = _scatter_delta.__wrapped__(state, flat, meta)
    out = apply_doc.__wrapped__(new_state, max_fids)
    return new_state, out


def _fid_survivor_hash(state, out, max_fids: int):
    """Order-independent per-field hash of the surviving (actor, value)
    pairs — changes whenever a field's conflict set changes even if the LWW
    winner didn't (op_set.js:95-103 is the reference surface this feeds).
    Actors are mixed by CONTENT hash (state["actor_hash"], the document's
    own row), not rank, so the hash survives the rank remap a device that
    joins the document causes."""
    from .kernels import _mix4
    actor_hashes = state["actor_hash"]
    safe_actor = jnp.clip(state["actor"], 0, actor_hashes.shape[1] - 1)
    ah = jnp.take_along_axis(actor_hashes, safe_actor, axis=1)
    contrib = _mix4(ah, state["value_hash"], ah ^ 0x5BF0,
                    state["value_hash"])
    n, _ = state["op_mask"].shape
    docs = jnp.arange(n)[:, None]
    safe_fid = jnp.clip(state["fid"], 0, max_fids - 1)
    return jnp.zeros((n, max_fids), jnp.uint32).at[docs, safe_fid].add(
        jnp.where(out["candidate"], contrib, jnp.uint32(0)))


@partial(jax.jit, static_argnames=("meta", "max_fids"), donate_argnums=(0,))
def _scatter_apply_diff(state, flat, meta, prev_present,
                        prev_win_value, prev_win_actor, prev_survh,
                        prev_vis, prev_rank, *, max_fids):
    """_scatter_and_apply plus device-side change detection: per-field and
    per-element changed masks vs the previous diff round's converged state
    (the engine-side analog of the reference's diff stream,
    op_set.js:105-176). The baseline arrays stay on device between rounds;
    only the changed-entry masks (and the state the decode reads) cross
    back to the host."""
    new_state = _scatter_delta.__wrapped__(state, flat, meta)
    out = apply_doc.__wrapped__(new_state, max_fids)
    survh = _fid_survivor_hash(new_state, out, max_fids)
    chg_fid = ((out["present"] != prev_present)
               | (out["win_value"] != prev_win_value)
               | (out["win_actor"] != prev_win_actor)
               | (survh != prev_survh))
    # an element changes if its visibility or rank moved, OR its field's
    # value/conflict state changed (a set on a stable visible element)
    ins_fid = new_state["ins_fid"]
    safe_if = jnp.clip(ins_fid, 0, max_fids - 1)
    docs3 = jnp.arange(chg_fid.shape[0])[:, None, None]
    chg_elem = ((out["elem_visible"] != prev_vis)
                | (out["vis_rank"] != prev_rank)
                | (chg_fid[docs3, safe_if] & (ins_fid >= 0)))
    return new_state, out, survh, chg_fid, chg_elem
