"""Device-resident incremental DocSet: delta application parity.

The resident path must converge to exactly the same state (and the same
canonical content hash) as the from-scratch batch path and the Python oracle,
including across incremental rounds, new actors appearing mid-stream, list
edits, and causal buffering of out-of-order deliveries.
"""

import numpy as np
import pytest

import automerge_tpu as am
from automerge_tpu.engine.batchdoc import apply_batch, oracle_state
from automerge_tpu.engine.resident import ResidentDocSet
from automerge_tpu.frontend.materialize import apply_changes_to_doc


def from_scratch_hash(changes):
    _, _, out = apply_batch([changes])
    return int(np.asarray(out["hash"])[0])


def oracle_of(changes):
    doc = am.init("oracle")
    return oracle_state(apply_changes_to_doc(doc, doc._doc.opset, changes,
                                             incremental=False))


class TestResidentParity:
    def test_single_round_matches_batch(self):
        s1 = am.change(am.init("A"), lambda d: am.assign(d, {"x": 1, "y": "two"}))
        s2 = am.change(am.init("B"), lambda d: d.__setitem__("x", 9))
        m = am.merge(s1, s2)
        changes = m._doc.opset.get_missing_changes({})

        r = ResidentDocSet(["doc"])
        r.apply_changes({"doc": changes})
        assert r.materialize("doc") == oracle_of(changes)
        assert int(r.reconcile()[0]) == from_scratch_hash(changes)

    def test_incremental_rounds(self):
        doc = am.change(am.init("A"), lambda d: d.__setitem__("n", 0))
        r = ResidentDocSet(["doc"])
        r.apply_changes({"doc": doc._doc.opset.get_missing_changes({})})
        applied = []
        for i in range(5):
            new = am.change(doc, lambda d, i=i: am.assign(
                d, {"n": i + 1, f"k{i}": i}))
            delta = new._doc.opset.get_missing_changes(
                doc._doc.opset.clock)
            doc = new
            applied.extend(delta)
            r.apply_changes({"doc": delta})
            all_changes = doc._doc.opset.get_missing_changes({})
            assert r.materialize("doc") == oracle_of(all_changes)
            assert int(r.reconcile()[0]) == from_scratch_hash(all_changes)

    def test_new_actor_mid_stream_remaps_ranks(self):
        # actor "M" joins after "Z": sorted ranks must shift so LWW still
        # breaks ties by string order
        s_z = am.change(am.init("Z"), lambda d: d.__setitem__("f", "from Z"))
        r = ResidentDocSet(["doc"])
        r.apply_changes({"doc": s_z._doc.opset.get_missing_changes({})})

        s_m = am.change(am.init("M"), lambda d: d.__setitem__("f", "from M"))
        r.apply_changes({"doc": s_m._doc.opset.get_missing_changes({})})

        merged = am.merge(am.merge(am.init("x"), s_z), s_m)
        all_changes = merged._doc.opset.get_missing_changes({})
        state = r.materialize("doc")
        assert state["data"]["f"] == "from Z"  # Z > M wins
        assert state == oracle_of(all_changes)
        assert int(r.reconcile()[0]) == from_scratch_hash(all_changes)

    def test_list_edits_across_rounds(self):
        doc = am.change(am.init("A"), lambda d: d.__setitem__("xs", ["a", "b"]))
        r = ResidentDocSet(["doc"])
        r.apply_changes({"doc": doc._doc.opset.get_missing_changes({})})

        prev = doc
        doc = am.change(doc, lambda d: d["xs"].insert_at(1, "mid"))
        doc = am.change(doc, lambda d: d["xs"].delete_at(0))
        delta = doc._doc.opset.get_missing_changes(prev._doc.opset.clock)
        r.apply_changes({"doc": delta})

        all_changes = doc._doc.opset.get_missing_changes({})
        assert r.materialize("doc") == oracle_of(all_changes)
        assert r.materialize("doc")["data"]["xs"] == ["mid", "b"]
        assert int(r.reconcile()[0]) == from_scratch_hash(all_changes)

    def test_out_of_order_delivery_buffers(self):
        s = am.change(am.init("A"), lambda d: d.__setitem__("a", 1))
        s = am.change(s, lambda d: d.__setitem__("b", 2))
        c1, c2 = s._doc.opset.get_missing_changes({})
        r = ResidentDocSet(["doc"])
        r.apply_changes({"doc": [c2]})  # dependency missing: buffered
        assert r.materialize("doc")["data"] == {}
        r.apply_changes({"doc": [c1]})  # both become visible
        assert r.materialize("doc")["data"] == {"a": 1, "b": 2}

    def test_duplicate_delivery_idempotent(self):
        s = am.change(am.init("A"), lambda d: d.__setitem__("a", 1))
        changes = s._doc.opset.get_missing_changes({})
        r = ResidentDocSet(["doc"])
        r.apply_changes({"doc": changes})
        h1 = int(r.reconcile()[0])
        r.apply_changes({"doc": changes})
        assert int(r.reconcile()[0]) == h1

    def test_many_docs_capacity_growth(self):
        docs = {}
        r = ResidentDocSet([f"d{i}" for i in range(16)])
        for i in range(16):
            s = am.change(am.init(f"a{i:02d}"),
                          lambda d, i=i: am.assign(d, {"n": i, "xs": [i] * (i + 1)}))
            docs[f"d{i}"] = s
        r.apply_changes({k: v._doc.opset.get_missing_changes({})
                         for k, v in docs.items()})
        for i in (0, 7, 15):
            all_changes = docs[f"d{i}"]._doc.opset.get_missing_changes({})
            assert r.materialize(f"d{i}") == oracle_of(all_changes)

    def test_hash_matches_across_replica_delivery_orders(self):
        s1 = am.change(am.init("A"), lambda d: d.__setitem__("xs", ["a"]))
        s2 = am.merge(am.init("B"), s1)
        s1 = am.change(s1, lambda d: d["xs"].append("b"))
        s2 = am.change(s2, lambda d: d["xs"].insert_at(0, "z"))
        m1 = am.merge(s1, s2)
        m2 = am.merge(s2, s1)
        ch1 = m1._doc.opset.get_missing_changes({})
        ch2 = m2._doc.opset.get_missing_changes({})

        ra = ResidentDocSet(["d"])
        # replica A receives its own changes first, then B's
        ra.apply_changes({"d": ch1[:len(ch1) // 2]})
        ra.apply_changes({"d": ch1[len(ch1) // 2:]})
        rb = ResidentDocSet(["d"])
        rb.apply_changes({"d": ch2})
        assert int(ra.reconcile()[0]) == int(rb.reconcile()[0])


class TestReserve:
    def test_reserve_presizes_and_preserves_state(self):
        s1 = am.change(am.init("A"), lambda d: am.assign(d, {"x": 1, "xs": [1, 2]}))
        changes = s1._doc.opset.get_missing_changes({})
        r = ResidentDocSet(["doc"])
        r.apply_changes({"doc": changes})
        before = r.materialize("doc")
        r.reserve(ops_per_doc=64, changes_per_doc=32, elems_per_list=64,
                  lists_per_doc=4, actors=8, fids_per_doc=64)
        assert r.cap_ops >= 64 and r.cap_changes >= 32
        assert r.cap_elems >= 64 and r.cap_actors >= 8
        # state survives the resize and no regrow happens within the horizon
        assert r.materialize("doc") == before
        caps = (r.cap_ops, r.cap_changes, r.cap_lists, r.cap_elems)
        doc = s1
        for i in range(10):
            new = am.change(doc, lambda d, i=i: d.__setitem__("n", i))
            delta = new._doc.opset.get_missing_changes(doc._doc.opset.clock)
            doc = new
            r.apply_changes({"doc": delta})
        assert (r.cap_ops, r.cap_changes, r.cap_lists, r.cap_elems) == caps
        all_changes = doc._doc.opset.get_missing_changes({})
        assert r.materialize("doc") == oracle_of(all_changes)

    def test_reserve_noop_when_smaller(self):
        r = ResidentDocSet(["doc"])
        caps = (r.cap_ops, r.cap_changes, r.cap_actors)
        r.reserve(ops_per_doc=1, changes_per_doc=1, actors=1)
        assert (r.cap_ops, r.cap_changes, r.cap_actors) == caps


class TestResidentRows:
    """Docs-minor resident state + micro-batched rounds (resident_rows.py).

    Runs against the native columnar ingress (apply_rounds routes Change
    rounds through the C++ delta encoder); TestResidentRowsPython below
    re-runs every test on the pure-Python fallback path."""

    native = None  # auto: use the native encoder when available

    def _mk_set(self, ids):
        from automerge_tpu.engine.resident_rows import ResidentRowsDocSet
        return ResidentRowsDocSet(ids, native=self.native)

    def _mk_docs(self, n=4):
        docs, logs = [], []
        for i in range(n):
            d1 = am.change(am.init("A"), lambda d, i=i: am.assign(
                d, {"n": i, "xs": [1, 2]}))
            d2 = am.merge(am.init("B"), d1)
            d1 = am.change(d1, lambda d: d["xs"].insert_at(1, 99))
            d2 = am.change(d2, lambda d, i=i: d.__setitem__("n", -i))
            m = am.merge(d1, d2)
            docs.append(m)
            logs.append(m._doc.opset.get_missing_changes({}))
        return docs, logs

    def _from_scratch_hashes(self, logs):
        from automerge_tpu.engine.encode import encode_doc, stack_docs
        from automerge_tpu.engine.pack import apply_packed_hash, pack_batch
        import jax
        aa = sorted({c.actor for c2 in logs for c in c2})
        b = stack_docs([encode_doc(c, aa) for c in logs])
        mf = b.pop("max_fids")
        flat, meta = pack_batch(b)
        return np.asarray(apply_packed_hash(jax.numpy.asarray(flat), meta, mf))

    def test_rounds_converge_with_from_scratch(self):
        docs, logs = self._mk_docs()
        ids = [f"d{i}" for i in range(len(docs))]
        rset = self._mk_set(ids)
        rset.apply_rounds([{ids[i]: logs[i] for i in range(len(ids))}])
        rounds = []
        for rnd in range(3):
            deltas = {}
            for i in (0, 2):
                prev = docs[i]
                new = am.change(prev, lambda d, rnd=rnd, i=i: d.__setitem__(
                    "n", rnd * 100 + i))
                deltas[ids[i]] = new._doc.opset.get_missing_changes(
                    prev._doc.opset.clock)
                docs[i] = new
            rounds.append(deltas)
        hs = rset.apply_rounds(rounds)
        assert hs.shape == (3, len(ids))
        full = [d._doc.opset.get_missing_changes({}) for d in docs]
        np.testing.assert_array_equal(hs[-1], self._from_scratch_hashes(full))

    def test_new_actor_mid_flight_remaps(self):
        docs, logs = self._mk_docs(2)
        ids = ["d0", "d1"]
        rset = self._mk_set(ids)
        rset.apply_rounds([{ids[i]: logs[i] for i in range(2)}])
        # actor "AA" sorts before "B" but after "A": ranks shift
        prev = docs[0]
        other = am.merge(am.init("AA"), prev)
        other = am.change(other, lambda d: d.__setitem__("n", 777))
        merged = am.merge(prev, other)
        delta = merged._doc.opset.get_missing_changes(prev._doc.opset.clock)
        docs[0] = merged
        hs = rset.apply_rounds([{ids[0]: delta}])
        full = [d._doc.opset.get_missing_changes({}) for d in docs]
        np.testing.assert_array_equal(hs[-1], self._from_scratch_hashes(full))

    def test_capacity_growth_mid_batch(self):
        docs, logs = self._mk_docs(2)
        ids = ["d0", "d1"]
        rset = self._mk_set(ids)
        rset.apply_rounds([{ids[i]: logs[i] for i in range(2)}])
        cap_before = rset.cap_ops
        rounds = []
        for rnd in range(max(cap_before, 8)):
            prev = docs[1]
            new = am.change(prev, lambda d, rnd=rnd: d["xs"].insert_at(
                0, rnd))
            rounds.append({ids[1]: new._doc.opset.get_missing_changes(
                prev._doc.opset.clock)})
            docs[1] = new
        hs = rset.apply_rounds(rounds)
        assert rset.cap_ops > cap_before
        full = [d._doc.opset.get_missing_changes({}) for d in docs]
        np.testing.assert_array_equal(hs[-1], self._from_scratch_hashes(full))

    def test_causal_buffering_across_rounds(self):
        docs, logs = self._mk_docs(1)
        ids = ["d0"]
        rset = self._mk_set(ids)
        rset.apply_rounds([{ids[0]: logs[0]}])
        prev = docs[0]
        s1 = am.change(prev, lambda d: d.__setitem__("a", 1))
        s2 = am.change(s1, lambda d: d.__setitem__("a", 2))
        c1 = s1._doc.opset.get_missing_changes(prev._doc.opset.clock)
        c2 = s2._doc.opset.get_missing_changes(s1._doc.opset.clock)
        # deliver the later change first: round 1 must leave state unchanged
        h_before = rset.hashes()
        hs = rset.apply_rounds([{ids[0]: c2}, {ids[0]: c1}])
        np.testing.assert_array_equal(hs[0], h_before)
        full = [s2._doc.opset.get_missing_changes({})]
        np.testing.assert_array_equal(hs[-1], self._from_scratch_hashes(full))

    def test_materialize_matches_oracle(self):
        from automerge_tpu.engine.batchdoc import oracle_state
        from automerge_tpu.frontend.materialize import apply_changes_to_doc
        docs, logs = self._mk_docs(2)
        ids = ["d0", "d1"]
        rset = self._mk_set(ids)
        rset.apply_rounds([{ids[i]: logs[i] for i in range(2)}])
        for i in range(2):
            doc = apply_changes_to_doc(am.init("o"), am.init("o")._doc.opset,
                                       logs[i], incremental=False)
            assert rset.materialize(ids[i]) == oracle_state(doc)

    def test_second_list_reserves_cap_lists(self):
        docs, logs = self._mk_docs(1)
        ids = ["d0"]
        rset = self._mk_set(ids)
        rset.apply_rounds([{ids[0]: logs[0]}])
        prev = docs[0]
        new = am.change(prev, lambda d: d.__setitem__("ys", [7, 8]))
        delta = new._doc.opset.get_missing_changes(prev._doc.opset.clock)
        docs[0] = new
        hs = rset.apply_rounds([{ids[0]: delta}])
        assert rset.cap_lists >= 2
        full = [d._doc.opset.get_missing_changes({}) for d in docs]
        np.testing.assert_array_equal(hs[-1], self._from_scratch_hashes(full))

    def test_queued_changes_count_toward_reservation(self):
        docs, logs = self._mk_docs(1)
        ids = ["d0"]
        rset = self._mk_set(ids)
        rset.apply_rounds([{ids[0]: logs[0]}])
        prev = docs[0]
        # c2 has many ops and depends on c1; deliver c2 first so it queues
        s1 = am.change(prev, lambda d: d.__setitem__("k", 0))
        s2 = am.change(s1, lambda d: am.assign(
            d, {f"q{j}": j for j in range(12)}))
        c1 = s1._doc.opset.get_missing_changes(prev._doc.opset.clock)
        c2 = s2._doc.opset.get_missing_changes(s1._doc.opset.clock)
        rset.apply_rounds([{ids[0]: c2}])           # buffers in the queue
        hs = rset.apply_rounds([{ids[0]: c1}])      # releases c1 AND c2
        assert int(rset.op_count[0]) <= rset.cap_ops
        full = [s2._doc.opset.get_missing_changes({})]
        np.testing.assert_array_equal(hs[-1], self._from_scratch_hashes(full))


class TestRoundFrames:
    """apply_round_frames: the AMR1 multi-doc-frame ingress with fast-path
    causal admission and merged async dispatch. Every scenario is checked
    for final-hash parity against the established apply_rounds path on an
    identical twin DocSet (and transitively against from-scratch encode,
    which apply_rounds' tests pin)."""

    native = None

    def _mk_set(self, ids):
        from automerge_tpu.engine.resident_rows import ResidentRowsDocSet
        return ResidentRowsDocSet(ids, native=self.native)

    def _mk_docs(self, n=4):
        return TestResidentRows._mk_docs(self, n)

    def _twin_check(self, ids, logs, rounds):
        """Run `rounds` through apply_round_frames on one set and through
        apply_rounds on a twin; final hashes must match."""
        from automerge_tpu.sync.frames import encode_round_frame
        a = self._mk_set(ids)
        b = self._mk_set(ids)
        boot = [{ids[i]: logs[i] for i in range(len(ids))}]
        a.apply_rounds(boot)
        b.apply_rounds(boot)
        frames = [encode_round_frame(r) for r in rounds]
        h = np.asarray(a.apply_round_frames(frames))[:len(ids)]
        hs = b.apply_rounds(rounds)
        np.testing.assert_array_equal(h, hs[-1])
        # host bookkeeping converged identically too (the fast path keeps
        # table dicts lazily — materialize before comparing)
        a.sync_tables()
        b.sync_tables()
        for ta, tb in zip(a.tables, b.tables):
            assert ta.clock == tb.clock
            assert ta.frontier == tb.frontier
            assert ta.n_changes == tb.n_changes
        return a

    def _deltas(self, docs, ids, edits):
        """edits: list of (doc_idx, fn) applied in order; returns one round
        dict of per-doc deltas."""
        deltas = {}
        for i, fn in edits:
            prev = docs[i]
            new = am.change(prev, fn)
            deltas.setdefault(ids[i], []).extend(
                new._doc.opset.get_missing_changes(prev._doc.opset.clock))
            docs[i] = new
        return deltas

    def test_in_order_rounds_match_apply_rounds(self):
        docs, logs = self._mk_docs(4)
        ids = [f"d{i}" for i in range(4)]
        rounds = []
        for rnd in range(3):
            rounds.append(self._deltas(
                docs, ids,
                [(i, lambda d, rnd=rnd, i=i: d.__setitem__(
                    "n", rnd * 100 + i)) for i in (0, 2, 3)]))
        self._twin_check(ids, logs, rounds)

    def test_in_order_chains_take_batched_path(self):
        """Streaming steady state (one actor's consecutive edits per doc
        across rounds) must ride the whole-batch vectorized admission, not
        the per-round fallback — and still match the twin bit for bit."""
        from automerge_tpu.sync.frames import encode_round_frame
        if self.native is False:
            pytest.skip("batched admission is a native-encoder path")
        docs, logs = self._mk_docs(3)
        ids = [f"d{i}" for i in range(3)]
        rounds = [self._deltas(
            docs, ids,
            [(i, lambda d, rnd=rnd, i=i: d.__setitem__("n", rnd * 10 + i))
             for i in range(3)]) for rnd in range(5)]
        a, b = self._mk_set(ids), self._mk_set(ids)
        boot = [{ids[i]: logs[i] for i in range(len(ids))}]
        a.apply_rounds(boot)
        b.apply_rounds(boot)
        # settle to single-head frontiers (the boot merge leaves two heads,
        # which the dense cache cannot verify coverage against — this first
        # micro-batch may fall back)
        np.asarray(a.apply_round_frames([encode_round_frame(rounds[0])]))
        am.metrics.reset()
        h = np.asarray(a.apply_round_frames(
            [encode_round_frame(r) for r in rounds[1:]]))[:len(ids)]
        snap = am.metrics.snapshot()
        assert snap.get("rows_rounds_batched", 0) == 4, snap
        hs = b.apply_rounds(rounds)
        np.testing.assert_array_equal(h, hs[-1])
        a.sync_tables()
        b.sync_tables()
        for ta, tb in zip(a.tables, b.tables):
            assert ta.clock == tb.clock
            assert ta.frontier == tb.frontier
            assert ta.n_changes == tb.n_changes

    def test_out_of_order_rounds_buffer_and_release(self):
        docs, logs = self._mk_docs(1)
        ids = ["d0"]
        prev = docs[0]
        s1 = am.change(prev, lambda d: d.__setitem__("a", 1))
        s2 = am.change(s1, lambda d: d.__setitem__("a", 2))
        c1 = s1._doc.opset.get_missing_changes(prev._doc.opset.clock)
        c2 = s2._doc.opset.get_missing_changes(s1._doc.opset.clock)
        # later change first: queues in round 1, released by round 2
        self._twin_check(ids, logs, [{ids[0]: c2}, {ids[0]: c1}])

    def test_queued_release_across_frames(self):
        """A change queued by an earlier apply_round_frames call is released
        by a later one — the released payload lives in a DIFFERENT frame
        than the releasing round's."""
        from automerge_tpu.sync.frames import encode_round_frame
        docs, logs = self._mk_docs(1)
        ids = ["d0"]
        a = self._mk_set(ids)
        b = self._mk_set(ids)
        boot = [{ids[0]: logs[0]}]
        a.apply_rounds(boot)
        b.apply_rounds(boot)
        prev = docs[0]
        s1 = am.change(prev, lambda d: d.__setitem__("x", 1))
        s2 = am.change(s1, lambda d: d.__setitem__("x", 2))
        c1 = s1._doc.opset.get_missing_changes(prev._doc.opset.clock)
        c2 = s2._doc.opset.get_missing_changes(s1._doc.opset.clock)
        a.apply_round_frames([encode_round_frame({ids[0]: c2})])
        assert a._queued_docs == {0}
        h = np.asarray(a.apply_round_frames(
            [encode_round_frame({ids[0]: c1})]))[:1]
        assert a._queued_docs == set()
        hs = b.apply_rounds([{ids[0]: c2}, {ids[0]: c1}])
        np.testing.assert_array_equal(h, hs[-1])

    def test_unknown_dep_actor_queues_instead_of_crashing(self):
        """A round frame can carry a change whose declared dep names an
        actor the DocSet has never seen (its changes not yet delivered):
        it must queue, not crash, and release when the dep arrives."""
        from automerge_tpu.sync.frames import encode_round_frame
        docs, logs = self._mk_docs(1)
        ids = ["d0"]
        a = self._mk_set(ids)
        b = self._mk_set(ids)
        boot = [{ids[0]: logs[0]}]
        a.apply_rounds(boot)
        b.apply_rounds(boot)
        prev = docs[0]
        # actor Y edits, then actor Z edits on top: Z's change deps on Y
        y = am.change(am.merge(am.init("Y"), prev),
                      lambda d: d.__setitem__("w", 1))
        z = am.change(am.merge(am.init("Z"), y),
                      lambda d: d.__setitem__("w", 2))
        cy = y._doc.opset.get_missing_changes(prev._doc.opset.clock)
        cz = z._doc.opset.get_missing_changes(y._doc.opset.clock)
        a.apply_round_frames([encode_round_frame({ids[0]: cz})])  # queues
        assert a._queued_docs == {0}
        h = np.asarray(a.apply_round_frames(
            [encode_round_frame({ids[0]: cy})]))[:1]
        assert a._queued_docs == set()
        hs = b.apply_rounds([{ids[0]: cz}, {ids[0]: cy}])
        np.testing.assert_array_equal(h, hs[-1])

    def test_empty_doc_entry_is_a_noop(self):
        """A doc mapped to an empty change list in a round frame must not
        perturb that doc (or steal a neighbour's change)."""
        from automerge_tpu.sync.frames import encode_round_frame
        docs, logs = self._mk_docs(2)
        ids = ["d0", "d1"]
        a = self._mk_set(ids)
        b = self._mk_set(ids)
        boot = [{ids[i]: logs[i] for i in range(2)}]
        a.apply_rounds(boot)
        b.apply_rounds(boot)
        clock_before = dict(a.tables[0].clock)
        nc_before = a.tables[0].n_changes
        prev = docs[1]
        new = am.change(prev, lambda d: d.__setitem__("n", 123))
        c1 = new._doc.opset.get_missing_changes(prev._doc.opset.clock)
        h = np.asarray(a.apply_round_frames(
            [encode_round_frame({ids[0]: [], ids[1]: c1})]))[:2]
        assert a.tables[0].clock == clock_before
        assert a.tables[0].n_changes == nc_before
        hs = b.apply_rounds([{ids[1]: c1}])
        np.testing.assert_array_equal(h, hs[-1])
        # empty doc LAST in the frame (the index-past-the-end variant)
        prev2 = new
        new2 = am.change(prev2, lambda d: d.__setitem__("n", 456))
        c2 = new2._doc.opset.get_missing_changes(prev2._doc.opset.clock)
        h = np.asarray(a.apply_round_frames(
            [encode_round_frame({ids[1]: c2, ids[0]: []})]))[:2]
        hs = b.apply_rounds([{ids[1]: c2}])
        np.testing.assert_array_equal(h, hs[-1])

    def test_duplicate_delivery_is_idempotent(self):
        docs, logs = self._mk_docs(1)
        ids = ["d0"]
        prev = docs[0]
        new = am.change(prev, lambda d: d.__setitem__("z", 9))
        c = new._doc.opset.get_missing_changes(prev._doc.opset.clock)
        docs[0] = new
        self._twin_check(ids, logs, [{ids[0]: c}, {ids[0]: c}])

    def test_new_actor_in_round_frame(self):
        docs, logs = self._mk_docs(2)
        ids = ["d0", "d1"]
        prev = docs[0]
        other = am.merge(am.init("AA"), prev)  # rank shifts: A < AA < B
        other = am.change(other, lambda d: d.__setitem__("n", 777))
        merged = am.merge(prev, other)
        delta = merged._doc.opset.get_missing_changes(prev._doc.opset.clock)
        docs[0] = merged
        self._twin_check(ids, logs, [{ids[0]: delta}])

    def test_concurrent_heads_fall_back_to_slow_path(self):
        """Two concurrent changes then a merge change whose deps only
        partially cover the frontier at admission time: exercises the
        closure walk (fast path must not claim the full clock)."""
        docs, logs = self._mk_docs(1)
        ids = ["d0"]
        prev = docs[0]
        x = am.change(am.merge(am.init("X"), prev),
                      lambda d: d.__setitem__("n", 1))
        y = am.change(am.merge(am.init("Y"), prev),
                      lambda d: d.__setitem__("n", 2))
        m = am.merge(x, y)
        m = am.change(m, lambda d: d.__setitem__("n", 3))
        delta = m._doc.opset.get_missing_changes(prev._doc.opset.clock)
        docs[0] = m
        self._twin_check(ids, logs, [{ids[0]: delta}])

    def test_list_edits_relinearize(self):
        docs, logs = self._mk_docs(1)
        ids = ["d0"]
        rounds = []
        for rnd in range(3):
            rounds.append(self._deltas(
                docs, ids,
                [(0, lambda d, rnd=rnd: d["xs"].insert_at(0, rnd * 10))]))
        self._twin_check(ids, logs, rounds)

    def test_round_frame_wire_roundtrip(self):
        from automerge_tpu.sync.frames import (decode_round_frame,
                                               encode_round_frame)
        docs, logs = self._mk_docs(2)
        deltas = {"a": logs[0], "b": logs[1]}
        rc = decode_round_frame(encode_round_frame(deltas))
        assert rc.doc_ids == ["a", "b"]
        out = rc.to_dict()
        for k in deltas:
            assert [c.to_dict() for c in out[k]] \
                == [c.to_dict() for c in deltas[k]]

    def test_oracle_state_parity_after_round_frames(self):
        from automerge_tpu.engine.batchdoc import oracle_state
        from automerge_tpu.frontend.materialize import apply_changes_to_doc
        docs, logs = self._mk_docs(2)
        ids = ["d0", "d1"]
        rounds = [self._deltas(docs, ids, [
            (0, lambda d: d.__setitem__("n", 41)),
            (1, lambda d: d["xs"].insert_at(0, 5))])]
        a = self._twin_check(ids, logs, rounds)
        for i in range(2):
            full = docs[i]._doc.opset.get_missing_changes({})
            doc = apply_changes_to_doc(am.init("o"), am.init("o")._doc.opset,
                                       full, incremental=False)
            assert a.materialize(ids[i]) == oracle_state(doc)


class TestRoundFramesPython(TestRoundFrames):
    """Round-frame ingress again on the Python-encoder fallback."""

    native = False


class TestResidentRowsPython(TestResidentRows):
    """Every rows test again on the pure-Python encoder fallback (the path
    taken when the native toolchain is unavailable)."""

    native = False
