"""Fleet kind `devices`: the maps fleet of `fleet.py`, written by its own
devices. In the reference every replica of every document has an actor id
of its own (`Automerge.init()` and `load()` default to `uuid()`,
`DocSet.applyChanges` makes an unknown document "with a fresh actorId");
here every document has 2 to `devices_cap` writers, each with a
uuid-shaped id (32 hex digits made from the seed, the document and the
device's number) that no other document shares.

A configuration's `fleet` holds `FleetSpec`'s numbers as `fleet10k` has them;
its `writers` group holds this kind's own:

- `devices_at_load`: `[lo, hi]`, a small document's writers at load, drawn
  uniformly for each document; the list, text and move documents keep two;
- `devices_cap`: the most writers a document may get; the heavy documents
  are written by that many, `heavy_ops / devices_cap` ops a device, one
  change each in a chain, so that they set the resident actor axis at load;
- `concurrent_share`: with this probability, where the document's latest
  change is another device's, the writer has not seen it: its `deps` name
  the document's frontier as it was before that change. Otherwise `deps`
  name the whole frontier, so the change after a concurrent pair names both
  heads. (`deps` never name the writer itself: its own last change is
  implied, as in the reference.)
- `join_share`: with this probability, where the document has fewer than
  `devices_cap` devices, the change is the first of a device new to the
  document (`seq` 1, `deps` the whole frontier).

The writer of a change is uniform over the document's devices and its `seq`
that device's next in that document. The load's history is made by the same
rule as the window's (`_write`), so the loaded fleet already holds
conflicts and two-headed documents. A request's three draws a document
(writer, concurrent, join) come from a generator seeded by what the
schedule drew, so `request_changes` is a function of the fleet's state and
the request alone and `replay` makes every change again from the seed.

The fleet also keeps, exactly, which keys of which documents hold a
conflict (`conflicted`: a change that saw everything leaves one survivor on
each key it writes; one that did not see the latest change stands beside
that change's `set` of the same key and replaces the others) and which
documents a device joined after the load (`joined`): the configuration's
check widens its sample of materialized states by them.
"""

from __future__ import annotations

import hashlib
import random
from dataclasses import dataclass

import numpy as np

import fleet as base

SMALL_KEYS = base.SMALL_KEYS


@dataclass
class Spec(base.FleetSpec):
    devices_at_load: tuple = (2, 4)
    devices_cap: int = 8
    concurrent_share: float = 0.10
    join_share: float = 0.01

    @classmethod
    def from_config(cls, config: dict) -> "Spec":
        """`fleet` holds `FleetSpec`'s numbers, unchanged, and `writers`
        this kind's own."""
        spec = cls(**vars(base.FleetSpec.from_config(config)),
                   **config["writers"])
        spec.devices_at_load = tuple(spec.devices_at_load)
        lo, hi = spec.devices_at_load
        if not 1 <= lo <= hi <= spec.devices_cap:
            raise ValueError(f"devices_at_load {lo}..{hi} does not lie in "
                             f"1..devices_cap {spec.devices_cap}")
        return spec


def actor_id(seed: int, doc_id: str, k: int) -> str:
    """Device `k` of a document: 32 hex digits, as a uuid without its
    dashes, of (seed, document, k); no two documents share one."""
    return hashlib.blake2b(f"{int(seed)}/{doc_id}/{k}".encode(),
                           digest_size=16).hexdigest()


class Doc:
    """What the fleet keeps of one small document to write its next
    change: its devices in the order they came, each one's last `seq`, the
    frontier now and as it was before the latest change, and that change's
    writer and keys."""
    __slots__ = ("devices", "seqs", "heads", "heads_before", "last",
                 "last_keys")

    def __init__(self, devices: list):
        self.devices = devices
        self.seqs = [0] * len(devices)
        self.heads: dict = {}
        self.heads_before: dict = {}
        self.last = -1
        self.last_keys: tuple = ()


class Fleet:
    dims_fixed = True
    n_fields = len(SMALL_KEYS)
    request_ops = staticmethod(len)    # ops of a request: one a document

    def __init__(self, spec: Spec, seed: int, *, small=None, structured=None,
                 first=None):
        self.spec, self.seed = spec, int(seed)
        self.small = small if small is not None else [
            f"doc{i:05d}" for i in range(spec.n_small)]
        self.first = first if first is not None else structured_load(
            spec, self.seed)
        self.structured = structured if structured is not None \
            else list(self.first)
        rng = random.Random(f"{self.seed}/devices")
        lo, hi = spec.devices_at_load
        self.docs = {d: Doc([actor_id(self.seed, d, k)
                             for k in range(rng.randint(lo, hi))])
                     for d in self.small}
        self.depth: dict = {}
        self.conflicted: dict = {}     # doc id -> keys that hold a conflict
        self.joined: set = set()       # documents a device joined, load aside
        self.loaded = False

    @property
    def doc_ids(self) -> list:
        return self.structured + self.small

    # -- one change ---------------------------------------------------------

    def _write(self, d: str, ops: tuple, u_writer: float, u_conc: float,
               u_join: float):
        """The document's next change, by the rule in the module's
        docstring; `u_*` are its three uniform draws."""
        from automerge_tpu.core.change import Change
        spec, doc = self.spec, self.docs[d]
        devices = doc.devices
        if doc.last >= 0 and u_join < spec.join_share \
                and len(devices) < spec.devices_cap:
            w = len(devices)
            devices.append(actor_id(self.seed, d, w))
            doc.seqs.append(0)
            if self.loaded:
                self.joined.add(d)
            seen_all = True
        else:
            w = int(u_writer * len(devices))
            seen_all = not (doc.last >= 0 and doc.last != w
                            and u_conc < spec.concurrent_share)
        actor = devices[w]
        seq = doc.seqs[w] = doc.seqs[w] + 1
        keys = tuple(op.key for op in ops)
        before = doc.heads if seen_all else doc.heads_before
        deps = {a: s for a, s in before.items() if a != actor}
        if seen_all:
            heads = {actor: seq}
            held = self.conflicted.get(d)
            if held:
                held.difference_update(keys)
        else:
            # what it did not see stays a head beside it
            latest = devices[doc.last]
            heads = {latest: doc.heads[latest], actor: seq}
            held = self.conflicted.setdefault(d, set())
            for k in keys:
                (held.add if k in doc.last_keys else held.discard)(k)
        doc.heads_before, doc.heads = doc.heads, heads
        doc.last, doc.last_keys = w, keys
        self.depth[d] = self.depth.get(d, 0) + len(ops)
        return Change(actor, seq, deps, ops)

    # -- what run.py and the drivers ask of a fleet -------------------------

    def load_rounds(self):
        """The load, one coalesced round at a time: the structured
        documents first (their writers set the resident actor axis), then
        the small ones."""
        yield self.first
        yield from self.small_load_rounds()
        self.loaded = True

    def small_load_rounds(self):
        """`load_batch` documents a round: a document's first three-op
        change by its first device and, behind it, 0..max seven-op edits
        (the number drawn for each document), written by the same rule as
        the window's changes."""
        from automerge_tpu.core.change import Op
        from automerge_tpu.core.ids import ROOT_ID
        spec = self.spec
        rng = random.Random(f"{self.seed}/small")
        draw = rng.random
        edits = [tuple(Op("set", ROOT_ID, key=k, value=rng.randrange(1 << 16))
                       for k in SMALL_KEYS) for _ in range(251)]
        for lo in range(0, spec.n_small, spec.load_batch):
            round_ = {}
            for d in self.small[lo:lo + spec.load_batch]:
                chs = [self._write(d, (
                    Op("set", ROOT_ID, key="title",
                       value=f"t{rng.randrange(999)}"),
                    Op("set", ROOT_ID, key="n", value=rng.randrange(1 << 16)),
                    Op("set", ROOT_ID, key="done",
                       value=bool(rng.randrange(2)))), 0.0, 1.0, 1.0)]
                for _ in range(rng.randrange(spec.history_changes_max + 1)):
                    chs.append(self._write(d, edits[rng.randrange(251)],
                                           draw(), draw(), draw()))
                round_[d] = chs
            yield round_

    def load_line(self) -> dict:
        depths = sorted(self.depth.values())
        n_dev = sorted(len(doc.devices) for doc in self.docs.values())
        return {"small_depth_min_median_max": [
                    depths[0], depths[len(depths) // 2], depths[-1]],
                "devices_min_median_max": [
                    n_dev[0], n_dev[len(n_dev) // 2], n_dev[-1]],
                "actor_ids": sum(n_dev) + sum(
                    len({c.actor for c in chs})
                    for chs in self.first.values()),
                "two_headed": sum(1 for doc in self.docs.values()
                                  if len(doc.heads) > 1),
                "conflicted": sum(1 for v in self.conflicted.values() if v)}

    def request_changes(self, drawn: tuple):
        """{doc id: [Change]} of one request as the schedule drew it: one
        one-op change a document, by one of the document's own devices; or
        `"history_cap"` where a document drawn can take no more."""
        from automerge_tpu.core.change import Op
        from automerge_tpu.core.ids import ROOT_ID
        docs, fields_, values = drawn
        small, depth, cap = self.small, self.depth, self.spec.history_cap
        idx = docs.tolist()
        if any(depth[small[i]] >= cap for i in idx):
            return "history_cap"
        u = np.random.default_rng(
            [self.seed, 0xDE71CE, len(idx), int(docs.sum()),
             int(values.sum())]).random((3, len(idx))).tolist()
        write = self._write
        return {small[i]: [write(small[i], (
            Op("set", ROOT_ID, key=SMALL_KEYS[f], value=v),), uw, uc, uj)]
            for i, f, v, uw, uc, uj in zip(idx, fields_.tolist(),
                                           values.tolist(), *u)}

    def replay(self, schedule, numbers) -> tuple:
        """Every acknowledged change made again from the seed on a fleet of
        its own: {doc id: [Change]} of the load and the requests `numbers`
        in the order they were sent, and {(doc id, actor, seq): request
        number} of the requests' changes."""
        again = type(self)(self.spec, self.seed, small=self.small,
                           structured=self.structured, first=self.first)
        sent = {d: list(chs) for d, chs in self.first.items()}
        for round_ in again.small_load_rounds():
            sent.update(round_)
        again.loaded = True
        origin = {}
        for r in numbers:
            for d, chs in again.request_changes(schedule.request(r)).items():
                sent[d].extend(chs)
                origin[(d, chs[0].actor, chs[0].seq)] = r
        return sent, origin


def make(config: dict, seed: int) -> Fleet:
    return Fleet(Spec.from_config(config), seed)


# ---------------------------------------------------------------------------
# the structured documents, as fleet.make_fleet makes them, under ids of
# their own


def structured_load(spec: Spec, seed: int) -> dict:
    """{doc id: [Change]} of the heavy, list, text and move documents:
    `fleet.make_fleet`'s, with every writer under an id of its own. A heavy
    document is written by `devices_cap` devices in a chain, an equal share
    of its ops each."""
    import automerge_tpu as am
    from automerge_tpu.core.change import Change, Op
    from automerge_tpu.core.ids import ROOT_ID

    rng = random.Random(seed)
    first: dict = {}
    n_dev = spec.devices_cap
    for h in range(spec.n_heavy):
        d = f"heavy{h:02d}"
        keys = [f"k{j}" for j in range(spec.heavy_ops)]
        chs, deps = [], {}
        for k in range(n_dev):
            actor = actor_id(seed, d, k)
            chs.append(Change(actor, 1, deps, [
                Op("set", ROOT_ID, key=key, value=rng.randrange(1 << 20))
                for key in keys[k::n_dev]]))
            deps = {actor: 1}
        first[d] = chs
    for i in range(spec.n_list):
        d = f"list{i:02d}"
        a = am.change(am.init(actor_id(seed, d, 0)), lambda doc: doc
                      .__setitem__("xs", [rng.randrange(100)
                                          for _ in range(12)]))
        b = am.merge(am.init(actor_id(seed, d, 1)), a)
        a = am.change(a, lambda doc: doc["xs"].insert_at(
            rng.randrange(12), -1))
        b = am.change(b, lambda doc: doc["xs"].delete_at(rng.randrange(12)))
        a = am.merge(a, b)
        first[d] = a._doc.opset.get_missing_changes({})
    for i in range(spec.n_text):
        d = f"text{i:02d}"
        a = am.change(am.init(actor_id(seed, d, 0)),
                      lambda doc: doc.__setitem__("t", am.Text()))
        a = am.change(a, lambda doc: doc["t"].insert_at(
            0, *(chr(97 + rng.randrange(26)) for _ in range(24))))
        b = am.merge(am.init(actor_id(seed, d, 1)), a)
        a = am.change(a, lambda doc: doc["t"].insert_at(
            rng.randrange(24), "A"))
        b = am.change(b, lambda doc: doc["t"].insert_at(
            rng.randrange(24), *"bb"))
        b = am.change(b, lambda doc: doc["t"].delete_at(rng.randrange(20), 2))
        a = am.merge(a, b)
        first[d] = a._doc.opset.get_missing_changes({})
    for i in range(spec.n_move):
        d = f"move{i:02d}"
        a, b = actor_id(seed, d, 0), actor_id(seed, d, 1)
        first[d] = _move_doc_base(a) + [
            Change(b, 1, {a: 1}, [Op("move", "f1", key="in", value="f0")]),
            Change(b, 2, {b: 1}, [Op("move", "L", key="_head",
                                     value=f"{a}:4", elem=9)])]
    return first


def _move_doc_base(actor: str) -> list:
    """`fleet._move_doc_base`'s small board, written by `actor`."""
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
        ops.append(Op("set", "L", key=f"{actor}:{e}", value=f"v{e}"))
        prev = f"{actor}:{e}"
    return [Change(actor, 1, {}, ops)]
