"""The plain reference of the card-board configuration (`boards10k`):
Automerge's semantics over nested maps and lists, and the canonical state
hash, in straightforward Python.

It imports nothing of the program and takes nothing the program has made.
From `reference.py` it takes the causal order and the hash primitives, which
are the same for a map document; everything about objects, lists and their
order is written out here. It reads the changes the benchmark itself sent
(`.actor`, `.seq`, `.deps`, `.ops[*].action/.obj/.key/.value/.elem`, by
attribute) and follows the published semantics (Automerge v0.8.0,
`src/op_set.js`):

- a change is applied once its dependencies are (`reference.causal_order`);
- `makeMap` / `makeList` make an object; `link` puts one under a map key or a
  list element, `set` a scalar;
- `ins` makes an element of a list, named `actor:elem`, after its anchor (the
  element named by its `key`, or `_head`). The sequence is the preorder of
  the tree of anchors, the elements under one anchor in **descending (elem
  counter, actor id)**: a later insert at one anchor comes first, and of two
  concurrent ones with the same counter the higher actor id;
- an assignment (`set`, `link`, `del`) to a map key or a list element removes
  every earlier assignment to it that its change had seen, and stands
  beside the ones it had not; `del` carries no value;
- an element is in the visible sequence while an assignment that carries a
  value survives on it (`del` of an element leaves a tombstone that still
  anchors what was inserted after it); a key's or an element's value is the
  surviving assignment of the highest actor id, the others its conflicts.

`state` renders a document as the service's `materialize` does: `{"data":
nested JSON, "conflicts": the root map's conflicts}`. `state_hash` is
`automerge_tpu/engine/kernels.py` `state_hash`, written out again: the sum
modulo 2**32, over every surviving assignment that carries a value, of
`mix4(k1, k2, crc32(actor), crc32(value bytes))`, where a map key gives
`(k1, k2) = (-7, crc32(object "\\0" key))` and a list element gives
`(crc32(list object id), the element's visible rank)`; a link's value bytes
are `"l:" + object id`.

Two modes break one rule each, for the check's controls: `siblings=
"ascending"` orders the elements under one anchor in ascending order, and
`tombstones="visible"` keeps a deleted element in the visible sequence with
the value it last had.
"""

from __future__ import annotations

import reference
from reference import ROOT_ID, _M32, _MAP_FIELD, _crc31, _memo_crc, _mix4

HEAD = "_head"
ASSIGN = ("set", "link", "del")


class Doc:
    """One document's objects, fields and elements, built from its log."""

    def __init__(self, changes, *, first_writer: bool = False,
                 siblings: str = "descending", tombstones: str = "hidden"):
        self.kind = {ROOT_ID: "map"}      # object id -> "map" | "list"
        self.fields: dict = {}            # (obj, key) -> [(actor, seq, action, value)]
        self.last_value: dict = {}        # (obj, key) -> the last value-carrying one
        self.keys: dict = {}              # obj -> keys in order of first assignment
        self.elems: dict = {}             # list -> {eid: (anchor, elem, actor)}
        self.siblings, self.tombstones = siblings, tombstones
        seen: dict = {}   # (actor, seq) -> everything that change had seen
        for c in reference.causal_order(changes):
            saw: dict = {}
            base = dict(c.deps)
            base[c.actor] = c.seq - 1
            for a, s in base.items():
                if s <= 0:
                    continue
                for a2, s2 in seen.get((a, s), {}).items():
                    if s2 > saw.get(a2, 0):
                        saw[a2] = s2
                if s > saw.get(a, 0):
                    saw[a] = s
            seen[(c.actor, c.seq)] = saw
            for op in c.ops:
                self._apply(c, op, saw, first_writer)
        self._order: dict = {}

    def _apply(self, c, op, saw: dict, first_writer: bool) -> None:
        action = op.action
        if action == "makeMap":
            self.kind.setdefault(op.obj, "map")
        elif action == "makeList":
            self.kind.setdefault(op.obj, "list")
        elif action == "ins":
            self.elems.setdefault(op.obj, {}).setdefault(
                f"{c.actor}:{op.elem}", (op.key, op.elem, c.actor))
        elif action in ASSIGN:
            at = (op.obj, op.key)
            held = self.fields.get(at)
            if held is None:
                held = self.fields[at] = []
                self.keys.setdefault(op.obj, []).append(op.key)
            if first_writer:
                # the control: an assignment never removes an earlier one
                if not held and action != "del":
                    held.append((c.actor, c.seq, action, op.value))
                return
            kept = [o for o in held if saw.get(o[0], 0) < o[1]]
            kept.append((c.actor, c.seq, action, op.value))
            self.fields[at] = kept
            if action != "del":
                self.last_value[at] = (c.actor, c.seq, action, op.value)
        else:
            raise ValueError(f"the board reference has no op {action!r}")

    # -- reads -------------------------------------------------------------

    def candidates(self, obj: str, key: str) -> list:
        """The surviving value-carrying assignments of one key or element;
        where tombstones stay visible, a deleted element keeps its last."""
        held = [o for o in self.fields.get((obj, key), ()) if o[2] != "del"]
        if not held and self.tombstones == "visible" \
                and self.kind.get(obj) == "list":
            last = self.last_value.get((obj, key))
            if last is not None:
                held = [last]
        return held

    def order(self, obj: str) -> list:
        """Every element of a list, tombstones among them, in sequence."""
        got = self._order.get(obj)
        if got is not None:
            return got
        under: dict = {}
        for eid, (anchor, elem, actor) in self.elems.get(obj, {}).items():
            under.setdefault(anchor, []).append(((elem, actor), eid))
        for kids in under.values():
            kids.sort(reverse=self.siblings == "descending")
        out = []
        stack = list(reversed(under.get(HEAD, ())))
        while stack:
            _, eid = stack.pop()
            out.append(eid)
            stack.extend(reversed(under.get(eid, ())))
        self._order[obj] = out
        return out

    def visible(self, obj: str) -> list:
        """[(eid, candidates)] of a list's visible elements, in sequence."""
        out = []
        for eid in self.order(obj):
            held = self.candidates(obj, eid)
            if held:
                out.append((eid, held))
        return out

    def _value(self, op):
        _a, _s, action, value = op
        return self.render(value) if action == "link" else value

    def render(self, obj: str):
        if self.kind.get(obj) == "list":
            return [self._value(max(held)) for _eid, held in self.visible(obj)]
        out = {}
        for key in self.keys.get(obj, ()):
            held = self.candidates(obj, key)
            if held:
                out[key] = self._value(max(held))
        return out

    def state(self) -> dict:
        """{"data", "conflicts"} as the service's `materialize` renders a
        document: the nested JSON of the winners, and the root map's
        conflicts (actor -> value) beside it."""
        conflicts = {}
        for key in self.keys.get(ROOT_ID, ()):
            held = sorted(self.candidates(ROOT_ID, key), reverse=True)
            if len(held) > 1:
                conflicts[key] = {o[0]: self._value(o) for o in held[1:]}
        return {"data": self.render(ROOT_ID), "conflicts": conflicts}

    def state_hash(self) -> int:
        total = 0
        for obj, kind in self.kind.items():
            if kind == "list":
                k1 = _memo_crc("o", obj)
                rows = [(k1, rank, held) for rank, (_eid, held)
                        in enumerate(self.visible(obj))]
            else:
                rows = [(_MAP_FIELD, _memo_crc("f", f"{obj}\x00{key}"),
                         self.candidates(obj, key))
                        for key in self.keys.get(obj, ())]
            for k1, k2, held in rows:
                for actor, _seq, action, value in held:
                    raw = (b"l:" + value.encode("utf-8", "surrogatepass")
                           if action == "link"
                           else reference.value_bytes(value))
                    total += _mix4(k1, k2, _memo_crc("a", actor), _crc31(raw))
        return total & _M32


def state(changes, **mode) -> dict:
    return Doc(changes, **mode).state()


def state_hash(changes, **mode) -> int:
    return Doc(changes, **mode).state_hash()
