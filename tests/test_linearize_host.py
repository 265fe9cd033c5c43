"""Host linearizer (native + Python fallback) vs the device scan."""

import random

import numpy as np
import pytest

from automerge_tpu.native.linearize import linearize_host


def random_tree(rng, n):
    """Random insertion tree honoring parent.elem < child.elem."""
    ins_mask = np.zeros(n, dtype=bool)
    ins_elem = np.zeros(n, dtype=np.int32)
    ins_actor = np.zeros(n, dtype=np.int32)
    ins_parent = np.full(n, -1, dtype=np.int32)
    k = rng.randint(1, n)
    for i in range(k):
        ins_mask[i] = True
        ins_elem[i] = i + 1
        ins_actor[i] = rng.randint(0, 3)
        ins_parent[i] = rng.randint(-1, i - 1) if i else -1
    return ins_mask, ins_elem, ins_actor, ins_parent


@pytest.mark.parametrize("seed", range(8))
def test_matches_device_scan(seed):
    import jax
    from automerge_tpu.engine.kernels import linearize
    rng = random.Random(seed)
    args = random_tree(rng, 32)
    host = linearize_host(*args)
    device = np.asarray(jax.jit(linearize)(*map(np.asarray, args)))
    valid = args[0]
    np.testing.assert_array_equal(host[valid], device[valid])
    # masked-out slots are -1 on the host path
    assert (host[~valid] == -1).all()


def test_python_fallback_matches_native():
    from automerge_tpu import native
    if not native.native_available():
        pytest.skip("no native lib; fallback is the only path")
    rng = random.Random(99)
    args = random_tree(rng, 64)
    native_out = linearize_host(*args)

    # force the fallback by monkeypatching get_lib
    import automerge_tpu.native.linearize as lin
    orig = lin.get_lib
    lin.get_lib = lambda: None
    try:
        fallback_out = linearize_host(*args)
    finally:
        lin.get_lib = orig
    np.testing.assert_array_equal(native_out, fallback_out)


def test_long_chain_fast():
    import time
    n = 65536
    ins_mask = np.ones(n, dtype=bool)
    ins_elem = np.arange(1, n + 1, dtype=np.int32)
    ins_actor = np.zeros(n, dtype=np.int32)
    ins_parent = np.arange(-1, n - 1, dtype=np.int32)
    t0 = time.perf_counter()
    pos = linearize_host(ins_mask, ins_elem, ins_actor, ins_parent)
    dt = time.perf_counter() - t0
    np.testing.assert_array_equal(pos, np.arange(n))
    assert dt < 1.0, f"host linearize too slow: {dt:.3f}s"


@pytest.mark.parametrize("fallback", [False, True])
@pytest.mark.parametrize("seed", range(4))
def test_many_lists_in_one_call_match_one_call_each(seed, fallback,
                                                    monkeypatch):
    """linearize_lists over lists of 0-40 elements, whose (elem, actor)
    keys repeat from list to list, gives each list's own order."""
    import automerge_tpu.native.linearize as lin
    if fallback:
        monkeypatch.setattr(lin, "get_lib", lambda: None)
    rng = random.Random(seed)
    lists = []
    for _ in range(rng.randint(1, 12)):
        n = rng.randint(0, 40)
        mask, elem, actor, parent = random_tree(rng, n) if n > 1 else (
            np.ones(n, bool), np.ones(n, np.int32), np.zeros(n, np.int32),
            np.full(n, -1, np.int32))
        lists.append((elem[mask], actor[mask], parent[mask]))
    starts = np.cumsum([0] + [len(e) for e, _, _ in lists])
    pos = lin.linearize_lists(*(np.concatenate([x[j] for x in lists])
                                for j in range(3)), starts)
    for k, (elem, actor, parent) in enumerate(lists):
        own = linearize_host(np.ones(len(elem), bool), elem, actor, parent)
        np.testing.assert_array_equal(pos[starts[k]:starts[k + 1]], own)


def test_empty():
    out = linearize_host(np.zeros(4, bool), np.zeros(4, np.int32),
                         np.zeros(4, np.int32), np.full(4, -1, np.int32))
    assert (out == -1).all()


def _dense(elem, actor, parent, slotted):
    """Full RGA positions of one list's entries, ranked over the slotted
    ones: the position column the rows mirror holds (ghosts order, but
    hold no cell)."""
    pos = linearize_host(np.ones(len(elem), bool), elem, actor, parent)
    rank = np.empty(len(elem), np.int64)
    rank[np.argsort(pos)] = np.arange(len(elem))
    keep = np.flatnonzero(slotted)
    out = np.empty(len(keep), np.int64)
    out[np.argsort(rank[keep])] = np.arange(len(keep))
    return out


@pytest.mark.parametrize("fallback", [False, True])
@pytest.mark.parametrize("seed", range(6))
def test_placed_inserts_equal_the_linearization(seed, fallback, monkeypatch):
    """place_lists over lists with ghosts (entries that order but hold no
    slot), each given 0-6 inserts that are the list's newest element,
    chained, at the head or at a slotted entry: the positions it returns
    and the ones it leaves alone are the linearization of the grown list,
    and it returns only the cells that moved and the new slots."""
    import automerge_tpu.native.linearize as lin
    if fallback:
        monkeypatch.setattr(lin, "get_lib", lambda: None)
    rng = random.Random(100 + seed)
    n_lists, rows = rng.randint(1, 9), 64
    mirror = np.full((rows * 2, n_lists + 3), -7, np.int32)
    lists, doc, base, n_old, ins_off, parents = [], [], [], [], [0], []
    for k in range(n_lists):
        mask, elem, actor, parent = random_tree(rng, rng.randint(2, 40))
        elem, actor, parent = elem[mask], actor[mask], parent[mask]
        n = len(elem)
        slotted = np.array([rng.random() < 0.6 for _ in range(n)], bool)
        slot = np.cumsum(slotted) - 1
        d, b = k + 1, rng.choice([0, rows])
        held = int(slotted.sum())
        mirror[b:b + held, d] = _dense(elem, actor, parent, slotted)
        # the round's inserts: counters past every counter in the list
        top = int(elem.max(initial=0))
        entry_of = {int(s): j for j, s in enumerate(slot) if slotted[j]}
        for t in range(rng.randint(0, 6)):
            anchor = rng.randint(-1, held + t - 1)
            top += rng.randint(1, 3)
            parents.append(anchor)
            entry_of[held + t] = len(elem)
            elem = np.append(elem, top)
            actor = np.append(actor, rng.randint(0, 3))
            parent = np.append(parent, entry_of[anchor] if anchor >= 0
                               else -1)
            slotted = np.append(slotted, True)
        lists.append((d, b, held, elem, actor, parent, slotted))
        doc.append(d)
        base.append(b)
        n_old.append(held)
        ins_off.append(len(parents))
    before = mirror.copy()
    docs, rows_, pos = lin.place_lists(mirror, np.array(doc), np.array(base),
                                       np.array(n_old), np.array(ins_off),
                                       np.array(parents))
    np.testing.assert_array_equal(mirror, before)      # only read
    after = mirror.copy()
    after[rows_, docs] = pos
    for d, b, held, elem, actor, parent, slotted in lists:
        want = _dense(elem, actor, parent, slotted)
        np.testing.assert_array_equal(after[b:b + len(want), d], want)
        moved = {r for r, dd in zip(rows_.tolist(), docs.tolist())
                 if dd == d and b <= r < b + len(want)}
        assert moved == {b + c for c in range(len(want))
                         if c >= held or want[c] != before[b + c, d]}


def test_placed_inserts_refuse_an_anchor_not_yet_placed():
    import automerge_tpu.native.linearize as lin
    mirror = np.zeros((8, 2), np.int32)
    with pytest.raises(ValueError):
        # the list's first insert anchored at the slot it is about to take
        lin.place_lists(mirror, np.array([0]), np.array([0]), np.array([2]),
                        np.array([0, 1]), np.array([2]))
