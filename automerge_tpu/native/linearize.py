"""Host RGA linearization (native with Python fallback).

The device linearizer is a sequential lax.scan — the right tool for the
short lists of typical documents, but a wall for long text (the next-pointer
chain is as deep as the document; ~400 ms at 64K elements on the bench
chip). For the from-scratch batch path the order can be computed on the host
at encode time instead and shipped as a position column; this module provides
that computation at C speed (microseconds up to ~1M elements), with a pure-
Python fallback implementing the identical algorithm.
"""

from __future__ import annotations

import ctypes

import numpy as np

from . import get_lib


def linearize_host(ins_mask: np.ndarray, ins_elem: np.ndarray,
                   ins_actor: np.ndarray, ins_parent: np.ndarray) -> np.ndarray:
    """Positions of each element slot in full RGA order (-1 for masked-out
    slots). Same contract as engine.kernels.linearize."""
    n = len(ins_mask)
    out = np.full(n, -1, dtype=np.int32)
    if n == 0 or not ins_mask.any():
        return out

    lib = get_lib()
    if lib is not None and hasattr(lib, "amtpu_linearize"):
        elem = np.ascontiguousarray(ins_elem, dtype=np.int32)
        actor = np.ascontiguousarray(ins_actor, dtype=np.int32)
        parent = np.ascontiguousarray(ins_parent, dtype=np.int32)
        mask = np.ascontiguousarray(ins_mask, dtype=np.uint8)

        def ptr(a):
            return a.ctypes.data_as(ctypes.c_void_p)

        lib.amtpu_linearize(n, ptr(elem), ptr(actor), ptr(parent), ptr(mask),
                            ptr(out))
        return out

    # Python fallback: identical algorithm.
    order = sorted((i for i in range(n) if ins_mask[i]),
                   key=lambda i: (ins_elem[i], ins_actor[i]))
    nxt = np.full(n + 1, -1, dtype=np.int32)  # node 0 = head; slot e -> e+1
    for idx in order:
        p = ins_parent[idx] + 1 if ins_parent[idx] >= 0 else 0
        e = idx + 1
        nxt[e] = nxt[p]
        nxt[p] = e
    pos = 0
    v = nxt[0]
    while v != -1:
        out[v - 1] = pos
        pos += 1
        v = nxt[v]
    return out


def linearize_lists(elem: np.ndarray, actor: np.ndarray, parent: np.ndarray,
                    starts: np.ndarray) -> np.ndarray:
    """Positions of many lists' elements, each in its own list's RGA order,
    in one linearize_host call. List k holds entries starts[k]:starts[k+1];
    an entry's parent indexes its own list's entries (-1: the list's head).
    Each list gets a head node of its own, keyed below every element and
    chained behind the previous list's head, so list k's elements are placed
    between its head and the next head and the lists never interleave."""
    starts = np.asarray(starts, np.int64)
    k = len(starts) - 1
    lens = np.diff(starts)
    owner = np.repeat(np.arange(k, dtype=np.int64), lens)
    parent = np.asarray(parent, np.int64)
    node_parent = np.where(parent >= 0, parent + starts[owner] + k, owner)
    pos = linearize_host(
        np.ones(k + len(owner), dtype=bool),
        np.concatenate([np.iinfo(np.int32).min + np.arange(k),
                        np.asarray(elem, np.int64)]),
        np.concatenate([np.zeros(k, np.int64), np.asarray(actor, np.int64)]),
        np.concatenate([np.arange(-1, k - 1), node_parent]))
    return pos[k:].astype(np.int64) - pos[owner] - 1


def place_lists(mirror: np.ndarray, doc: np.ndarray, base: np.ndarray,
                n_old: np.ndarray, ins_off: np.ndarray,
                parent: np.ndarray):
    """Place many lists' round inserts against the positions a row-major
    int32 `mirror` holds. List k is the column doc[k], its cells the rows
    base[k] + slot: the first n_old[k] hold the dense positions of its
    slotted entries before the round, the next ones are the round's new
    slots. Its inserts ins_off[k]:ins_off[k+1], in admission order, are each
    the list's newest element, parent[j] the anchor's slot (-1: the head), a
    slot the list held before that insert. Each lands right after its
    anchor: it takes the anchor's position + 1 (0 at the head) and every
    placed cell at or past it moves up by one. Returns (docs, rows,
    positions), int64 arrays, of the cells whose position changed and of
    the new slots; the mirror is only read. One native call
    (amtpu_place_lists), with a pure-Python fallback of the same loops."""
    doc = np.ascontiguousarray(doc, dtype=np.int64)
    base = np.ascontiguousarray(base, dtype=np.int64)
    n_old = np.ascontiguousarray(n_old, dtype=np.int64)
    ins_off = np.ascontiguousarray(ins_off, dtype=np.int64)
    parent = np.ascontiguousarray(parent, dtype=np.int32)
    k = len(doc)
    n_new = np.diff(ins_off)
    owner = np.repeat(np.arange(k), n_new)
    turn = np.arange(len(parent)) - ins_off[owner]
    if (mirror.dtype != np.int32 or mirror.ndim != 2
            or not mirror.flags.c_contiguous
            or not (len(base) == len(n_old) == k == len(ins_off) - 1)
            or len(parent) != ins_off[-1] or (n_new < 0).any()
            or ((doc < 0) | (doc >= mirror.shape[1])).any()
            or ((base < 0) | (base + n_old + n_new > mirror.shape[0])).any()
            or ((parent < -1) | (parent >= n_old[owner] + turn)).any()):
        raise ValueError("place_lists: inconsistent list layout")
    size = int((n_old + n_new).sum())
    out = np.empty((3, size), np.int64)
    lib = get_lib()
    if lib is not None and hasattr(lib, "amtpu_place_lists"):
        def ptr(a):
            return a.ctypes.data_as(ctypes.c_void_p)

        n = lib.amtpu_place_lists(k, ptr(mirror), mirror.shape[1], ptr(doc),
                                  ptr(base), ptr(n_old), ptr(ins_off),
                                  ptr(parent), size, ptr(out))
        return tuple(out[:, :n])
    # Python fallback: identical algorithm.
    n = 0
    for lst in range(k):
        held = int(n_old[lst])
        cell = np.empty(held + int(n_new[lst]), np.int64)
        cell[:held] = mirror[base[lst]:base[lst] + held, doc[lst]]
        was = cell[:held].copy()
        t = held
        for j in range(int(ins_off[lst]), int(ins_off[lst + 1])):
            p = int(cell[parent[j]]) + 1 if parent[j] >= 0 else 0
            cell[:t] += cell[:t] >= p
            cell[t] = p
            t += 1
        moved = np.ones(len(cell), bool)
        moved[:held] = cell[:held] != was
        c = np.flatnonzero(moved)
        out[0][n:n + len(c)] = doc[lst]
        out[1][n:n + len(c)] = base[lst] + c
        out[2][n:n + len(c)] = cell[c]
        n += len(c)
    return tuple(out[:, :n])
