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
