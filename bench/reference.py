"""The plain reference: a Python dict of key -> value, fed the same
requests in the order the service was called, and the comparisons that
decide ``correct``.

It shares nothing with the store under test: no hash, no table layout,
no status codes.  A GET of a key answers whether the key is present and
its value; an update replaces the whole value of its key.
"""
from __future__ import annotations

import collections
from typing import Iterable

import numpy as np

from . import ycsb


class Reference:
    def __init__(self, keys: np.ndarray, vals: np.ndarray):
        self.table = {k: tuple(v) for k, v in zip(keys.tolist(),
                                                  vals.tolist())}

    def get(self, key: int):
        value = self.table.get(key)
        return value is not None, value

    def update(self, key: int, value) -> None:
        self.table[key] = tuple(int(w) for w in value)


class Tally(collections.Counter):
    """Numbers compared (``<what>_compared``) and mismatches found
    (``<what>_mismatches``)."""

    def add(self, what: str, compared: int, mismatches: int):
        self[f"{what}_compared"] += compared
        self[f"{what}_mismatches"] += mismatches


def _get_rows(ref: Reference, call) -> tuple[int, int]:
    compared = bad = 0
    for s, j in zip(*np.nonzero((call.keys != 0) & call.ok)):
        found, value = ref.get(int(call.keys[s, j]))
        compared += 1
        if bool(call.found[s, j]) != found or (
                found and tuple(call.values[s, j].tolist()) != value):
            bad += 1
    return compared, bad


def replay(ref: Reference, calls: Iterable, tally: Tally) -> set:
    """Compare every answered GET and update of ``calls`` with the
    reference, applying updates in call order and, within a SET call, in
    source-major row order.  Returns the keys updated."""
    updated = set()
    for call in calls:
        if call.kind == ycsb.READ:
            tally.add("get", *_get_rows(ref, call))
            continue
        compared = bad = 0
        for s, j in zip(*np.nonzero((call.keys != 0) & call.ok)):
            key = int(call.keys[s, j])
            ref.update(key, call.vals[s, j])
            updated.add(key)
            compared += 1
            bad += not bool(call.applied[s, j])
        tally.add("update", compared, bad)
    return updated


def read_back(ref: Reference, calls: Iterable, tally: Tally) -> None:
    """Compare the read-back GETs made after the window."""
    for call in calls:
        tally.add("readback", *_get_rows(ref, call))


def compare_table(ref: Reference, keys: np.ndarray, vals: np.ndarray,
                  tally: Tally) -> None:
    """Every bucket of the device tables against the reference: each
    occupied bucket holds a key of the reference with its value, no key
    twice, every key of the reference somewhere, and no empty bucket
    holds a value.  One mismatch per offending bucket or missing key."""
    flat_k = np.asarray(keys).reshape(-1)
    flat_v = np.asarray(vals).reshape(flat_k.size, -1)
    ref_k = np.fromiter(ref.table, np.int64, len(ref.table))
    ref_v = np.asarray(list(ref.table.values()), np.int64).reshape(
        len(ref_k), flat_v.shape[1])
    order = np.argsort(ref_k)
    ref_k, ref_v = ref_k[order], ref_v[order]

    occupied = flat_k != 0
    dk = flat_k[occupied].astype(np.int64)
    dv = flat_v[occupied].astype(np.int64)
    idx = np.minimum(np.searchsorted(ref_k, dk), max(len(ref_k) - 1, 0))
    known = (ref_k[idx] == dk) if len(ref_k) else np.zeros(len(dk), bool)
    right = known & (dv == ref_v[idx]).all(axis=1)
    uniq, counts = np.unique(dk[known], return_counts=True)
    extra_copies = int((counts - 1).sum())
    missing = len(ref_k) - len(uniq)
    stale = int((flat_v[~occupied] != 0).any(axis=1).sum())
    tally.add("table", flat_k.size,
              int((~right).sum()) + extra_copies + missing + stale)
