"""Plain sequential reference of the store's semantics.

It imports nothing of the program.  The loaded records are two sorted
arrays; every operation after the load runs one at a time, in the order
of its linearization timestamp, against a dict of the latest value per
written key (``TOMBSTONE`` for a delete).  The semantics are the ADT's
(``harness.semantics``):

  * SEARCH returns the key's value, ``NOT_FOUND`` if absent or deleted;
  * INSERT and DELETE return the value before them, then write;
  * RANGE [k1, k2] returns every live (key, value) at its own timestamp,
    sorted by key, and its count as its value;
  * NOP returns ``NOT_FOUND``.

``stale_batch`` is the control: reads resolve against the state at the
start of their batch of ``stale_batch`` consecutive timestamps, so an
operation does not see writes made earlier in its own batch.  That breaks
the linearizability the configurations state, and the comparison has to
catch it.
"""

from __future__ import annotations

import bisect
from typing import Dict, Optional, Tuple

import numpy as np

from harness import semantics as sem


class Reference:
    def __init__(self, keys: np.ndarray, vals: np.ndarray):
        order = np.argsort(keys, kind="stable")
        self.keys = np.asarray(keys, np.int32)[order]
        self.vals = np.asarray(vals, np.int64)[order]
        if len(self.keys) > 1 and not (np.diff(self.keys) > 0).all():
            raise ValueError("loaded keys must be distinct")
        self.latest: Dict[int, int] = {}     # key -> value written after load
        self.written: list = []              # sorted keys of ``latest``

    def loaded_values(self, keys: np.ndarray) -> np.ndarray:
        """Loaded value of each key, ``NOT_FOUND`` where not loaded."""
        keys = np.asarray(keys, np.int32)
        if not len(self.keys):
            return np.full(keys.shape, sem.NOT_FOUND, np.int64)
        i = np.minimum(np.searchsorted(self.keys, keys), len(self.keys) - 1)
        return np.where(self.keys[i] == keys, self.vals[i], sem.NOT_FOUND)

    def _read(self, key: int, loaded: int) -> int:
        v = self.latest.get(key)
        if v is None:
            return loaded
        return sem.NOT_FOUND if v == sem.TOMBSTONE else v

    def _write(self, key: int, value: int, keep_sorted: bool) -> None:
        if keep_sorted and key not in self.latest:
            bisect.insort(self.written, key)
        self.latest[key] = value

    def _page(self, k1: int, k2: int, lo: int, hi: int) -> np.ndarray:
        ks, vs = self.keys[lo:hi], self.vals[lo:hi]
        a = bisect.bisect_left(self.written, k1)
        b = bisect.bisect_right(self.written, k2)
        if a == b:
            return np.stack([ks, vs.astype(np.int32)], axis=1)
        items = dict(zip(ks.tolist(), vs.tolist()))
        for k in self.written[a:b]:
            v = self.latest[k]
            if v == sem.TOMBSTONE:
                items.pop(k, None)
            else:
                items[k] = v
        return np.array(sorted(items.items()), np.int32).reshape(-1, 2)

    def replay(self, codes, keys, vals, ts, *, stale_batch: Optional[int] = None
               ) -> Tuple[np.ndarray, Dict[int, np.ndarray]]:
        """Run the operations in timestamp order.  Returns each one's value
        and, for each RANGE by its position, its (key, value) page."""
        codes = np.asarray(codes, np.int32)
        keys = np.asarray(keys, np.int32)
        vals = np.asarray(vals, np.int64)
        ts = np.asarray(ts, np.int64)
        order = np.argsort(ts, kind="stable")
        loaded = self.loaded_values(keys).tolist()
        is_range = codes == sem.OP_RANGE
        keep_sorted = bool(is_range.any())
        lo = np.searchsorted(self.keys, keys, side="left").tolist()
        hi = np.searchsorted(self.keys, vals.astype(np.int32), side="right").tolist()
        out = np.full(len(codes), sem.NOT_FOUND, np.int64)
        pages: Dict[int, np.ndarray] = {}
        cl, kl, vl, tl = codes.tolist(), keys.tolist(), vals.tolist(), ts.tolist()
        pending: Dict[int, int] = {}
        batch = None
        for i in order.tolist():
            if stale_batch is not None:
                b = (tl[i] - tl[order[0]]) // stale_batch
                if b != batch:
                    for k, v in pending.items():
                        self._write(k, v, keep_sorted)
                    pending.clear()
                    batch = b
            op, k = cl[i], kl[i]
            if op == sem.OP_RANGE:
                page = self._page(k, vl[i], lo[i], hi[i])
                pages[i] = page
                out[i] = len(page)
            elif op in (sem.OP_SEARCH, sem.OP_INSERT, sem.OP_DELETE):
                out[i] = self._read(k, loaded[i])
                if op != sem.OP_SEARCH:
                    new = vl[i] if op == sem.OP_INSERT else sem.TOMBSTONE
                    if stale_batch is None:
                        self._write(k, new, keep_sorted)
                    else:
                        pending[k] = new
        for k, v in pending.items():
            self._write(k, v, keep_sorted)
        return out, pages

    def final_values(self, keys: np.ndarray) -> np.ndarray:
        """Each key's value after everything replayed so far."""
        loaded = self.loaded_values(keys).tolist()
        return np.array([self._read(k, v) for k, v in
                         zip(np.asarray(keys).tolist(), loaded)], np.int64)
