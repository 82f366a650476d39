"""The comparison that decides ``correct``.

Every request the run sent through the front end is held to the
reference (``harness.reference``), replayed in the order of the
linearization timestamps the program reported:

  * ``failed``              requests rejected or never completed;
  * ``order_violations``    completed requests whose timestamp is not above
                            that of the request submitted before them
                            (the front end admits first in, first out), or
                            below the clock the store was adopted at;
  * ``result_mismatches``   values, found flags and range pages that differ
                            from the reference's;
  * ``readback_mismatches`` every key written in the run, and a sample of
                            the loaded keys, read back after the run and
                            compared with the reference's final state.

Each is an exact count, so each limit is 0 (PERF.md gives the readings).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

from harness import semantics as sem
from harness.reference import Reference

LIMITS = {
    "failed": 0,
    "order_violations": 0,
    "result_mismatches": 0,
    "readback_mismatches": 0,
}


def order_violations(ts: np.ndarray, done: np.ndarray, floor: int) -> int:
    t = np.asarray(ts, np.int64)[np.asarray(done, bool)]
    if not len(t):
        return 0
    return int((np.diff(t) <= 0).sum() + (t < floor).sum())


def result_mismatches(rec, want: np.ndarray, pages: Dict[int, np.ndarray]) -> int:
    """Requests whose value, found flag or page differs from the reference."""
    done = rec.done
    bad = done & ((rec.value != want) | (rec.found != (want != sem.NOT_FOUND)))
    for i, page in pages.items():
        got = rec.pages.get(i)
        if done[i] and (got is None or not np.array_equal(got, page)):
            bad[i] = True
    return int(bad.sum())


def readback_keys(rec, loaded: np.ndarray, rng: np.random.Generator,
                  sample: int) -> np.ndarray:
    """Every key written in the run and a seeded sample of loaded keys."""
    writes = rec.keys[rec.done & ((rec.codes == sem.OP_INSERT)
                                  | (rec.codes == sem.OP_DELETE))]
    some = loaded[rng.integers(0, len(loaded), min(sample, len(loaded)))]
    return np.unique(np.concatenate([writes, some]).astype(np.int32))


def compare(rec, ref: Reference, floor: int, read_keys: np.ndarray,
            read_values: np.ndarray) -> List[Tuple[str, int, int]]:
    """All numbers compared, as (name, value, limit).  ``read_values`` is
    what the program read back for ``read_keys`` after the run."""
    done = rec.done
    want, pages = ref.replay(rec.codes[done], rec.keys[done], rec.vals[done],
                             rec.ts[done])
    full = np.full(len(done), sem.NOT_FOUND, np.int64)
    full[done] = want
    at = np.nonzero(done)[0]
    pages = {int(at[i]): p for i, p in pages.items()}
    numbers = {
        "failed": int((~done).sum()),
        "order_violations": order_violations(rec.ts, done, floor),
        "result_mismatches": result_mismatches(rec, full, pages),
        "readback_mismatches": int((np.asarray(read_values, np.int64)
                                    != ref.final_values(read_keys)).sum()),
    }
    return [(name, numbers[name], LIMITS[name]) for name in LIMITS]


def passed(numbers: List[Tuple[str, int, int]]) -> bool:
    return all(value <= limit for _, value, limit in numbers)


def compare_served(s) -> List[Tuple[str, int, int]]:
    """``compare`` for one served window (``session.Served``)."""
    return compare(s.records, Reference(s.loaded_keys, s.loaded_vals),
                   s.floor, s.read_keys, s.read_values)


def control(s, batch: int) -> List[Tuple[str, int, int]]:
    """The control: the reference with one stated guarantee broken, put in
    the program's place.  Reads resolve against the state at the start of
    their batch of ``batch`` timestamps (``Reference.replay``'s
    ``stale_batch``), on the same requests at the same timestamps as the
    program's run; the comparison has to fail it."""
    rec = s.records
    done = rec.done
    stale = Reference(s.loaded_keys, s.loaded_vals)
    want, pages = stale.replay(rec.codes[done], rec.keys[done],
                               rec.vals[done], rec.ts[done], stale_batch=batch)
    value = np.full(len(done), sem.NOT_FOUND, np.int64)
    value[done] = want
    at = np.nonzero(done)[0]
    fake = dataclasses.replace(
        rec, value=value, found=value != sem.NOT_FOUND,
        pages={int(at[i]): p for i, p in pages.items()})
    return compare(fake, Reference(s.loaded_keys, s.loaded_vals), s.floor,
                   s.read_keys, stale.final_values(s.read_keys))
