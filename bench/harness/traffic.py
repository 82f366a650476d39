"""Request streams drawn from a configuration and a traffic mix.

One general generator serves every cell: the configuration says how keys
are formed (a uniform key universe, or hashed YCSB record ids) and the
mix file says which operations are drawn, in what shares, from which key
chooser.  The stream is a pure function of the seed: request ``i`` is the
same in every run of a seed, whatever the system's speed, because chunks
are drawn in order from one generator.

Operation kinds (``op`` in a mix file):

  * ``search``     SEARCH of a chosen key
  * ``insert``     INSERT of a chosen key with a fresh value (upsert)
  * ``delete``     DELETE of a chosen key
  * ``insert_new`` INSERT of the next record id beyond the loaded ones
  * ``scan``       RANGE from a chosen key, over ``scan_length`` records

Key choosers (``keys`` in a mix entry): ``uniform`` draws from the
configuration's key universe, or uniformly over its loaded records;
``zipfian`` is YCSB's scrambled zipfian over the loaded records.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from harness import semantics as sem

# YCSB ScrambledZipfianGenerator: a zipfian over 10 billion items with
# the constant 0.99 and its precomputed zeta, folded onto the record ids
# by a 64-bit FNV hash (core/src/main/java/site/ycsb/generator/).
YCSB_ITEM_COUNT = 10_000_000_000
YCSB_ZIPF_CONSTANT = 0.99
YCSB_ZETAN = 26.46902820178302

FNV_OFFSET = np.uint64(0xCBF29CE484222325)
FNV_PRIME = np.uint64(1099511628211)

# ``insertorder=hashed`` into int32 keys: a multiplicative permutation
# modulo the largest prime key domain below the store's sentinels, so
# distinct record ids give distinct keys spread over the whole domain.
HASH_PRIME = 2147483629
HASH_MULT = 1640531527

VALUE_HI = 1 << 30           # values are drawn from [1, VALUE_HI)
BLOCK = 4096                 # requests drawn at a time


def fnv1a64(x: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` over the 8 octets of each id (uint64)."""
    x = np.asarray(x, np.uint64)
    h = np.full(x.shape, FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for i in range(8):
            h ^= (x >> np.uint64(8 * i)) & np.uint64(0xFF)
            h *= FNV_PRIME
    return h


def zipf_ranks(rng: np.random.Generator, n: int, items: int, theta: float,
               zetan: float) -> np.ndarray:
    """YCSB ``ZipfianGenerator.nextValue`` (Gray et al.), vectorised:
    ranks in [0, items), rank 0 the most popular."""
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(n)
    uz = u * zetan
    tail = np.floor(items * (eta * u - eta + 1.0) ** alpha)
    out = np.where(uz < 1.0, 0.0, np.where(uz < zeta2, 1.0, tail))
    return np.minimum(out, items - 1).astype(np.int64)


def zeta(items: int, theta: float) -> float:
    return float(np.sum(np.arange(1, items + 1, dtype=np.float64) ** -theta))


def record_keys(ids: np.ndarray) -> np.ndarray:
    """Keys of YCSB record ids under ``insertorder=hashed``."""
    ids = np.asarray(ids, np.int64)
    return (1 + (ids * HASH_MULT) % HASH_PRIME).astype(np.int32)


class KeySpace:
    """How a configuration forms keys, loads its records and spans scans."""

    def __init__(self, config: Dict):
        ks = config["keys"]
        self.kind = ks["kind"]
        self.records = int(config["prefill_keys"])
        if self.kind == "universe":
            self.lo, self.hi = int(ks["lo"]), int(ks["hi"])
            domain = self.hi - self.lo + 1
        elif self.kind == "hashed_records":
            self.lo, self.hi = 1, HASH_PRIME
            domain = HASH_PRIME
        else:
            raise ValueError(f"unknown key space kind {self.kind!r}")
        if not (1 <= self.lo <= self.hi <= sem.KEY_DOMAIN_HI):
            raise ValueError("key space outside the store's key domain")
        # a scan of ``n`` records spans n * domain / records keys
        self.scan_span = max(1, round(domain / self.records))

    def prefill(self, rng: np.random.Generator) -> Tuple[np.ndarray, np.ndarray]:
        """The loaded keys (distinct, in load order) and their values."""
        n = self.records
        if self.kind == "universe":
            domain = self.hi - self.lo + 1
            if n > domain:
                raise ValueError("prefill larger than the key universe")
            keys = (rng.choice(domain, n, replace=False) + self.lo)
        else:
            keys = record_keys(np.arange(n))
        vals = rng.integers(1, VALUE_HI, n)
        return keys.astype(np.int32), vals.astype(np.int32)


class Traffic:
    """The request stream of one (configuration, mix, seed)."""

    def __init__(self, config: Dict, mix: Dict, seed: int, loaded: np.ndarray):
        self.space = KeySpace(config)
        self.loaded = loaded
        self.rng = np.random.default_rng([seed, 1])
        self.ops = mix["ops"]
        shares = np.array([float(o["share"]) for o in self.ops])
        if not np.isclose(shares.sum(), 1.0) or (shares < 0).any():
            raise ValueError("mix shares must be >= 0 and sum to 1")
        self.cum = np.cumsum(shares)
        self.cum[-1] = 1.0
        self.scan = mix.get("scan_length")
        self.next_record = self.space.records
        self._buf = (np.zeros(0, np.int32),) * 3

    def _choose(self, how: str, n: int) -> np.ndarray:
        rng, sp = self.rng, self.space
        if how == "uniform" and sp.kind == "universe":
            return rng.integers(sp.lo, sp.hi + 1, n).astype(np.int32)
        if how == "uniform":
            return self.loaded[rng.integers(0, len(self.loaded), n)]
        if how == "zipfian":
            ranks = zipf_ranks(rng, n, YCSB_ITEM_COUNT, YCSB_ZIPF_CONSTANT,
                               YCSB_ZETAN)
            ids = fnv1a64(ranks) % np.uint64(len(self.loaded))
            return self.loaded[ids.astype(np.int64)]
        raise ValueError(f"unknown key chooser {how!r}")

    def _scan_lengths(self, n: int) -> np.ndarray:
        s = self.scan
        if s is None or s["distribution"] != "uniform":
            raise ValueError("a scan needs scan_length: uniform min..max")
        return self.rng.integers(int(s["min"]), int(s["max"]) + 1, n)

    def next(self, n: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The next ``n`` single-operation requests: (codes, keys, values)
        with RANGE's k2 in ``values``.  Requests are drawn in blocks of
        ``BLOCK``, so the stream does not depend on how it is asked for."""
        while len(self._buf[0]) < n:
            more = self._draw(BLOCK)
            self._buf = tuple(np.concatenate([a, b])
                              for a, b in zip(self._buf, more))
        out = tuple(a[:n] for a in self._buf)
        self._buf = tuple(a[n:] for a in self._buf)
        return out

    def _draw(self, n: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        kind = np.searchsorted(self.cum, self.rng.random(n), side="right")
        kind = np.minimum(kind, len(self.ops) - 1)
        codes = np.empty(n, np.int32)
        keys = np.empty(n, np.int32)
        vals = np.zeros(n, np.int32)
        for j, o in enumerate(self.ops):
            at = np.nonzero(kind == j)[0]
            m = len(at)
            if not m:
                continue
            op = o["op"]
            if op == "insert_new":
                ids = self.next_record + np.arange(m)
                self.next_record += m
                keys[at] = record_keys(ids)
            else:
                keys[at] = self._choose(o["keys"], m)
            if op == "search":
                codes[at] = sem.OP_SEARCH
            elif op in ("insert", "insert_new"):
                codes[at] = sem.OP_INSERT
                vals[at] = self.rng.integers(1, VALUE_HI, m)
            elif op == "delete":
                codes[at] = sem.OP_DELETE
            elif op == "scan":
                codes[at] = sem.OP_RANGE
                span = self._scan_lengths(m).astype(np.int64) * self.space.scan_span
                vals[at] = np.minimum(keys[at].astype(np.int64) + span - 1,
                                      self.space.hi)
            else:
                raise ValueError(f"unknown operation {op!r}")
        return codes, keys, vals
