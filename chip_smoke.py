#!/usr/bin/env python3
"""Bring-up check of the Uruv store on one TPU chip (or four).

Drives the store's main path once through ``repro.api`` and checks every
answer against a host reference built from the same seed:

  1. prefill   — ``--prefill`` distinct keys (default 4M) drawn from the
                 paper's universe [1, 500M], inserted by announce arrays of
                 width 4096 (``configs/uruv_paper.py``); the store starts
                 small enough that its leaf pool grows at least once.
  2. snapshot  — a registered ``snapshot()`` and a range scan at it.
  3. mixed     — ``--batches`` announce arrays of width 4096 in the paper's
                 Fig. 9a mix (94% search, 5% update, 1% range), ranges of
                 about ``range_size`` = 1000 keys each.
  4. recheck   — the snapshot scan again after those updates: it must be
                 byte-identical to the first one.

The host reference holds the prefill as sorted numpy arrays and the mixed
phase in ``repro.core.ref.RefStore`` (the repo's sequential oracle); any
mismatch exits non-zero.  Compile and steady seconds, compile counts and
device bytes are printed for information only.  On a TPU the last line of
standard output is ``{"ok": true, "device": {...}}``.  Anywhere else the
phases run as a rehearsal at a small default size and the script exits 1.

    python chip_smoke.py [--prefill N] [--batches B] [--seed S]
    python chip_smoke.py --four-chips      # sharded store over 4 chips

``--four-chips`` runs only the key-range-sharded store
(``Uruv.sharded``) over a 4-device mesh, fed the same phases, and compares
it with a single-device ``Uruv`` and with the reference.  Rehearse it on
CPU with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

from repro.api import (  # noqa: E402
    NOT_FOUND, OP_DELETE, OP_INSERT, OP_RANGE, OP_SEARCH, TOMBSTONE,
    OpBatch, ShardedConfig, Uruv, UruvConfig, get_backend,
)
from repro.compat import make_mesh  # noqa: E402
from repro.configs.uruv_paper import config as paper_config  # noqa: E402
from repro.core.ref import RefStore  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402

PAPER = paper_config()
UNIVERSE = PAPER.key_universe          # keys drawn from [1, 500M]
WIDTH = PAPER.batch                    # announce-array width 4096
MIX = (0.94, 0.05, 0.01)               # Fig. 9a: search, update, range
RANGE_EVERY = 100                      # one RANGE op per 100 slots (~1%)
FULL_PREFILL = 4_000_000             # default on a TPU (see PERF.md)
REHEARSAL_PREFILL = 50_000             # default off the chip
SNAPSHOT_SCANS = 8                     # intervals in the snapshot scan


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# compile accounting (jax.monitoring events)
# ---------------------------------------------------------------------------

class CompileMeter:
    """Counts backend compiles and sums lowering + compile seconds
    (tracing is left out: nested jits report overlapping trace events)."""

    _SECONDS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in self._SECONDS:
            self.seconds += secs
        if name == self._SECONDS[-1]:
            self.count += 1


class Phase:
    """Wall clock of one phase, split into compile and steady seconds."""

    def __init__(self, name: str, meter: CompileMeter):
        self.name, self.meter = name, meter

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0, self.s0 = self.meter.count, self.meter.seconds
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self.t0
        comp = self.meter.seconds - self.s0
        print(f"phase {self.name}: wall_s={wall} compile_s={comp} "
              f"steady_s={wall - comp} "
              f"compiles={self.meter.count - self.c0}", flush=True)


# ---------------------------------------------------------------------------
# workload (all host-side, from --seed)
# ---------------------------------------------------------------------------

def prefill_data(rng, n: int):
    """``n`` distinct keys from [1, UNIVERSE] in random order, and values."""
    keys = np.unique(rng.integers(1, UNIVERSE + 1, n + n // 8 + 64,
                                  dtype=np.int32))
    while len(keys) < n:
        more = rng.integers(1, UNIVERSE + 1, n, dtype=np.int32)
        keys = np.unique(np.concatenate([keys, more]))
    keys = rng.permutation(keys)[:n]
    vals = rng.integers(1, 1 << 30, n, dtype=np.int32)
    return keys, vals


def mixed_plan(rng, loaded: np.ndarray, range_width: int) -> OpBatch:
    """One Fig. 9a announce array: RANGE at every RANGE_EVERY-th slot
    (fixed positions keep the CRUD segment widths, and so the compiled
    shapes, fixed), 5% updates (half inserts, half deletes) and searches
    elsewhere.  Half the point keys are loaded keys, half uniform."""
    codes = np.full(WIDTH, OP_SEARCH, np.int32)
    rpos = np.arange(RANGE_EVERY - 1, WIDTH, RANGE_EVERY)
    crud = np.setdiff1d(np.arange(WIDTH), rpos)
    upos = rng.choice(crud, int(round(MIX[1] * WIDTH)), replace=False)
    codes[upos] = np.where(rng.random(len(upos)) < 0.5, OP_INSERT, OP_DELETE)
    codes[rpos] = OP_RANGE
    keys = np.where(rng.random(WIDTH) < 0.5,
                    loaded[rng.integers(0, len(loaded), WIDTH)],
                    rng.integers(1, UNIVERSE + 1, WIDTH)).astype(np.int32)
    vals = np.where(codes == OP_INSERT,
                    rng.integers(1, 1 << 30, WIDTH), 0).astype(np.int32)
    k1 = rng.integers(1, UNIVERSE - range_width + 2, len(rpos))
    keys[rpos] = k1
    vals[rpos] = k1 + range_width - 1
    return OpBatch(codes, keys, vals)


class HostReference:
    """The store's semantics, independent of the code under test: the
    prefill as sorted numpy arrays (every prefill version predates the
    mixed phase) and the mixed phase's versions in a ``RefStore``."""

    def __init__(self, keys: np.ndarray, vals: np.ndarray, ts: int):
        order = np.argsort(keys)
        self.keys, self.vals = keys[order], vals[order]
        self.over = RefStore(ts=ts)

    @property
    def ts(self) -> int:
        return self.over.ts

    def prefilled(self, keys) -> np.ndarray:
        """Prefill value of each key (NOT_FOUND if not loaded), in one
        vectorised search: a scalar Python-int search would cast the
        whole int32 key array on every call."""
        keys = np.asarray(keys, np.int32)
        i = np.minimum(np.searchsorted(self.keys, keys), len(self.keys) - 1)
        return np.where(self.keys[i] == keys, self.vals[i],
                        NOT_FOUND).astype(np.int64)

    def get(self, key: int, snap: int, prefilled: int) -> int:
        chain = self.over.versions.get(key)
        if chain and chain[0].ts <= snap:
            return self.over.search_at(key, snap)
        return prefilled

    def lookup(self, keys: np.ndarray, snap: int) -> np.ndarray:
        return np.array([self.get(int(k), snap, int(p)) for k, p in
                         zip(keys, self.prefilled(keys))], np.int64)

    def range(self, k1: int, k2: int, snap: int) -> np.ndarray:
        lo, hi = np.searchsorted(self.keys, np.array([k1, k2], np.int32),
                                 side="left")
        hi += int(hi < len(self.keys) and self.keys[hi] == k2)
        page = dict(zip(self.keys[lo:hi].tolist(), self.vals[lo:hi].tolist()))
        for key in self.over.versions:
            if k1 <= key <= k2 and self.over.versions[key][0].ts <= snap:
                v = self.over.search_at(key, snap)
                if v == NOT_FOUND:
                    page.pop(key, None)
                else:
                    page[key] = v
        return np.array(sorted(page.items()), np.int32).reshape(-1, 2)

    def snapshot(self) -> int:
        return self.over.snapshot()

    def apply(self, batch: OpBatch):
        """``RefStore.apply_batch`` semantics; returns (values, pages)."""
        base = self.over.ts
        out, pages = [], []
        for i, (op, key, val, pre) in enumerate(zip(
                np.asarray(batch.codes).tolist(),
                np.asarray(batch.keys).tolist(),
                np.asarray(batch.values).tolist(),
                self.prefilled(batch.keys).tolist())):
            ts_i = base + i
            if op in (OP_INSERT, OP_DELETE):
                out.append(self.get(key, ts_i, pre))
                self.over._append_version(
                    key, val if op == OP_INSERT else TOMBSTONE, ts=ts_i)
            elif op == OP_SEARCH:
                out.append(self.get(key, ts_i, pre))
            elif op == OP_RANGE:
                pages.append(self.range(key, val, ts_i))
                out.append(len(pages[-1]))
            else:
                out.append(NOT_FOUND)
        self.over.ts = base + len(batch)
        return np.asarray(out, np.int64), pages


# ---------------------------------------------------------------------------
# sizing
# ---------------------------------------------------------------------------

def pow2ceil(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def store_config(n_keys: int, n_shards: int = 1) -> UruvConfig:
    """Paper widths (leaf_cap 64, max_chain 64); per-shard pools sized so
    the version pool holds the whole run and the leaf pool (one slot per
    ``leaf_cap`` keys) must grow once as leaves split to ~70% fill."""
    per = -(-n_keys // n_shards)
    return UruvConfig(
        leaf_cap=PAPER.store.leaf_cap,
        max_chain=PAPER.store.max_chain,
        max_leaves=pow2ceil(max(per // PAPER.store.leaf_cap, 64)),
        max_versions=pow2ceil(per + 16 * WIDTH),
    )


def store_bytes(db: Uruv) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(db.store))


def pages_of(res) -> list:
    return [np.asarray(p, np.int32).reshape(-1, 2) for p in res.range_pages]


def same_pages(a, b) -> bool:
    return len(a) == len(b) and all(
        x.shape == y.shape and x.tobytes() == y.tobytes()
        for x, y in zip(a, b))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def run_phases(dbs: dict, args, meter: CompileMeter) -> None:
    """Run every phase on each client in ``dbs`` (same plans, same order)
    and hold each to the host reference (and so to one another)."""
    rng = np.random.default_rng(args.seed)
    with Phase("data", meter):
        keys, vals = prefill_data(rng, args.prefill)
    range_width = max(1, round(PAPER.range_size * UNIVERSE / args.prefill))
    print(f"prefill={args.prefill} width={WIDTH} "
          f"range_width={range_width} batches={args.batches}", flush=True)

    n_plans = -(-args.prefill // WIDTH)
    for name, db in dbs.items():
        with Phase(f"prefill[{name}]", meter):
            for b in range(n_plans):
                sl = slice(b * WIDTH, (b + 1) * WIDTH)
                res = db.apply(OpBatch.inserts(keys[sl], vals[sl])
                               .pad_to(WIDTH))
                check(bool(np.all(np.asarray(res.values) == NOT_FOUND)),
                      f"{name}: prefill plan {b} found a key drawn distinct")
        print(f"{name}: ts={db.ts} stats={db.stats} "
              f"capacity={db.capacity} store_bytes={store_bytes(db)}",
              flush=True)
        check(db.stats["grows"] >= 1, f"{name}: no grow() during prefill")

    ref = HostReference(keys, vals, ts=n_plans * WIDTH)
    probe = np.concatenate([keys[rng.integers(0, len(keys), WIDTH)],
                            rng.integers(1, UNIVERSE + 1, WIDTH,
                                         dtype=np.int32)])
    want = ref.lookup(probe, ref.ts)
    for name, db in dbs.items():
        check(db.ts == ref.ts, f"{name}: clock {db.ts} != {ref.ts}")
        with Phase(f"lookup[{name}]", meter):
            got = np.asarray(db.lookup(probe))
        check(np.array_equal(got, want), f"{name}: lookup after prefill")

    # registered snapshot + scan; re-scanned after the mixed phase
    k1s = rng.integers(1, UNIVERSE - range_width + 2, SNAPSHOT_SCANS)
    k2s = k1s + range_width - 1
    snap_ref = ref.snapshot()
    snaps, before = {}, {}
    for name, db in dbs.items():
        snaps[name] = db.acquire_snapshot()
        check(snaps[name] == snap_ref, f"{name}: snapshot ts {snaps[name]}")
        with Phase(f"snapshot_scan[{name}]", meter):
            before[name] = [np.asarray(p, np.int32).reshape(-1, 2)
                            for p in db.range_all(k1s, k2s, snaps[name])]
    want_scan = [ref.range(int(a), int(b), snap_ref)
                 for a, b in zip(k1s, k2s)]
    for name in dbs:
        check(same_pages(before[name], want_scan),
              f"{name}: snapshot scan != reference")

    loaded = keys
    n_ops = n_checked = 0
    for b in range(args.batches):
        plan = mixed_plan(rng, loaded, range_width)
        want_vals, want_pages = ref.apply(plan)
        for name, db in dbs.items():
            with Phase(f"mixed[{name}] plan {b}", meter):
                res = db.apply(plan)
            got = np.asarray(res.values, np.int64)
            bad = np.nonzero(got != want_vals)[0]
            check(len(bad) == 0,
                  f"{name}: plan {b}: {len(bad)} values differ, first at "
                  f"{bad[:4].tolist()}: got {got[bad[:4]].tolist()} "
                  f"want {want_vals[bad[:4]].tolist()}")
            check(same_pages(pages_of(res), want_pages),
                  f"{name}: plan {b}: range pages != reference")
            check(db.ts == ref.ts, f"{name}: clock {db.ts} != {ref.ts}")
        n_ops += len(plan)
        n_checked += sum(len(p) for p in want_pages)
    print(f"mixed: {n_ops} ops checked, {n_checked} range rows checked",
          flush=True)

    want_scan_after = [ref.range(int(a), int(b), snap_ref)
                       for a, b in zip(k1s, k2s)]
    check(same_pages(want_scan_after, want_scan),
          "reference snapshot moved (harness fault)")
    for name, db in dbs.items():
        with Phase(f"snapshot_rescan[{name}]", meter):
            after = [np.asarray(p, np.int32).reshape(-1, 2)
                     for p in db.range_all(k1s, k2s, snaps[name])]
        check(same_pages(after, before[name]),
              f"{name}: snapshot scan changed after later updates")
        db.release_snapshot(snaps[name])
        check(db.active_snapshots == 0, f"{name}: snapshot not released")
        final = np.asarray(db.lookup(probe))
        check(np.array_equal(final, ref.lookup(probe, db.ts)),
              f"{name}: final lookup != reference")
    print(f"snapshot: {SNAPSHOT_SCANS} intervals, "
          f"{sum(len(p) for p in want_scan)} rows byte-stable", flush=True)


def describe_shards(db: Uruv) -> set:
    """Print the device of each shard of the stacked store."""
    placed = {}
    for sh in db.store.leaf_keys.addressable_shards:
        placed[sh.index[0].start] = sh.device
    for i in sorted(placed):
        d = placed[i]
        print(f"shard {i}: device id={d.id} {d.device_kind}", flush=True)
    return {d.id for d in placed.values()}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--prefill", type=int, default=None,
                    help=f"keys to load (default {FULL_PREFILL} on a TPU, "
                         f"{REHEARSAL_PREFILL} elsewhere)")
    ap.add_argument("--batches", type=int, default=8,
                    help="mixed-phase announce arrays of width 4096")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded store and its "
                         "comparison with a single-device store")
    args = ap.parse_args()

    cache = enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    on_tpu = dev.platform == "tpu"
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)} jax={jax.__version__} "
          f"backend={get_backend()} compile_cache={cache}", flush=True)
    if args.prefill is None:
        args.prefill = FULL_PREFILL if on_tpu else REHEARSAL_PREFILL
    meter = CompileMeter()
    t0 = time.perf_counter()

    if args.four_chips:
        check(len(devs) == 4, f"--four-chips needs 4 devices, found "
                              f"{len(devs)}")
        mesh = make_mesh((4,), ("data",))
        sharded = Uruv.sharded(
            ShardedConfig(base=store_config(args.prefill, 4),
                          key_lo=1, key_hi=UNIVERSE + 1), mesh)
        local = Uruv(store_config(args.prefill))
        run_phases({"sharded": sharded, "local": local}, args, meter)
        held = describe_shards(sharded)
        check(len(held) == 4, f"shards sit on {len(held)} distinct devices")
        count = 4
        dbs = (sharded, local)
    else:
        db = Uruv(store_config(args.prefill))
        run_phases({"local": db}, args, meter)
        count = len(devs)
        dbs = (db,)

    stats = dev.memory_stats() or {}
    print(f"total: wall_s={time.perf_counter() - t0} "
          f"compile_s={meter.seconds} compiles={meter.count} "
          f"store_bytes={[store_bytes(d) for d in dbs]} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}",
          flush=True)
    if not on_tpu:
        print(f"rehearsal on {dev.platform}: every check passed, but this "
              "is not a chip run", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        sys.exit(1)
