"""The generators: mix shares, YCSB's zipfian, scan lengths, keys."""

import numpy as np
import pytest

from harness import manifest
from harness import semantics as S
from harness.traffic import (HASH_PRIME, KeySpace, Traffic, fnv1a64,
                             record_keys, zeta, zipf_ranks)

N = 200_000          # draws per check: shares are within ~0.5% at 4 sigma

# YCSB CoreWorkload's key space and its A and E mixes, which no cell runs
# yet (PERF.md): the generator serves them as data
YCSB = {"keys": {"kind": "hashed_records"}, "prefill_keys": 50_000}
MIXES = {
    "ycsb.a": {"ops": [{"op": "search", "share": 0.5, "keys": "zipfian"},
                       {"op": "insert", "share": 0.5, "keys": "zipfian"}]},
    "ycsb.e": {"ops": [{"op": "scan", "share": 0.95, "keys": "zipfian"},
                       {"op": "insert_new", "share": 0.05}],
               "scan_length": {"distribution": "uniform", "min": 1,
                               "max": 100}},
}


def cell(name):
    if name in MIXES:
        return {"config_data": YCSB, "mix_data": MIXES[name]}
    return manifest.cell(manifest.load(), name)


def small(config, records=50_000):
    return {**config, "prefill_keys": records}


@pytest.mark.parametrize("workload, shares", [
    ("paper.fig8c", {S.OP_SEARCH: 0.5, S.OP_INSERT: 0.25, S.OP_DELETE: 0.25}),
    ("ycsb.a", {S.OP_SEARCH: 0.5, S.OP_INSERT: 0.5}),
])
def test_mix_shares(workload, shares):
    c = cell(workload)
    cfg = small(c["config_data"])
    keys, _ = KeySpace(cfg).prefill(np.random.default_rng(0))
    codes, k, v = Traffic(cfg, c["mix_data"], 5, keys).next(N)
    for code, share in shares.items():
        assert abs((codes == code).mean() - share) < 0.005, (code, share)
    assert set(np.unique(codes)) == set(shares)
    assert (k >= 1).all() and (k <= S.KEY_DOMAIN_HI).all()


def test_zipfian_rank_frequencies():
    items, theta = 1000, 0.99
    zn = zeta(items, theta)
    r = zipf_ranks(np.random.default_rng(1), N, items, theta, zn)
    assert r.min() == 0 and r.max() < items
    # YCSB's generator is exact for the two most popular ranks
    for rank in (0, 1):
        want = (rank + 1) ** -theta / zn
        sd = np.sqrt(want * (1 - want) / N)
        assert abs((r == rank).mean() - want) < 4 * sd
    # and heavy-tailed overall: the top 1% of ranks carry about 40%
    top = (r < items // 100).mean()
    want = zeta(items // 100, theta) / zn
    assert abs(top - want) < 0.02


def test_ycsb_scrambled_zipfian_hot_record():
    """The YCSB constants: rank 0 has probability 1/zeta(10^10, 0.99),
    and scrambling sends it to one fixed record."""
    c = cell("ycsb.a")
    cfg = small(c["config_data"])
    keys, _ = KeySpace(cfg).prefill(np.random.default_rng(0))
    codes, k, _ = Traffic(cfg, c["mix_data"], 9, keys).next(N)
    hot = keys[int(fnv1a64(np.array([0]))[0] % np.uint64(len(keys)))]
    assert abs((k == hot).mean() - 1 / 26.46902820178302) < 0.003


def test_scan_lengths_and_spans():
    """YCSB E's mix: scans of 1-100 records and inserts of new records."""
    cfg, mix = YCSB, MIXES["ycsb.e"]
    keys, _ = KeySpace(cfg).prefill(np.random.default_rng(0))
    t = Traffic(cfg, mix, 3, keys)
    codes, _, _ = t.next(N)
    assert abs((codes == S.OP_RANGE).mean() - 0.95) < 0.005
    t = Traffic(cfg, mix, 3, keys)
    codes, k1, k2 = t.next(N)
    scan = codes == S.OP_RANGE
    span = t.space.scan_span
    assert span == round(HASH_PRIME / len(keys))
    lengths = (k2[scan].astype(np.int64) - k1[scan] + 1) / span
    inside = k2[scan] < t.space.hi          # not clipped at the domain end
    ln = lengths[inside]
    assert ln.min() == 1 and ln.max() == 100 and np.all(ln == np.round(ln))
    assert abs(ln.mean() - 50.5) < 0.5
    # inserts are of new records, each a key not loaded before
    new = k1[codes == S.OP_INSERT]
    assert len(np.unique(new)) == len(new)
    assert not np.isin(new, keys).any()


def test_same_seed_same_stream():
    c = cell("paper.fig8c")
    cfg = small(c["config_data"])
    keys, vals = KeySpace(cfg).prefill(np.random.default_rng([2**33 + 5, 0]))
    a = Traffic(cfg, c["mix_data"], 2**33 + 5, keys)
    b = Traffic(cfg, c["mix_data"], 2**33 + 5, keys)
    first, rest = a.next(1000), a.next(5000)     # asked for in other chunks
    whole = b.next(6000)
    for x, y, z in zip(first, rest, whole):
        assert np.array_equal(np.concatenate([x, y]), z)
    other = Traffic(cfg, c["mix_data"], 6, keys).next(6000)
    assert not np.array_equal(other[1], whole[1])


def test_loaded_keys_are_distinct_and_in_the_domain():
    ids = np.arange(0, 5_000_000, 7)
    k = record_keys(ids)
    assert len(np.unique(k)) == len(k)
    assert k.min() >= 1 and k.max() <= HASH_PRIME < S.KEY_MAX - 1
    cfg = small(cell("paper.fig8c")["config_data"], 20_000)
    keys, vals = KeySpace(cfg).prefill(np.random.default_rng(4))
    assert len(np.unique(keys)) == len(keys) == 20_000
    assert keys.min() >= 1 and keys.max() <= cfg["keys"]["hi"]
    assert (vals >= 1).all()
