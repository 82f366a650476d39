"""Set-up of one run: the store loaded from the seed, and the warm phase.

The configuration file fixes every size.  The store is loaded through
the public API with width-``plan_width`` insert plans under a lifecycle
policy that defers maintenance, its dead leaves (split-leavings) are
reclaimed by one ``maintain`` pass, and it is then adopted by a client
under the default lifecycle policy, as a deployment that has finished
its load would run.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from harness.traffic import KeySpace


def store_config(api, config: Dict):
    s = config["store"]
    return api.UruvConfig(
        leaf_cap=int(config["leaf_cap"]), max_chain=int(config["max_chain"]),
        max_leaves=int(s["max_leaves"]), max_versions=int(s["max_versions"]),
        tracker_cap=int(s["tracker_cap"]),
        index_fanout=int(s["index_fanout"]))


def load(api, config: Dict, seed: int):
    """The loaded client, with the loaded keys and values (load order).
    The load goes through the front end, one width-``plan_width`` insert
    request per plan, so two plans are in flight and the host's work
    overlaps the device's; rejected plans replay through the slow path."""
    from repro.serve.coalescer import AdmissionPolicy, Coalescer

    keys, vals = KeySpace(config).prefill(np.random.default_rng([seed, 0]))
    width = int(config["prefill"]["plan_width"])
    db = api.Uruv(store_config(api, config),
                  policy=api.LifecyclePolicy(auto_maintain=False))
    co = Coalescer(db, AdmissionPolicy(max_width=width))
    for i in range(0, len(keys), width):
        co.submit(api.OpBatch.inserts(keys[i:i + width], vals[i:i + width]))
    co.flush()
    # one maintain pass whose budget (a power of two, so every seed shares
    # one compiled program) covers every dead leaf; compact() would do the
    # same but does not fit a 16 GB chip at these pool sizes
    w = api.pool_watermarks(db.store)
    db.maintain(budget=api.pow2_width(max(1, w.n_alloc - w.n_leaves)))
    return api.Uruv.from_store(db.store), keys, vals

