"""End-to-end behaviour tests for the paper's system.

1. The full paper workflow on one store: streaming ingestion + wait-free
   updates + linearizable range scans + GC (the Uruv ADT contract).
2. The framework loop: train a reduced LM with checkpoints, crash, restart,
   serve it with prefix-cached continuous batching.
"""

import numpy as np
import jax
import jax.numpy as jnp

from repro.config import get_arch
from repro.core import batch as B
from repro.core import store as S
from repro.core.ref import RefStore, OP_INSERT
from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import make_batch
from repro.distributed.fault import run_with_restarts
from repro.models.registry import get_model
from repro.optim import adamw
from repro.serve.engine import Engine, Request
from repro.train import steps


def test_paper_workflow_end_to_end():
    """Prefill -> concurrent update/scan mix -> delete wave -> GC; oracle-
    checked at every stage (the paper's Sec 6 workload in miniature)."""
    rng = np.random.default_rng(0)
    store = S.create(S.UruvConfig(leaf_cap=16, max_leaves=1024,
                                  max_versions=1 << 15, max_chain=32))
    ref = RefStore()

    # prefill (paper: uniform keys from a universe)
    keys = rng.choice(5000, 1500, replace=False).astype(np.int32)
    for i in range(0, len(keys), 128):
        ch = keys[i:i+128]
        store, _ = B.apply_updates(store, ch, ch)
        ref.apply_batch([(OP_INSERT, int(k), int(k)) for k in ch])

    # interleaved updates + snapshot scans
    snaps = []
    for round_ in range(5):
        store, snap = S.snapshot(store)
        rs = ref.snapshot()
        snaps.append((int(snap), rs, ref.range_query(1000, 3000, rs)))
        upd = rng.choice(5000, 200).astype(np.int32)
        vals = rng.integers(0, 10**6, 200).astype(np.int32)
        store, _ = B.apply_updates(store, upd, vals)
        ref.apply_batch([(OP_INSERT, int(k), int(v))
                         for k, v in zip(upd, vals)])
    for snap, rs, want in snaps:
        store, got = B.range_query_all(store, 1000, 3000, snap)
        assert got == want
    # release all, GC, verify latest state intact
    for snap, rs, _ in snaps:
        store = S.release(store, snap)
        ref.release(rs)
    before = int(store.n_vers)
    store, _ = S.compact(store)
    assert int(store.n_vers) < before
    assert S.live_items(store) == ref.live_items()
    S.check_invariants(store)


def test_framework_train_crash_serve(tmp_path):
    cfg = get_arch("llama3_2_1b").reduced()
    opt = adamw.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=25)
    step_fn = jax.jit(steps.make_train_step(cfg, opt))
    mgr = CheckpointManager(tmp_path, keep=2, async_write=False)

    state, hist = run_with_restarts(
        init_fn=lambda: steps.init_state(cfg, jax.random.key(0)),
        step_fn=step_fn,
        batch_fn=lambda s: make_batch(cfg, 4, 32, s),
        ckpt=mgr, total_steps=25, ckpt_every=5, crash_at=[12],
    )
    losses = [l for k, s, l in hist if k == "step"]
    assert any(k == "restart" for k, *_ in hist)
    assert losses[-1] < losses[0], "loss should decrease"

    # serve the trained params
    api = get_model(cfg)
    eng = Engine(cfg, state.params, n_slots=2, max_len=48)
    reqs = [Request(rid=i, prompt=[3, 1, 4, 1, 5], max_new=4)
            for i in range(3)]
    eng.run(reqs)
    assert all(r.done and len(r.out) == 4 for r in reqs)
    # deterministic greedy decode: identical prompts -> identical outputs
    assert reqs[0].out == reqs[1].out == reqs[2].out


def test_compile_cache_dir_follows_env_else_repo_default(monkeypatch):
    """Entry points keep jax's persistent cache where
    JAX_COMPILATION_CACHE_DIR says (jax reads it itself) and otherwise
    at the fixed ``.jax_cache/`` in the repository root."""
    from pathlib import Path

    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.launch import cache

    was = jax.config.jax_compilation_cache_dir
    root = Path(__file__).resolve().parents[1]
    try:
        monkeypatch.setenv(cache.ENV_VAR, "/elsewhere/cache")
        assert cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == was

        monkeypatch.delenv(cache.ENV_VAR)
        assert cache.enable_compile_cache() == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        cc.reset_cache()
