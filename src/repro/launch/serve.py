"""CLI server driver: batched generation through the continuous-batching
engine (reduced configs on CPU; the full-config serve path is proven by the
decode dry-run cells).

  PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_1b --reduced \
      --requests 6 --max-new 8
"""

from __future__ import annotations

import argparse
import time

import numpy as np
import jax

from repro.config import get_arch
from repro.launch.cache import enable_compile_cache
from repro.models.registry import get_model
from repro.serve.engine import Engine, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    api = get_model(cfg)
    if api.decode_step is None:
        raise SystemExit(f"{cfg.name} is encoder-only: no serve path")

    params = api.init(cfg, jax.random.key(0))
    eng = Engine(cfg, params, n_slots=args.slots, max_len=args.max_len)

    rng = np.random.default_rng(0)
    shared_prefix = rng.integers(0, cfg.vocab, 6).tolist()
    reqs = []
    for i in range(args.requests):
        prompt = shared_prefix + rng.integers(0, cfg.vocab, 3 + i % 3).tolist()
        reqs.append(Request(rid=i, prompt=prompt, max_new=args.max_new))

    t0 = time.time()
    eng.run(reqs)
    dt = time.time() - t0
    toks = sum(len(r.out) for r in reqs)
    for r in reqs:
        print(f"req {r.rid}: prompt {len(r.prompt)} "
              f"reused_prefix {r.prefix_reused} out {r.out}")
    print(f"{toks} tokens in {dt:.2f}s = {toks/dt:.1f} tok/s "
          f"(batched decode, {args.slots} slots)")
    print(f"prefix-table entries: {len(eng.snapshot_view())}")


if __name__ == "__main__":
    main()
