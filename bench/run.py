#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name
through ``BENCHMARK.json`` (``bench/README.md``).  A run loads the store
from the seed and warms up (set-up), serves the cell's closed-loop
traffic for ``--seconds`` (the window), checks every answer against the
plain reference, and prints one JSON line as the last line of standard
output: with ``--trace 0`` the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window.  The numbers compared are printed last on standard error, and
last in the line under ``checks``.

Without a TPU with as many chips as the cell asks for, a run prints no
result and exits 2.  ``--rehearse`` runs the whole path at the
configuration's small ``rehearsal`` sizes on whatever JAX finds, prints
the comparison but no metric, and exits 1: it is a check of the path,
never a measurement.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH))

from harness import manifest  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="small sizes, any platform, no metric, exit 1")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    m = manifest.load()
    cell = manifest.cell(m, args.workload)

    import jax
    from harness import device, session

    device.enable_compile_cache(ROOT)
    devices = jax.devices()
    dev = device.describe(devices)
    if not args.rehearse and (dev["platform"] != "tpu"
                              or dev["count"] < cell["chips"]):
        print(f"no result: the cell needs {cell['chips']} TPU chip(s), JAX "
              f"found {dev['count']} {dev['platform']} device(s)",
              file=sys.stderr)
        return 2
    kind = "per_layer" if args.trace else "end_to_end"
    specs = manifest.metrics_for(m, args.workload, kind)
    if args.rehearse:
        cell, specs = manifest.rehearsal(cell), []
    out = session.run(cell, specs, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), t_start=T_START, devices=devices)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    if args.rehearse:
        print(f"rehearsal on {dev['platform']}: correct={out['correct']}, "
              f"attempted={out['attempted']}; not a chip run, no metric",
              file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
