#!/usr/bin/env python3
"""Read the comparison's numbers for sound runs and for the control.

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds <s>

For each seed, in one process, the cell is served as ``bench/run.py``
serves it, and the numbers that decide ``correct`` are read twice: for
the program's answers, and for the control's — the plain reference with
in-batch visibility removed (``harness.checks.control``), at the same
requests and timestamps.  One JSON line per seed.  The limits in
``harness/checks.py`` are set from these readings (PERF.md); the
benchmark's own runs never run the control.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

from harness import checks, device, manifest, session  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = manifest.cell(manifest.load(), args.workload)

    import jax

    device.enable_compile_cache(BENCH.parent)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("no result: the control is read on the chip", file=sys.stderr)
        return 2
    batch = int(cell["config_data"]["announce_width"])
    t_start = T_START
    for seed in (int(x) for x in args.seeds.split(",")):
        s = session.serve(cell, seed=seed, seconds=args.seconds, trace=False,
                          t_start=t_start, devices=devices)
        sound = checks.compare_served(s)
        ctrl = checks.control(s, batch)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "requests": int(s.records.done.sum()), "crash": s.crash,
            "sound": {n: v for n, v, _ in sound},
            "control": {n: v for n, v, _ in ctrl}}), flush=True)
        t_start = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
