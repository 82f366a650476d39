#!/usr/bin/env bash
# Tier-1 gate + perf trajectory.  Run from the repo root:  bash scripts/check.sh
# (or `make check`).  Writes BENCH_mixed.json + BENCH_range.json so the
# fused-pass speedups accumulate across PRs.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== uruvlint (static analysis: layering, device-pass purity, donation"
echo "   safety, determinism, kernel parity/VMEM, sentinel literals) =="
# Replaces the former api/index grep gates with AST analysis (resolves
# relative imports, never trips on docstring prose) and adds the purity /
# donation / determinism / kernel / sentinel rules on top.  Rule catalog +
# suppression syntax: DESIGN.md Sec 13.  `make lint` runs the same stage.
python -m repro.analysis src/repro benchmarks examples scripts
echo "ok"

echo "== tier-1 tests (slow-marked growth batteries excluded via pytest.ini) =="
# The full suite (pytest -x -q) includes the range/snapshot battery
# (tests/test_range_property.py), the kernel + sharded range parity tests
# (tests/test_kernels.py, tests/test_sharding_dist.py) and the public-API
# surface battery (tests/test_api.py).
python -m pytest -x -q

echo "== kernel microbench (quick) =="
python -m benchmarks.run --quick --only kernels

echo "== fused mixed-op pass vs two-pass (quick; writes BENCH_mixed.json) =="
python -m benchmarks.run --quick --only mixed

echo "== batched bulk_range vs host-paged loop (quick; writes BENCH_range.json) =="
python -m benchmarks.run --quick --only range

echo "== lifecycle: maintain vs compact + grow amortization (quick; writes BENCH_lifecycle.json) =="
python -m benchmarks.run --quick --only lifecycle

echo "== index: delta maintenance vs flat full-rebuild + locate depth sweep (quick; writes BENCH_index.json) =="
python -m benchmarks.run --quick --only index

echo "== wal: group-commit vs fsync-per-plan, delta vs full checkpoint,"
echo "   recovery replay (quick; gates delta<=25% of full + replay>=50kops/s;"
echo "   writes BENCH_wal.json) =="
python -m benchmarks.run --quick --only wal

echo "== BENCH_wal.json =="
cat BENCH_wal.json

echo "== BENCH_index.json =="
cat BENCH_index.json

echo "== BENCH_mixed.json =="
cat BENCH_mixed.json

echo "== BENCH_range.json =="
cat BENCH_range.json

echo "== BENCH_lifecycle.json =="
cat BENCH_lifecycle.json

echo "== examples under pallas_interpret (DeprecationWarning from repro = fail) =="
python scripts/run_examples.py

echo "== docs-that-run: README/DESIGN fenced python blocks under pallas_interpret =="
python scripts/check_docs.py
