.PHONY: check lint test test-slow test-range api examples docs bench-kernels \
	bench-mixed bench-range bench-lifecycle bench-index bench-wal

check:
	bash scripts/check.sh

# uruvlint: the repo's structural invariants as AST static analysis —
# layering, @device_pass purity, donation safety, determinism, kernel
# parity/VMEM, sentinel-literal confinement (DESIGN.md Sec 13).
# `make lint FORMAT=json` emits the machine-diffable report.
FORMAT ?= text
lint:
	PYTHONPATH=src python -m repro.analysis --format=$(FORMAT) \
		src/repro benchmarks examples scripts

test:
	PYTHONPATH=src python -m pytest -x -q

# the slow-marked batteries excluded from tier-1: large-pool growth and
# the full kill -9 crash-recovery sweep (tests/test_wal_recovery.py)
test-slow:
	PYTHONPATH=src python -m pytest -x -q -m slow

test-range:
	PYTHONPATH=src python -m pytest -x -q tests/test_range_property.py \
		tests/test_kernels.py tests/test_sharding_dist.py

# the public repro.api surface: OpBatch/Result/client/executors battery
api:
	PYTHONPATH=src python -m pytest -x -q tests/test_api.py

# all examples, routed through the Pallas interpret backend; fails on any
# DeprecationWarning raised from inside src/repro (internals must be
# fully migrated onto repro.api)
examples:
	PYTHONPATH=src python scripts/run_examples.py

bench-kernels:
	PYTHONPATH=src python -m benchmarks.run --quick --only kernels

bench-mixed:
	PYTHONPATH=src python -m benchmarks.run --quick --only mixed

bench-range:
	PYTHONPATH=src python -m benchmarks.run --quick --only range

# self-sizing lifecycle: incremental maintain vs stop-the-world compact,
# grow amortization; writes BENCH_lifecycle.json
bench-lifecycle:
	PYTHONPATH=src python -m benchmarks.run --quick --only lifecycle

# multi-level fat-node index: delta maintenance vs flat full-rebuild,
# locate at depth 1 vs multi-level; writes BENCH_index.json
bench-index:
	PYTHONPATH=src python -m benchmarks.run --quick --only index

# durability: group-commit WAL ingest vs fsync-per-plan, delta vs full
# checkpoint bytes (gate: delta <= 25% of full), crash-recovery replay
# throughput (gate: >= 50k ops/s); writes BENCH_wal.json
bench-wal:
	PYTHONPATH=src python -m benchmarks.run --quick --only wal

# extract + run every fenced ```python block in README.md / DESIGN.md
# under URUV_BACKEND=pallas_interpret (docs can never rot)
docs:
	PYTHONPATH=src python scripts/check_docs.py
