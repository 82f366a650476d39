"""uruvlint fixture battery (DESIGN.md Sec 13).

Every rule gets three fixtures: a BAD source that must fire, a GOOD
source that must pass, and a suppression variant that silences the bad
source.  Fixtures are inline strings fed through :class:`FileContext`
with synthetic repo-relative paths, so the battery needs no tmp files
and pins each rule's path-scoping logic too.  The battery closes with
the self-lint gate: the merged tree lints clean through the same entry
point scripts/check.sh uses.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis.engine import Allowlist, FileContext, run_contexts
from repro.analysis.marks import DEVICE_PASS_REGISTRY, device_pass
from repro.analysis.reporters import exit_code, render_json, render_text
from repro.analysis.rules import (
    DeterminismRule, DevicePassPurityRule, DonationSafetyRule,
    KernelParityRule, KernelVmemRule, LayeringApiRule, LayeringIndexRule,
    SentinelLiteralRule, default_rules,
)

ROOT = Path(__file__).resolve().parents[1]


def lint(rule, *files):
    """Run one rule over (path, source) fixture pairs."""
    ctxs = [FileContext(p, textwrap.dedent(src)) for p, src in files]
    return run_contexts(ctxs, [rule])


def rule_ids(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# layering-api
# ---------------------------------------------------------------------------

BAD_LAYERING = ("src/repro/serve/engine2.py", """
    from repro.core import store
    from repro.core.batch import apply_updates
    import repro.core.lifecycle
""")


def test_layering_api_bad_fires():
    fs = lint(LayeringApiRule(), BAD_LAYERING)
    assert len(fs) == 3 and rule_ids(fs) == ["layering-api"]
    assert "bypasses repro.api" in fs[0].message


def test_layering_api_good_passes():
    assert lint(LayeringApiRule(), ("src/repro/serve/engine2.py", """
        from repro.api import OpBatch, Uruv
        from repro.core.ref import KEY_MAX          # ref is not restricted
        from repro.core import index
    """)) == []


def test_layering_api_core_and_api_are_exempt():
    src = "from repro.core import store, batch\n"
    assert lint(LayeringApiRule(), ("src/repro/api/client2.py", src)) == []
    assert lint(LayeringApiRule(), ("src/repro/core/lifecycle2.py", src)) == []
    assert len(lint(LayeringApiRule(), ("benchmarks/run2.py", src))) == 2


def test_layering_api_relative_import_resolved():
    fs = lint(LayeringApiRule(), ("src/repro/serve/engine2.py",
                                  "from ..core import store\n"))
    assert rule_ids(fs) == ["layering-api"]


def test_layering_api_suppressed():
    path, src = BAD_LAYERING
    src = "# uruvlint: disable-file=layering-api\n" + textwrap.dedent(src)
    assert lint(LayeringApiRule(), (path, src)) == []


# ---------------------------------------------------------------------------
# layering-index
# ---------------------------------------------------------------------------

def test_layering_index_bad_fires():
    fs = lint(LayeringIndexRule(), ("src/repro/serve/sched.py", """
        import jax.numpy as jnp
        def pick(dir_keys, q):
            return jnp.searchsorted(dir_keys, q)
    """))
    assert len(fs) >= 2 and rule_ids(fs) == ["layering-index"]


def test_layering_index_allowed_files_pass():
    src = "def f(dir_keys, q):\n    return searchsorted(dir_keys, q)\n"
    for p in ("src/repro/core/index.py", "src/repro/core/backend.py",
              "src/repro/core/baseline.py",
              "src/repro/kernels/uruv_search/ops.py"):
        assert lint(LayeringIndexRule(), (p, src)) == []


def test_layering_index_suppressed():
    fs = lint(LayeringIndexRule(), ("src/repro/serve/sched.py",
        "x = dir_keys  # uruvlint: disable=layering-index\n"))
    assert fs == []


# ---------------------------------------------------------------------------
# device-pass-purity
# ---------------------------------------------------------------------------

BAD_PURITY = ("src/repro/core/hot.py", """
    import numpy as np
    from repro.analysis.marks import device_pass

    @device_pass
    def hot(store, keys):
        n = int(store.ts)              # host sync
        h = np.asarray(keys)           # host transfer
        keys.block_until_ready()       # host sync
        if store:                      # python branch on traced value
            return n, h
""")


def test_purity_bad_fires():
    fs = lint(DevicePassPurityRule(), BAD_PURITY)
    msgs = " | ".join(f.message for f in fs)
    assert rule_ids(fs) == ["device-pass-purity"] and len(fs) == 4
    assert "int()" in msgs and "np.asarray" in msgs
    assert "block_until_ready" in msgs and "`if`" in msgs


def test_purity_host_read_fires():
    """``repro.obs.host_read`` is the request path's counted device read:
    a host sync, whether imported by name or through its module."""
    fs = lint(DevicePassPurityRule(), ("src/repro/api/hot.py", """
        from repro import obs
        from repro.obs import host_read
        from repro.analysis.marks import device_pass

        @device_pass
        def hot(self, pending):
            ok = host_read(self.stats, pending.ok)
            ts = obs.host_read(self.stats, pending.ts)
            return ok, ts
    """))
    assert rule_ids(fs) == ["device-pass-purity"] and len(fs) == 2
    assert all("host_read()" in f.message for f in fs)


def test_purity_good_passes():
    assert lint(DevicePassPurityRule(), ("src/repro/core/hot.py", """
        import jax.numpy as jnp
        from repro.analysis.marks import device_pass

        @device_pass(static=("backend",))
        def hot(store, keys, base_ts=None, *, backend):
            if base_ts is None:        # optional-arg check: host-static
                base_ts = store.ts
            if backend == "xla":       # static param: legal dispatch
                return jnp.where(keys > 0, keys, base_ts)
            return keys
    """)) == []


def test_purity_unmarked_function_ignored():
    path, src = BAD_PURITY
    src = textwrap.dedent(src).replace("@device_pass\ndef hot", "def hot")
    assert lint(DevicePassPurityRule(), (path, src)) == []


def test_purity_suppressed_line():
    path, src = BAD_PURITY
    src = src.replace("n = int(store.ts)              # host sync",
                      "n = int(store.ts)  # uruvlint: disable=device-pass-purity")
    assert len(lint(DevicePassPurityRule(), (path, src))) == 3


# ---------------------------------------------------------------------------
# donation-safety
# ---------------------------------------------------------------------------

BAD_DONATION = ("src/repro/api/pipe.py", """
    import functools, jax

    @functools.partial(jax.jit, donate_argnums=(0,))
    def pass_dstore(store, keys):
        return store

    def caller(store, keys):
        out = pass_dstore(store, keys)
        return store.ts                # use-after-donation
""")


def test_donation_bad_fires():
    fs = lint(DonationSafetyRule(), BAD_DONATION)
    assert rule_ids(fs) == ["donation-safety"] and len(fs) == 1
    assert "after it was donated" in fs[0].message


def test_donation_rebind_passes():
    path, src = BAD_DONATION
    src = src.replace("out = pass_dstore(store, keys)\n        return store.ts"
                      "                # use-after-donation",
                      "store = pass_dstore(store, keys)\n        return store.ts")
    assert lint(DonationSafetyRule(), (path, src)) == []


def test_donation_donate_store_keyword_taints_store_args_only():
    fs = lint(DonationSafetyRule(), ("src/repro/serve/x.py", """
        def go(ex, store, plan):
            ex.apply(store, plan, donate_store=True)
            a = plan                   # plan was NOT donated
            return store.ts            # store WAS
    """))
    assert len(fs) == 1 and "'store'" in fs[0].message


def test_donation_branch_isolation():
    # a donation inside one branch must not poison uses earlier in it
    assert lint(DonationSafetyRule(), ("src/repro/serve/x.py", """
        def go(ex, store, flag):
            if flag:
                n = store.ts
                ex.apply(store, donate_store=True)
            return n
    """)) == []


def test_donation_suppressed():
    path, src = BAD_DONATION
    src = src.replace("return store.ts                # use-after-donation",
                      "return store.ts  # uruvlint: disable=donation-safety")
    assert lint(DonationSafetyRule(), (path, src)) == []


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------

BAD_DETERMINISM = ("src/repro/core/batch2.py", """
    import time
    import numpy as np
    def stamp(ops):
        seed = time.time()
        noise = np.random.rand()
        for k in {1, 2, 3}:
            pass
        return seed, noise
""")


def test_determinism_bad_fires():
    fs = lint(DeterminismRule(), BAD_DETERMINISM)
    assert rule_ids(fs) == ["determinism"] and len(fs) >= 3


def test_determinism_scope_is_core_only():
    path, src = BAD_DETERMINISM
    assert lint(DeterminismRule(), ("src/repro/serve/metrics.py", src)) == []
    assert lint(DeterminismRule(), ("benchmarks/run2.py", src)) == []


def test_determinism_covers_durability_replay_path():
    # the WAL replay path is a deterministic-by-construction contract
    # (DESIGN.md Sec 14): wall clock / host RNG fire there too
    path, src = BAD_DETERMINISM
    fs = lint(DeterminismRule(), ("src/repro/durability/wal2.py", src))
    assert rule_ids(fs) == ["determinism"] and len(fs) >= 3


def test_determinism_os_urandom_fires_but_os_io_ok():
    fs = lint(DeterminismRule(), ("src/repro/durability/wal2.py", """
        import os
        def seg_id(f):
            os.fsync(f.fileno())           # durable I/O is fine
            return os.urandom(8)           # entropy is not
    """))
    assert rule_ids(fs) == ["determinism"]
    assert len(fs) == 1 and "os.urandom" in fs[0].message


def test_determinism_jax_random_ok():
    assert lint(DeterminismRule(), ("src/repro/core/batch2.py", """
        import jax
        def stamp(ops, key):
            return jax.random.bits(key)
    """)) == []


def test_determinism_suppressed():
    path, src = BAD_DETERMINISM
    src = "# uruvlint: disable-file=determinism\n" + textwrap.dedent(src)
    assert lint(DeterminismRule(), (path, src)) == []


# ---------------------------------------------------------------------------
# kernel-parity
# ---------------------------------------------------------------------------

GOOD_KERNEL = """
    def scan(keys, vals, *, block_q=128, interpret=True):
        return keys
"""
GOOD_REF = """
    def scan_ref(keys, vals):
        return keys
"""


def test_kernel_parity_good_passes():
    assert lint(KernelParityRule(),
                ("src/repro/kernels/foo/foo.py", GOOD_KERNEL),
                ("src/repro/kernels/foo/ref.py", GOOD_REF)) == []


def test_kernel_parity_positional_mismatch_fires():
    fs = lint(KernelParityRule(),
              ("src/repro/kernels/foo/foo.py", GOOD_KERNEL),
              ("src/repro/kernels/foo/ref.py",
               "def scan_ref(keys, wrong_name):\n    return keys\n"))
    assert rule_ids(fs) == ["kernel-parity"]


def test_kernel_parity_missing_twin_fires():
    fs = lint(KernelParityRule(),
              ("src/repro/kernels/foo/foo.py",
               textwrap.dedent(GOOD_KERNEL) + "\ndef other(a):\n    return a\n"),
              ("src/repro/kernels/foo/ref.py", GOOD_REF))
    assert any("no oracle twin" in f.message for f in fs)


def test_kernel_parity_ref_extra_kwonly_fires():
    fs = lint(KernelParityRule(),
              ("src/repro/kernels/foo/foo.py", GOOD_KERNEL),
              ("src/repro/kernels/foo/ref.py",
               "def scan_ref(keys, vals, *, exotic=1):\n    return keys\n"))
    assert any("missing from kernel" in f.message for f in fs)


# ---------------------------------------------------------------------------
# kernel-vmem
# ---------------------------------------------------------------------------

VMEM_SRC = """
    import functools
    from jax.experimental import pallas as pl

    def launch(x, *, block_q={bq}):
        return pl.pallas_call(
            kernel,
            out_shape=x,
            in_specs=[pl.BlockSpec((block_q, 4096), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((block_q, 4096), lambda i: (i, 0)),
        )(x)
"""


def test_kernel_vmem_over_budget_fires():
    fs = lint(KernelVmemRule(budget=1 << 20),
              ("src/repro/kernels/foo/foo.py", VMEM_SRC.format(bq=4096)))
    assert rule_ids(fs) == ["kernel-vmem"]
    assert "budget" in fs[0].message


def test_kernel_vmem_small_blocks_pass():
    assert lint(KernelVmemRule(budget=1 << 20),
                ("src/repro/kernels/foo/foo.py", VMEM_SRC.format(bq=8))) == []


def test_kernel_vmem_scope_is_kernels_only():
    assert lint(KernelVmemRule(budget=1),
                ("src/repro/serve/x.py", VMEM_SRC.format(bq=4096))) == []


def test_kernel_vmem_min_bound_used():
    # bq = min(block_q, P): the 16 bound applies even though P is unknown
    src = VMEM_SRC.format(bq=4096).replace(
        "        return pl.pallas_call(",
        "        bq = min(16, P)\n        return pl.pallas_call(").replace(
        "(block_q, 4096)", "(bq, 4096)")
    assert lint(KernelVmemRule(budget=1 << 20),
                ("src/repro/kernels/foo/foo.py", src)) == []


# ---------------------------------------------------------------------------
# sentinel-literal
# ---------------------------------------------------------------------------

BAD_SENTINEL = ("src/repro/serve/hashing.py", """
    PAD = 2**31 - 1
    KPAD = 0x7FFFFFFF - 1
    HI = 2147483645
""")


def test_sentinel_bad_fires():
    fs = lint(SentinelLiteralRule(), BAD_SENTINEL)
    assert rule_ids(fs) == ["sentinel-literal"] and len(fs) >= 3
    assert "core/ref.py" in fs[0].message


def test_sentinel_blessed_module_passes():
    assert lint(SentinelLiteralRule(),
                ("src/repro/core/ref.py", BAD_SENTINEL[1])) == []


def test_sentinel_unrelated_literals_pass():
    assert lint(SentinelLiteralRule(), ("src/repro/serve/hashing.py", """
        FNV = 16777619
        MASK = 2**16 - 1
    """)) == []


def test_sentinel_suppressed():
    fs = lint(SentinelLiteralRule(), ("src/repro/serve/hashing.py",
        "PAD = 2**31 - 1  # uruvlint: disable=sentinel-literal\n"))
    assert fs == []


# ---------------------------------------------------------------------------
# engine mechanics: allowlist, dedup, parse errors, reporters
# ---------------------------------------------------------------------------

def test_allowlist_filters_by_rule_and_glob():
    allow = Allowlist([("sentinel-literal", "src/repro/serve/*")])
    ctx = FileContext(BAD_SENTINEL[0], textwrap.dedent(BAD_SENTINEL[1]))
    assert run_contexts([ctx], [SentinelLiteralRule()], allow) == []
    # a different rule id still fires through the same glob
    ctx2 = FileContext(BAD_LAYERING[0], textwrap.dedent(BAD_LAYERING[1]))
    assert run_contexts([ctx2], [LayeringApiRule()], allow) != []


def test_reporters_text_json_exit_code():
    fs = lint(SentinelLiteralRule(), BAD_SENTINEL)
    text = render_text(fs, 1)
    assert "sentinel-literal" in text and "finding(s)" in text
    doc = json.loads(render_json(fs, 1))
    assert doc["version"] == 1 and doc["files"] == 1
    assert doc["counts"]["sentinel-literal"] == len(fs)
    assert {f["rule"] for f in doc["findings"]} == {"sentinel-literal"}
    assert exit_code(fs) == 1 and exit_code([]) == 0
    assert "clean" in render_text([], 3)


def test_device_pass_registry_populated():
    @device_pass(static=("backend",))
    def probe(store, *, backend):
        return store

    key = f"{probe.__module__}.{probe.__qualname__}"
    assert DEVICE_PASS_REGISTRY[key] == ("backend",)
    assert probe("s", backend="xla") == "s"     # identity at runtime
    # the real hot paths registered on import
    import repro.core.store  # noqa: F401
    assert any(k.endswith("_bulk_apply_impl") for k in DEVICE_PASS_REGISTRY)


# ---------------------------------------------------------------------------
# self-lint: the merged tree is clean through the CLI check.sh runs
# ---------------------------------------------------------------------------

def test_self_lint_src_clean():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "src/"],
        cwd=ROOT, capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_self_lint_full_default_paths_clean():
    from repro.analysis.engine import run_paths

    findings = run_paths(
        [ROOT / p for p in ("src/repro", "benchmarks", "examples", "scripts")],
        rules=default_rules(), root=ROOT)
    assert findings == [], "\n".join(f.render() for f in findings)
