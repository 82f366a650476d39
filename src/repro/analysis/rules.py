"""The uruvlint rule catalog (DESIGN.md Sec 13).

Every headline structural claim the repo makes has a rule that proves it
statically, replacing the former ``grep -RnE`` gates in scripts/check.sh:

  layering-api        outside core/ only repro.api touches the mutable
                      internals (core.store / batch / sharded / lifecycle)
  layering-index      descent internals (dir_keys / dir_leaf /
                      searchsorted) confined to index / backend /
                      baseline / kernels-uruv_search
  device-pass-purity  no host syncs inside ``@device_pass`` hot paths
  donation-safety     no use of a store after it was donated into a
                      ``donate_argnums`` pass (the PR 7 rollback hazard)
  determinism         no wall clock / host RNG / set-iteration order in
                      the op_ts plumbing (bit-exact sharded == local)
  kernel-parity       each kernels/<k>/ package: kernel and ref twins
                      agree on signatures
  kernel-vmem         BlockSpec footprint of each pallas_call stays
                      under a VMEM budget (bounded block shapes only)
  sentinel-literal    key-sentinel literals (2**31-1 family) appear only
                      in the blessed domain module core/ref.py — the
                      exact silent-loss bug class fixed in PR 7
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from repro.analysis.engine import (
    ERROR, WARNING, FileContext, Finding, Rule,
)
from repro.core.ref import KEY_MAX


def _const_eval(node: ast.AST) -> Optional[int]:
    """Fold an int-literal expression tree (``2**31 - 1``); None when any
    leaf is not a constant."""
    if isinstance(node, ast.Constant):
        return node.value if type(node.value) is int else None
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        v = _const_eval(node.operand)
        return None if v is None else -v
    if isinstance(node, ast.BinOp):
        a, b = _const_eval(node.left), _const_eval(node.right)
        if a is None or b is None:
            return None
        try:
            if isinstance(node.op, ast.Add):
                return a + b
            if isinstance(node.op, ast.Sub):
                return a - b
            if isinstance(node.op, ast.Mult):
                return a * b
            if isinstance(node.op, ast.Pow):
                return a ** b if abs(b) < 64 else None
            if isinstance(node.op, ast.LShift):
                return a << b if 0 <= b < 64 else None
            if isinstance(node.op, ast.BitOr):
                return a | b
            if isinstance(node.op, ast.BitAnd):
                return a & b
            if isinstance(node.op, ast.FloorDiv) and b:
                return a // b
            if isinstance(node.op, ast.Mod) and b:
                return a % b
        except Exception:
            return None
    return None


def _root_name(node: ast.AST) -> Optional[str]:
    """Leftmost Name of an attribute chain (``np.random.x`` -> ``np``)."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _expr_key(node: ast.AST) -> Optional[str]:
    """Stable key for a Name / dotted-Name chain (``self._store``)."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _expr_key(node.value)
        return None if base is None else f"{base}.{node.attr}"
    return None


# ---------------------------------------------------------------------------
# 1. layering-api — the former check.sh api grep gate, as import analysis
# ---------------------------------------------------------------------------

RESTRICTED_CORE = ("store", "batch", "sharded", "lifecycle")


class LayeringApiRule(Rule):
    id = "layering-api"
    description = (
        "outside repro/core, only repro/api may import the mutable core "
        "internals (core.store/batch/sharded/lifecycle); everything else "
        "goes through the repro.api front door (DESIGN.md Sec 9)")

    def check_file(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.in_dir("repro/core", "repro/api"):
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    yield from self._check(ctx, node, alias.name)
            elif isinstance(node, ast.ImportFrom):
                mod = self._absolute(ctx, node)
                if mod is None:
                    continue
                if mod == "repro.core":
                    for alias in node.names:
                        if alias.name in RESTRICTED_CORE:
                            yield self._finding(
                                ctx, node, f"repro.core.{alias.name}")
                else:
                    yield from self._check(ctx, node, mod)

    @staticmethod
    def _absolute(ctx: FileContext, node: ast.ImportFrom) -> Optional[str]:
        if not node.level:
            return node.module or None
        # resolve `from ..core import store` against the file's module
        parts = ctx.module_name().split(".")
        if len(parts) < node.level:
            return node.module or None
        base = parts[:len(parts) - node.level]
        return ".".join(base + ([node.module] if node.module else []))

    def _check(self, ctx, node, mod: str) -> Iterable[Finding]:
        parts = mod.split(".")
        if (len(parts) >= 3 and parts[0] == "repro" and parts[1] == "core"
                and parts[2] in RESTRICTED_CORE):
            yield self._finding(ctx, node, ".".join(parts[:3]))

    def _finding(self, ctx, node, mod: str) -> Finding:
        return Finding(self.id, ctx.posix, node.lineno, node.col_offset,
                       f"import of {mod} bypasses repro.api "
                       "(core internals are core/api-only)")


# ---------------------------------------------------------------------------
# 2. layering-index — the former check.sh index grep gate, on identifiers
# ---------------------------------------------------------------------------

INDEX_TOKENS = ("dir_keys", "dir_leaf", "searchsorted")
INDEX_ALLOWED_FILES = ("repro/core/index.py", "repro/core/backend.py",
                       "repro/core/baseline.py")


class LayeringIndexRule(Rule):
    id = "layering-index"
    description = (
        "flat-directory / descent internals (dir_keys, dir_leaf, "
        "searchsorted) are confined to core/index.py + core/backend.py "
        "(+ the uruv_search kernels and the flat baseline); ordinal and "
        "rank access goes through repro.core.index helpers")

    def check_file(self, ctx: FileContext) -> Iterable[Finding]:
        p = "/" + ctx.posix
        if any(p.endswith("/" + f) for f in INDEX_ALLOWED_FILES):
            return
        if ctx.in_dir("repro/kernels/uruv_search"):
            return
        for node in ast.walk(ctx.tree):
            tok = None
            if isinstance(node, ast.Name) and node.id in INDEX_TOKENS:
                tok = node.id
            elif isinstance(node, ast.Attribute) and node.attr in INDEX_TOKENS:
                tok = node.attr
            elif isinstance(node, ast.arg) and node.arg in INDEX_TOKENS:
                tok = node.arg
            elif (isinstance(node, ast.keyword)
                  and node.arg in INDEX_TOKENS):
                tok = node.arg
            elif isinstance(node, ast.alias) and node.name in INDEX_TOKENS:
                tok = node.name
            if tok is not None:
                yield Finding(
                    self.id, ctx.posix, getattr(node, "lineno", 0),
                    getattr(node, "col_offset", 0),
                    f"descent internal '{tok}' used outside "
                    "core/index.py + core/backend.py "
                    "(use repro.core.index.rank()/ordinal helpers)")


# ---------------------------------------------------------------------------
# 3. device-pass-purity — no host syncs inside @device_pass hot paths
# ---------------------------------------------------------------------------

HOST_SYNC_METHODS = ("item", "tolist", "block_until_ready")
HOST_CASTS = ("int", "float", "bool")
HOST_READ = "host_read"     # repro.obs.host_read: a counted device->host read


def _device_pass_static(fn: ast.AST) -> Optional[Tuple[str, ...]]:
    """The decorator's static-parameter tuple when ``fn`` is marked
    ``@device_pass`` (any syntactic spelling); None when unmarked."""
    for dec in getattr(fn, "decorator_list", ()):
        target, static = dec, ()
        if isinstance(dec, ast.Call):
            target = dec.func
            for kw in dec.keywords:
                if kw.arg == "static":
                    elts = getattr(kw.value, "elts", None)
                    if elts is not None:
                        static = tuple(
                            e.value for e in elts
                            if isinstance(e, ast.Constant))
                    elif isinstance(kw.value, ast.Constant):
                        static = (kw.value.value,)
        name = (target.attr if isinstance(target, ast.Attribute)
                else getattr(target, "id", None))
        if name == "device_pass":
            return tuple(static)
    return None


def _param_names(fn) -> List[str]:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    return [n for n in names if n not in ("self", "cls")]


def _names_outside_none_checks(test: ast.AST) -> Set[str]:
    """Name loads in a condition, skipping ``x is None`` comparisons
    (branching on an optional argument is host-static, not a sync)."""
    out: Set[str] = set()

    def visit(node):
        if (isinstance(node, ast.Compare)
                and all(isinstance(op, (ast.Is, ast.IsNot))
                        for op in node.ops)
                and all(isinstance(c, ast.Constant) and c.value is None
                        for c in node.comparators)):
            return
        if isinstance(node, ast.Name):
            out.add(node.id)
        for child in ast.iter_child_nodes(node):
            visit(child)

    visit(test)
    return out


class DevicePassPurityRule(Rule):
    id = "device-pass-purity"
    description = (
        "inside a @device_pass function, host syncs are errors: .item() "
        "/ .tolist() / block_until_ready / jax.device_get / host_read "
        "(repro.obs, the request path's counted read), int()/float()"
        "/bool() on non-literals, np.asarray/np.array, and Python "
        "if/while on a non-static parameter (a traced value)")

    def check_file(self, ctx: FileContext) -> Iterable[Finding]:
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            static = _device_pass_static(fn)
            if static is None:
                continue
            yield from self._check_fn(ctx, fn, set(static))

    def _check_fn(self, ctx, fn, static: Set[str]) -> Iterable[Finding]:
        traced = set(_param_names(fn)) - static
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                msg = self._call_violation(node)
                if msg:
                    yield Finding(self.id, ctx.posix, node.lineno,
                                  node.col_offset,
                                  f"{msg} in device pass '{fn.name}'")
            elif isinstance(node, (ast.If, ast.While)):
                hot = _names_outside_none_checks(node.test) & traced
                if hot:
                    kind = "if" if isinstance(node, ast.If) else "while"
                    yield Finding(
                        self.id, ctx.posix, node.lineno, node.col_offset,
                        f"Python `{kind}` on traced parameter(s) "
                        f"{sorted(hot)} in device pass '{fn.name}' "
                        "(use lax.cond/jnp.where, or declare the "
                        "parameter jit-static via device_pass(static=...))")

    @staticmethod
    def _call_violation(node: ast.Call) -> Optional[str]:
        f = node.func
        if isinstance(f, ast.Attribute):
            if f.attr in HOST_SYNC_METHODS:
                return f"host sync `.{f.attr}()`"
            if f.attr == "device_get" and _root_name(f) == "jax":
                return "host sync `jax.device_get`"
            if f.attr == HOST_READ:
                return f"host sync `{HOST_READ}()`"
            if (f.attr in ("asarray", "array")
                    and _root_name(f) in ("np", "numpy")):
                return f"host transfer `np.{f.attr}()`"
        elif isinstance(f, ast.Name) and f.id == HOST_READ:
            return f"host sync `{HOST_READ}()`"
        elif isinstance(f, ast.Name) and f.id in HOST_CASTS:
            if node.args and not all(
                    isinstance(a, ast.Constant) for a in node.args):
                return f"host sync `{f.id}()` on a non-literal"
        return None


# ---------------------------------------------------------------------------
# 4. donation-safety — no use of a buffer after it was donated
# ---------------------------------------------------------------------------

class DonationSafetyRule(Rule):
    id = "donation-safety"
    description = (
        "a store passed to a donate_argnums callee (donate_store=True / "
        "a function defined with donate_argnums) is invalidated: any "
        "later use in the same scope before rebinding is an error — the "
        "generalized _bulk_apply_dstore rollback hazard of DESIGN.md "
        "Sec 12")

    def check_file(self, ctx: FileContext) -> Iterable[Finding]:
        donating = self._donating_defs(ctx.tree)
        for fn in ast.walk(ctx.tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                findings: List[Finding] = []
                self._walk_block(ctx, fn.body, set(), donating, findings)
                yield from findings

    @staticmethod
    def _donating_defs(tree) -> Dict[str, Tuple[int, ...]]:
        """Functions defined in this module with jit donate_argnums —
        their call sites donate the listed positional args."""
        out: Dict[str, Tuple[int, ...]] = {}
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for dec in fn.decorator_list:
                for kw in getattr(dec, "keywords", ()):
                    if kw.arg != "donate_argnums":
                        continue
                    elts = getattr(kw.value, "elts", None)
                    if elts is None and isinstance(kw.value, ast.Constant):
                        elts = [kw.value]
                    if elts:
                        out[fn.name] = tuple(
                            e.value for e in elts
                            if isinstance(e, ast.Constant))
        return out

    def _walk_block(self, ctx, stmts, tainted: Set[str], donating,
                    findings: List[Finding]) -> Set[str]:
        for stmt in stmts:
            # compound statements: process only the header expression
            # here, then recurse so body statements see taint in order
            # (branches fork the taint; loops run twice for wraparound)
            if isinstance(stmt, ast.If):
                self._scan_expr(ctx, stmt.test, tainted, donating, findings)
                t1 = self._walk_block(ctx, stmt.body, set(tainted),
                                      donating, findings)
                t2 = self._walk_block(ctx, stmt.orelse, set(tainted),
                                      donating, findings)
                tainted = t1 | t2
            elif isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                header = stmt.iter if hasattr(stmt, "iter") else stmt.test
                self._scan_expr(ctx, header, tainted, donating, findings)
                body = stmt.body + stmt.orelse
                for _ in range(2):
                    tainted |= self._walk_block(ctx, body, set(tainted),
                                                donating, findings)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    self._scan_expr(ctx, item.context_expr, tainted,
                                    donating, findings)
                tainted = self._walk_block(ctx, stmt.body, tainted,
                                           donating, findings)
            elif isinstance(stmt, ast.Try):
                for block in (stmt.body, stmt.orelse, stmt.finalbody,
                              *[h.body for h in stmt.handlers]):
                    tainted = self._walk_block(ctx, block, tainted,
                                               donating, findings)
            elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                continue        # nested scopes are analyzed on their own
            else:
                self._scan_expr(ctx, stmt, tainted, donating, findings)
                for key in self._assigned_keys(stmt):
                    tainted.discard(key)
        return tainted

    def _scan_expr(self, ctx, node, tainted: Set[str], donating,
                   findings: List[Finding]) -> None:
        """Flag loads of tainted keys in ``node``, then add the taints
        its donating calls introduce (uses in the donating statement
        itself are pre-donation and stay legal)."""
        if tainted:
            for sub in ast.walk(node):
                if not isinstance(sub, (ast.Name, ast.Attribute)):
                    continue
                if not isinstance(getattr(sub, "ctx", None), ast.Load):
                    continue
                key = _expr_key(sub)
                # exact match suffices: a use through a longer chain
                # (self._store.ts) walks the tainted sub-node itself
                if key is not None and key in tainted:
                    findings.append(Finding(
                        self.id, ctx.posix, sub.lineno, sub.col_offset,
                        f"use of '{key}' after it was donated into a "
                        "device pass (donated buffers are invalidated; "
                        "rebind from the pass result)"))
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                tainted |= self._donated_args(sub, donating)

    @staticmethod
    def _donated_args(call: ast.Call, donating) -> Set[str]:
        fname = (call.func.attr if isinstance(call.func, ast.Attribute)
                 else getattr(call.func, "id", None))
        out: Set[str] = set()
        # exact knowledge: the callee is defined in this module with
        # donate_argnums — taint the listed positional args verbatim
        for pos in donating.get(fname, ()):
            if pos < len(call.args):
                key = _expr_key(call.args[pos])
                if key is not None:
                    out.add(key)
        # heuristic: a call carrying donate_store=<truthy-or-unknown>
        # donates its store argument; only store-named args are tainted
        # (a client-level call like db.apply_nowait(plan, donate_store=x)
        # donates db's INTERNAL store, which the client rebinds itself)
        for kw in call.keywords:
            if kw.arg != "donate_store":
                continue
            if isinstance(kw.value, ast.Constant) and not kw.value.value:
                continue                # donate_store=False
            for arg in call.args:
                key = _expr_key(arg)
                if key is not None and "store" in key.rsplit(".", 1)[-1]:
                    out.add(key)
        return out

    @staticmethod
    def _assigned_keys(stmt) -> Set[str]:
        out: Set[str] = set()
        targets: List[ast.AST] = []
        if isinstance(stmt, ast.Assign):
            targets = list(stmt.targets)
        elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
            targets = [stmt.target]
        for t in targets:
            for node in ast.walk(t):
                key = _expr_key(node)
                if key is not None:
                    out.add(key)
        return out


# ---------------------------------------------------------------------------
# 5. determinism — no wall clock / host RNG in the op_ts plumbing
# ---------------------------------------------------------------------------

DETERMINISM_SCOPE = ("repro/core", "repro/durability")
NONDET_MODULES = ("time", "random", "secrets", "uuid")


class DeterminismRule(Rule):
    id = "determinism"
    description = (
        "bit-exact sharded == local timestamps are a gated invariant: "
        "core modules (the op_ts plumbing and sharded apply paths) and "
        "the durability package (crash recovery replays the WAL at its "
        "recorded timestamps) must not read the wall clock, host RNGs "
        "(random.*, np.random.*, os.urandom), or iterate sets "
        "(jax.random with explicit keys is fine)")

    def check_file(self, ctx: FileContext) -> Iterable[Finding]:
        if not ctx.in_dir(*DETERMINISM_SCOPE):
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name.split(".")[0] in NONDET_MODULES:
                        yield self._finding(ctx, node, alias.name,
                                            "import of")
            elif isinstance(node, ast.ImportFrom) and not node.level:
                mod = (node.module or "").split(".")[0]
                if mod in NONDET_MODULES:
                    yield self._finding(ctx, node, node.module, "import from")
            elif isinstance(node, ast.Attribute):
                root = _root_name(node)
                if root in NONDET_MODULES:
                    yield self._finding(ctx, node, f"{root}.{node.attr}",
                                        "use of")
                elif (root in ("np", "numpy") and node.attr == "random"):
                    yield self._finding(ctx, node, f"{root}.random",
                                        "use of")
                elif root == "os" and node.attr == "urandom":
                    # os is legitimate in durability (fsync, rename, kill)
                    # — only its entropy source is a replay hazard
                    yield self._finding(ctx, node, "os.urandom", "use of")
            elif isinstance(node, (ast.For, ast.comprehension)):
                it = node.iter
                if isinstance(it, ast.Set) or (
                        isinstance(it, ast.Call)
                        and getattr(it.func, "id", None) == "set"):
                    yield Finding(
                        self.id, ctx.posix, it.lineno, it.col_offset,
                        "iteration over a set has no deterministic order "
                        "in core (sort it)")

    def _finding(self, ctx, node, what, verb) -> Finding:
        return Finding(self.id, ctx.posix, node.lineno, node.col_offset,
                       f"{verb} '{what}' in deterministic core "
                       "(timestamps/linearization must be replayable)")


# ---------------------------------------------------------------------------
# 6. kernel-parity — kernels/<k>/: kernel and ref twins agree
# ---------------------------------------------------------------------------

class KernelParityRule(Rule):
    id = "kernel-parity"
    description = (
        "each kernels/<k>/ package keeps kernel (<k>.py) and oracle "
        "(ref.py) twins signature-compatible: same positional parameter "
        "names in order, ref keyword-onlys a subset of the kernel's "
        "(the kernel may add block/interpret knobs)")

    def check_project(self, ctxs: Sequence[FileContext]) -> Iterable[Finding]:
        pkgs: Dict[str, Dict[str, FileContext]] = {}
        for ctx in ctxs:
            parts = ctx.posix.split("/")
            if "kernels" not in parts:
                continue
            i = parts.index("kernels")
            if len(parts) != i + 3:
                continue
            pkg, fname = parts[i + 1], parts[i + 2]
            pkgs.setdefault(pkg, {})[fname] = ctx
        for pkg, files in sorted(pkgs.items()):
            kctx = files.get(f"{pkg}.py")
            rctx = files.get("ref.py")
            if kctx is None or rctx is None:
                continue
            yield from self._check_pkg(pkg, kctx, rctx)

    def _check_pkg(self, pkg, kctx, rctx) -> Iterable[Finding]:
        kfns = self._publics(kctx.tree)
        rfns = self._publics(rctx.tree)
        for name, kfn in kfns.items():
            rfn = rfns.get(f"{name}_ref")
            if rfn is None and len(kfns) == 1 and len(rfns) == 1:
                rfn = next(iter(rfns.values()))     # sole-function pairing
            if rfn is None:
                yield Finding(
                    self.id, kctx.posix, kfn.lineno, kfn.col_offset,
                    f"kernel '{pkg}.{name}' has no oracle twin "
                    f"'{name}_ref' in ref.py")
                continue
            kpos, kkw = self._sig(kfn)
            rpos, rkw = self._sig(rfn)
            if kpos != rpos:
                yield Finding(
                    self.id, kctx.posix, kfn.lineno, kfn.col_offset,
                    f"kernel '{pkg}.{name}' positional params {kpos} != "
                    f"ref twin '{rfn.name}' params {rpos}")
            extra = set(rkw) - set(kkw)
            if extra:
                yield Finding(
                    self.id, rctx.posix, rfn.lineno, rfn.col_offset,
                    f"ref '{rfn.name}' keyword-only params {sorted(extra)} "
                    f"missing from kernel '{pkg}.{name}'")

    @staticmethod
    def _publics(tree) -> Dict[str, ast.FunctionDef]:
        return {n.name: n for n in tree.body
                if isinstance(n, ast.FunctionDef)
                and not n.name.startswith("_")}

    @staticmethod
    def _sig(fn) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
        a = fn.args
        return (tuple(p.arg for p in a.posonlyargs + a.args),
                tuple(p.arg for p in a.kwonlyargs))


# ---------------------------------------------------------------------------
# 7. kernel-vmem — BlockSpec footprint of a pallas_call under budget
# ---------------------------------------------------------------------------

DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024      # conservative VMEM per call
_ELEM_BYTES = 4                             # int32/float32 pools


class KernelVmemRule(Rule):
    id = "kernel-vmem"
    description = (
        "per pallas_call, the summed footprint of BlockSpec block shapes "
        "(bounded dims only: literals, keyword defaults, min() bounds) "
        "must stay under the VMEM budget; full-array specs with "
        "runtime-sized dims are skipped")

    def __init__(self, budget: int = DEFAULT_VMEM_BUDGET):
        self.budget = budget

    def check_file(self, ctx: FileContext) -> Iterable[Finding]:
        if not ctx.in_dir("repro/kernels"):
            return
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            env = self._bound_env(fn)
            for node in ast.walk(fn):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, (ast.Attribute, ast.Name))
                        and (getattr(node.func, "attr", None)
                             or getattr(node.func, "id", None))
                        == "pallas_call"):
                    yield from self._check_call(ctx, fn, node, env)

    def _check_call(self, ctx, fn, call, env) -> Iterable[Finding]:
        total, unbounded = 0, 0
        for node in ast.walk(call):
            if not (isinstance(node, ast.Call)
                    and (getattr(node.func, "attr", None)
                         or getattr(node.func, "id", None)) == "BlockSpec"):
                continue
            if not node.args:
                continue
            shape = node.args[0]
            if not isinstance(shape, (ast.Tuple, ast.List)):
                unbounded += 1
                continue
            n = 1
            for dim in shape.elts:
                v = self._bound(dim, env)
                if v is None:
                    n = None
                    break
                n *= max(v, 0)
            if n is None:
                unbounded += 1
            else:
                total += n * _ELEM_BYTES
        if total > self.budget:
            yield Finding(
                self.id, ctx.posix, call.lineno, call.col_offset,
                f"pallas_call in '{fn.name}' stages ~{total} bytes of "
                f"bounded BlockSpecs (budget {self.budget}; "
                f"{unbounded} unbounded specs not counted) — shrink the "
                "block shapes or raise --vmem-budget")

    def _bound_env(self, fn) -> Dict[str, int]:
        """Upper bounds for local names: int keyword defaults, constant
        assignments, and min() of any known bound (min <= each arg)."""
        env: Dict[str, int] = {}
        a = fn.args
        kw = a.args[len(a.args) - len(a.defaults):] + a.kwonlyargs
        for p, d in zip(kw, list(a.defaults) + list(a.kw_defaults)):
            if isinstance(d, ast.Constant) and type(d.value) is int:
                env[p.arg] = d.value
        for stmt in ast.walk(fn):
            if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)):
                v = self._bound(stmt.value, env)
                if v is not None:
                    env[stmt.targets[0].id] = v
        return env

    def _bound(self, node, env) -> Optional[int]:
        if isinstance(node, ast.Name):
            return env.get(node.id)
        if (isinstance(node, ast.Call)
                and getattr(node.func, "id", None) == "min" and node.args):
            known = [self._bound(a, env) for a in node.args]
            known = [k for k in known if k is not None]
            return min(known) if known else None
        v = _const_eval(node)
        if v is not None:
            return v
        if isinstance(node, ast.BinOp):
            a, b = self._bound(node.left, env), self._bound(node.right, env)
            if a is None or b is None:
                return None
            if isinstance(node.op, ast.Mult):
                return a * b
            if isinstance(node.op, ast.Add):
                return a + b
        return None


# ---------------------------------------------------------------------------
# 8. sentinel-literal — key sentinels only in the blessed domain module
# ---------------------------------------------------------------------------

# KEY_MAX (mask-out / padding), KEY_MAX - 1 (the kernels' internal pad),
# KEY_MAX - 2 (largest user-visible key): a literal spelling of any of
# these outside core/ref.py is exactly the bug class opbatch.check_keys
# exists for (PR 7's silent-loss fix)
SENTINEL_VALUES = (KEY_MAX, KEY_MAX - 1, KEY_MAX - 2)
SENTINEL_BLESSED = ("repro/core/ref.py",)


class SentinelLiteralRule(Rule):
    id = "sentinel-literal"
    description = (
        "key-sentinel literals (2**31-1 / 0x7FFFFFFF masks and the "
        "derived pad/domain values) may be spelled only in core/ref.py; "
        "everywhere else import KEY_MAX / KEY_DOMAIN_HI (repro.api "
        "re-exports them)")

    def check_file(self, ctx: FileContext) -> Iterable[Finding]:
        p = "/" + ctx.posix
        if any(p.endswith("/" + f) for f in SENTINEL_BLESSED):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.BinOp, ast.Constant)):
                continue
            v = _const_eval(node)
            if v is None or v not in SENTINEL_VALUES:
                continue
            # flag the OUTERMOST folded expression only: skip constants
            # whose value can't be told apart from a nested fold — handled
            # by dedup in the engine via identical (line, col) spans
            yield Finding(
                self.id, ctx.posix, node.lineno, node.col_offset,
                f"key-sentinel literal {v} (= KEY_MAX - {KEY_MAX - v}) "
                "outside core/ref.py — import KEY_MAX / KEY_DOMAIN_HI "
                "from repro.api instead")


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def default_rules(vmem_budget: int = DEFAULT_VMEM_BUDGET) -> List[Rule]:
    return [
        LayeringApiRule(),
        LayeringIndexRule(),
        DevicePassPurityRule(),
        DonationSafetyRule(),
        DeterminismRule(),
        KernelParityRule(),
        KernelVmemRule(vmem_budget),
        SentinelLiteralRule(),
    ]


ALL_RULE_CLASSES = (
    LayeringApiRule, LayeringIndexRule, DevicePassPurityRule,
    DonationSafetyRule, DeterminismRule, KernelParityRule, KernelVmemRule,
    SentinelLiteralRule,
)
