"""blitzlint rules BL001-BL007 (see DESIGN.md §10 for the catalog).

Every rule is narrow on purpose: each encodes one invariant this repo has
already paid for in debugging time (uint16 version-tag wrap, double-counted
telemetry, per-row slow paths hiding inside the batched engine) or will pay
for when the worker-per-shard scale-out lands (shared mutable globals,
out-of-owner mutation of shard state).
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from .core import NAME_RE, Finding, LintContext, Rule, register

# ---------------------------------------------------------------------------
# Shared AST helpers
# ---------------------------------------------------------------------------


def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for nested Name/Attribute chains, else None."""
    parts: List[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
        return ".".join(reversed(parts))
    return None


def _functions(tree: ast.Module) -> Iterator[ast.AST]:
    """All function-like scopes, outermost first (module last)."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _walk_scope(scope: ast.AST) -> Iterator[ast.AST]:
    """Walk ``scope`` without descending into nested function scopes, so
    scope-sensitive rules visit every node exactly once."""
    stack: List[ast.AST] = [scope]
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            stack.append(child)


def _source_of(ctx: LintContext, node: ast.AST) -> str:
    lo = getattr(node, "lineno", 1) - 1
    hi = getattr(node, "end_lineno", lo + 1)
    return "\n".join(ctx.lines[lo:hi])


# ---------------------------------------------------------------------------
# BL001 — per-row Python loops in hot-path modules
# ---------------------------------------------------------------------------

ROWISH_NAMES = frozenset(
    {
        "rows",
        "vals",
        "values",
        "pvals",
        "records",
        "tuples",
        "ids",
        "keys",
        "pending",
        "_pending",
    }
)

_UNWRAP_CALLS = frozenset(
    {"enumerate", "zip", "reversed", "sorted", "list", "tuple", "iter"}
)


@register
class HotLoopRule(Rule):
    id = "BL001"
    title = "per-row Python loop in a hot-path module"
    rationale = (
        "The paper's batched fast path exists to eliminate value-at-a-time "
        "Python; a statement loop over rows in plan/blitzcrank/engine/store "
        "is either the scalar escape path (waive with the reason) or an "
        "accidental O(rows) regression.  Comprehensions are exempt: they are "
        "boundary conversions, not control flow."
    )

    def applies_to(self, ctx: LintContext) -> bool:
        return ctx.rel in ctx.config.hot_modules

    def check(self, ctx: LintContext) -> Iterator[Finding]:
        for scope in list(_functions(ctx.tree)) + [ctx.tree]:
            len_names = self._len_aliases(scope)
            for node in _walk_scope(scope):
                if isinstance(node, (ast.For, ast.AsyncFor)):
                    name = self._rowish(node.iter, len_names)
                    if name:
                        yield self.finding(
                            ctx,
                            node,
                            f"statement loop over per-row iterable {name!r} "
                            "(vectorize, or waive with the reason the scalar "
                            "path is required)",
                        )

    @staticmethod
    def _len_aliases(scope: ast.AST) -> Set[str]:
        """Names assigned ``len(<rowish>)`` in this scope (``n = len(rows)``)."""
        out: Set[str] = set()
        for node in _walk_scope(scope):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                call = node.value
                if (
                    isinstance(call.func, ast.Name)
                    and call.func.id == "len"
                    and call.args
                    and isinstance(call.args[0], ast.Name)
                    and call.args[0].id in ROWISH_NAMES
                ):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            out.add(t.id)
        return out

    def _rowish(self, e: ast.AST, len_names: Set[str]) -> Optional[str]:
        if isinstance(e, ast.Name) and e.id in ROWISH_NAMES:
            return e.id
        if isinstance(e, ast.Attribute) and e.attr in ROWISH_NAMES:
            return _dotted(e) or e.attr
        if isinstance(e, ast.Call):
            fname = None
            if isinstance(e.func, ast.Name):
                fname = e.func.id
            if fname in _UNWRAP_CALLS:
                for a in e.args:
                    hit = self._rowish(a, len_names)
                    if hit:
                        return hit
                return None
            if fname == "range":
                for a in e.args:
                    # range(len(rows)) / range(n) with n = len(rows)
                    if isinstance(a, ast.Call) and isinstance(a.func, ast.Name):
                        if a.func.id == "len" and a.args:
                            inner = self._rowish(a.args[0], len_names)
                            if inner:
                                return inner
                    if isinstance(a, ast.Name) and a.id in len_names:
                        return a.id
                    # range(x.shape[0]) — a row-count loop over array x
                    if (
                        isinstance(a, ast.Subscript)
                        and isinstance(a.value, ast.Attribute)
                        and a.value.attr == "shape"
                    ):
                        return (_dotted(a.value) or "array") + "[0]"
                return None
            # rows.values() / rows.items() style
            if isinstance(e.func, ast.Attribute) and e.func.attr in (
                "values",
                "items",
                "keys",
            ):
                return self._rowish(e.func.value, len_names)
        return None


# ---------------------------------------------------------------------------
# BL002 — telemetry-name discipline
# ---------------------------------------------------------------------------

_TELEMETRY_FACTORIES = frozenset({"counter", "histogram", "span"})


@register
class TelemetryNameRule(Rule):
    id = "BL002"
    title = "telemetry name off-catalog or non-literal"
    rationale = (
        "Metric names are the join key for dashboards, the phase "
        "attribution report, and the regression gate; a typo silently "
        "forks a series.  Every literal name must match "
        "repro.<subsystem>.<verb> and appear in telemetry/catalog.py; "
        "dynamic names need a waiver naming the test that pins them."
    )

    def applies_to(self, ctx: LintContext) -> bool:
        # The telemetry package itself forwards caller-supplied names.
        if ctx.rel.startswith("src/repro/telemetry/"):
            return ctx.rel == ctx.config.catalog_rel
        return True

    def check(self, ctx: LintContext) -> Iterator[Finding]:
        if ctx.rel == ctx.config.catalog_rel:
            yield from self._check_catalog(ctx)
            return
        bare = self._bare_imports(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if not self._is_factory(node, bare):
                continue
            if not node.args:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                yield from self._check_name(ctx, arg, arg.value)
            else:
                yield self.finding(
                    ctx,
                    arg,
                    "non-literal metric name (enumerate the names in the "
                    "catalog and waive with the reason + pinning test)",
                )

    @staticmethod
    def _bare_imports(tree: ast.Module) -> Set[str]:
        out: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module and (
                node.module == "repro.telemetry"
                or node.module.startswith("repro.telemetry.")
            ):
                for a in node.names:
                    if a.name in _TELEMETRY_FACTORIES:
                        out.add(a.asname or a.name)
        return out

    @staticmethod
    def _is_factory(node: ast.Call, bare: Set[str]) -> bool:
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in _TELEMETRY_FACTORIES:
            base = _dotted(f.value)
            return base is not None and (
                base == "telemetry"
                or base.endswith(".telemetry")
                or base == "REGISTRY"
                or base.endswith("registry")
            )
        if isinstance(f, ast.Name) and f.id in bare:
            return True
        return False

    def _check_name(
        self, ctx: LintContext, node: ast.AST, name: str
    ) -> Iterator[Finding]:
        if not NAME_RE.match(name):
            yield self.finding(
                ctx,
                node,
                f"metric name {name!r} does not match repro.<subsystem>.<verb>",
            )
            return
        if ctx.rel.startswith("tests/") and name.startswith("repro.test."):
            return  # scratch names for registry mechanics tests
        if ctx.config.catalog and name not in ctx.config.catalog:
            yield self.finding(
                ctx,
                node,
                f"metric name {name!r} is not in telemetry/catalog.py "
                "(add it there, or fix the typo)",
            )

    def _check_catalog(self, ctx: LintContext) -> Iterator[Finding]:
        seen: Dict[str, int] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                name = node.value
                if not name.startswith("repro."):
                    continue
                if not NAME_RE.match(name):
                    yield self.finding(
                        ctx, node, f"catalog entry {name!r} violates the pattern"
                    )
                if name in seen:
                    yield self.finding(
                        ctx,
                        node,
                        f"duplicate catalog entry {name!r} "
                        f"(first at line {seen[name]})",
                    )
                else:
                    seen[name] = node.lineno


# ---------------------------------------------------------------------------
# BL003 — module-level mutable globals in concurrency-bound trees
# ---------------------------------------------------------------------------

_MUTABLE_CALLS = frozenset(
    {"list", "dict", "set", "bytearray", "deque", "defaultdict", "OrderedDict",
     "Counter"}
)


@register
class MutableGlobalRule(Rule):
    id = "BL003"
    title = "module-level mutable global in core/db/oltp"
    rationale = (
        "The worker-per-shard scale-out imports these modules into every "
        "shard worker; a module-level dict/list is cross-shard shared "
        "state with no lock.  Freeze it (tuple / frozenset / "
        "MappingProxyType) or waive with the synchronization story."
    )

    def applies_to(self, ctx: LintContext) -> bool:
        return ctx.in_tree(ctx.config.mutable_global_trees)

    def check(self, ctx: LintContext) -> Iterator[Finding]:
        for stmt in self._module_stmts(ctx.tree):
            targets: Sequence[ast.expr]
            if isinstance(stmt, ast.Assign):
                targets, value = stmt.targets, stmt.value
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                targets, value = [stmt.target], stmt.value
            else:
                continue
            if not self._mutable(value):
                continue
            for t in targets:
                if isinstance(t, ast.Name) and t.id != "__all__":
                    yield self.finding(
                        ctx,
                        stmt,
                        f"module-level mutable global {t.id!r} "
                        "(freeze it or waive with the synchronization story)",
                    )

    @staticmethod
    def _module_stmts(tree: ast.Module) -> Iterator[ast.stmt]:
        """Module body plus top-level if/try bodies (import-fallback idiom)."""
        stack: List[ast.stmt] = list(tree.body)
        while stack:
            stmt = stack.pop()
            if isinstance(stmt, (ast.If, ast.Try)):
                for body in (
                    getattr(stmt, "body", []),
                    getattr(stmt, "orelse", []),
                    getattr(stmt, "finalbody", []),
                ):
                    stack.extend(body)
                for h in getattr(stmt, "handlers", []):
                    stack.extend(h.body)
                continue
            yield stmt

    @staticmethod
    def _mutable(value: ast.expr) -> bool:
        if isinstance(value, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                              ast.DictComp, ast.SetComp)):
            return True
        if isinstance(value, ast.Call) and isinstance(value.func, ast.Name):
            return value.func.id in _MUTABLE_CALLS
        return False


# ---------------------------------------------------------------------------
# BL004 — shard-state mutation outside the designated owners
# ---------------------------------------------------------------------------

_MUTATOR_METHODS = frozenset(
    {"append", "extend", "insert", "add", "update", "pop", "popitem", "remove",
     "discard", "clear", "setdefault", "sort"}
)

_BL004_TREES = (
    "src/repro/core/",
    "src/repro/db/",
    "src/repro/oltp/",
    "src/repro/scan/",
    "src/repro/adaptive/",
    "src/repro/durability/",
)


@register
class ForeignStateMutationRule(Rule):
    id = "BL004"
    title = "mutation of another object's private state"
    rationale = (
        "CompressedTable/DiskArena/ResidencyManager internals are "
        "shard-local; once shard workers run concurrently, an out-of-owner "
        "write (store poking table._res, the scan engine bumping residency "
        "counters) races with the owner.  Mutate through a public entry "
        "point on the owner, or waive with the reason the write is safe."
    )

    def applies_to(self, ctx: LintContext) -> bool:
        return ctx.in_tree(_BL004_TREES) and (
            ctx.rel not in ctx.config.state_owner_modules
        )

    def check(self, ctx: LintContext) -> Iterator[Finding]:
        for scope in _functions(ctx.tree):
            handles = self._foreign_handles(scope)
            for node in _walk_scope(scope):
                if isinstance(node, (ast.Assign, ast.AugAssign)):
                    targets = (
                        node.targets
                        if isinstance(node, ast.Assign)
                        else [node.target]
                    )
                    for t in targets:
                        chain = self._foreign_private(t, handles)
                        if chain:
                            yield self.finding(
                                ctx,
                                node,
                                f"write through foreign private state "
                                f"({chain}); add an entry point on the owner",
                            )
                elif isinstance(node, ast.Call):
                    f = node.func
                    if (
                        isinstance(f, ast.Attribute)
                        and f.attr in _MUTATOR_METHODS
                    ):
                        chain = self._foreign_private(f.value, handles)
                        if chain:
                            yield self.finding(
                                ctx,
                                node,
                                f"mutating call .{f.attr}() through foreign "
                                f"private state ({chain}); add an entry point "
                                "on the owner",
                            )

    def _foreign_handles(self, scope: ast.AST) -> Set[str]:
        """Local names bound to a foreign object's private attribute
        (``res = table._res``): writes through them are owner writes."""
        out: Set[str] = set()
        for node in _walk_scope(scope):
            if isinstance(node, ast.Assign) and self._has_foreign_private(
                node.value
            ):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out.add(t.id)
        return out

    def _foreign_private(
        self, target: ast.AST, handles: Set[str]
    ) -> Optional[str]:
        """Dotted chain when ``target`` writes through foreign private
        state, else None."""
        node = target
        while isinstance(node, ast.Subscript):
            node = node.value
        if not isinstance(node, ast.Attribute):
            return None
        if self._has_foreign_private(node):
            return _dotted(node) or node.attr
        root = node
        while isinstance(root.value, ast.Attribute):
            root = root.value
        if isinstance(root.value, ast.Name) and root.value.id in handles:
            return _dotted(node) or node.attr
        return None

    @staticmethod
    def _has_foreign_private(expr: ast.AST) -> bool:
        for sub in ast.walk(expr):
            if (
                isinstance(sub, ast.Attribute)
                and sub.attr.startswith("_")
                and not sub.attr.startswith("__")
                and not (
                    isinstance(sub.value, ast.Name)
                    and sub.value.id in ("self", "cls")
                )
            ):
                return True
        return False


# ---------------------------------------------------------------------------
# BL005 — unguarded numpy narrowing casts
# ---------------------------------------------------------------------------

_NARROW_DTYPES = frozenset({"uint16", "int32"})

_GUARD_PAT = re.compile(
    r"0xFFFF|65535|2147483647|0x7FFF_?FFFF|iinfo|checked_astype|"
    r"np\.clip|np\.minimum|assert_fits"
)


@register
class NarrowingCastRule(Rule):
    id = "BL005"
    title = "narrowing cast without a bounds guard"
    rationale = (
        "uint16/int32 casts wrap silently (the plan-version-tag wrap bug "
        "class).  A narrowing astype/asarray needs a bounds guard in the "
        "same function, the sanitize-aware core.casts.checked_astype "
        "helper, or a waiver proving the value range statically."
    )

    # The version-tag-wrap bug class lives in the table/codec layer; the
    # Pallas kernel lowerings cast domain-bounded symbol data to int32
    # because jax mandates it, and are covered by kernel parity tests.
    _TREES = (
        "src/repro/core/",
        "src/repro/db/",
        "src/repro/oltp/",
        "src/repro/scan/",
        "src/repro/durability/",
        "src/repro/adaptive/",
    )

    def applies_to(self, ctx: LintContext) -> bool:
        return ctx.in_tree(self._TREES)

    def check(self, ctx: LintContext) -> Iterator[Finding]:
        for scope in list(_functions(ctx.tree)) + [ctx.tree]:
            guarded = bool(_GUARD_PAT.search(_source_of(ctx, scope)))
            if guarded:
                continue
            for node in _walk_scope(scope):
                dtype = self._narrow_cast(node)
                if dtype:
                    yield self.finding(
                        ctx,
                        node,
                        f"narrowing cast to {dtype} without a bounds guard "
                        "(use core.casts.checked_astype, guard, or waive "
                        "with the static range argument)",
                    )

    def _narrow_cast(self, node: ast.AST) -> Optional[str]:
        if not isinstance(node, ast.Call):
            return None
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "astype" and node.args:
            return self._narrow_dtype(node.args[0])
        dotted = _dotted(f) if isinstance(f, (ast.Attribute, ast.Name)) else None
        if dotted in ("np.asarray", "np.array", "numpy.asarray", "numpy.array"):
            src = node.args[0] if node.args else None
            if isinstance(src, (ast.List, ast.Tuple, ast.Constant)):
                return None  # literal source: range visible at the call
            for kw in node.keywords:
                if kw.arg == "dtype":
                    return self._narrow_dtype(kw.value)
            if len(node.args) >= 2:
                return self._narrow_dtype(node.args[1])
        return None

    @staticmethod
    def _narrow_dtype(e: ast.AST) -> Optional[str]:
        if isinstance(e, ast.Attribute) and e.attr in _NARROW_DTYPES:
            return e.attr
        if isinstance(e, ast.Name) and e.id in _NARROW_DTYPES:
            return e.id
        if (
            isinstance(e, ast.Constant)
            and isinstance(e.value, str)
            and e.value in _NARROW_DTYPES
        ):
            return e.value
        return None


# ---------------------------------------------------------------------------
# BL006 — bare except
# ---------------------------------------------------------------------------


@register
class BareExceptRule(Rule):
    id = "BL006"
    title = "bare except"
    rationale = (
        "A bare except swallows KeyboardInterrupt/SystemExit and turns "
        "poisoned-state bugs into silent data corruption; name the "
        "exception types the handler can actually recover from."
    )

    def check(self, ctx: LintContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ExceptHandler) and node.type is None:
                yield self.finding(
                    ctx, node, "bare except (name the recoverable exceptions)"
                )


# ---------------------------------------------------------------------------
# BL007 — raw wall-clock reads where the telemetry clock is required
# ---------------------------------------------------------------------------

_CLOCK_ATTRS = frozenset(
    {"time", "perf_counter", "perf_counter_ns", "monotonic", "monotonic_ns"}
)


@register
class RawClockRule(Rule):
    id = "BL007"
    title = "raw time.* read in a telemetry-clocked module"
    rationale = (
        "Hot-path timing goes through telemetry.span so disabled mode "
        "stays zero-cost and phase attribution and the profiler trace see "
        "every region; a raw time.time() is invisible to both and keeps "
        "costing syscalls when telemetry is off."
    )

    def applies_to(self, ctx: LintContext) -> bool:
        return ctx.in_tree(ctx.config.clocked_trees)

    def check(self, ctx: LintContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _CLOCK_ATTRS
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "time"
            ):
                yield self.finding(
                    ctx,
                    node,
                    f"time.{node.func.attr}() bypasses the telemetry clock "
                    "(use telemetry.span, or waive with "
                    "why wall time is data here)",
                )
