"""blitzlint core: AST lint framework with waivers and a rule registry.

Stdlib-only by design — the CI job runs it before any dependency install,
and a lint pass must never import the code under analysis (rules parse,
they do not execute).

Framework pieces:

* :class:`Rule` — subclass, set ``id``/``title``/``rationale``, implement
  ``check(ctx)`` yielding :class:`Finding`; decorate with :func:`register`.
* :class:`LintContext` — one parsed file: source, lines, AST, repo-relative
  path, and the shared :class:`LintConfig`.
* Waivers — ``# blitzlint: waive[BL001] -- reason`` on the flagged line or
  the line above suppresses that rule there.  The reason is mandatory and
  waivers must be *consumed*: a reasonless, unknown-rule, or unused waiver
  is itself a finding (``BL000``), so the waiver set can never rot.
"""

from __future__ import annotations

import ast
import dataclasses
import pathlib
import re
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

WAIVER_RE = re.compile(
    r"#\s*blitzlint:\s*waive\[(?P<ids>[A-Za-z0-9_,\s]*)\]"
    r"(?:\s*--\s*(?P<reason>.*\S))?"
)

NAME_RE = re.compile(r"^repro\.[a-z0-9_]+(\.[a-z0-9_]+)+$")

EXCLUDED_DIR_NAMES = frozenset({".git", "__pycache__", ".ruff_cache", ".mypy_cache"})

# The linter's own sources embed waiver syntax as string data and the
# fixtures violate rules on purpose; neither belongs in a repo sweep
# (the package is covered by tests/test_blitzlint.py instead).
EXCLUDED_RELS = ("tools/blitzlint/",)


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str  # repo-relative posix path
    line: int
    col: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"


@dataclasses.dataclass
class Waiver:
    line: int
    rules: Tuple[str, ...]
    reason: str
    used: bool = False


@dataclasses.dataclass(frozen=True)
class LintConfig:
    """Repo-specific rule scoping (paths are repo-relative posix)."""

    # Modules on the sub-microsecond OLTP path: per-row Python loops here
    # are the loops the paper's batched fast path exists to eliminate.
    hot_modules: Tuple[str, ...] = (
        "src/repro/core/plan.py",
        "src/repro/core/blitzcrank.py",
        "src/repro/scan/engine.py",
        "src/repro/oltp/store.py",
    )
    # Trees that the worker-per-shard scale-out will run concurrently:
    # module-level mutable containers there are cross-shard shared state.
    mutable_global_trees: Tuple[str, ...] = (
        "src/repro/core/",
        "src/repro/db/",
        "src/repro/oltp/",
    )
    # Modules allowed to mutate CompressedTable/DiskArena internals
    # directly (the shard-local owners).  Everyone else goes through
    # public entry points.
    state_owner_modules: Tuple[str, ...] = (
        "src/repro/core/blitzcrank.py",
        "src/repro/core/arena.py",
    )
    # Trees where regions must be timed by telemetry spans (so
    # disabled-mode stays zero-cost and phase attribution stays
    # consistent).  The telemetry package itself implements the spans.
    clocked_trees: Tuple[str, ...] = (
        "src/repro/core/",
        "src/repro/db/",
        "src/repro/oltp/",
        "src/repro/scan/",
        "src/repro/durability/",
        "src/repro/adaptive/",
        "src/repro/kernels/",
    )
    catalog_rel: str = "src/repro/telemetry/catalog.py"
    catalog: Tuple[str, ...] = ()


class LintContext:
    """One file under analysis plus the shared config."""

    def __init__(
        self, path: pathlib.Path, rel: str, source: str, config: LintConfig
    ) -> None:
        self.path = path
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=str(path))
        self.config = config

    def in_tree(self, trees: Sequence[str]) -> bool:
        return any(self.rel.startswith(t) for t in trees)


class Rule:
    """Base class; subclasses register themselves via :func:`register`."""

    id: str = "BL000"
    title: str = ""
    rationale: str = ""

    def applies_to(self, ctx: LintContext) -> bool:
        return True

    def check(self, ctx: LintContext) -> Iterator[Finding]:
        raise NotImplementedError

    def finding(self, ctx: LintContext, node: ast.AST, message: str) -> Finding:
        return Finding(
            self.id,
            ctx.rel,
            getattr(node, "lineno", 1),
            getattr(node, "col_offset", 0) + 1,
            message,
        )


RULES: Dict[str, Rule] = {}


def register(cls: type) -> type:
    inst = cls()
    if inst.id in RULES:
        raise ValueError(f"duplicate rule id {inst.id}")
    RULES[inst.id] = inst
    return cls


def parse_waivers(lines: Sequence[str]) -> Tuple[List[Waiver], List[Finding]]:
    """Extract waiver comments; malformed ones become BL000 findings
    immediately (they can never suppress anything)."""
    waivers: List[Waiver] = []
    bad: List[Finding] = []
    for i, text in enumerate(lines, start=1):
        m = WAIVER_RE.search(text)
        if not m:
            if "blitzlint:" in text and "waive" in text:
                bad.append(
                    Finding("BL000", "", i, 1, "malformed blitzlint waiver comment")
                )
            continue
        ids = tuple(s.strip() for s in m.group("ids").split(",") if s.strip())
        reason = (m.group("reason") or "").strip()
        if not ids:
            bad.append(Finding("BL000", "", i, 1, "waiver names no rule ids"))
            continue
        unknown = [r for r in ids if r not in RULES]
        if unknown:
            bad.append(
                Finding("BL000", "", i, 1, f"waiver names unknown rules: {unknown}")
            )
        if not reason:
            bad.append(
                Finding(
                    "BL000",
                    "",
                    i,
                    1,
                    f"waiver for {list(ids)} has no reason "
                    "(syntax: # blitzlint: waive[BLxxx] -- why)",
                )
            )
            continue
        waivers.append(Waiver(i, ids, reason))
    return waivers, bad


def apply_waivers(
    findings: List[Finding], waivers: List[Waiver], rel: str
) -> List[Finding]:
    """Drop findings covered by a waiver on the same or preceding line;
    flag waivers that covered nothing."""
    kept: List[Finding] = []
    for f in findings:
        suppressed = False
        for w in waivers:
            if f.rule in w.rules and w.line in (f.line, f.line - 1):
                w.used = True
                suppressed = True
        if not suppressed:
            kept.append(f)
    for w in waivers:
        if not w.used:
            kept.append(
                Finding(
                    "BL000",
                    rel,
                    w.line,
                    1,
                    f"unused waiver for {list(w.rules)} (nothing to suppress)",
                )
            )
    kept.sort(key=lambda f: (f.line, f.col, f.rule))
    return kept


def lint_source(
    source: str,
    rel: str,
    config: LintConfig,
    path: Optional[pathlib.Path] = None,
    rules: Optional[Sequence[Rule]] = None,
) -> List[Finding]:
    ctx = LintContext(path or pathlib.Path(rel), rel, source, config)
    active = list(rules) if rules is not None else list(RULES.values())
    raw: List[Finding] = []
    for rule in active:
        if rule.applies_to(ctx):
            raw.extend(rule.check(ctx))
    waivers, bad = parse_waivers(ctx.lines)
    out = apply_waivers(raw, waivers, rel)
    out.extend(dataclasses.replace(b, path=rel) for b in bad)
    out.sort(key=lambda f: (f.line, f.col, f.rule))
    return out


def lint_file(
    path: pathlib.Path,
    root: pathlib.Path,
    config: LintConfig,
    rules: Optional[Sequence[Rule]] = None,
) -> List[Finding]:
    rel = path.resolve().relative_to(root.resolve()).as_posix()
    return lint_source(path.read_text(), rel, config, path=path, rules=rules)


def iter_python_files(
    paths: Iterable[pathlib.Path], root: pathlib.Path
) -> Iterator[pathlib.Path]:
    seen = set()
    rroot = root.resolve()
    for p in paths:
        cands = [p] if p.is_file() else sorted(p.rglob("*.py"))
        for f in cands:
            rf = f.resolve()
            rel = rf.relative_to(rroot).as_posix()
            if rf in seen or any(rel.startswith(e) for e in EXCLUDED_RELS):
                continue
            if any(part in EXCLUDED_DIR_NAMES for part in rf.parts):
                continue
            seen.add(rf)
            yield f


def load_catalog(root: pathlib.Path, catalog_rel: str) -> Tuple[str, ...]:
    """Read METRICS from the catalog module *without importing it* — the
    lint job must not require the library's dependencies."""
    path = root / catalog_rel
    if not path.exists():
        return ()
    tree = ast.parse(path.read_text())
    for node in ast.walk(tree):
        targets: List[ast.expr] = []
        if isinstance(node, ast.Assign):
            targets = node.targets
            value = node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
            value = node.value
        else:
            continue
        for t in targets:
            if isinstance(t, ast.Name) and t.id == "METRICS":
                names = ast.literal_eval(value)
                return tuple(str(n) for n in names)
    return ()


def make_config(root: pathlib.Path) -> LintConfig:
    cfg = LintConfig()
    return dataclasses.replace(cfg, catalog=load_catalog(root, cfg.catalog_rel))


def lint_paths(
    paths: Sequence[pathlib.Path],
    root: pathlib.Path,
    config: Optional[LintConfig] = None,
    rules: Optional[Sequence[Rule]] = None,
) -> List[Finding]:
    cfg = config or make_config(root)
    out: List[Finding] = []
    for f in iter_python_files(paths, root):
        out.extend(lint_file(f, root, cfg, rules=rules))
    return out
