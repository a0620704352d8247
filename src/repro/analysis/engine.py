"""Rule engine: parse once, run repo-aware AST rules, honor suppressions.

A ``Rule`` sees one parsed module at a time through a ``ModuleContext``
(AST with parent links, source lines, per-line suppressions) and yields
``Finding``s. The engine is deliberately tiny — rules carry the domain
knowledge; this module only owns parsing, the suppression contract and the
registry.

Suppression syntax (both forms require the rule id, so a suppression can
never silently widen)::

    x = flat_ids + 1   # repolint: ignore[id-space] -- why the rule is wrong here
    # repolint: file-ignore[jax-purity] -- module-wide, put near the top

``# repolint: ignore`` with no rule list is NOT honored: every suppression
names what it silences.
"""
from __future__ import annotations

import ast
import io
import os
import re
import tokenize
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Set, Tuple

_SUPPRESS = re.compile(r"#\s*repolint:\s*(ignore|file-ignore)\[([a-z0-9_,\- ]+)\]")

_SKIP_DIRS = {"__pycache__", ".git", ".ruff_cache", ".pytest_cache", "build",
              "dist", "node_modules", ".mypy_cache"}


@dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""
    path: str
    line: int
    col: int
    rule: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: [{self.rule}] {self.message}"


@dataclass
class AnalysisConfig:
    """Knobs shared by the rules (CLI flags map 1:1 onto these).

    ``assumed_dims`` bounds symbolic block/scratch dimensions the Pallas
    VMEM estimator cannot resolve statically; ``default_dim`` bounds names
    absent from the table. Both are deliberately worst-case-ish: the
    estimate is an upper bound, not a measurement.
    """
    vmem_cap_bytes: int = 16 * 1024 * 1024   # one TPU core's VMEM
    default_dim: int = 512
    assumed_dims: Dict[str, int] = field(default_factory=lambda: {
        # repo-wide kernel parameter conventions (see kernels/*.py defaults)
        "block_b": 64, "block_q": 512, "block_k": 512,
        "B": 1024, "T": 64, "H": 64, "D": 256, "G": 32, "K": 8192,
        "R": 1 << 20, "n": 64, "n_k": 64, "n_q": 64,
        "W": 256,   # lane-line width of a packed row store: max(D, 128)
    })


class ModuleContext:
    """One parsed module plus everything rules repeatedly need."""

    def __init__(self, path: str, source: str,
                 config: AnalysisConfig) -> None:
        self.path = path
        self.source = source
        self.config = config
        self.tree = ast.parse(source, filename=path)
        for parent in ast.walk(self.tree):
            for child in ast.iter_child_nodes(parent):
                child._repolint_parent = parent  # type: ignore[attr-defined]
        self.line_suppressions: Dict[int, Set[str]] = {}
        self.file_suppressions: Set[str] = set()
        self.warnings: List[str] = []
        self._scan_suppressions()

    def _scan_suppressions(self) -> None:
        try:
            tokens = tokenize.generate_tokens(io.StringIO(self.source).readline)
            for tok in tokens:
                if tok.type != tokenize.COMMENT:
                    continue
                m = _SUPPRESS.search(tok.string)
                if not m:
                    continue
                rules = {r.strip() for r in m.group(2).split(",") if r.strip()}
                if m.group(1) == "file-ignore":
                    self.file_suppressions |= rules
                else:
                    self.line_suppressions.setdefault(
                        tok.start[0], set()).update(rules)
        except tokenize.TokenError as e:
            # ast.parse already accepted the file, so this is near-unreachable;
            # surface it anyway — a failed comment scan means suppressions in
            # this file may silently not apply
            self.warnings.append(
                f"{self.path}: suppression scan failed: {e}")

    def parent(self, node: ast.AST) -> Optional[ast.AST]:
        return getattr(node, "_repolint_parent", None)

    def parents(self, node: ast.AST) -> Iterator[ast.AST]:
        cur = self.parent(node)
        while cur is not None:
            yield cur
            cur = self.parent(cur)

    def suppressed(self, rule: str, line: int) -> bool:
        return (rule in self.file_suppressions
                or rule in self.line_suppressions.get(line, set()))


class Rule:
    """Base class: subclasses set ``id``/``summary`` and implement ``check``."""

    id: str = ""
    summary: str = ""

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        raise NotImplementedError

    def finding(self, ctx: ModuleContext, node: ast.AST, message: str) -> Finding:
        return Finding(ctx.path, getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0), self.id, message)


def all_rules() -> List[Rule]:
    """The registry, in documentation order (``repolint --list-rules``)."""
    from repro.analysis.hygiene import SilentExceptRule
    from repro.analysis.idspace import IdSpaceRule
    from repro.analysis.pallas_resources import DmaPairingRule, VmemBudgetRule
    from repro.analysis.purity import JaxPurityRule, UnseededRandomRule
    from repro.analysis.threadsafety import ThreadSafetyRule
    return [IdSpaceRule(), JaxPurityRule(), UnseededRandomRule(),
            VmemBudgetRule(), DmaPairingRule(), ThreadSafetyRule(),
            SilentExceptRule()]


def iter_python_files(paths: Sequence[str]) -> Iterator[str]:
    """Expand files/directories into a sorted stream of ``*.py`` paths."""
    out: Set[str] = set()
    for p in paths:
        if os.path.isfile(p):
            out.add(p)
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = [d for d in dirnames
                           if d not in _SKIP_DIRS and not d.startswith(".")]
            for name in filenames:
                if name.endswith(".py"):
                    out.add(os.path.join(dirpath, name))
    yield from sorted(out)


def run_paths(paths: Sequence[str], rules: Optional[Iterable[Rule]] = None,
              config: Optional[AnalysisConfig] = None,
              ) -> Tuple[List[Finding], List[str]]:
    """Run ``rules`` over every python file under ``paths``.

    Returns ``(findings, errors)`` — ``errors`` are files that failed to
    parse (reported, never silently skipped: an unparsable file would
    otherwise exempt itself from every invariant).
    """
    config = config or AnalysisConfig()
    active = list(rules) if rules is not None else all_rules()
    findings: List[Finding] = []
    errors: List[str] = []
    for path in iter_python_files(paths):
        try:
            with open(path, encoding="utf-8") as f:
                ctx = ModuleContext(path, f.read(), config)
        except (SyntaxError, UnicodeDecodeError) as e:
            errors.append(f"{path}: {type(e).__name__}: {e}")
            continue
        errors.extend(ctx.warnings)
        for rule in active:
            for finding in rule.check(ctx):
                if not ctx.suppressed(rule.id, finding.line):
                    findings.append(finding)
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings, errors
