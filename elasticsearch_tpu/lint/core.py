"""estpu-lint core: file model, pragma handling, report shaping.

The analyzer is a project-specific forbidden-APIs layer (the role
forbidden-apis/error-prone play in the reference's Gradle build,
PAPER.md `buildSrc/`): it walks the package's own AST (stdlib ``ast``,
no dependencies) and machine-enforces the cross-cutting contracts the
first ten PRs established by hand — trace-safety (ESTPU-JIT),
resource pairing (ESTPU-PAIR), determinism (ESTPU-DET), recompile
hazards (ESTPU-SHAPE), and the typed-error hierarchy (ESTPU-ERR).

Suppression surfaces, in precedence order:

1. **Inline pragma** — ``# estpu: allow[RULE-ID] <one-line reason>``
   on the violating line or the line directly above it. The reason is
   MANDATORY: a pragma without one is itself a violation
   (ESTPU-LINT00), so every exemption is documented where it lives.
2. **Rule allowlists** — a rule module may carry a named allowlist of
   legitimate call sites (e.g. the wall-clock sites in ``rest/api.py``,
   see ``rules/det.py``); each entry names path + function + reason.
3. **Baseline** — ``lint_baseline.json`` at the repo root holds
   pre-existing violations that are real but out of scope to fix now.
   Matching is exact (rule + path + message, with an occurrence
   count); an entry that no longer matches FAILS the run, so the
   baseline only ever shrinks.
"""

from __future__ import annotations

import ast
import os
import re
import tokenize
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Tuple

__all__ = [
    "Violation", "LintModule", "Report", "collect_modules",
    "package_root", "PRAGMA_RE",
]

# `# estpu: allow[ESTPU-DET01] epoch display field (ES parity)`
PRAGMA_RE = re.compile(
    r"#\s*estpu:\s*allow\[([A-Z0-9\-, ]+)\]\s*(.*)")


@dataclass(frozen=True)
class Violation:
    rule: str
    path: str           # forward-slash path relative to the scan root
    line: int
    col: int
    message: str

    @property
    def key(self) -> Tuple[str, str, str]:
        """Baseline identity: line numbers drift with unrelated edits,
        the (rule, path, message) triple does not."""
        return (self.rule, self.path, self.message)

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} " \
               f"{self.message}"


class LintModule:
    """One parsed source file plus the lookups rules need."""

    def __init__(self, path: str, rel: str, source: str):
        self.path = path
        self.rel = rel.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        # import alias maps: `import random as _random` -> {_random:
        # random}; `from jax import jit as j` -> {j: (jax, jit)}
        self.module_aliases: Dict[str, str] = {}
        self.from_imports: Dict[str, Tuple[str, str]] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.module_aliases[a.asname or a.name.split(".")[0]] \
                        = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.from_imports[a.asname or a.name] = \
                        (node.module, a.name)
        self._pragmas: Optional[Dict[int, Tuple[List[str], str]]] = None

    # -- pragmas ----------------------------------------------------------

    def pragmas(self) -> Dict[int, Tuple[List[str], str]]:
        """line -> ([rule ids], reason). Comments are found with the
        tokenizer, not line regexes, so a pragma inside a string
        literal never suppresses anything."""
        if self._pragmas is None:
            out: Dict[int, Tuple[List[str], str]] = {}
            try:
                import io
                for tok in tokenize.generate_tokens(
                        io.StringIO(self.source).readline):
                    if tok.type != tokenize.COMMENT:
                        continue
                    m = PRAGMA_RE.search(tok.string)
                    if m:
                        rules = [r.strip() for r in m.group(1).split(",")
                                 if r.strip()]
                        out[tok.start[0]] = (rules, m.group(2).strip())
            except tokenize.TokenError:
                pass
            self._pragmas = out
        return self._pragmas

    def pragma_allows(self, line: int, rule: str) -> bool:
        """Pragma on the violating line or the line above. The rule id
        must match exactly or by family prefix (``ESTPU-DET`` covers
        ``ESTPU-DET01``)."""
        for ln in (line, line - 1):
            entry = self.pragmas().get(ln)
            if not entry:
                continue
            rules, reason = entry
            if not reason:
                continue        # undocumented pragma: never suppresses
            for r in rules:
                if rule == r or rule.startswith(r):
                    return True
        return False

    def undocumented_pragmas(self) -> Iterable[Violation]:
        for ln, (rules, reason) in sorted(self.pragmas().items()):
            if not reason:
                yield Violation(
                    "ESTPU-LINT00", self.rel, ln, 0,
                    f"allow[{','.join(rules)}] pragma without a "
                    f"justification — every exemption must say why")


@dataclass
class Report:
    violations: List[Violation] = field(default_factory=list)
    baselined: int = 0
    allowlisted: int = 0
    stale_baseline: List[Dict[str, Any]] = field(default_factory=list)
    rules_run: List[str] = field(default_factory=list)
    files: int = 0
    parse_errors: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.violations and not self.stale_baseline \
            and not self.parse_errors

    def summary(self) -> Dict[str, Any]:
        """The BENCH-json / CI-facing rollup."""
        return {
            "rules_run": len(self.rules_run),
            "files": self.files,
            "violations": len(self.violations),
            "baselined": self.baselined,
            "allowlisted": self.allowlisted,
            "stale_baseline": len(self.stale_baseline),
            "ok": self.ok,
        }


def package_root() -> str:
    """The elasticsearch_tpu package directory — the default scan root."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def collect_modules(root: str,
                    files: Optional[List[str]] = None,
                    ) -> Tuple[List[LintModule], List[str]]:
    """Parse ``files`` (or every .py under ``root``); returns (modules,
    parse_errors). Paths in violations are reported relative to root."""
    paths: List[str] = []
    if files:
        for f in files:
            paths.append(os.path.abspath(f))
    else:
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = sorted(d for d in dirnames
                                 if d != "__pycache__")
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    paths.append(os.path.join(dirpath, fn))
    modules: List[LintModule] = []
    errors: List[str] = []
    root = os.path.abspath(root)
    for p in paths:
        rel = os.path.relpath(p, root)
        try:
            with open(p, encoding="utf-8") as fh:
                src = fh.read()
            modules.append(LintModule(p, rel, src))
        except (OSError, SyntaxError) as e:
            errors.append(f"{rel}: {e}")
    return modules, errors
