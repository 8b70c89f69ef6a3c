"""ESTPU-ERR — typed-error hierarchy.

``failure_type_of`` / the PR-1/PR-4 retryability matrix classify by
exception type. A ``raise ValueError`` in ``cluster/`` or ``rest/``
falls through classification as an opaque 500 and breaks retry
totality — raise sites there must use ``common/errors.py`` types.

Bare re-raises (``raise`` / ``raise e``) pass: the original type is
preserved. Control-flow builtins (StopIteration & co) pass: they never
cross the failure-classification boundary.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Tuple

from elasticsearch_tpu.lint.core import LintModule, Violation
from elasticsearch_tpu.lint.registry import ProjectIndex

RULES = {
    "ESTPU-ERR01": "raise outside the common/errors.py hierarchy in "
                   "cluster//rest/",
}

SCOPED_DIRS = ("cluster/", "rest/")

_CONTROL_FLOW_OK = {"StopIteration", "StopAsyncIteration",
                    "GeneratorExit", "KeyboardInterrupt", "SystemExit",
                    "NotImplementedError", "AssertionError"}


def _raised_class(exc: ast.expr) -> Optional[str]:
    """Class name of a raise site, or None when it cannot be a direct
    construction (re-raise of a bound name, dynamic expr)."""
    if isinstance(exc, ast.Call):
        f = exc.func
        if isinstance(f, ast.Attribute):
            return f.attr
        if isinstance(f, ast.Name):
            return f.id
        return None
    if isinstance(exc, ast.Name):
        # `raise SomeError` without parens: classes are CamelCase by
        # project convention; lowercase names are bound exception
        # objects being re-raised
        return exc.id if exc.id[:1].isupper() else None
    return None


def run(modules: List[LintModule],
        index: ProjectIndex) -> Tuple[List[Violation], int]:
    vs: List[Violation] = []
    hierarchy = index.hierarchy
    for mod in modules:
        if not mod.rel.startswith(SCOPED_DIRS):
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Raise) or node.exc is None:
                continue
            cls = _raised_class(node.exc)
            if cls is None or cls in _CONTROL_FLOW_OK \
                    or cls in hierarchy:
                continue
            vs.append(Violation(
                "ESTPU-ERR01", mod.rel, node.lineno, node.col_offset,
                f"raise {cls} — use a common/errors.py type so "
                f"failure_type_of and the retryability matrix stay "
                f"total"))
    return vs, 0
