"""estpu-lint: the project's own static analyzer (stdlib ``ast``, no
dependencies, no imports of the code under analysis — runs offline
with no jax).

Machine-enforces the engine's cross-cutting contracts:

- **ESTPU-JIT**   trace-safety / tracked_jit routing / attribution rows
- **ESTPU-PAIR**  breaker-task-span pairing on all paths
- **ESTPU-DET**   injectable clocks + seeded rng + ordered iteration
- **ESTPU-SHAPE** bucketed shapes at jit launch surfaces
- **ESTPU-ERR**   typed-error hierarchy at raise sites

Run ``python -m elasticsearch_tpu.lint`` (exit 0 clean, 1 violations,
2 stale baseline / parse errors), or call :func:`run_lint`. Tier-1 CI
runs the same thing through ``tests/test_lint.py``.
"""

from __future__ import annotations

import os
from typing import List, Optional

from elasticsearch_tpu.lint.baseline import (
    apply_baseline, default_baseline_path, load_baseline,
)
from elasticsearch_tpu.lint.core import (
    Report, Violation, collect_modules, package_root,
)
from elasticsearch_tpu.lint.registry import build_index
from elasticsearch_tpu.lint.rules import ALL_RULE_MODULES, all_rules

__all__ = ["run_lint", "Report", "Violation", "all_rules",
           "package_root"]

# the analyzer does not analyze itself: rule sources quote the very
# patterns they forbid
_SELF = "lint/"


def run_lint(root: Optional[str] = None,
             files: Optional[List[str]] = None,
             baseline_path: Optional[str] = None,
             use_baseline: bool = True) -> Report:
    scan_root = os.path.abspath(root or package_root())
    modules, parse_errors = collect_modules(scan_root, files)
    modules = [m for m in modules if not m.rel.startswith(_SELF)]
    index = build_index(modules)

    violations: List[Violation] = []
    allowlisted = 0
    for rmod in ALL_RULE_MODULES:
        vs, al = rmod.run(modules, index)
        violations.extend(vs)
        allowlisted += al

    # inline pragmas (documented only), then the pragma meta-rule
    mod_by_rel = {m.rel: m for m in modules}
    kept: List[Violation] = []
    for v in violations:
        m = mod_by_rel.get(v.path)
        if m is not None and m.pragma_allows(v.line, v.rule):
            allowlisted += 1
        else:
            kept.append(v)
    for m in modules:
        kept.extend(m.undocumented_pragmas())

    baselined = 0
    stale: List[dict] = []
    if use_baseline:
        bpath = baseline_path or (
            default_baseline_path()
            if scan_root == os.path.abspath(package_root()) and not files
            else None)
        if bpath and os.path.exists(bpath):
            kept, baselined, stale = apply_baseline(
                kept, load_baseline(bpath))

    return Report(
        violations=sorted(kept, key=lambda v: (v.path, v.line, v.col,
                                               v.rule)),
        baselined=baselined,
        allowlisted=allowlisted,
        stale_baseline=stale,
        rules_run=sorted(all_rules()),
        files=len(modules),
        parse_errors=parse_errors,
    )
