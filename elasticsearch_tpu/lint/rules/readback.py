"""ESTPU-RB — readback provenance.

The flight recorder (telemetry/flightrecorder.py) attributes every
device→host transfer to a named call site, but only because the engine
dirs route them through ONE funnel: ``ops/device.readback(site, ...)``.
An ``np.asarray`` straight off a jitted output is an *untracked*
readback — it stalls the launch pipeline exactly the same, yet never
shows up in ``GET /_flight_recorder``, never feeds the regime
classifier, and silently re-opens the BENCH ×56-79 attribution gap the
recorder exists to close. These rules keep the funnel total.

RB01 catches the numpy spellings with clear device provenance (the
argument is a launch-surface call, or a name bound from one in the
same scope). RB02 catches the explicit JAX transfer APIs
(``jax.device_get`` / ``.block_until_ready()``), which are
device-touching by construction. ``ops/device.py`` itself is exempt —
it IS the funnel.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set, Tuple

from elasticsearch_tpu.lint.core import LintModule, Violation
from elasticsearch_tpu.lint.registry import ProjectIndex, _call_func_name

RULES = {
    "ESTPU-RB01": "untracked device→host readback (np.asarray/np.array "
                  "on a jitted output) — route through "
                  "ops.device.readback(site, ...)",
    "ESTPU-RB02": "explicit device transfer API (jax.device_get / "
                  ".block_until_ready) outside the readback funnel",
}

ENGINE_DIRS = ("ops/", "search/", "parallel/")

# the funnel itself (and its module) is the one legitimate home for
# raw transfers
FUNNEL_MODULE = "ops/device.py"

_NP_READBACK_CALLS = {"asarray", "array"}

# Named allowlist: (path, enclosing function or None, rule id, reason).
# Warmup code synchronizes DELIBERATELY and discards the result —
# there is no serving-path readback to attribute.
READBACK_ALLOWLIST: List[Tuple[str, Optional[str], str, str]] = [
    ("search/fastpath.py", None, "ESTPU-RB02",
     "warmup compiles sync on purpose (block_until_ready measures "
     "readiness, results discarded); the serving loop reads back "
     "through the funnel"),
]


def _enclosing_fn(mod: LintModule, line: int) -> Optional[str]:
    best: Optional[ast.FunctionDef] = None
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            end = getattr(node, "end_lineno", node.lineno)
            if node.lineno <= line <= end:
                if best is None or node.lineno > best.lineno:
                    best = node
    return best.name if best else None


def _allowlisted(mod: LintModule, v: Violation) -> bool:
    fn = _enclosing_fn(mod, v.line)
    for path, func, rule, _reason in READBACK_ALLOWLIST:
        if path == v.path and rule == v.rule \
                and (func is None or func == fn):
            return True
    return False


def _numpy_aliases(mod: LintModule) -> Set[str]:
    return {alias for alias, real in mod.module_aliases.items()
            if real == "numpy"}


def _jax_aliases(mod: LintModule) -> Set[str]:
    return {alias for alias, real in mod.module_aliases.items()
            if real == "jax"}


def _launch_bound_names(scope: ast.AST,
                        launch_surfaces: Set[str]) -> Set[str]:
    """Names bound (directly or by tuple unpack) from a call to a
    launch surface within ``scope`` — the values whose host conversion
    is a device readback."""
    out: Set[str] = set()
    for node in ast.walk(scope):
        if not isinstance(node, ast.Assign):
            continue
        v = node.value
        if not (isinstance(v, ast.Call)
                and _call_func_name(v.func) in launch_surfaces):
            continue
        for tgt in node.targets:
            if isinstance(tgt, ast.Name):
                out.add(tgt.id)
            elif isinstance(tgt, (ast.Tuple, ast.List)):
                for el in tgt.elts:
                    if isinstance(el, ast.Name):
                        out.add(el.id)
    return out


def _check_module(mod: LintModule, index: ProjectIndex,
                  vs: List[Violation]) -> None:
    np_aliases = _numpy_aliases(mod)
    jax_aliases = _jax_aliases(mod)
    surfaces = index.launch_surfaces
    # jitted bodies are trace-time code — ESTPU-JIT02's jurisdiction,
    # and np.asarray inside a traced body is a different defect class
    traced = {id(fn) for fn in index.traced_functions.get(mod.rel, [])}

    scopes: List[ast.AST] = [fn for fn in mod.tree.body
                             if isinstance(fn, ast.FunctionDef)
                             and id(fn) not in traced]
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ClassDef):
            scopes.extend(fn for fn in node.body
                          if isinstance(fn, ast.FunctionDef)
                          and id(fn) not in traced)

    for scope in scopes:
        bound = _launch_bound_names(scope, surfaces)
        for node in ast.walk(scope):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute):
                recv = f.value
                # np.asarray(<launch>(...)) / np.asarray(bound_name)
                if isinstance(recv, ast.Name) and recv.id in np_aliases \
                        and f.attr in _NP_READBACK_CALLS and node.args:
                    arg = node.args[0]
                    hit = None
                    if isinstance(arg, ast.Call) \
                            and _call_func_name(arg.func) in surfaces:
                        hit = _call_func_name(arg.func)
                    elif isinstance(arg, ast.Name) and arg.id in bound:
                        hit = arg.id
                    if hit is not None:
                        vs.append(Violation(
                            "ESTPU-RB01", mod.rel, node.lineno,
                            node.col_offset,
                            f"untracked readback np.{f.attr}({hit}"
                            f"{'(...)' if isinstance(arg, ast.Call) else ''}"
                            f") — use ops.device.readback(site, ...) so "
                            f"the flight recorder sees it"))
                # jax.device_get(...) — explicit transfer
                elif isinstance(recv, ast.Name) \
                        and recv.id in jax_aliases \
                        and f.attr == "device_get":
                    vs.append(Violation(
                        "ESTPU-RB02", mod.rel, node.lineno,
                        node.col_offset,
                        "jax.device_get outside the readback funnel — "
                        "use ops.device.readback(site, ...)"))
                # x.block_until_ready() — a device sync by definition
                elif f.attr == "block_until_ready":
                    vs.append(Violation(
                        "ESTPU-RB02", mod.rel, node.lineno,
                        node.col_offset,
                        ".block_until_ready() outside the readback "
                        "funnel — use ops.device.readback(site, ...) "
                        "(or bench-only code outside the engine dirs)"))


def run(modules: List[LintModule],
        index: ProjectIndex) -> Tuple[List[Violation], int]:
    vs: List[Violation] = []
    allowlisted = 0
    for mod in modules:
        if not mod.rel.startswith(ENGINE_DIRS):
            continue
        if mod.rel == FUNNEL_MODULE:
            continue
        found: List[Violation] = []
        _check_module(mod, index, found)
        for v in found:
            if _allowlisted(mod, v):
                allowlisted += 1
            else:
                vs.append(v)
    return vs, allowlisted
