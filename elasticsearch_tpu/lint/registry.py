"""Cross-module facts the rules share: which functions are traced
(jit/shard_map), which ops/ kernels exist and under what names, the
KERNEL_ATTRIBUTION key set, and the typed-error hierarchy.

Everything here is STATIC — derived from the AST, never from imports —
so the linter runs offline with no jax (and flags code that would not
even import). ``tests/test_lint.py`` pins the static kernel extraction
against the runtime ``pkgutil`` discovery the PR-8 drift guard used,
so the two views cannot drift silently.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Set, Tuple

from elasticsearch_tpu.lint.core import LintModule, package_root

__all__ = ["ProjectIndex", "build_index"]

# decorator spellings that make a function body TRACED: its statements
# execute at trace time, where host-impure operations are contract
# violations (ESTPU-JIT02)
_TRACING_WRAPPERS = ("tracked_jit", "jit", "shard_map", "pjit")


def _call_func_name(node: ast.AST) -> Optional[str]:
    """Terminal name of a decorator/callee expression: ``tracked_jit``,
    ``jax.jit``, ``partial(jax.jit, ...)`` all resolve to their
    wrapper's last attribute."""
    if isinstance(node, ast.Call):
        fname = _call_func_name(node.func)
        if fname == "partial" and node.args:
            return _call_func_name(node.args[0])
        return fname
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def is_tracing_decorator(dec: ast.AST) -> bool:
    return _call_func_name(dec) in _TRACING_WRAPPERS


def is_bare_jax_jit(node: ast.AST) -> bool:
    """``jax.jit`` / ``partial(jax.jit, ...)`` / bare ``jit`` imported
    from jax — the UNTRACKED spellings ESTPU-JIT01 forbids in the
    engine dirs (``telemetry.engine.tracked_jit`` is the tracked one)."""
    if isinstance(node, ast.Call):
        if _call_func_name(node.func) == "partial" and node.args:
            return is_bare_jax_jit(node.args[0])
        return is_bare_jax_jit(node.func)
    if isinstance(node, ast.Attribute):
        return node.attr == "jit" and isinstance(node.value, ast.Name) \
            and node.value.id in ("jax",)
    return False


def _kernel_name_from_call(call: ast.Call,
                           fn_name: str) -> Optional[str]:
    """tracked_jit's kernel name: the first positional string arg, else
    the wrapped function's name with leading underscores stripped
    (mirrors ``tracked_jit``'s own ``name or fn.__name__.lstrip('_')``)."""
    for a in call.args:
        if isinstance(a, ast.Constant) and isinstance(a.value, str):
            return a.value
    return fn_name.lstrip("_")


class ProjectIndex:
    """Static facts over one scan root (plus real-package fallbacks for
    fixture corpora that do not carry their own profile.py/errors.py)."""

    def __init__(self) -> None:
        # FunctionDef nodes whose bodies run under trace, per module rel
        self.traced_functions: Dict[str, List[ast.FunctionDef]] = {}
        # ops/ kernel name -> (rel, line of the defining statement)
        self.ops_kernels: Dict[str, Tuple[str, int]] = {}
        # every statically-derived tracked_jit kernel name (all dirs)
        self.all_kernels: Dict[str, Tuple[str, int]] = {}
        # KERNEL_ATTRIBUTION key set (search/profile.py)
        self.attribution_keys: Set[str] = set()
        self.attribution_source: Optional[str] = None
        # names that launch device kernels when called (jitted entry
        # points + the ops/ host wrappers that call one directly)
        self.launch_surfaces: Set[str] = set()
        # exception classes reachable from ElasticsearchTpuException
        self.hierarchy: Set[str] = set()

    # -- construction -----------------------------------------------------

    def scan_module(self, mod: LintModule) -> None:
        traced: List[ast.FunctionDef] = []
        jitted_names: Set[str] = set()

        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if is_tracing_decorator(dec):
                        traced.append(node)
                        jitted_names.add(node.name)
                        if _call_func_name(dec) == "tracked_jit":
                            kname = (_kernel_name_from_call(dec, node.name)
                                     if isinstance(dec, ast.Call)
                                     else node.name.lstrip("_"))
                            self._record_kernel(kname, mod.rel,
                                                node.lineno)
                        break
            elif isinstance(node, ast.Assign):
                # call form: `_impl = tracked_jit("name", ...)(body_fn)`
                v = node.value
                if isinstance(v, ast.Call) and isinstance(v.func, ast.Call) \
                        and _call_func_name(v.func.func) == "tracked_jit":
                    kname = _kernel_name_from_call(
                        v.func, _assign_name(node) or "")
                    if kname:
                        self._record_kernel(kname, mod.rel, node.lineno)
                    tgt = _assign_name(node)
                    if tgt:
                        jitted_names.add(tgt)
                    for a in v.args:      # the wrapped body function
                        if isinstance(a, ast.Name):
                            fn = _find_function(mod.tree, a.id)
                            if fn is not None:
                                traced.append(fn)
                elif isinstance(v, ast.Call) and is_bare_jax_jit(v):
                    tgt = _assign_name(node)
                    if tgt:
                        jitted_names.add(tgt)
                    for a in v.args:
                        if isinstance(a, ast.Name):
                            fn = _find_function(mod.tree, a.id)
                            if fn is not None:
                                traced.append(fn)

        if traced:
            self.traced_functions[mod.rel] = traced
        if jitted_names:
            self.launch_surfaces |= jitted_names
            if mod.rel.startswith("ops/"):
                # host wrappers that call a jitted entry directly are
                # launch surfaces too (search/ calls plan_topk, not
                # _plan_topk_impl)
                for node in mod.tree.body:
                    if isinstance(node, ast.FunctionDef) \
                            and node.name not in jitted_names:
                        for sub in ast.walk(node):
                            if isinstance(sub, ast.Call):
                                n = _call_func_name(sub.func)
                                if n in jitted_names:
                                    self.launch_surfaces.add(node.name)
                                    break

        if mod.rel == "search/profile.py":
            self._scan_attribution(mod)

    def _record_kernel(self, kname: str, rel: str, line: int) -> None:
        self.all_kernels.setdefault(kname, (rel, line))
        if rel.startswith("ops/"):
            self.ops_kernels.setdefault(kname, (rel, line))

    def _scan_attribution(self, mod: LintModule) -> None:
        for node in mod.tree.body:
            # plain or annotated assignment (`X: Dict[str, str] = {..}`)
            if isinstance(node, (ast.Assign, ast.AnnAssign)) \
                    and _target_name(node) == "KERNEL_ATTRIBUTION" \
                    and isinstance(node.value, ast.Dict):
                for k in node.value.keys:
                    if isinstance(k, ast.Constant) \
                            and isinstance(k.value, str):
                        self.attribution_keys.add(k.value)
                self.attribution_source = mod.rel

    def build_hierarchy(self, modules: List[LintModule],
                       extra_bases: Dict[str, List[str]]) -> None:
        """Transitive by-name subclass closure of
        ElasticsearchTpuException across every scanned module (plus the
        real package's classes, for fixture corpora)."""
        bases: Dict[str, List[str]] = dict(extra_bases)
        for mod in modules:
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.ClassDef):
                    bases.setdefault(node.name, []).extend(
                        b.attr if isinstance(b, ast.Attribute) else b.id
                        for b in node.bases
                        if isinstance(b, (ast.Name, ast.Attribute)))
        known = {"ElasticsearchTpuException"}
        changed = True
        while changed:
            changed = False
            for cls, bs in bases.items():
                if cls not in known and any(b in known for b in bs):
                    known.add(cls)
                    changed = True
        self.hierarchy = known


def _assign_name(node: ast.Assign) -> Optional[str]:
    if len(node.targets) == 1 and isinstance(node.targets[0], ast.Name):
        return node.targets[0].id
    return None


def _target_name(node: ast.stmt) -> Optional[str]:
    if isinstance(node, ast.Assign):
        return _assign_name(node)
    if isinstance(node, ast.AnnAssign) and isinstance(node.target,
                                                      ast.Name):
        return node.target.id
    return None


def _find_function(tree: ast.Module,
                   name: str) -> Optional[ast.FunctionDef]:
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == name:
            return node
    return None


def _real_package_module(rel: str) -> Optional[LintModule]:
    path = os.path.join(package_root(), rel)
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as fh:
        return LintModule(path, rel, fh.read())


def build_index(modules: List[LintModule]) -> ProjectIndex:
    idx = ProjectIndex()
    rels = {m.rel for m in modules}
    for mod in modules:
        idx.scan_module(mod)

    # fixture corpora fall back to the REAL package's attribution table
    # and error hierarchy when they don't ship their own
    if idx.attribution_source is None \
            and "search/profile.py" not in rels:
        real = _real_package_module("search/profile.py")
        if real is not None:
            idx._scan_attribution(real)

    extra_bases: Dict[str, List[str]] = {}
    if "common/errors.py" not in rels:
        real = _real_package_module("common/errors.py")
        if real is not None:
            for node in ast.walk(real.tree):
                if isinstance(node, ast.ClassDef):
                    extra_bases.setdefault(node.name, []).extend(
                        b.id for b in node.bases
                        if isinstance(b, ast.Name))
    idx.build_hierarchy(modules, extra_bases)
    return idx
