"""AQP5xx — static-shape / retrace hygiene.

XLA compiles one executable per distinct input-shape signature. A
data-dependent output shape (``jnp.nonzero`` without ``size=``) either
errors under jit or — when the call sits just outside the jit boundary
— quietly forces a retrace per distinct selection count, which is
exactly the per-round retrace storm PR 3's static-shape padding fixed.
Slicing with a traced bound fails at trace time; a non-hashable static
arg raises on every call. All three are cheap to catch in the AST.

AQP501 — shape-producing call (``jnp.nonzero`` / ``flatnonzero`` /
  ``argwhere`` / ``unique``, or 1-arg ``jnp.where``) without ``size=``
  in jit-traced code.
AQP502 — slice bound that is a traced function parameter in jit-traced
  code (``x[:n]`` where ``n`` is a non-static param — use
  ``lax.dynamic_slice`` or a mask instead).
AQP503 — non-hashable literal (list/dict/set) passed to a declared
  ``static_argnames`` parameter of a jit-rooted project function.

The dynamic counterpart of this pass is :mod:`aqplint.retrace` — a
pytest helper that counts actual XLA compilations against committed
budgets (``tools/aqplint/retrace_budgets.json``).
"""

from __future__ import annotations

import ast
from typing import List, Optional

from aqplint.core import Finding, Project

_SIZE_REQUIRED = {"nonzero", "flatnonzero", "argwhere", "unique",
                  "unique_values"}


def run(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for mod in project.modules.values():
        for f in mod.functions.values():
            traced = f.fid in project.traced
            for node in ast.walk(f.node):
                if getattr(node, "lineno", None) is None:
                    continue
                if mod.enclosing_function(node.lineno) != f.qualname:
                    continue
                if isinstance(node, ast.Call):
                    if traced:
                        _check_size(mod, f, node, findings)
                    _check_static_args(project, mod, f, node, findings)
                elif traced and isinstance(node, ast.Subscript):
                    _check_slice(mod, f, node, findings)
    return findings


# -- AQP501 ------------------------------------------------------------------


def _check_size(mod, f, node: ast.Call, findings: List[Finding]) -> None:
    dotted = mod.resolve_call_name(node.func)
    if dotted is None or not dotted.startswith("jax."):
        return
    leaf = dotted.rsplit(".", 1)[-1]
    data_dependent = (leaf in _SIZE_REQUIRED
                      or (leaf == "where" and len(node.args) == 1
                          and not node.keywords))
    if not data_dependent:
        return
    if any(kw.arg == "size" for kw in node.keywords):
        return
    findings.append(Finding(
        code="AQP501", path=mod.relpath, line=node.lineno,
        col=node.col_offset, symbol=f.qualname,
        message=(f"data-dependent-shape call `{leaf}` without `size=` "
                 "in jit-traced code — errors under jit, or retraces "
                 "per distinct count at the jit boundary; pass "
                 "size=/fill_value=")))


# -- AQP502 ------------------------------------------------------------------


def _check_slice(mod, f, node: ast.Subscript,
                 findings: List[Finding]) -> None:
    # only at a *declared* jit boundary do we know which params are
    # traced; helpers deeper in the trace often take static Python ints
    # by construction (e.g. _fold_local's num_groups)
    if not f.is_jit_root:
        return
    traced_params = set(f.params) - set(f.static_params) - {"self"}
    slices = []
    sl = node.slice
    if isinstance(sl, ast.Slice):
        slices = [sl]
    elif isinstance(sl, ast.Tuple):
        slices = [e for e in sl.elts if isinstance(e, ast.Slice)]
    for s in slices:
        for bound in (s.lower, s.upper):
            if isinstance(bound, ast.Name) and bound.id in traced_params:
                findings.append(Finding(
                    code="AQP502", path=mod.relpath, line=node.lineno,
                    col=node.col_offset, symbol=f.qualname,
                    message=(f"slice bound `{bound.id}` is a traced "
                             "parameter — shapes must be static under "
                             "jit; use lax.dynamic_slice, a mask, or "
                             "declare it static")))
                return


# -- AQP503 ------------------------------------------------------------------


def _check_static_args(project: Project, mod, f, node: ast.Call,
                       findings: List[Finding]) -> None:
    target = _single_target(project, mod, f, node)
    if target is None or not target.static_params:
        return
    for kw in node.keywords:
        if kw.arg in target.static_params and _non_hashable(kw.value):
            findings.append(Finding(
                code="AQP503", path=mod.relpath, line=node.lineno,
                col=node.col_offset, symbol=f.qualname,
                message=(f"non-hashable literal for static arg "
                         f"`{kw.arg}` of jit-rooted `{target.name}` — "
                         "jit static args must hash; pass a tuple")))


def _single_target(project: Project, mod, f, node: ast.Call):
    dotted = mod.resolve_call_name(node.func)
    if dotted is None:
        return None
    hits = project._lookup_dotted(mod, f, dotted)
    return hits[0] if len(hits) == 1 else None


def _non_hashable(value: ast.AST) -> bool:
    return isinstance(value, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                              ast.DictComp, ast.SetComp))
