"""Shared analysis core: module walker, suppressions, call graph,
jit/shard reachability.

Every pass operates on a :class:`Project` — the parsed ASTs of every
``.py`` file under the analyzed roots, with

  * a per-module symbol table (functions incl. nested/methods, classes,
    import aliases),
  * a project-wide call graph (name-resolved where possible, with a
    conservative by-method-name fallback for attribute calls so
    dynamically-dispatched twins like ``bounder.interval_batch_device``
    still get edges),
  * the *traced* closure: functions reachable from jit entry points
    (``jax.jit`` / ``functools.partial(jax.jit, ...)`` decorations,
    ``lax.while_loop`` / ``lax.cond`` / ``lax.scan`` bodies,
    ``pallas_call`` kernels, ``shard_map``-wrapped callables, and
    closures passed via ``*_fn`` / ``*_fns`` / ``*_src`` callback
    parameters — the repo's traced-callback convention),
  * the *sharded* closure: functions reachable from ``shard_map``
    callables only (collectives must stay inside it).

The analysis is intentionally static and conservative: it never imports
the analyzed code.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import re
import tokenize
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Set, Tuple

# --------------------------------------------------------------------------
# Findings & suppressions
# --------------------------------------------------------------------------

#: ``# aqplint: disable=AQP101(reason), AQP302(other reason)``
_SUPPRESS_RE = re.compile(r"#\s*aqplint:\s*disable=(.+?)\s*$")
_ENTRY_RE = re.compile(r"(AQP\d{3}|AQP0\d{2})\s*(?:\(([^()]*)\))?")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One violation: a code, a location and a message."""

    code: str
    path: str       # repo-relative posix path
    line: int
    col: int
    symbol: str     # dotted function/class context ("" at module level)
    message: str

    def key(self) -> Tuple[str, str, str]:
        """Baseline identity — line numbers excluded so unrelated edits
        above a baselined finding do not un-baseline it."""
        return (self.code, self.path, self.symbol)

    def render(self) -> str:
        where = f"{self.path}:{self.line}:{self.col}"
        sym = f" [{self.symbol}]" if self.symbol else ""
        return f"{where}: {self.code}{sym} {self.message}"


@dataclasses.dataclass
class Suppression:
    line: int       # line the suppression applies to
    code: str
    reason: str
    comment_line: int
    used: bool = False


def parse_suppressions(source: str) -> List[Suppression]:
    """Parse inline ``# aqplint: disable=CODE(reason)`` comments.

    Only real COMMENT tokens count (the marker inside a string literal —
    e.g. a fixture snippet in a test — is ignored). A suppression on a
    code line applies to that line; one on a comment-only line applies
    to the next line. Reasons are mandatory — a missing/empty reason is
    reported by the driver as AQP001 rather than honoured.
    """
    out: List[Suppression] = []
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(source).readline))
    except (tokenize.TokenError, IndentationError):
        return out
    lines = source.splitlines()
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = _SUPPRESS_RE.search(tok.string)
        if not m:
            continue
        i = tok.start[0]
        before = lines[i - 1][: tok.start[1]].strip() if i <= len(lines) else ""
        target = i if before else i + 1
        for code, reason in _ENTRY_RE.findall(m.group(1)):
            out.append(Suppression(line=target, code=code,
                                   reason=(reason or "").strip(),
                                   comment_line=i))
    return out


# --------------------------------------------------------------------------
# Module model
# --------------------------------------------------------------------------


@dataclasses.dataclass
class FunctionInfo:
    """One ``def`` — module-level, method, or nested closure."""

    module: "Module"
    qualname: str                  # e.g. "Bounder.lbound_batch", "f.inner"
    node: ast.AST                  # FunctionDef / AsyncFunctionDef / Lambda
    params: Tuple[str, ...]
    lineno: int
    parent_class: Optional[str]    # immediate enclosing class name
    static_params: Tuple[str, ...] = ()   # from jit static_argnames
    annotations: Dict[str, str] = dataclasses.field(default_factory=dict)
    is_jit_root: bool = False
    is_shard_root: bool = False
    #: local names assigned a function value (``loop_body = a if c else b``)
    aliases: Dict[str, List["FunctionInfo"]] = dataclasses.field(
        default_factory=dict)

    @property
    def name(self) -> str:
        return self.qualname.rsplit(".", 1)[-1]

    @property
    def fid(self) -> str:
        return f"{self.module.name}:{self.qualname}"


@dataclasses.dataclass
class ClassInfo:
    module: "Module"
    name: str
    node: ast.ClassDef
    bases: Tuple[str, ...]         # textual base names ("Bounder", ...)
    methods: Dict[str, FunctionInfo]


class Module:
    """One parsed source file with its symbol table."""

    def __init__(self, path: Path, root: Path, repo_root: Path):
        self.path = path
        self.relpath = path.relative_to(repo_root).as_posix()
        self.name = _module_name(path, root)
        self.source = path.read_text()
        self.source_lines = self.source.splitlines()
        self.tree = ast.parse(self.source, filename=str(path))
        self.suppressions = parse_suppressions(self.source)
        self.functions: Dict[str, FunctionInfo] = {}
        self.classes: Dict[str, ClassInfo] = {}
        self.imports: Dict[str, str] = {}     # local alias -> dotted target
        self._index()

    # -- symbol table --------------------------------------------------------

    def _index(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.imports[a.asname or a.name.split(".")[0]] = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.imports[a.asname or a.name] = (
                        f"{node.module}.{a.name}")
        self._index_scope(self.tree.body, prefix="", parent_class=None)

    def _index_scope(self, body, prefix: str,
                     parent_class: Optional[str]) -> None:
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{node.name}"
                args = (node.args.posonlyargs + node.args.args
                        + node.args.kwonlyargs)
                info = FunctionInfo(
                    module=self, qualname=qual, node=node,
                    params=tuple(a.arg for a in args),
                    lineno=node.lineno, parent_class=parent_class,
                    static_params=_jit_static_params(node, self.imports),
                    annotations={a.arg: _ann_leaf(a.annotation)
                                 for a in args if a.annotation is not None},
                    is_jit_root=_is_jit_decorated(node, self.imports))
                self.functions[qual] = info
                self._index_scope(node.body, prefix=f"{qual}.",
                                  parent_class=None)
            elif isinstance(node, ast.ClassDef):
                qual = f"{prefix}{node.name}"
                self._index_scope(node.body, prefix=f"{qual}.",
                                  parent_class=node.name)
                methods = {
                    f.name: f for f in self.functions.values()
                    if f.qualname.startswith(f"{qual}.")
                    and "." not in f.qualname[len(qual) + 1:]}
                self.classes[node.name] = ClassInfo(
                    module=self, name=node.name, node=node,
                    bases=tuple(_base_name(b) for b in node.bases),
                    methods=methods)
            elif isinstance(node, (ast.If, ast.Try, ast.With)):
                inner = list(getattr(node, "body", []))
                for attr in ("orelse", "finalbody"):
                    inner.extend(getattr(node, attr, []))
                for h in getattr(node, "handlers", []):
                    inner.extend(h.body)
                self._index_scope(inner, prefix=prefix,
                                  parent_class=parent_class)

    # -- name resolution -----------------------------------------------------

    def resolve_call_name(self, func: ast.AST) -> Optional[str]:
        """Best-effort dotted name of a call target: ``jnp.nonzero`` with
        ``import jax.numpy as jnp`` -> ``jax.numpy.nonzero``."""
        parts: List[str] = []
        node = func
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        parts.append(node.id)
        parts.reverse()
        head = self.imports.get(parts[0], parts[0])
        return ".".join([head] + parts[1:])

    def enclosing_function(self, lineno: int) -> str:
        """Innermost function qualname containing ``lineno`` ("" if
        module level)."""
        best, best_span = "", None
        for f in self.functions.values():
            end = getattr(f.node, "end_lineno", f.lineno)
            if f.lineno <= lineno <= end:
                span = end - f.lineno
                if best_span is None or span <= best_span:
                    best, best_span = f.qualname, span
        return best


def _module_name(path: Path, root: Path) -> str:
    rel = path.relative_to(root).with_suffix("")
    parts = list(rel.parts)
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts) if parts else root.name


def _base_name(node: ast.AST) -> str:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return ""


def _ann_leaf(node: ast.AST) -> str:
    """Textual leaf of an annotation: ``DevStatsBatch``,
    ``state.StatsBatch`` -> ``StatsBatch``, ``"StatsBatch"`` (string
    forward ref) -> ``StatsBatch``."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value.rsplit(".", 1)[-1].strip('"')
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Subscript):      # Optional[X] -> X (best effort)
        return _ann_leaf(node.slice)
    return ""


# -- jit decoration ---------------------------------------------------------

_JIT_NAMES = {"jax.jit", "jit", "jax.pjit", "pjit"}


def _call_name_with(imports: Dict[str, str], func: ast.AST) -> Optional[str]:
    parts: List[str] = []
    node = func
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    parts.reverse()
    head = imports.get(parts[0], parts[0])
    return ".".join([head] + parts[1:])


def _is_jit_name(name: Optional[str]) -> bool:
    return name in _JIT_NAMES


def _is_jit_decorated(node, imports) -> bool:
    for dec in getattr(node, "decorator_list", []):
        if _is_jit_name(_call_name_with(imports, dec)):
            return True
        if isinstance(dec, ast.Call):
            name = _call_name_with(imports, dec.func)
            if _is_jit_name(name):
                return True
            if name in ("functools.partial", "partial") and dec.args:
                if _is_jit_name(_call_name_with(imports, dec.args[0])):
                    return True
    return False


def _jit_static_params(node, imports) -> Tuple[str, ...]:
    """static_argnames / static_argnums declared on a jit decoration."""
    for dec in getattr(node, "decorator_list", []):
        if not isinstance(dec, ast.Call):
            continue
        name = _call_name_with(imports, dec.func)
        inner_jit = (name in ("functools.partial", "partial") and dec.args
                     and _is_jit_name(_call_name_with(imports, dec.args[0])))
        if not (_is_jit_name(name) or inner_jit):
            continue
        statics: List[str] = []
        params = [a.arg for a in (node.args.posonlyargs + node.args.args
                                  + node.args.kwonlyargs)]
        for kw in dec.keywords:
            if kw.arg == "static_argnames":
                statics.extend(_str_elements(kw.value))
            elif kw.arg == "static_argnums":
                for idx in _int_elements(kw.value):
                    if 0 <= idx < len(params):
                        statics.append(params[idx])
        return tuple(statics)
    return ()


def _str_elements(node: ast.AST) -> List[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
        return out
    return []


def _int_elements(node: ast.AST) -> List[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, int)]
    return []


# --------------------------------------------------------------------------
# Project: modules + call graph + traced/sharded closures
# --------------------------------------------------------------------------

#: callables whose function-valued arguments are traced entry points
_TRACING_CALLEES = {
    "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.cond", "lax.cond",
    "jax.lax.scan", "lax.scan",
    "jax.lax.fori_loop", "lax.fori_loop",
    "jax.lax.map", "lax.map",
    "jax.jit", "jit", "jax.pjit",
    "jax.vmap", "jax.pmap",
    "jax.checkpoint", "jax.remat",
    "jax.experimental.pallas.pallas_call", "pallas.pallas_call",
    "pl.pallas_call", "pallas_call",
}
_SHARD_CALLEES = {"jax.shard_map", "shard_map"}
#: closures passed under these parameter-name patterns are traced by
#: convention (the engine hands CI-refresh closures to the loop builders)
_CALLBACK_PARAM_RE = re.compile(r"(_fn|_fns|_src)$")

#: attribute-call fallback resolution skips nothing by default; names
#: here would be too ubiquitous to resolve by method name alone
_FALLBACK_SKIP = {"get", "put", "copy", "items", "keys", "values",
                  "append", "extend", "pop", "add", "join", "split",
                  "update", "replace", "_replace", "format", "read",
                  "write", "sum", "any", "all", "min", "max", "mean",
                  "reshape", "astype", "flatten"}


class Project:
    """All modules under the analyzed roots + the project call graph."""

    def __init__(self, roots: Iterable[Path], repo_root: Path):
        self.repo_root = repo_root
        self.modules: Dict[str, Module] = {}
        for root in roots:
            root = root.resolve()
            files = [root] if root.is_file() else sorted(
                p for p in root.rglob("*.py")
                if "__pycache__" not in p.parts)
            base = root.parent if root.is_file() else root
            for f in files:
                try:
                    mod = Module(f, base, repo_root)
                except SyntaxError:
                    continue
                self.modules[mod.name] = mod
        # symbol indexes
        self.functions: Dict[str, FunctionInfo] = {}
        self.by_name: Dict[str, List[FunctionInfo]] = {}
        for mod in self.modules.values():
            for f in mod.functions.values():
                self.functions[f.fid] = f
                self.by_name.setdefault(f.name, []).append(f)
        self._build_graph()
        self.traced: Set[str] = self._closure(
            {f.fid for f in self.functions.values() if f.is_jit_root})
        self.sharded: Set[str] = self._closure(
            {f.fid for f in self.functions.values() if f.is_shard_root})

    # -- call graph ----------------------------------------------------------

    def _build_graph(self) -> None:
        self.calls: Dict[str, Set[str]] = {fid: set()
                                           for fid in self.functions}
        # pass 0: local function aliases (loop_body = cadence_body if
        # cadence else body) so closures picked by a conditional still
        # resolve when later passed to while_loop/shard_map
        for mod in self.modules.values():
            for f in mod.functions.values():
                self._collect_aliases(mod, f)
        for mod in self.modules.values():
            for f in mod.functions.values():
                self._scan_function(mod, f)

    def _collect_aliases(self, mod: Module, f: FunctionInfo) -> None:
        for node in ast.walk(f.node):
            if not isinstance(node, ast.Assign):
                continue
            if mod.enclosing_function(node.lineno) != f.qualname:
                continue
            if len(node.targets) != 1 or not isinstance(
                    node.targets[0], ast.Name):
                continue
            values = self._function_values(mod, f, node.value)
            if values:
                f.aliases[node.targets[0].id] = values

    def _alias_lookup(self, mod: Module, f: FunctionInfo,
                      name: str) -> List[FunctionInfo]:
        """Alias defined in ``f`` or any lexically enclosing function."""
        parts = f.qualname.split(".")
        for i in range(len(parts), 0, -1):
            anc = mod.functions.get(".".join(parts[:i]))
            if anc is not None and name in anc.aliases:
                return anc.aliases[name]
        return []

    def _scan_function(self, mod: Module, f: FunctionInfo) -> None:
        for node in ast.walk(f.node):
            if not isinstance(node, ast.Call):
                continue
            if mod.enclosing_function(node.lineno) != f.qualname:
                continue  # belongs to a nested def, scanned separately
            callee = mod.resolve_call_name(node.func)
            targets = self._resolve_targets(mod, f, node, callee)
            self.calls[f.fid].update(t.fid for t in targets)
            self._mark_roots(mod, f, node, callee)

    def _resolve_targets(self, mod: Module, f: FunctionInfo,
                         node: ast.Call,
                         callee: Optional[str]) -> List[FunctionInfo]:
        func = node.func
        # 1. plain / dotted name resolving inside the project
        if callee:
            hits = self._lookup_dotted(mod, f, callee)
            if hits:
                return hits
        if not isinstance(func, ast.Attribute):
            return []
        name = func.attr
        recv = func.value
        # 2a. typed receiver: s.reflect() with `s: DevStatsBatch` in the
        #     signature resolves to exactly that class's method — this
        #     keeps host/device twins with the same method name apart
        if isinstance(recv, ast.Name):
            ann = f.annotations.get(recv.id, "")
            cls = self._find_class(ann)
            if cls is not None:
                m = self._method_on(cls, name)
                return [m] if m is not None else []
            # self.method() resolves within the class and its subclasses
            if recv.id == "self" and f.parent_class:
                own = self._find_class(f.parent_class)
                if own is not None:
                    hits = []
                    for c in [own] + self.subclasses_of({own.name}):
                        m = c.methods.get(name)
                        if m is not None:
                            hits.append(m)
                    if hits:
                        return hits
        # 2b. external-module call (jnp.round, np.clip): the chain root
        #     is an import alias and project resolution already failed —
        #     never fall back by bare method name
        root = recv
        while isinstance(root, ast.Attribute):
            root = root.value
        if isinstance(root, ast.Name) and root.id in mod.imports:
            return []
        # 2c. attribute fallback: x.method(...) -> every project def
        #     named `method` (conservative over-approximation for
        #     dynamic dispatch: bounder.interval_batch_device)
        if name not in _FALLBACK_SKIP and name in self.by_name:
            return self.by_name[name]
        return []

    def _find_class(self, name: str) -> Optional[ClassInfo]:
        if not name:
            return None
        for mod in self.modules.values():
            if name in mod.classes:
                return mod.classes[name]
        return None

    def _method_on(self, cls: ClassInfo,
                   name: str) -> Optional[FunctionInfo]:
        """Method looked up on ``cls`` or (textually) up its base chain."""
        seen = set()
        frontier = [cls]
        while frontier:
            c = frontier.pop()
            if c.name in seen:
                continue
            seen.add(c.name)
            if name in c.methods:
                return c.methods[name]
            for b in c.bases:
                parent = self._find_class(b)
                if parent is not None:
                    frontier.append(parent)
        return None

    def _lookup_dotted(self, mod: Module, f: FunctionInfo,
                       dotted: str) -> List[FunctionInfo]:
        parts = dotted.split(".")
        leaf = parts[-1]
        # nested sibling or own-module function (innermost scope first)
        if len(parts) == 1:
            prefix = f.qualname
            while True:
                cand = f"{prefix}.{leaf}" if prefix else leaf
                if cand in mod.functions:
                    return [mod.functions[cand]]
                if "." not in prefix:
                    break
                prefix = prefix.rsplit(".", 1)[0]
            if leaf in mod.functions:
                return [mod.functions[leaf]]
            # imported plain name: "from x import f"
            tgt = mod.imports.get(leaf)
            if tgt:
                return self._lookup_qualified(tgt)
            return []
        return self._lookup_qualified(dotted)

    def _lookup_qualified(self, dotted: str) -> List[FunctionInfo]:
        """repro.kernels.ops.grouped_sums -> FunctionInfo, including
        Class.method targets and package-qualified module names."""
        parts = dotted.split(".")
        for split in range(len(parts) - 1, 0, -1):
            mod_name = ".".join(parts[:split])
            rest = ".".join(parts[split:])
            for cand_mod, mod in self.modules.items():
                if cand_mod == mod_name or cand_mod.endswith(
                        "." + mod_name) or mod_name.endswith(
                        "." + cand_mod):
                    if rest in mod.functions:
                        return [mod.functions[rest]]
                    # Class attribute: Class.method
                    if rest in mod.classes:
                        return []
        return []

    # -- traced / sharded roots ---------------------------------------------

    def _mark_roots(self, mod: Module, f: FunctionInfo, node: ast.Call,
                    callee: Optional[str]) -> None:
        leaf = callee.rsplit(".", 1)[-1] if callee else ""
        is_tracer = (callee in _TRACING_CALLEES
                     or leaf in ("pallas_call",))
        is_shard = callee in _SHARD_CALLEES or leaf == "shard_map"
        if is_tracer or is_shard:
            for arg in list(node.args) + [k.value for k in node.keywords]:
                for target in self._function_values(mod, f, arg):
                    if is_shard:
                        target.is_shard_root = True
                    target.is_jit_root = True
                    # the callback executes within the caller's trace, so
                    # it is also a call edge (shard reachability needs it)
                    self.calls[f.fid].add(target.fid)
            return
        # traced-callback convention: f(..., refresh_fn=g) / build(g)
        # where the receiving parameter matches _fn/_fns/_src
        resolved = self._resolve_targets(mod, f, node, callee)
        param_map: Dict[int, str] = {}
        target_info = resolved[0] if len(resolved) == 1 else None
        if target_info is not None:
            params = [p for p in target_info.params if p != "self"]
            param_map = dict(enumerate(params))
        for i, arg in enumerate(node.args):
            pname = param_map.get(i, "")
            if _CALLBACK_PARAM_RE.search(pname):
                for t in self._function_values(mod, f, arg):
                    t.is_jit_root = True
                    self.calls[f.fid].add(t.fid)
        for kw in node.keywords:
            if kw.arg and _CALLBACK_PARAM_RE.search(kw.arg):
                for t in self._function_values(mod, f, kw.value):
                    t.is_jit_root = True
                    self.calls[f.fid].add(t.fid)

    def _function_values(self, mod: Module, f: FunctionInfo,
                         expr: ast.AST) -> List[FunctionInfo]:
        """Function objects an argument expression may denote: a plain
        name, a ``functools.partial(name, ...)`` wrap, or a nested-def
        reference. Tuples/lists are walked elementwise."""
        out: List[FunctionInfo] = []
        if isinstance(expr, (ast.Tuple, ast.List)):
            for e in expr.elts:
                out.extend(self._function_values(mod, f, e))
            return out
        if isinstance(expr, ast.IfExp):
            return (self._function_values(mod, f, expr.body)
                    + self._function_values(mod, f, expr.orelse))
        if isinstance(expr, ast.Call):
            name = mod.resolve_call_name(expr.func)
            if name in ("functools.partial", "partial") and expr.args:
                return self._function_values(mod, f, expr.args[0])
            return out
        if isinstance(expr, ast.Name):
            aliased = self._alias_lookup(mod, f, expr.id)
            if aliased:
                return aliased
            return self._lookup_dotted(mod, f, expr.id)
        if isinstance(expr, ast.Attribute):
            dotted = mod.resolve_call_name(expr)
            if dotted:
                return self._lookup_dotted(mod, f, dotted)
        return out

    def _closure(self, roots: Set[str]) -> Set[str]:
        seen = set(roots)
        frontier = list(roots)
        while frontier:
            fid = frontier.pop()
            for nxt in self.calls.get(fid, ()):
                if nxt not in seen:
                    seen.add(nxt)
                    frontier.append(nxt)
        # by-name convention: nested closures named like traced callbacks
        # (refresh_fn, flags_src) are traced even when only constructed
        for f in self.functions.values():
            if (_CALLBACK_PARAM_RE.search(f.name)
                    and f.fid not in seen):
                seen.add(f.fid)
                frontier.append(f.fid)
        while frontier:
            fid = frontier.pop()
            for nxt in self.calls.get(fid, ()):
                if nxt not in seen:
                    seen.add(nxt)
                    frontier.append(nxt)
        return seen

    # -- class hierarchy helpers --------------------------------------------

    def subclasses_of(self, base_names: Set[str]) -> List[ClassInfo]:
        """Classes whose (textual, transitively expanded) base chain hits
        one of ``base_names``."""
        out = []
        # iterate to a fixed point over textual base names
        matches: Set[str] = set(base_names)
        changed = True
        all_classes = [c for m in self.modules.values()
                       for c in m.classes.values()]
        while changed:
            changed = False
            for c in all_classes:
                if c.name in matches:
                    continue
                if any(b in matches for b in c.bases):
                    matches.add(c.name)
                    changed = True
        for c in all_classes:
            if c.name in matches and c.name not in base_names:
                out.append(c)
        return out

    def is_traced(self, mod: Module, qualname: str) -> bool:
        return f"{mod.name}:{qualname}" in self.traced

    def is_sharded(self, mod: Module, qualname: str) -> bool:
        return f"{mod.name}:{qualname}" in self.sharded
