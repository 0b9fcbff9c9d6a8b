"""ProjectInfo: the whole-program layer under tpulint's interprocedural
rules.

PR 2's engine was strictly per-module: every rule was a pure function
over one `ModuleInfo`, so any defect that crossed a module boundary — a
helper that syncs called from a fit loop two files away, a retried
dispatch re-reading donated buffers, a builder snapshotting a
process-wide flag — was invisible. `ProjectInfo` parses every module
under the scan root ONCE, derives module names from their paths, and
answers the cross-cutting questions rules need:

- which project module a canonical dotted name lives in (longest-prefix
  match over the module table);
- what a name resolves to ACROSS modules, following import-alias and
  re-export chains (``from pkg.sub import helper`` in ``pkg/__init__``
  then ``from pkg import helper`` elsewhere) with a bounded hop count so
  a re-export cycle cannot loop;
- the lazily-built call graph with per-function effect summaries
  (`analysis.callgraph.CallGraph`).

Soundness caveats (documented, deliberate): resolution follows static
names only — dynamic dispatch (``obj.method()`` on a non-``self``
receiver, callables stored in containers, listener protocols) breaks
the chain, so interprocedural findings are under- not over-approximate;
relative imports and ``import *`` are not followed; unparsable modules
are skipped here (the scan itself still reports them as parse-error
findings). Everything stays stdlib-`ast` so the lint lane runs anywhere
the package imports.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from deeplearning4j_tpu.analysis.core import (
    ModuleInfo, iter_python_files)

#: maximum import-alias / re-export hops followed while resolving one
#: name — bounds work on pathological re-export cycles
MAX_RESOLVE_HOPS = 6


def module_name_for(rel_path: str) -> str:
    """Dotted module name for a posix rel path: ``pkg/sub/mod.py`` ->
    ``pkg.sub.mod``; a package ``__init__.py`` names the package."""
    p = rel_path[:-3] if rel_path.endswith(".py") else rel_path
    parts = [s for s in p.split("/") if s]
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


class ProjectInfo:
    """Parsed view of every module under the scan root."""

    def __init__(self, root: str):
        self.root = root
        #: dotted module name -> ModuleInfo
        self.modules: Dict[str, ModuleInfo] = {}
        #: posix rel path -> dotted module name
        self.by_rel_path: Dict[str, str] = {}
        self._callgraph = None

    # -- construction --------------------------------------------------
    @classmethod
    def build(cls, paths: Sequence[str],
              root: Optional[str] = None) -> "ProjectInfo":
        """Parse every .py under `paths` (skipping unparsable files —
        the scan reports those as parse-error findings on its own)."""
        root = root or os.getcwd()
        proj = cls(root)
        for path in iter_python_files(paths):
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            try:
                with open(path, "r", encoding="utf-8") as f:
                    mod = ModuleInfo(path, rel, f.read())
            except (SyntaxError, OSError, UnicodeDecodeError):
                continue
            proj.add_module(mod)
        return proj

    def add_module(self, mod: ModuleInfo) -> None:
        name = module_name_for(mod.rel_path)
        self.modules[name] = mod
        self.by_rel_path[mod.rel_path] = name

    def module_for_path(self, rel_path: str) -> Optional[ModuleInfo]:
        name = self.by_rel_path.get(rel_path)
        return self.modules.get(name) if name else None

    # -- import graph --------------------------------------------------
    def imported_project_modules(self, mod: ModuleInfo) -> Set[str]:
        """Project modules this module's imports resolve under."""
        out: Set[str] = set()
        for canon in mod.aliases.values():
            hit = self.split_module_prefix(canon)
            if hit is not None:
                out.add(hit[0])
        return out

    def import_graph(self) -> Dict[str, Set[str]]:
        return {name: self.imported_project_modules(mod)
                for name, mod in self.modules.items()}

    # -- name resolution -----------------------------------------------
    def split_module_prefix(
            self, canonical: str) -> Optional[Tuple[str, str]]:
        """Longest project-module prefix of a canonical dotted name:
        ``pkg.sub.mod.Class.method`` -> (``pkg.sub.mod``,
        ``Class.method``)."""
        parts = canonical.split(".")
        for i in range(len(parts), 0, -1):
            name = ".".join(parts[:i])
            if name in self.modules:
                return name, ".".join(parts[i:])
        return None

    def resolve_name(self, canonical: str,
                     _hops: int = 0) -> Optional[Tuple[str, str]]:
        """Resolve a canonical dotted name to (module_name, qualname) of
        an actual def/class, following re-export alias chains up to
        MAX_RESOLVE_HOPS. None when the name leaves the project or the
        definition cannot be found statically."""
        if _hops > MAX_RESOLVE_HOPS:
            return None
        hit = self.split_module_prefix(canonical)
        if hit is None:
            return None
        mod_name, qual = hit
        if not qual:
            return mod_name, ""
        mod = self.modules[mod_name]
        if self._find_def(mod, qual) is not None:
            return mod_name, qual
        # re-export: the first segment is an import alias in mod
        head, _, rest = qual.partition(".")
        target = mod.aliases.get(head)
        if target is not None and target != head:
            chained = target + ("." + rest if rest else "")
            return self.resolve_name(chained, _hops + 1)
        return None

    def lookup_function(self, module_name: str,
                        qualname: str) -> Optional[ast.AST]:
        """The FunctionDef/AsyncFunctionDef for module:qualname, walking
        Class.method paths; None when absent or not a function."""
        mod = self.modules.get(module_name)
        if mod is None:
            return None
        node = self._find_def(mod, qualname)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return node
        return None

    @staticmethod
    def _find_def(mod: ModuleInfo, qualname: str) -> Optional[ast.AST]:
        """Walk a dotted qualname through class bodies to its def."""
        scope: List[ast.stmt] = mod.tree.body
        node: Optional[ast.AST] = None
        for part in qualname.split("."):
            node = None
            for stmt in scope:
                if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.ClassDef)) and stmt.name == part:
                    node = stmt
                    break
            if node is None:
                return None
            scope = node.body if isinstance(node, ast.ClassDef) else []
        return node

    def resolve_call(self, mod: ModuleInfo,
                     call: ast.Call) -> Optional[Tuple[str, str]]:
        """(module_name, qualname) for a call's target when it resolves
        to a project function: module-level names / dotted attributes
        through import aliases, and ``self.method(...)`` within the
        enclosing class. None for anything dynamic."""
        func = call.func
        # self.method(...): same-class lookup in the same module
        if isinstance(func, ast.Attribute) \
                and isinstance(func.value, ast.Name) \
                and func.value.id == "self":
            cls = next((a for a in mod.ancestors(call)
                        if isinstance(a, ast.ClassDef)), None)
            if cls is None:
                return None
            for stmt in cls.body:
                if isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) \
                        and stmt.name == func.attr:
                    mod_name = self.by_rel_path.get(mod.rel_path)
                    if mod_name is None:
                        return None
                    return mod_name, f"{cls.name}.{func.attr}"
            return None
        canonical = mod.resolve(func)
        if canonical is None:
            return None
        resolved = self.resolve_name(canonical)
        if resolved is not None and resolved[1]:
            return resolved
        # same-module bare-name call (`helper(x)` with helper defined
        # here): no project-module prefix to strip, look it up directly
        if isinstance(func, ast.Name) and func.id == canonical:
            own = self.by_rel_path.get(mod.rel_path)
            if own is not None and isinstance(
                    self._find_def(mod, canonical),
                    (ast.FunctionDef, ast.AsyncFunctionDef)):
                return own, canonical
        return None

    # -- call graph ----------------------------------------------------
    @property
    def callgraph(self):
        if self._callgraph is None:
            from deeplearning4j_tpu.analysis.callgraph import CallGraph
            self._callgraph = CallGraph(self)
        return self._callgraph

    # -- mutable process-wide state (jit-key-drift support) ------------
    def mutable_globals(self, module_name: str) -> Set[str]:
        """Module-scope names that some function in the module rebinds
        via a ``global`` statement — the set_*-seam shape
        (`set_stream_cache_sharding` rebinding
        `_STREAM_CACHE_SHARDING`). A global only ever bound at
        import time is configuration, not mutable process state."""
        mod = self.modules.get(module_name)
        if mod is None:
            return set()
        return module_mutable_globals(mod)


def module_mutable_globals(mod: ModuleInfo) -> Set[str]:
    """Same as ProjectInfo.mutable_globals for a standalone module.
    Memoized per module."""
    return mod.fact("mutable_globals", _compute_mutable_globals)


def _compute_mutable_globals(mod: ModuleInfo) -> Set[str]:
    bound: Set[str] = set()
    for stmt in mod.tree.body:
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    bound.add(t.id)
        elif isinstance(stmt, ast.AnnAssign) \
                and isinstance(stmt.target, ast.Name):
            bound.add(stmt.target.id)
    written: Set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Global):
            written.update(node.names)
    return bound & written


def iter_functions(mod: ModuleInfo) -> Iterator[Tuple[str, ast.AST]]:
    """(qualname, def-node) for every function in a module, nested defs
    included (``outer.<locals>.inner`` style qualnames)."""

    def walk(scope: List[ast.stmt], prefix: str, in_func: bool):
        for stmt in scope:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{stmt.name}"
                yield qual, stmt
                yield from walk(stmt.body, f"{qual}.<locals>.", True)
            elif isinstance(stmt, ast.ClassDef):
                sep = ".<locals>." if in_func else "."
                yield from walk(stmt.body, f"{prefix}{stmt.name}{sep}"
                                if prefix else f"{stmt.name}.", in_func)

    yield from walk(mod.tree.body, "", False)
