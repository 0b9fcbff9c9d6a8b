"""jit-key-drift: process-wide mutable state baked into a trace without
being part of the jit cache key.

The repo's exactness contract for process-wide knobs (ISSUE 13,
generalizing PR 11's env-read special case): anything mutable at process
scope that a step-builder or dispatch-construction body reads — an
``os.environ`` value, a module global flipped through a documented
``set_*`` seam (``_STREAM_CACHE_SHARDING``), or an accessor function
over one — MUST either enter the jit cache key (flipping it then
retraces, the correct behavior) or be resolved to an explicit argument
at the API boundary. Otherwise the value bakes into the compiled step at
trace time and a later flip silently keeps the stale trace — or, when a
caller keys its own cache on it, retraces on every flip. The
construction-time variant: an object snapshots the global into ``self``
at __init__ while what it dispatches follows the LIVE process-wide
setting, and its own accounting of what ran is then wrong.

Shapes:

1. env — ``os.environ`` / ``os.getenv`` read inside a step-builder-named
   or jit-constructing body (moved here from recompile-hazard, PR 11);
2. mutable-global / accessor read inside a jit-CONSTRUCTING top-level
   body (nested defs included — those are the traced bodies) without the
   value flowing into the jit cache key. "Mutable global" means a
   module-scope name some function rebinds via ``global`` (the set_*
   seam shape); an accessor is a project function whose own body loads
   one. The key-flow exemption recognizes the sanctioned pattern: the
   read lands in an assignment to a ``key``-named target, a ``*key*``
   call, or a ``*cache*``/``*key*`` subscript — and once one read of a
   global is keyed in a function, other reads of the SAME global there
   are exempt too (building the key next to using the value is how the
   pattern is written).
3. construction snapshot — ``self.X = <accessor()/global>`` inside
   ``__init__`` outside the global's own module: dispatch-time consumers
   must read the live accessor (the PR 10 fix shape).

Stays stdlib-ast and degrades gracefully: without a ProjectInfo only the
same-module shapes fire.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, Optional, Set

from deeplearning4j_tpu.analysis.core import (
    Finding, ModuleInfo, Rule, SEVERITY_WARNING)
from deeplearning4j_tpu.analysis.rules._common import (
    functions_building_jit, norm_source as _norm)

#: function names that ARE plan-resolution / step-builder seams even
#: when the jit construction lives in a helper they call
STEP_BUILDER_NAME = re.compile(
    r"^(_get_\w*_(step|steps|fn)|resolve_\w+|apply_execution_plan"
    r"|set_fusion\w*)$")

_KEYISH = re.compile(r"key", re.IGNORECASE)
_CACHEISH = re.compile(r"cache|key", re.IGNORECASE)


def _is_env_read(mod: ModuleInfo, node: ast.AST) -> bool:
    if isinstance(node, ast.Call):
        fn = mod.resolve(node.func)
        if fn == "os.getenv":
            return True
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr == "get" \
                and mod.resolve(node.func.value) == "os.environ":
            return True
    if isinstance(node, ast.Subscript) \
            and mod.resolve(node.value) == "os.environ":
        return True
    return False


def _flows_into_key(mod: ModuleInfo, node: ast.AST) -> bool:
    """True when a read's value lands in the jit-cache-key idiom."""
    for anc in mod.ancestors(node):
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return False
        if isinstance(anc, ast.Assign):
            if any(_KEYISH.search(_norm(t)) for t in anc.targets):
                return True
        elif isinstance(anc, ast.AnnAssign):
            if _KEYISH.search(_norm(anc.target)):
                return True
        elif isinstance(anc, ast.Subscript):
            if _CACHEISH.search(_norm(anc.value)):
                return True
        elif isinstance(anc, ast.Call):
            if _KEYISH.search(_norm(anc.func)):
                return True
    return False


class JitKeyDriftRule(Rule):
    id = "jit-key-drift"
    severity = SEVERITY_WARNING
    description = ("process-wide mutable state (os.environ, set_*-seam "
                   "module globals, accessors over them) read inside a "
                   "step-builder/jit-constructing body without entering "
                   "the jit cache key: the trace bakes the value in and "
                   "a later flip keeps the stale compiled step")

    def check(self, mod: ModuleInfo) -> Iterator[Finding]:
        yield from self.check_project(mod, None)

    def check_project(self, mod: ModuleInfo, project) -> Iterator[Finding]:
        yield from self._env_shape(mod)
        yield from self._mutable_read_shape(mod, project)
        yield from self._construction_snapshot_shape(mod, project)

    # -- shape 1: env reads (the PR 11 class, migrated) ----------------
    def _env_shape(self, mod: ModuleInfo) -> Iterator[Finding]:
        env_nodes = [n for n in ast.walk(mod.tree)
                     if _is_env_read(mod, n)]
        if not env_nodes:
            return
        env_by_fn: Dict[int, list] = {}
        fns = []
        for n in env_nodes:
            for fn in mod.enclosing_functions(n):
                if id(fn) not in env_by_fn:
                    fns.append(fn)
                env_by_fn.setdefault(id(fn), []).append(n)
        builders = functions_building_jit(mod)
        seen: Set[int] = set()   # a nested jit-building closure inside a
        # named builder is walked from both functions — one finding per
        # read, not two
        # outermost-first (matches pre-order walk): the named builder
        # claims the read before its nested closure can
        for fn in sorted(fns, key=lambda f: f.lineno):
            named = bool(STEP_BUILDER_NAME.match(fn.name))
            if not (named or fn in builders):
                continue
            for n in sorted(env_by_fn[id(fn)],
                            key=lambda x: getattr(x, "lineno", 0)):
                if id(n) in seen:
                    continue
                seen.add(id(n))
                yield self.finding(
                    mod, n,
                    f"os.environ read inside step-builder "
                    f"'{fn.name}': the value bakes into the trace "
                    f"but is not part of any jit key — flipping it "
                    f"keeps a stale compiled step (or retraces per "
                    f"flip); resolve it to an explicit argument at "
                    f"the API boundary")
                break  # one finding per builder is enough signal

    # -- mutable-global machinery --------------------------------------
    def _local_mutable(self, mod: ModuleInfo) -> Set[str]:
        from deeplearning4j_tpu.analysis.project import (
            module_mutable_globals)
        return module_mutable_globals(mod)

    def _canonical_mutable(self, mod: ModuleInfo, node: ast.AST,
                           project, local: Set[str]) -> Optional[str]:
        """'module.GLOBAL' when `node` loads a mutable module global —
        locally, through an alias, or (with a project) in another
        project module."""
        if not isinstance(node, (ast.Name, ast.Attribute)):
            return None
        if not isinstance(getattr(node, "ctx", None), ast.Load):
            return None
        if isinstance(node, ast.Name) and node.id in local \
                and node.id not in mod.aliases:
            return f"{mod.rel_path}:{node.id}" if project is None else \
                self._own_canonical(mod, project, node.id)
        canonical = mod.resolve(node)
        if canonical is None or project is None:
            return None
        hit = project.split_module_prefix(canonical)
        if hit is None:
            return None
        mod_name, rest = hit
        if rest and "." not in rest \
                and rest in project.mutable_globals(mod_name):
            return f"{mod_name}.{rest}"
        return None

    @staticmethod
    def _own_canonical(mod: ModuleInfo, project, name: str) -> str:
        own = project.by_rel_path.get(mod.rel_path, mod.rel_path)
        return f"{own}.{name}"

    def _accessor_reads(self, project, mod_name: str,
                        qualname: str) -> Set[str]:
        """Canonical mutable globals an accessor function's own body
        loads (depth 1 — accessors are thin by convention)."""
        cache: Dict = getattr(project, "_accessor_reads", None)
        if cache is None:
            cache = {}
            project._accessor_reads = cache
        key = f"{mod_name}:{qualname}"
        if key in cache:
            return cache[key]
        out: Set[str] = set()
        fn = project.lookup_function(mod_name, qualname)
        target_mod = project.modules.get(mod_name)
        if fn is not None and target_mod is not None:
            mut = project.mutable_globals(mod_name)
            for n in ast.walk(fn):
                if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load) \
                        and n.id in mut:
                    out.add(f"{mod_name}.{n.id}")
        cache[key] = out
        return out

    # -- shape 2: reads inside jit-constructing bodies ------------------
    def _mutable_read_shape(self, mod: ModuleInfo,
                            project) -> Iterator[Finding]:
        local = self._local_mutable(mod)
        builders = functions_building_jit(mod)
        for fn in self._top_fns(mod):
            if fn not in builders:
                continue
            # pass 1: globals whose reads are keyed somewhere in fn
            keyed: Set[str] = set()
            reads = []
            for n in ast.walk(fn):
                canon = self._canonical_mutable(mod, n, project, local)
                if canon is not None:
                    if _flows_into_key(mod, n):
                        keyed.add(canon)
                    else:
                        reads.append((n, canon, None))
                    continue
                if isinstance(n, ast.Call) and project is not None:
                    target = project.resolve_call(mod, n)
                    if target is None:
                        continue
                    accessed = self._accessor_reads(project, *target)
                    if not accessed:
                        continue
                    canon = sorted(accessed)[0]
                    if _flows_into_key(mod, n):
                        keyed.add(canon)
                    else:
                        reads.append((n, canon, target[1]))
            for n, canon, accessor in reads:
                if canon in keyed:
                    continue
                if accessor is not None:
                    yield self.finding(
                        mod, n,
                        f"process-wide accessor '{accessor}()' (reads "
                        f"'{canon}') called inside jit-constructing "
                        f"'{fn.name}' without entering the jit cache "
                        f"key: the live value bakes into the trace and "
                        f"a later set_* flip keeps the stale compiled "
                        f"step — add it to the cache key (the "
                        f"_STREAM_CACHE_SHARDING pattern) or take it as "
                        f"an explicit argument")
                else:
                    yield self.finding(
                        mod, n,
                        f"process-wide mutable global '{canon}' read "
                        f"inside jit-constructing '{fn.name}' without "
                        f"entering the jit cache key: the value bakes "
                        f"into the trace and a later set_* flip keeps "
                        f"the stale compiled step — add it to the cache "
                        f"key (the _STREAM_CACHE_SHARDING pattern) or "
                        f"take it as an explicit argument")

    @staticmethod
    def _top_fns(mod: ModuleInfo):
        """Top-level functions and methods (no enclosing function):
        nested builders are walked from their top-level owner so one
        read yields one finding. Memoized per module."""
        return mod.fact("top_level_functions", lambda m: [
            node for node in ast.walk(m.tree)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            and not m.enclosing_functions(node)])

    # -- shape 3: construction-time snapshot (the PR 10 health bug) ----
    def _construction_snapshot_shape(self, mod: ModuleInfo,
                                     project) -> Iterator[Finding]:
        if project is None:
            return
        own_name = project.by_rel_path.get(mod.rel_path)
        local = self._local_mutable(mod)
        for fn in self._top_fns(mod):
            if fn.name != "__init__":
                continue
            for stmt in ast.walk(fn):
                if not isinstance(stmt, ast.Assign):
                    continue
                if not any(isinstance(t, ast.Attribute)
                           and isinstance(t.value, ast.Name)
                           and t.value.id == "self"
                           for t in stmt.targets):
                    continue
                for n in ast.walk(stmt.value):
                    canon = self._canonical_mutable(mod, n, project, local)
                    accessor = None
                    if canon is None and isinstance(n, ast.Call):
                        target = project.resolve_call(mod, n)
                        if target is not None:
                            accessed = self._accessor_reads(project,
                                                            *target)
                            if accessed:
                                canon = sorted(accessed)[0]
                                accessor = target[1]
                    if canon is None:
                        continue
                    # the owning module wiring its own seam is the
                    # documented pattern, not drift
                    if own_name is not None \
                            and canon.startswith(own_name + "."):
                        continue
                    what = f"accessor '{accessor}()'" if accessor \
                        else f"global '{canon}'"
                    yield self.finding(
                        mod, n,
                        f"construction-time snapshot of process-wide "
                        f"{what} stored on self: dispatch-time behavior "
                        f"follows the LIVE setting, which a later "
                        f"set_* call can flip — read the accessor "
                        f"at use time or key the jit cache on it")
                    break
