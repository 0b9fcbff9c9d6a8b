"""lock-held-across-dispatch: device work inside a `with <lock>:` block.

The serving/parallel hot paths hand work between threads under
``threading.Lock``s. A jitted dispatch — or worse, a blocking device
sync — made while HOLDING such a lock couples every other waiter to
the device's latency: a stalled TPU call (lost device, preempted core,
a multi-second compile) under the engine lock freezes ``submit()``,
health probes, and metrics scrapes along with it, turning one slow
dispatch into a process-wide stall. The sanctioned shapes are (a)
snapshot state under the lock, dispatch outside it, or (b) a
deliberately single-threaded dispatcher whose lock guards ONLY the
dispatch path while submit/health/metrics read lock-free — the serving
engine's design, carried as justified inline suppressions.

Flagged inside a lock-holding ``with`` block:

- calls to module-local functions decorated ``@jax.jit`` (directly or
  via ``partial(jax.jit, ...)``);
- the repo's canonical dispatch entry points (``rnn_time_step``,
  ``util.decoding.prime_prompt/step_tokens/step_greedy/verify_tokens``,
  ``serving.paging.gather_pages/scatter_pages``);
- blocking device syncs: ``block_until_ready`` (function or method),
  ``jax.device_get``, ``jax.effects_barrier``.

Condition variables (`cond`) are exempt: a ``Condition.wait`` park is
the queue idiom, not a device-latency coupling.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator, Set

from deeplearning4j_tpu.analysis.core import (
    Finding, ModuleInfo, Rule, SEVERITY_WARNING)

#: lock-like context expressions (cond/sem deliberately absent: waiting
#: on a Condition is the handoff idiom, not a device stall under a lock)
_LOCKISH = re.compile(r"lock|mutex", re.IGNORECASE)

#: canonical dotted names of repo dispatch entry points + jax syncs
_DISPATCH_CALLS = {
    "deeplearning4j_tpu.util.decoding.prime_prompt",
    "deeplearning4j_tpu.util.decoding.step_tokens",
    "deeplearning4j_tpu.util.decoding.step_greedy",
    "deeplearning4j_tpu.util.decoding.verify_tokens",
    "deeplearning4j_tpu.serving.paging.gather_pages",
    "deeplearning4j_tpu.serving.paging.scatter_pages",
}
_SYNC_CALLS = {"jax.device_get", "jax.block_until_ready",
               "jax.effects_barrier"}
#: method names that are dispatches/syncs wherever they appear
_DISPATCH_ATTRS = {"rnn_time_step"}
_SYNC_ATTRS = {"block_until_ready"}


def _is_jax_jit(mod: ModuleInfo, node: ast.AST) -> bool:
    """True for a decorator expression meaning jax.jit: bare ``jax.jit``,
    ``jax.jit(...)``, or ``partial(jax.jit, ...)``."""
    if mod.resolve(node) == "jax.jit":
        return True
    if isinstance(node, ast.Call):
        fn = mod.resolve(node.func)
        if fn == "jax.jit":
            return True
        if fn == "functools.partial" and node.args \
                and mod.resolve(node.args[0]) == "jax.jit":
            return True
    return False


def _jitted_locals(mod: ModuleInfo) -> Set[str]:
    out: Set[str] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and any(_is_jax_jit(mod, d) for d in node.decorator_list):
            out.add(node.name)
    return out


def _lock_with(mod: ModuleInfo, node: ast.With) -> bool:
    return any(_LOCKISH.search(mod.segment(item.context_expr))
               for item in node.items)


class LockHeldAcrossDispatchRule(Rule):
    id = "lock-held-across-dispatch"
    severity = SEVERITY_WARNING
    description = ("jitted dispatch or blocking device sync while "
                   "holding a threading lock — a stalled device call "
                   "freezes every other waiter on the lock")

    def check(self, mod: ModuleInfo) -> Iterator[Finding]:
        if not mod.imports_module("jax") and \
                not mod.imports_module("deeplearning4j_tpu"):
            return
        jitted = _jitted_locals(mod)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            what = self._classify(mod, node, jitted)
            if what is None:
                continue
            holder = self._enclosing_lock_with(mod, node)
            if holder is None:
                continue
            yield self.finding(
                mod, node,
                f"{what} inside `with "
                f"{mod.segment(holder.items[0].context_expr)}:` — a "
                f"stalled device call here blocks every thread waiting "
                f"on the lock; snapshot under the lock and dispatch "
                f"outside it (or carry a justified suppression)")

    # -- helpers -------------------------------------------------------
    @staticmethod
    def _enclosing_lock_with(mod: ModuleInfo, node: ast.AST):
        """Nearest lock-guarded With between `node` and its enclosing
        function (a lock taken in an OUTER function is that function's
        finding, not this one's)."""
        for a in mod.ancestors(node):
            if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                return None
            if isinstance(a, ast.With) and _lock_with(mod, a):
                return a
        return None

    def _classify(self, mod: ModuleInfo, call: ast.Call,
                  jitted: Set[str]):
        name = mod.resolve(call.func)
        if name is not None:
            if name in _SYNC_CALLS:
                return f"blocking device sync `{name}`"
            if name in _DISPATCH_CALLS:
                return f"jitted dispatch `{name.rsplit('.', 1)[-1]}`"
            if name in jitted:
                return f"locally-jitted dispatch `{name}`"
        if isinstance(call.func, ast.Attribute):
            if call.func.attr in _SYNC_ATTRS:
                return f"blocking device sync `.{call.func.attr}()`"
            if call.func.attr in _DISPATCH_ATTRS:
                return f"jitted dispatch `.{call.func.attr}()`"
        return None
