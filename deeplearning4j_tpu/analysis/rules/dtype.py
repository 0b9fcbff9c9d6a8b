"""dtype rules: float64 creeping into jax program modules, and int8
buffers reaching arithmetic without an explicit widen.

TPUs execute f64 in slow software emulation (or jax silently truncates
to f32 with `jax_enable_x64` off, masking the intent). Either way a
float64 literal or dtype in a module that builds jax computations is a
hazard — except in the finite-difference gradient checker, whose whole
point is f64 reference arithmetic (scoped by ``jax.enable_x64``).

The int8 rule is the ISSUE 18 companion: a quantized KV pool hands
int8 arrays to dispatch code, and jax's type promotion silently widens
`int8 op float` to whatever the lattice says — or worse, `int8 @ int8`
runs an integer dot whose accumulator semantics differ between the
interpreter and the MXU. The quant kernel's contract is that every
int8 read is EXPLICITLY widened (`.astype(jnp.float32)`) before any
arithmetic; this rule flags the spots where an int8-typed local slips
into a BinOp or a dot/einsum bare.
"""

from __future__ import annotations

import ast
from typing import Iterator

from deeplearning4j_tpu.analysis.core import (
    Finding, ModuleInfo, Rule, SEVERITY_WARNING)

_EXEMPT_PATH_PARTS = ("gradient_check",)
_F64_OWNERS = ("numpy", "jax.numpy", "jax")


def _is_exempt(mod: ModuleInfo) -> bool:
    return any(part in mod.rel_path for part in _EXEMPT_PATH_PARTS)


class DtypePromotionRule(Rule):
    id = "dtype-promotion"
    severity = SEVERITY_WARNING
    description = ("float64 dtype in a jax-importing module outside the "
                   "gradient checker risks x64 emulation or silent "
                   "truncation")

    def check(self, mod: ModuleInfo) -> Iterator[Finding]:
        if _is_exempt(mod) or not mod.imports_module("jax"):
            return
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Attribute) and node.attr == "float64":
                owner = mod.resolve(node.value)
                if owner in _F64_OWNERS:
                    yield self.finding(
                        mod, node,
                        f"{owner}.float64 in a jax module: f64 emulates "
                        f"slowly on TPU (or truncates silently with x64 "
                        f"off); keep device math in f32/bf16")
            elif isinstance(node, ast.keyword) and node.arg == "dtype" \
                    and isinstance(node.value, ast.Constant) \
                    and node.value.value == "float64":
                yield self.finding(
                    mod, node.value,
                    "dtype='float64' in a jax module: keep device math "
                    "in f32/bf16")
            elif isinstance(node, ast.Call):
                fn = mod.resolve(node.func)
                if fn and fn.endswith("config.update") and node.args \
                        and isinstance(node.args[0], ast.Constant) \
                        and node.args[0].value == "jax_enable_x64":
                    yield self.finding(
                        mod, node,
                        "jax_enable_x64 toggled process-wide: scope f64 "
                        "with the jax.enable_x64 context manager so the "
                        "flag can't leak into production paths")
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "astype" and node.args \
                        and isinstance(node.args[0], ast.Constant) \
                        and node.args[0].value == "float64":
                    yield self.finding(
                        mod, node,
                        ".astype('float64') in a jax module: keep device "
                        "math in f32/bf16")


_INT8_OWNERS = ("numpy", "jax.numpy", "jax")
_DOT_FNS = ("dot", "einsum", "matmul", "dot_general", "tensordot")


def _is_int8_dtype(mod: ModuleInfo, node: ast.AST) -> bool:
    """`jnp.int8` / `np.int8` / the string 'int8'."""
    if isinstance(node, ast.Constant):
        return node.value == "int8"
    if isinstance(node, ast.Attribute) and node.attr == "int8":
        return mod.resolve(node.value) in _INT8_OWNERS
    return False


def _int8_producer(mod: ModuleInfo, node: ast.AST) -> bool:
    """Does this expression syntactically yield an int8 array?
    `.astype(int8)` or any call carrying `dtype=int8`."""
    if not isinstance(node, ast.Call):
        return False
    if isinstance(node.func, ast.Attribute) and node.func.attr == "astype" \
            and node.args and _is_int8_dtype(mod, node.args[0]):
        return True
    return any(kw.arg == "dtype" and _is_int8_dtype(mod, kw.value)
               for kw in node.keywords)


class Int8PromotionRule(Rule):
    id = "int8-promotion-in-dispatch"
    severity = SEVERITY_WARNING
    description = ("arithmetic on an int8-typed local without an explicit "
                   "widen silently promotes (or runs an integer dot) — "
                   "quantized-pool reads must .astype() before math")

    def check(self, mod: ModuleInfo) -> Iterator[Finding]:
        if not mod.imports_module("jax"):
            return
        for fn in ast.walk(mod.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            # last-assignment-wins, in line order: `q = x.astype(int8)`
            # marks q; a later `q = q.astype(f32)` clears it
            assigns = []
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name):
                    assigns.append((node.lineno, node.targets[0].id,
                                    _int8_producer(mod, node.value)))
            assigns.sort()
            if not any(is8 for _, _, is8 in assigns):
                continue

            def int8_at(name: str, lineno: int) -> bool:
                last = None
                for aline, aname, is8 in assigns:
                    if aname == name and aline <= lineno:
                        last = is8
                return bool(last)

            for node in ast.walk(fn):
                operands = ()
                what = "arithmetic"
                if isinstance(node, ast.BinOp):
                    operands = (node.left, node.right)
                elif isinstance(node, ast.Call):
                    f = node.func
                    name = f.attr if isinstance(f, ast.Attribute) else \
                        (f.id if isinstance(f, ast.Name) else None)
                    if name in _DOT_FNS:
                        operands, what = tuple(node.args), name
                for op in operands:
                    if isinstance(op, ast.Name) \
                            and int8_at(op.id, op.lineno):
                        yield self.finding(
                            mod, node,
                            f"int8 local '{op.id}' used in {what} without "
                            f"an explicit widen: promotion is silent and "
                            f"integer-dot accumulator semantics differ "
                            f"across backends; .astype(jnp.float32) first")
