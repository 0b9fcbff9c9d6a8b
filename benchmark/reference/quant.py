"""The lower precision the controls compute in: one definition for every
cell.

The configurations state bfloat16: the program keeps each op's arithmetic
wide (a product accumulates in float32, a normalisation takes its
statistics in float32) and hands every tensor from one op to the next in
bfloat16. The nearest precision below is 8-bit floating point (Micikevicius
et al. 2022, "FP8 formats for deep learning"), and the control is the plain
reference with float8 wherever the program has bfloat16:

- both operands of every matrix product and convolution, and
- every tensor an op hands on (a product's result, a normalisation's, an
  activation's, a block's or the residual stream's sum, pooled features)

are rounded to float8_e4m3 under a per-tensor amax scale; where there is a
way back, the gradient through each such tensor is rounded to float8_e5m2,
again per tensor. Arithmetic inside an op stays float32. One tensor is
left wide on purpose: the logits that sampling reads, since rounding those
alone would fail any comparison of tokens and say nothing of the model.

Rounding the operands alone is NOT the control: on ResNet50 at batch 256 it
read about twice the program's own bfloat16 noise on every number compared
(PERF.md, PR 25; limits/resnet50.fit_b256.json), because operand rounding
averages out over contractions of 576 to 4,608 terms, so no limit could
both pass the program and fail it.
"""

import jax
import jax.numpy as jnp


def _round(x, dtype):
    """Round to ``dtype`` under a per-tensor amax scale; float32 out."""
    xf = x.astype(jnp.float32)
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30) / top
    return (xf / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def fp8_round(x):
    """A tensor kept in 8 bits: e4m3 forward, e5m2 backward."""
    return _round(x, jnp.float8_e4m3fn)


def _fwd(x):
    return fp8_round(x), None


def _bwd(_, g):
    return (_round(g, jnp.float8_e5m2),)


fp8_round.defvjp(_fwd, _bwd)


def stored(x, low: bool):
    """A tensor as it is handed on: as it is, or in the 8-bit control
    rounded to float8."""
    return fp8_round(x) if low else x
