"""Plain reference for the ``resnet50`` configuration: forward pass, loss,
gradients and the Nesterov update in straightforward ``jax.numpy``,
float32, ``highest`` matmul precision. Imports nothing of the program.

He et al. 2015 (arXiv:1512.03385), table 1, 50-layer column: 7x7/2 stem,
3x3/2 max pool, bottleneck stages of 3, 4, 6, 3 blocks, global average
pool, 1000-way softmax. Departures are the configuration's ``assumed``:
stride 2 on a stage's first 1x1, no weight decay, training-mode BatchNorm
(batch statistics, biased variance, eps 1e-5).

``low`` is the 8-bit control as ``reference/quant.py`` defines it: the same
mathematics with every tensor an op hands on (a convolution's operands and
result, a BatchNorm's and a block's output, the pooled features) rounded to
float8, where the program hands them on in bfloat16; arithmetic inside an
op stays float32.

``train_flops`` is the operation count the whole step's share of the peak
is taken from (``metrics/train_step.mfu.py`` finds it here, by the
configuration's ``model``).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.flops import resnet50_train_flops as train_flops  # noqa: F401
from benchmark.reference.quant import stored as _stored

HI = lax.Precision.HIGHEST


def _blocks(cfg):
    c_in = cfg["stem_filters"]
    for s, (reps, filters, stride) in enumerate(zip(
            cfg["stage_blocks"], cfg["stage_filters"],
            cfg["stage_strides"])):
        for r in range(reps):
            yield f"s{s + 2}b{r}", c_in, filters, stride if r == 0 else 1, \
                r == 0
            c_in = filters[2]


def param_specs(cfg):
    """(name, shape, mean, std); convolution weights are [O, I, kH, kW]."""
    specs = []

    def conv_bn(name, c_out, c_in, k, gamma=(1.0, 0.1)):
        specs.append((f"{name}_conv/W", (c_out, c_in, k, k), 0.0,
                      math.sqrt(2.0 / (c_in * k * k))))
        specs.append((f"{name}_bn/gamma", (c_out,)) + gamma)
        specs.append((f"{name}_bn/beta", (c_out,), 0.0, 0.1))

    conv_bn("stem", cfg["stem_filters"], cfg["channels"], 7)
    for name, c_in, (f1, f2, f3), _, first in _blocks(cfg):
        conv_bn(f"{name}_a", f1, c_in, 1)
        conv_bn(f"{name}_b", f2, f1, 3)
        # a block's last BatchNorm starts small, as trained residual nets
        # have it (and as zero-gamma initialisation starts them): with
        # gain 1 everywhere the residual stream doubles block by block and
        # a fresh net's gradients are ill-conditioned even in float32
        conv_bn(f"{name}_c", f3, f2, 1, gamma=(0.25, 0.05))
        if first:
            conv_bn(f"{name}_skip", f3, c_in, 1)
    c_last = cfg["stage_filters"][-1][2]
    specs.append(("output/W", (c_last, cfg["num_classes"]), 0.0,
                  math.sqrt(2.0 / c_last)))
    specs.append(("output/b", (cfg["num_classes"],), 0.0, 0.02))
    return specs


def _conv(x, w_oihw, stride, pad, low):
    w = jnp.transpose(w_oihw, (2, 3, 1, 0))                     # HWIO
    return _stored(lax.conv_general_dilated(
        _stored(x, low), _stored(w, low), (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI), low)


def _bn(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean((x - mean) ** 2, axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * gamma + beta


def _conv_bn(p, name, x, stride, pad, eps, low, relu=True):
    y = _conv(x, p[f"{name}_conv/W"], stride, pad, low)
    y = _bn(y, p[f"{name}_bn/gamma"], p[f"{name}_bn/beta"], eps)
    return _stored(jnp.maximum(y, 0.0) if relu else y, low)


def loss_fn(params, x_nchw, y_onehot, cfg, low=False, rows=None):
    """Mean softmax cross-entropy of the batch (of its first ``rows`` rows
    when given: the half-batch fault)."""
    eps = cfg["batch_norm"]["eps"]
    if rows is not None:
        x_nchw, y_onehot = x_nchw[:rows], y_onehot[:rows]
    x = jnp.transpose(x_nchw.astype(jnp.float32), (0, 2, 3, 1))
    x = _conv_bn(params, "stem", x, 2, 3, eps, low)
    x = _stored(lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)]), low)

    def block(name, stride, first, p, x):
        y = _conv_bn(p, f"{name}_a", x, stride, 0, eps, low)
        y = _conv_bn(p, f"{name}_b", y, 1, 1, eps, low)
        y = _conv_bn(p, f"{name}_c", y, 1, 0, eps, low, relu=False)
        skip = _conv_bn(p, f"{name}_skip", x, stride, 0, eps, low,
                        relu=False) if first else x
        return _stored(jnp.maximum(y + skip, 0.0), low)

    for name, _, _, stride, first in _blocks(cfg):
        keys = [k for k in params if k.startswith(name + "_")]
        # recompute each block's inside on the way back, so that float32
        # activations of the timed batch fit beside the program's peak
        x = jax.checkpoint(functools.partial(block, name, stride, first))(
            {k: params[k] for k in keys}, x)
    x = _stored(jnp.mean(x, axis=(1, 2)), low)
    logits = jnp.matmul(x, _stored(params["output/W"], low),
                        precision=HI) + params["output/b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.mean(-jnp.sum(y_onehot * logp, axis=-1))


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def make_step(cfg, low=False, rows=None):
    return _make_step(json.dumps(cfg, sort_keys=True), low, rows)


@functools.lru_cache(maxsize=8)
def _make_step(cfg_json, low, rows):
    """One Nesterov step on float32 parameters: ``v' = mu v - lr g``, the
    parameters move by ``mu v' - lr g``. Returns the new parameters and
    velocity, the loss and the per-leaf gradient norms."""
    cfg = json.loads(cfg_json)
    lr = cfg["updater"]["learning_rate"]
    mu = cfg["updater"]["momentum"]

    @jax.jit
    def step(params, vel, x, y):
        loss, g = jax.value_and_grad(loss_fn)(params, x, y, cfg, low, rows)
        new_v = {k: mu * vel[k] - lr * g[k] for k in params}
        new_p = {k: params[k] + mu * new_v[k] - lr * g[k] for k in params}
        return new_p, new_v, loss, leaf_norms(g)

    return step


def train_readings(cfg, params0, batches, low=False, rows=None):
    """Follow the first ``len(batches)`` steps from ``params0``. Returns
    ``losses``, ``grad1_norms`` (step 1's gradient, per leaf) and
    ``change_norms`` (||P_n - P_0|| per leaf), all on the host."""
    import numpy as np
    step = make_step(cfg, low, rows)
    params = dict(params0)
    vel = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses, grad1 = [], None
    for i, (x, y) in enumerate(batches):
        params, vel, loss, gn = step(params, vel, x, y)
        losses.append(loss)
        if i == 0:
            grad1 = gn
    change = leaf_norms({k: params[k] - params0[k] for k in params})
    return {"losses": [float(v) for v in losses],
            "grad1_norms": {k: float(v) for k, v in grad1.items()},
            "change_norms": {k: float(np.asarray(v))
                             for k, v in change.items()}}
