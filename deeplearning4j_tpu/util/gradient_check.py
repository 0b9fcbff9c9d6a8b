"""Numeric-vs-analytic gradient checking.

TPU-native equivalent of deeplearning4j-nn/.../gradientcheck/
GradientCheckUtil.java:57-454 (checkGradients MLN :112, CG :281): central
finite differences on every parameter vs the analytic gradient, with a
max-relative-error threshold. The reference calls this "the correctness
backbone" of its test suite (SURVEY §4); here the analytic side is jax.grad,
so this validates layer math + loss wiring end to end.
"""

from __future__ import annotations

import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64

log = logging.getLogger(__name__)

DEFAULT_EPS = 1e-5
DEFAULT_MAX_REL_ERROR = 1e-3
DEFAULT_MIN_ABS_ERROR = 1e-8


def check_gradients_fn(loss_fn, params, eps: float = DEFAULT_EPS,
                       max_rel_error: float = DEFAULT_MAX_REL_ERROR,
                       min_abs_error: float = DEFAULT_MIN_ABS_ERROR,
                       max_per_param: int = 64, seed: int = 0,
                       print_failures: bool = True) -> bool:
    """Check d loss_fn / d params via central differences (float64 on CPU).

    loss_fn: params_pytree -> scalar. Checks up to `max_per_param` randomly
    chosen elements per parameter array (the reference checks every element;
    sampling keeps large nets tractable — pass max_per_param=0 for all).

    Runs under a local enable_x64 scope: central differences with eps=1e-5
    are meaningless in float32 (the reference runs on float64 ND4J arrays,
    GradientCheckUtil.java:112 requires DataBuffer.Type.DOUBLE).
    """
    with enable_x64(True):
        return _check_gradients_fn_x64(loss_fn, params, eps, max_rel_error,
                                       min_abs_error, max_per_param, seed,
                                       print_failures)


def _check_gradients_fn_x64(loss_fn, params, eps, max_rel_error,
                            min_abs_error, max_per_param, seed,
                            print_failures) -> bool:
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float64), params)
    analytic = jax.grad(loss_fn)(params)
    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(analytic)
    # one compile, thousands of perturbed evaluations: the eager per-eval
    # dispatch dominates check time otherwise (2 * max_per_param * n_params
    # full forward passes)
    jitted_loss = jax.jit(lambda flat: loss_fn(
        jax.tree_util.tree_unflatten(treedef, flat)))
    rng = np.random.default_rng(seed)
    ok = True
    for pi, (p, g) in enumerate(zip(flat_p, flat_g)):
        p_np = np.asarray(p, np.float64)
        g_np = np.asarray(g, np.float64)
        n = p_np.size
        if max_per_param and n > max_per_param:
            idxs = rng.choice(n, size=max_per_param, replace=False)
        else:
            idxs = np.arange(n)
        for flat_idx in idxs:
            idx = np.unravel_index(flat_idx, p_np.shape)
            orig = p_np[idx]

            def eval_at(v):
                p_mod = p_np.copy()
                p_mod[idx] = v
                flat2 = list(flat_p)
                flat2[pi] = jnp.asarray(p_mod)
                return float(jitted_loss(flat2))

            plus = eval_at(orig + eps)
            minus = eval_at(orig - eps)
            numeric = (plus - minus) / (2 * eps)
            a = g_np[idx]
            abs_err = abs(numeric - a)
            denom = abs(numeric) + abs(a)
            rel_err = abs_err / denom if denom > 0 else 0.0
            if rel_err > max_rel_error and abs_err > min_abs_error:
                ok = False
                if print_failures:
                    log.warning(
                        "grad check FAIL param %d idx %s: numeric=%.8g analytic=%.8g "
                        "relErr=%.4g", pi, idx, numeric, a, rel_err)
    return ok


def check_gradients(net, ds, eps: float = DEFAULT_EPS,
                    max_rel_error: float = DEFAULT_MAX_REL_ERROR,
                    min_abs_error: float = DEFAULT_MIN_ABS_ERROR,
                    max_per_param: int = 32, seed: int = 0) -> bool:
    """Gradient-check a MultiLayerNetwork or ComputationGraph on a DataSet
    (ref: GradientCheckUtil.checkGradients :112/:281). Dropout must be
    disabled (train=True forward but rng=None disables dropout here)."""
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    if not net._initialized:
        net.init()
    with enable_x64(True):
        return _check_gradients_x64(net, ds, eps, max_rel_error,
                                    min_abs_error, max_per_param, seed)


def _check_gradients_x64(net, ds, eps, max_rel_error, min_abs_error,
                         max_per_param, seed) -> bool:
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    x = jnp.asarray(ds.features, jnp.float64)
    y = jnp.asarray(ds.labels, jnp.float64)
    fmask = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
    lmask = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)

    if isinstance(net, MultiLayerNetwork):
        def loss_fn(p):
            loss, _ = net._loss(p, net.state, x, y, None, fmask, lmask, train=True)
            return loss
    else:
        inputs = net._as_input_dict(x)
        labels = {net.conf.network_outputs[0]: y}
        fmasks = None if fmask is None else {net.conf.network_inputs[0]: fmask}
        lmasks = None if lmask is None else {net.conf.network_outputs[0]: lmask}

        def loss_fn(p):
            loss, _ = net._loss(p, net.state, inputs, labels, None, fmasks,
                                lmasks, train=True)
            return loss

    return check_gradients_fn(loss_fn, net.params, eps=eps,
                              max_rel_error=max_rel_error,
                              min_abs_error=min_abs_error,
                              max_per_param=max_per_param, seed=seed)
