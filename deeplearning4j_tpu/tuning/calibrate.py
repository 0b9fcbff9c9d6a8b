"""The explicit calibration harness: fill the crossover store from a
run on the chip.

``calibrate_training_kernels(net)`` walks the net's fusion candidates
(every distinct bottleneck-block shape + the stem), builds
representative tensors at each shape, and times the fused kernel chain
against its exact-semantics XLA fallback — fwd+bwd through jit, synced
— recording each paired measurement into the store. One call on a TPU
writes the entries every later ``execution_plan="auto"`` resolution
reads.

On a non-TPU backend the kernels run in interpret mode — the timings
are meaningless as TPU predictions, which is exactly why store entries
carry platform + device kind and a CPU-calibrated entry never decides
a TPU run. Calibrating on CPU is still useful in tests (it exercises
the full record/resolve loop) and harmless in production (the entries
only ever match an identical platform).
"""

from __future__ import annotations

import logging
from typing import Optional

from deeplearning4j_tpu.tuning.crossover import (
    KernelCrossoverStore, default_store)
from deeplearning4j_tpu.tuning.plan import (
    _block_key, _net_dtype, _stem_key)

log = logging.getLogger(__name__)


def _jdtype(dtype: str):
    import jax.numpy as jnp
    return jnp.bfloat16 if dtype in ("bfloat16", "bf16") else jnp.float32


def training_kernel_probes(net, *, batch_size: int = 8,
                           include_stem: bool = True):
    """Yield ``(key, kernel, fallback)`` for every distinct fusable
    shape on ``net`` (each distinct bottleneck-block shape, then the
    stem): ``key`` is the crossover fingerprint, and ``kernel()`` /
    ``fallback()`` run fwd+bwd of the fused Pallas chain / its
    exact-semantics XLA reference through a fresh jit on representative
    tensors at that shape. Shared by the calibration harness below and
    by ``chip_smoke.py --kernels`` (which only needs each kernel to
    compile and run once). A net without fusion candidates yields
    nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn.layers.bottleneck import (
        BnParams, fused_bottleneck, reference_bottleneck)
    from deeplearning4j_tpu.nn.layers.stem import (
        fused_stem, reference_stem)

    if not hasattr(net, "fusion_candidates"):
        return
    dtype = _net_dtype(net)
    jdt = _jdtype(dtype)
    interpret = jax.default_backend() != "tpu"
    bcands, scands = net.fusion_candidates()
    rng = np.random.default_rng(0)

    def arr(*shape, scale=1.0):
        return jnp.asarray(
            rng.standard_normal(shape).astype(np.float32) * scale, jdt)

    def bn_of(c):
        return BnParams(gamma=jnp.ones((c,), jdt),
                        beta=jnp.zeros((c,), jdt),
                        running_mean=jnp.zeros((c,), jnp.float32),
                        running_var=jnp.ones((c,), jnp.float32))

    def pair(make_grad, args):
        gk = make_grad(True)
        gf = make_grad(False)
        return (lambda: gk(args)), (lambda: gf(args))

    seen = set()
    for grp in bcands.values():
        key = _block_key(grp, dtype)
        if key in seen:
            continue
        seen.add(key)
        h, w, cin = grp["h"], grp["w"], grp["cin"]
        cmid, cout = grp["cmid"], grp["cout"]
        stride = grp.get("stride", 1)
        has_skip = "conv_skip" in grp
        bns = (bn_of(cmid), bn_of(cmid), bn_of(cout))
        bn_s = bn_of(cout) if has_skip else None

        def block_grad(fused, bns=bns, bn_s=bn_s, stride=stride):
            fn = fused_bottleneck if fused else reference_bottleneck
            kw = {"interpret": interpret} if fused else {}

            def f(args):
                out, _ = fn(args[0], args[1], bns[0], args[2], bns[1],
                            args[3], bns[2], w_skip=args[4],
                            bn_skip=bn_s, stride=stride, train=True,
                            **kw)
                return jnp.sum(out.astype(jnp.float32))
            return jax.jit(jax.grad(f))

        yield (key,) + pair(block_grad, (
            arr(batch_size, h, w, cin), arr(cin, cmid, scale=0.1),
            arr(9, cmid, cmid, scale=0.05), arr(cmid, cout, scale=0.1),
            arr(cin, cout, scale=0.1) if has_skip else None))
    if not include_stem:
        return
    for grp in scands.values():
        key = _stem_key(grp, dtype)
        if key in seen:
            continue
        seen.add(key)
        bnp = bn_of(grp["cout"])

        def stem_grad(fused, bnp=bnp):
            fn = fused_stem if fused else reference_stem
            kw = {"interpret": interpret} if fused else {}

            def f(args):
                out, _ = fn(args[0], args[1], bnp, train=True, **kw)
                return jnp.sum(out.astype(jnp.float32))
            return jax.jit(jax.grad(f))

        yield (key,) + pair(stem_grad, (
            arr(batch_size, grp["h"], grp["w"], grp["cin"]),
            arr(grp["cout"], grp["cin"], 7, 7, scale=0.1)))


def calibrate_training_kernels(
        net, *, batch_size: int = 8,
        store: Optional[KernelCrossoverStore] = None,
        warmup: int = 1, iters: int = 3, persist: bool = False,
        include_stem: bool = True) -> dict:
    """Measure kernel-vs-fallback for every distinct fusable shape on
    ``net`` and record the results. Returns {key: entry}."""
    # the store is this harness's OUTPUT sink (measurements are written
    # into it), not a knob baked into a cached trace: every timed jit
    # here is built fresh per call and discarded
    # tpulint: disable=jit-key-drift
    store = default_store() if store is None else store
    results = {}
    for key, kernel, fallback in training_kernel_probes(
            net, batch_size=batch_size, include_stem=include_stem):
        results[key] = store.calibrate(key, kernel, fallback,
                                       warmup=warmup, iters=iters)
        log.info("calibrated %s: kernel %.3fms vs fallback %.3fms",
                 key, results[key]["kernel_ms"],
                 results[key]["fallback_ms"])
    if persist and results:
        try:
            store.save()
        except OSError as e:
            # a read-only install dir must not discard a completed
            # calibration run — the measurements are in the returned
            # (and in-memory) store either way
            log.warning("kernel-crossover store not persisted to %s "
                        "(%s); pass a writable path via "
                        "KernelCrossoverStore(path=...)", store.path, e)
    return results
