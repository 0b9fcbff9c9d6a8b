#!/usr/bin/env python
"""Benchmark: ResNet50 training throughput (images/sec/chip) on a TPU.

BASELINE.json metric: "ResNet50 ImageNet images/sec/chip; top-1 parity vs
deeplearning4j-cuda". The reference publishes no numbers (BASELINE.md), so
vs_baseline is reported against DL4J_CUDA_REF_IMG_S below — a representative
figure for the reference's cuDNN path on a contemporary GPU (ResNet50/ImageNet
fwd+bwd, fp32, single card) used as the provisional bar until a measured
reference number exists.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "platform",
"device_kind", ...} and exits 0. A run that finds no TPU prints
{"error": "tpu-unavailable"} and exits 3 (it never reports a CPU number as
the chip benchmark); anything the benchmark code raises — a Mosaic
lowering failure of the fused plan included — propagates as a traceback
and a non-zero exit. One process per chip: run this from a parent that
has not touched jax.

Env knobs: BENCH_BATCH/IMAGE/WARMUP/STEPS shapes; BENCH_SCAN_STEPS=K
runs the fused K-step lax.scan train step (K optimizer steps per
Python->XLA dispatch; every record carries steps_per_dispatch /
dispatches / prefetch_h2d_bytes either way); BENCH_FUSE pins the
execution plan — DEPRECATED spelling kept for driver back-compat, now
delegating to the production execution_plan API (tuning/plan.py, the
same seam `net.fit(..., execution_plan=...)` resolves): 0 -> "xla",
2/"bottleneck" -> "fused", "auto" -> store-resolved; 1 keeps the
legacy bn→act→conv plan (measured SLOWER, PERF.md round 3).
One plan per invocation (the cross-plan comparison is bench_all.py's
train_plan leg); BENCH_CALIBRATE=1 additionally runs the per-shape
calibration harness into the kernel-crossover store
(KERNEL_CROSSOVER.json), so one chip run teaches every future "auto"
run; BENCH_ALLOW_CPU=1 permits running on a CPU backend (smoke
tests with tiny shapes only; select it with JAX_PLATFORMS=cpu).
"""

import json
import os
import sys
import time

DL4J_CUDA_REF_IMG_S = 200.0  # provisional reference bar (see module docstring)

METRIC = "ResNet50 ImageNet train images/sec/chip (bf16 compute)"
BATCH = int(os.environ.get("BENCH_BATCH", "128"))
IMAGE = int(os.environ.get("BENCH_IMAGE", "224"))
CLASSES = 1000
WARMUP = int(os.environ.get("BENCH_WARMUP", "5"))
STEPS = int(os.environ.get("BENCH_STEPS", "30"))
# fused multi-step dispatch (ISSUE 3): K optimizer steps per Python->XLA
# round-trip via the lax.scan train step. 1 = the per-batch step.
SCAN_STEPS = max(1, int(os.environ.get("BENCH_SCAN_STEPS", "1")))


def _metrics_snapshot():
    """Compact telemetry-registry snapshot for the record: phase spans,
    jit compile counts, HBM high-water marks. Never raises and never
    initializes a backend (exporters.metrics_snapshot's contract) — the
    tpu-unavailable record carries one too."""
    from deeplearning4j_tpu.monitoring.exporters import metrics_snapshot
    return metrics_snapshot(refresh_timeout=0.5)


def _emit(value, vs_baseline, **extra):
    """Print the single JSON result line."""
    from deeplearning4j_tpu.pipeline.prefetch import prefetch_bytes_total
    extra.setdefault("metrics", _metrics_snapshot())
    # dispatch-overhead fields in EVERY record (failure records get
    # the knob values + 0 dispatches) so the bench trajectory shows
    # the fused-dispatch / prefetch win
    extra.setdefault("steps_per_dispatch", SCAN_STEPS)
    extra.setdefault("dispatches", 0)
    extra.setdefault("prefetch_h2d_bytes", prefetch_bytes_total())
    print(json.dumps({"metric": METRIC, "value": value,
                      "unit": "images/sec",
                      "vs_baseline": vs_baseline, **extra}), flush=True)


def main():
    import jax

    from deeplearning4j_tpu import monitoring
    from deeplearning4j_tpu.util.compile_cache import (
        configure_compile_cache)
    configure_compile_cache()
    # telemetry on before any compile happens: the registry snapshot in
    # the record then carries per-fn jit compile counts and phase spans
    # for the whole run
    monitoring.ensure_started()
    device = jax.devices()[0]
    platform = device.platform
    if platform != "tpu" and os.environ.get("BENCH_ALLOW_CPU") != "1":
        _emit(None, None, error="tpu-unavailable", platform=platform,
              detail=f"backend is {platform!r} ({jax.devices()}); refusing "
              "to report it as the chip benchmark (set BENCH_ALLOW_CPU=1 "
              "for smoke tests)")
        return 3

    def _measure(plan):
        """One full measurement of the given execution plan ("xla",
        "fused", "auto" through the production tuning/plan.py seam;
        "bn_act_conv" keeps the legacy fuse=True path). Fresh model
        + jit cache each call; returns (images/sec, dispatch count of
        the measured loop). With BENCH_SCAN_STEPS=K>1 the measured unit
        is the fused K-step lax.scan dispatch (K optimizer steps, one
        Python->XLA round-trip)."""
        import jax.numpy as jnp
        import numpy as np

        from deeplearning4j_tpu.zoo import ResNet50
        from deeplearning4j_tpu.nn.updater import Nesterovs

        # NHWC internal layout: profile-driven (see PERF.md) — BN stat
        # reductions and channel work are lane-aligned, ~9% over NCHW.
        kw = ({"fuse": True} if plan == "bn_act_conv"
              else {"execution_plan": plan})
        model = ResNet50(num_classes=CLASSES, height=IMAGE, width=IMAGE,
                         updater=Nesterovs(0.1, momentum=0.9),
                         data_format=os.environ.get("BENCH_FORMAT", "NHWC"),
                         **kw)
        net = model.init()
        net.conf.dtype = "bfloat16"  # MXU path, fp32 master params + accum
        if plan != "bn_act_conv":
            # re-resolve under the bench dtype: the crossover keys (and
            # the stem's VMEM gate) are dtype-keyed, and conf.dtype was
            # just flipped to bf16 after the zoo init resolved at f32
            from deeplearning4j_tpu.tuning.plan import apply_execution_plan
            apply_execution_plan(net, plan)

        rng = np.random.default_rng(0)
        x = rng.standard_normal((BATCH, 3, IMAGE, IMAGE)).astype(np.float32)
        y = np.zeros((BATCH, CLASSES), np.float32)
        y[np.arange(BATCH), rng.integers(0, CLASSES, BATCH)] = 1.0

        k = SCAN_STEPS
        if k > 1:
            step = net._get_scan_train_step(k)
            inputs = {net.conf.network_inputs[0]:
                      jnp.stack([jnp.asarray(x)] * k)}
            labels = {net.conf.network_outputs[0]:
                      jnp.stack([jnp.asarray(y)] * k)}
            key = jax.random.split(jax.random.PRNGKey(0), k)
        else:
            step = net._get_train_step(False)
            inputs = {net.conf.network_inputs[0]: jnp.asarray(x)}
            labels = {net.conf.network_outputs[0]: jnp.asarray(y)}
            key = jax.random.PRNGKey(0)
        n_disp = max(1, STEPS // k)

        from deeplearning4j_tpu.monitoring.tracing import span

        params, state, upd = net.params, net.state, net.updater_state
        with span("bench_warmup"):  # compile + warmup, visible in "metrics"
            for _ in range(WARMUP):
                params, state, upd, loss = step(params, state, upd, inputs,
                                                labels, key, None, None)
            # sync on a scalar device->host fetch. ravel()[-1]: the scan
            # step returns the per-step loss VECTOR.
            float(loss.ravel()[-1])

        with span("bench_measure"):
            t0 = time.perf_counter()
            for _ in range(n_disp):
                params, state, upd, loss = step(params, state, upd, inputs,
                                                labels, key, None, None)
            float(loss.ravel()[-1])
            dt = time.perf_counter() - t0
        # HBM/RSS gauges at the run's high-water mark, for the record
        from deeplearning4j_tpu.monitoring import runtime
        runtime.refresh()
        return BATCH * k * n_disp / dt, n_disp

    # BENCH_FUSE (deprecated spelling, kept for driver back-compat —
    # values now delegate to the execution_plan API): 0 / unset -> "xla",
    # 1 -> legacy bn→act→conv plan, 2/"bottleneck" -> "fused",
    # "auto" -> store-resolved. One plan per invocation; the cross-plan
    # comparison is bench_all.py's train_plan leg.
    fuse_env = os.environ.get("BENCH_FUSE", "0")
    fuse_levels = {"0": "xla", "1": "bn_act_conv",
                   "2": "fused", "bottleneck": "fused",
                   "auto": "auto"}
    if fuse_env not in fuse_levels:
        raise ValueError(f"BENCH_FUSE={fuse_env!r}: expected 0, 1, 2, "
                         "'bottleneck' or 'auto'")
    plan = fuse_levels[fuse_env]
    img_s, n_disp = _measure(plan)
    extra = {"steps_per_dispatch": SCAN_STEPS, "dispatches": n_disp,
             "plan": plan}
    if os.environ.get("BENCH_CALIBRATE") == "1":
        # per-shape kernel-vs-fallback micro-calibration into the
        # committed store — one chip run teaches every future "auto"
        # resolution
        from deeplearning4j_tpu.tuning import (
            calibrate_training_kernels, default_store, winner)
        from deeplearning4j_tpu.zoo import ResNet50
        from deeplearning4j_tpu.nn.updater import Nesterovs
        net = ResNet50(
            num_classes=CLASSES, height=IMAGE, width=IMAGE,
            updater=Nesterovs(0.1, momentum=0.9),
            data_format="NHWC").init()
        net.conf.dtype = "bfloat16"
        entries = calibrate_training_kernels(
            net, batch_size=min(BATCH, 16),
            store=default_store(), persist=True)
        extra["calibrated"] = {k: winner(v) for k, v in entries.items()}

    _emit(round(img_s, 2), round(img_s / DL4J_CUDA_REF_IMG_S, 3),
          platform=platform, device_kind=device.device_kind,
          device_count=jax.device_count(), **extra)
    return 0


if __name__ == "__main__":
    sys.exit(main())
