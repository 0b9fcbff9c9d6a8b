#!/usr/bin/env python
"""Extended benchmark suite covering the BASELINE.md configs beyond the
headline ResNet50 line that bench.py prints.

Prints one JSON line per config:
- resnet50_train: same as bench.py (ResNet50 NHWC bf16, images/sec/chip)
- lstm_train: TextGenerationLSTM-class stacked LSTM (BASELINE config[2]),
  tokens/sec through the jitted train step (lax.scan recurrence — measured
  14x faster than the pallas per-step kernel on v5e, see PERF.md)
- lenet_train: LeNet MNIST-shape throughput (BASELINE config[0])
- vgg16_train: VGG16 training throughput (BASELINE config[1])
- keras_inceptionv3_infer: InceptionV3-topology .h5 import -> batched
  inference (BASELINE config[3]; graph built programmatically, zero-egress)
- scaling_8dev: data-parallel ResNet step on an 8-device mesh — ICI
  allreduce scaling. Fails on a host with fewer than 8 devices: a
  multichip leg never measures the host instead.

Usage: python bench_all.py [resnet|lstm|lenet|vgg16|inception|attention|transformer|scaling]...

Every leg needs a TPU backend; BENCH_ALLOW_CPU=1 (with JAX_PLATFORMS=cpu)
lets the mechanism legs run on a CPU for smoke tests. One process per
chip: run this from a parent that has not touched jax.
"""

import json
import os
import sys
import time

_prev_metrics_snap = [None]  # full registry snapshot at the last record

# fused multi-step dispatch (ISSUE 3): BENCH_SCAN_STEPS=K swaps the
# per-batch train step for the K-step lax.scan step in every train
# bench; each record carries steps_per_dispatch / dispatches /
# prefetch_h2d_bytes so the trajectory shows the dispatch-overhead win.
_SCAN_STEPS = max(1, int(os.environ.get("BENCH_SCAN_STEPS", "1")))
_dispatches = [0]        # train-step dispatches issued (see _sync_time)
_prev_dispatches = [0]   # ... at the last record
_prev_prefetch_bytes = [0.0]


def _prefetch_bytes_total():
    from deeplearning4j_tpu.pipeline.prefetch import prefetch_bytes_total
    return prefetch_bytes_total()


def _print_line(s, flush=True):
    """All result lines go through here: every record picks up a
    telemetry-registry DELTA — the increment since the previous record
    (phase spans, jit compiles; gauges stay point-in-time) — so the Nth
    bench's "metrics" carries only its own telemetry, not the
    cumulative totals of every earlier bench in the process. One choke
    point instead of twenty call sites."""
    d = json.loads(s)
    if isinstance(d, dict) and "metrics" not in d:
        from deeplearning4j_tpu.monitoring.exporters import (
            refresh_runtime_bounded, snapshot_delta_compact)
        from deeplearning4j_tpu.monitoring.metrics import global_registry
        refresh_runtime_bounded(0.5)
        cur = global_registry().snapshot()
        d["metrics"] = snapshot_delta_compact(_prev_metrics_snap[0], cur)
        _prev_metrics_snap[0] = cur
        # dispatch-overhead fields, delta'd like the metrics snapshot:
        # this record's train-step dispatches and prefetch H2D bytes
        d.setdefault("steps_per_dispatch", _SCAN_STEPS)
        d.setdefault("dispatches",
                     _dispatches[0] - _prev_dispatches[0])
        _prev_dispatches[0] = _dispatches[0]
        pb = _prefetch_bytes_total()
        d.setdefault("prefetch_h2d_bytes",
                     round(pb - _prev_prefetch_bytes[0]))
        _prev_prefetch_bytes[0] = pb
        s = json.dumps(d)
    print(s, flush=flush)


def _sync_time(step, args, steps, measured=True):
    """Chained steps; sync via a scalar fetch of the last loss. Returns
    (elapsed, args_after) so donated state threads into the next call.
    ravel()[-1]: the K-step scan step returns the per-step loss VECTOR;
    the last element syncs the whole chain either way. `measured=False`
    (warmup legs) keeps the record's "dispatches" field aligned with
    the dispatches the throughput value was computed from (bench.py
    counts the same way)."""
    out = None
    t0 = time.perf_counter()
    for _ in range(steps):
        out = step(*args)
        args = (out[0], out[1], out[2]) + args[3:]
    if measured:
        _dispatches[0] += steps
    float(out[3].ravel()[-1])
    return time.perf_counter() - t0, args


def _fused_step(net, args):
    """BENCH_SCAN_STEPS=K>1: swap the per-batch train step for the
    fused K-step lax.scan step, replicating the benchmark batch K times
    along the scan axis. Returns (step, args, k) — throughput callers
    multiply their per-dispatch work by k."""
    k = _SCAN_STEPS
    if k == 1:
        return net._get_train_step(False), args, 1
    import jax
    import jax.numpy as jnp
    p, s, u, x, y, key = args[:6]
    stack = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jnp.stack([a] * k), t)
    return (net._get_scan_train_step(k),
            (p, s, u, stack(x), stack(y),
             jax.random.split(key, k)) + args[6:], k)


def bench_resnet():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.zoo import ResNet50
    from deeplearning4j_tpu.nn.updater import Nesterovs

    B = int(os.environ.get("BENCH_BATCH", "128"))
    net = ResNet50(num_classes=1000, height=224, width=224,
                   updater=Nesterovs(0.1, momentum=0.9),
                   data_format="NHWC").init()
    net.conf.dtype = "bfloat16"
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, 3, 224, 224)).astype(np.float32))
    y = np.zeros((B, 1000), np.float32)
    y[np.arange(B), rng.integers(0, 1000, B)] = 1.0
    inputs = {net.conf.network_inputs[0]: x}
    labels = {net.conf.network_outputs[0]: jnp.asarray(y)}
    key = jax.random.PRNGKey(0)
    args = (net.params, net.state, net.updater_state, inputs, labels, key,
            None, None)
    step, args, k = _fused_step(net, args)
    _, args = _sync_time(step, args, 3, measured=False)  # warmup
    dt, _ = _sync_time(step, args, 10)
    _print_line(json.dumps({"metric": "resnet50_train",
                      "value": round(B * k * 10 / dt, 1),
                      "unit": "images/sec"}), flush=True)


def bench_lstm():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.zoo import TextGenerationLSTM
    from deeplearning4j_tpu.nn.updater import RmsProp

    B = int(os.environ.get("BENCH_LSTM_BATCH", "256"))
    T = int(os.environ.get("BENCH_LSTM_SEQ", "256"))
    V = 128  # character vocab (ref TextGenerationLSTM totalUniqueCharacters)
    net = TextGenerationLSTM(vocab_size=V, max_length=T,
                             updater=RmsProp(0.001)).init()
    net.conf.dtype = "bfloat16"
    rng = np.random.default_rng(0)
    ids = rng.integers(0, V, (B, T))
    x = np.zeros((B, V, T), np.float32)
    x[np.arange(B)[:, None], ids, np.arange(T)[None, :]] = 1.0
    y = np.roll(x, -1, axis=2)
    key = jax.random.PRNGKey(0)
    args = (net.params, net.state, net.updater_state, jnp.asarray(x),
            jnp.asarray(y), key, None, None)
    step, args, k = _fused_step(net, args)
    _, args = _sync_time(step, args, 3, measured=False)  # warmup
    dt, _ = _sync_time(step, args, 10)
    _print_line(json.dumps({"metric": "lstm_train",
                      "value": round(B * T * k * 10 / dt, 1),
                      "unit": "tokens/sec"}), flush=True)


def bench_lenet():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.zoo import LeNet
    from deeplearning4j_tpu.nn.updater import Adam

    B = 512
    net = LeNet(num_classes=10, updater=Adam(0.001)).init()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((B, 1, 28, 28)).astype(np.float32)
    y = np.zeros((B, 10), np.float32)
    y[np.arange(B), rng.integers(0, 10, B)] = 1.0
    key = jax.random.PRNGKey(0)
    args = (net.params, net.state, net.updater_state, jnp.asarray(x),
            jnp.asarray(y), key, None, None)
    step, args, k = _fused_step(net, args)
    _, args = _sync_time(step, args, 3, measured=False)  # warmup
    dt, _ = _sync_time(step, args, 20)
    _print_line(json.dumps({"metric": "lenet_train",
                      "value": round(B * k * 20 / dt, 1),
                      "unit": "images/sec"}), flush=True)


def bench_vgg16():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.zoo import VGG16
    from deeplearning4j_tpu.nn.updater import Nesterovs

    # B=128: +34% over 64 (1389 vs 1037 img/s); 256 is only marginal
    B = int(os.environ.get("BENCH_VGG_BATCH", "128"))
    net = VGG16(num_classes=1000, updater=Nesterovs(0.01, momentum=0.9),
                data_format="NHWC").init()
    net.conf.dtype = "bfloat16"
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, 3, 224, 224)).astype(np.float32))
    y = np.zeros((B, 1000), np.float32)
    y[np.arange(B), rng.integers(0, 1000, B)] = 1.0
    key = jax.random.PRNGKey(0)
    if hasattr(net.conf, "network_inputs"):  # graph
        args = (net.params, net.state, net.updater_state,
                {net.conf.network_inputs[0]: x},
                {net.conf.network_outputs[0]: jnp.asarray(y)}, key,
                None, None)
    else:
        args = (net.params, net.state, net.updater_state, x,
                jnp.asarray(y), key, None, None)
    step, args, k = _fused_step(net, args)
    _, args = _sync_time(step, args, 3, measured=False)  # warmup
    dt, _ = _sync_time(step, args, 10)
    _print_line(json.dumps({"metric": "vgg16_train",
                      "value": round(B * k * 10 / dt, 1),
                      "unit": "images/sec"}), flush=True)


def bench_keras_inception():
    """BASELINE config[3]: InceptionV3-topology .h5 import -> inference."""
    import sys as _sys
    import tempfile
    import jax.numpy as jnp
    import numpy as np
    tests_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tests")
    _sys.path.insert(0, tests_dir)
    try:
        from test_keras_import import (
            _iv3_config_and_weights, write_keras_h5,
        )
    finally:
        _sys.path.remove(tests_dir)
    from deeplearning4j_tpu.modelimport.keras import KerasModelImport

    cfg, weights, _ = _iv3_config_and_weights(classes=1000)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "iv3.h5")
        write_keras_h5(path, cfg, weights)
        net = KerasModelImport.import_keras_model_and_weights(path)
    # imported graphs take the internal NHWC layout + bf16 like native
    # zoo models (outputs equal to the NCHW import, tested)
    net.conf.use_cnn_data_format("NHWC")
    net.conf.dtype = "bfloat16"
    B = int(os.environ.get("BENCH_IV3_BATCH", "32"))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, 3, 299, 299)).astype(np.float32))
    def head(o):  # output() returns an array (single output) or a list
        return o[0] if isinstance(o, (list, tuple)) else o

    out = net.output(x)  # warmup/compile
    float(jnp.sum(head(out)[:1, :1]))
    t0 = time.perf_counter()
    n = 10
    for _ in range(n):
        out = net.output(x)
    float(jnp.sum(head(out)[:1, :1]))
    dt = time.perf_counter() - t0
    _print_line(json.dumps({"metric": "keras_inceptionv3_infer",
                      "value": round(B * n / dt, 1), "unit": "images/sec"}), flush=True)


def bench_attention():
    """Long-context single-chip attention: blockwise (flash-style) causal
    attention at T=32k — the naive [T,T] path would need ~4GB/head and
    OOM; the blockwise scan runs it in O(T*block) memory."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.parallel.sequence import blockwise_attention

    B, H, T, D = 1, 8, int(os.environ.get("BENCH_ATTN_T", "32768")), 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.bfloat16)
    # chained (o feeds back into q) + scalar fetch: no two dispatches
    # are identical, and the fetch cannot return before the chain has run
    f = jax.jit(lambda q, k, v: 0.5 * q +
                0.5 * blockwise_attention(q, k, v, causal=True,
                                          block_size=4096))
    o = f(q, k, v)
    float(jnp.float32(o[0, 0, 0, 0]))
    t0 = time.perf_counter()
    n = 10
    for _ in range(n):
        o = f(o, k, v)
    float(jnp.float32(o[0, 0, 0, 0]))
    dt = (time.perf_counter() - t0) / n
    _print_line(json.dumps({"metric": f"blockwise_attention_T{T}",
                      "value": round(B * T / dt, 1), "unit": "tokens/sec"}), flush=True)


def bench_transformer():
    """Long-context decoder-only LM training on one chip: 6-layer E=512
    TextGenerationTransformer at T=8192 (blockwise attention + per-block
    remat keep HBM bounded)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.zoo import TextGenerationTransformer
    from deeplearning4j_tpu.nn.updater import Adam

    V = 256
    T = int(os.environ.get("BENCH_TFM_T", "8192"))
    B = int(os.environ.get("BENCH_TFM_B", "4"))
    net = TextGenerationTransformer(
        vocab_size=V, embed_dim=512, n_heads=8, n_layers=6, max_length=T,
        block_size=1024, updater=Adam(3e-4)).init()
    net.conf.dtype = "bfloat16"
    rng = np.random.default_rng(0)
    ids = rng.integers(0, V, (B, T))
    x = np.zeros((B, V, T), np.float32)
    x[np.arange(B)[:, None], ids, np.arange(T)[None, :]] = 1.0
    y = np.roll(x, -1, axis=2)
    step = net._get_train_step(False)
    key = jax.random.PRNGKey(0)
    args = (net.params, net.state, net.updater_state,
            {net.conf.network_inputs[0]: jnp.asarray(x)},
            {net.conf.network_outputs[0]: jnp.asarray(y)}, key, None, None)
    _, args = _sync_time(step, args, 3, measured=False)  # warmup
    dt, _ = _sync_time(step, args, 10)
    _print_line(json.dumps({"metric": f"transformer_train_T{T}",
                      "value": round(B * T * 10 / dt, 1),
                      "unit": "tokens/sec"}), flush=True)


def bench_train_plan():
    """Execution-plan A/B/A over the SAME zoo ResNet50 code path users
    run (`execution_plan=` on the builder / fit loops, tuning/plan.py):
    "xla" vs "fused" vs "auto". Tokens of truth for the next live
    window: per-plan img/s, the per-step HBM-traffic model the fused
    plan removes, which blocks/stem each plan engaged, and — with
    BENCH_CALIBRATE=1 — the per-shape store decisions the run wrote
    (KERNEL_CROSSOVER.json), so "auto" stops being a guess the moment
    one window measures it. Env: BENCH_PLAN_BATCH/IMAGE/CLASSES size
    the model (CPU smoke shrinks them), BENCH_PLAN_STEPS the loop."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.tuning import (
        calibrate_training_kernels, default_store,
        modeled_train_step_traffic, winner)
    from deeplearning4j_tpu.zoo import ResNet50
    from deeplearning4j_tpu.nn.updater import Nesterovs

    B = int(os.environ.get("BENCH_PLAN_BATCH",
                           os.environ.get("BENCH_BATCH", "128")))
    IMG = int(os.environ.get("BENCH_PLAN_IMAGE",
                             os.environ.get("BENCH_IMAGE", "224")))
    NC = int(os.environ.get("BENCH_PLAN_CLASSES", "1000"))
    STEPS = int(os.environ.get("BENCH_PLAN_STEPS", "10"))
    calibrate = os.environ.get("BENCH_CALIBRATE") == "1"
    rng = np.random.default_rng(0)
    x = rng.standard_normal((B, 3, IMG, IMG)).astype(np.float32)
    y = np.zeros((B, NC), np.float32)
    y[np.arange(B), rng.integers(0, NC, B)] = 1.0
    rec = {"metric": "train_plan", "unit": "images/sec",
           "batch": B, "image": IMG, "steps": STEPS}

    def leg(plan):
        from deeplearning4j_tpu.tuning.plan import apply_execution_plan
        net = ResNet50(num_classes=NC, height=IMG, width=IMG,
                       updater=Nesterovs(0.1, momentum=0.9),
                       data_format="NHWC",
                       execution_plan=plan).init()
        net.conf.dtype = "bfloat16"
        # re-resolve under bf16 (the crossover keys + stem gate are
        # dtype-keyed; zoo init resolved before the dtype flip)
        resolution = apply_execution_plan(net, plan)
        step, args, k = _fused_step(net, (
            net.params, net.state, net.updater_state,
            {net.conf.network_inputs[0]: jnp.asarray(x)},
            {net.conf.network_outputs[0]: jnp.asarray(y)},
            jax.random.PRNGKey(0), None, None))
        _, args = _sync_time(step, args, 2, measured=False)   # warmup
        dt, _ = _sync_time(step, args, STEPS)
        return (round(B * k * STEPS / dt, 1),
                {"blocks": resolution["blocks"],
                 "stem": resolution["stem"],
                 "level": str(resolution["level"])}, net)

    if calibrate:
        # calibrate FIRST so this very run's "auto" leg resolves from
        # fresh measured entries
        net = ResNet50(num_classes=NC, height=IMG, width=IMG,
                       updater=Nesterovs(0.1, momentum=0.9),
                       data_format="NHWC").init()
        net.conf.dtype = "bfloat16"
        entries = calibrate_training_kernels(
            net, batch_size=min(B, 16), store=default_store(),
            persist=True)
        rec["store_decisions"] = {k: winner(v)
                                 for k, v in entries.items()}
    last_net = None
    for plan in ("xla", "fused", "auto"):
        img_s, info, last_net = leg(plan)
        rec[f"{plan}_img_s"] = img_s
        rec[f"{plan}_resolved"] = info
    # per-step HBM-traffic model (what the fused plan removes) priced
    # against the measured numbers — read off the last leg's net
    # (candidates are plan-independent; no fourth model build)
    rec["hbm_model_bytes_per_step"] = modeled_train_step_traffic(
        last_net, B)
    rec["value"] = rec["fused_img_s"]
    _print_line(json.dumps(rec), flush=True)


def bench_scaling():
    import jax
    if jax.device_count() < 8:
        raise RuntimeError(
            f"scaling_8dev needs 8 devices, found {jax.device_count()} "
            f"({jax.default_backend()}); the virtual-CPU-device dry run "
            "is __graft_entry__.dryrun_multichip, not a measurement")
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
    from deeplearning4j_tpu.zoo import ResNet50
    from deeplearning4j_tpu.nn.updater import Nesterovs
    from deeplearning4j_tpu.datasets.dataset import DataSet

    devices = jax.devices()[:8]
    mesh = make_mesh(devices=devices)
    net = ResNet50(num_classes=1000, height=224, width=224,
                   updater=Nesterovs(0.1, momentum=0.9),
                   data_format="NHWC").init()
    net.conf.dtype = "bfloat16"
    pw = ParallelWrapper(net, mesh=mesh, training_mode="allreduce",
                         prefetch_buffer=0)
    B = 128 * 8
    rng = np.random.default_rng(0)
    x = rng.standard_normal((B, 3, 224, 224)).astype(np.float32)
    y = np.zeros((B, 1000), np.float32)
    y[np.arange(B), rng.integers(0, 1000, B)] = 1.0
    ds = DataSet(x, y)
    pw.fit([ds])  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(10):
        pw.fit([ds])
    dt = time.perf_counter() - t0
    _print_line(json.dumps({"metric": "scaling_8dev",
                      "value": round(B * 10 / dt, 1), "unit": "images/sec"}), flush=True)


def bench_window_attention():
    """Sliding-window local attention at long T: the kernel skips blocks
    outside the window, so cost is O(T*W) — compare against full causal
    attention at the same length."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.parallel.sequence import blockwise_attention

    B, H, T, D = 1, 8, int(os.environ.get("BENCH_ATTN_T", "32768")), 128
    W = int(os.environ.get("BENCH_ATTN_W", "4096"))
    rng = np.random.default_rng(0)
    q0 = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.bfloat16)

    def bench(step, n=10):
        x = step(q0)
        float(jnp.sum(x.astype(jnp.float32)))
        t0 = time.perf_counter()
        for _ in range(n):
            x = step(x)          # chained: defeats execution caching
        float(jnp.sum(x.astype(jnp.float32)))
        return (time.perf_counter() - t0) / n

    # blockwise_attention dispatches to the Pallas kernel on TPU and
    # degrades to the scan path elsewhere (like the sibling benches)
    full = jax.jit(lambda q: 0.5 * q +
                   0.5 * blockwise_attention(q, k, v, causal=True,
                                             block_size=4096))
    local = jax.jit(lambda q: 0.5 * q +
                    0.5 * blockwise_attention(q, k, v, causal=True,
                                              window=W, block_size=4096))
    tf, tl = bench(full), bench(local)
    _print_line(json.dumps({"metric": f"window_attention_T{T}_W{W}",
                      "value": round(B * T / tl, 1), "unit": "tokens/sec",
                      "full_causal_tokens_per_sec": round(B * T / tf, 1)}), flush=True)


def bench_word2vec():
    """Word2Vec skip-gram/NS embedding training throughput (words/sec):
    host pair-gen + batched device scatter-add steps (the reference's
    multithreaded SequenceVectors engine role)."""
    import string

    import numpy as np

    from deeplearning4j_tpu.nlp.sentence import CollectionSentenceIterator
    from deeplearning4j_tpu.nlp.word2vec import Word2Vec

    rng = np.random.default_rng(0)
    letters = np.array(list(string.ascii_lowercase))
    vocab = np.asarray(["".join(rng.choice(letters, 6))
                        for _ in range(20000)])
    probs = 1.0 / np.arange(1, len(vocab) + 1)
    probs /= probs.sum()
    sents = [" ".join(rng.choice(vocab, size=20, p=probs))
             for _ in range(int(os.environ.get("BENCH_W2V_SENTS", "20000")))]
    total_words = 20 * len(sents)
    w2v = Word2Vec(sentence_iterator=CollectionSentenceIterator(sents),
                   layer_size=128, window=5, min_word_frequency=1,
                   iterations=1, epochs=1, negative=5, seed=1,
                   batch_size=65536)  # collision clamp bounds per vocab
    w2v.fit()        # warmup epoch: jit compiles + backend init
    float(np.asarray(w2v.syn0[0, 0]))
    t0 = time.perf_counter()
    w2v.fit()
    # scalar host fetch: dispatches are async, the queue must drain
    float(np.asarray(w2v.syn0[0, 0]))
    dt = time.perf_counter() - t0
    _print_line(json.dumps({"metric": "word2vec_train", "unit": "words/sec",
                      "value": round(total_words / dt, 1)}), flush=True)


def bench_quant():
    """int8 weight-only quantization speedup on a weight-heavy MLP
    (optimize/quantization.py W8A16): chained forwards (no two
    dispatches identical), f32 vs int8 of the SAME compute — the delta
    is pure weight-byte traffic."""
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Adam
    from deeplearning4j_tpu.optimize.quantization import (
        quantize_for_inference)

    H, L, B = 8192, 4, 64
    b = (NeuralNetConfiguration.Builder()
         .seed(1).updater(Adam(1e-3)).weight_init("xavier").list())
    for _ in range(L):
        b.layer(DenseLayer(n_out=H, activation="relu"))
    b.layer(OutputLayer(n_out=64, loss="mcxent", activation="softmax"))
    net = MultiLayerNetwork(b.set_input_type(InputType.feed_forward(H))
                            .build()).init()
    x0 = jnp.asarray(np.random.default_rng(0).standard_normal(
        (B, H)).astype(np.float32))

    def measure(n=30):
        x = x0
        out = net.output(x)
        float(jnp.sum(out[:1, :1]))
        t0 = time.perf_counter()
        for _ in range(n):
            out = net.output(x)
            x = x.at[:, :64].add(out * 1e-9)     # chain
        float(jnp.sum(out[:1, :1]))
        return (time.perf_counter() - t0) / n

    fp = measure()
    quantize_for_inference(net)
    q = measure()
    _print_line(json.dumps({"metric": "quant_mlp_int8_speedup",
                      "value": round(fp / q, 2), "unit": "x",
                      "fp32_ms": round(fp * 1e3, 2),
                      "int8_ms": round(q * 1e3, 2)}), flush=True)


def bench_decode():
    """Serving decode throughput: per-prompt sample_stream vs batched
    sample_stream_batch (B prompts per dispatch — the dispatch-latency
    multiplier on this platform). Greedy, rope positions, bf16."""
    import numpy as np
    from deeplearning4j_tpu.zoo import TextGenerationTransformer

    V, B, STEPS = 2048, 8, 48
    model = TextGenerationTransformer(vocab_size=V, embed_dim=512,
                                      n_heads=8, n_layers=6,
                                      max_length=256, positional="rope")
    net = model.init()
    net.conf.dtype = "bfloat16"
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, V, int(n)))
               for n in rng.integers(8, 24, B)]
    # warm both paths — EVERY prompt once, so all priming chunk shapes
    # compile outside the timed region (jit shapes are per chunk size)
    for p in prompts:
        model.sample_stream(net, p, steps=1, top_k=1)
    model.sample_stream_batch(net, prompts, steps=4, top_k=1)

    t0 = time.perf_counter()
    for p in prompts:
        model.sample_stream(net, p, steps=STEPS, top_k=1)
    dt_seq = time.perf_counter() - t0
    t0 = time.perf_counter()
    model.sample_stream_batch(net, prompts, steps=STEPS, top_k=1)
    dt_batch = time.perf_counter() - t0
    total = B * STEPS
    _print_line(json.dumps({"metric": "decode_batch8_vs_sequential",
                      "value": round(total / dt_batch, 1),
                      "unit": "tokens/sec",
                      "sequential_tokens_per_sec": round(total / dt_seq, 1),
                      "batch_speedup": round(dt_seq / dt_batch, 2)}),
          flush=True)


def bench_specdec():
    """Prompt-lookup speculative decoding vs plain greedy decoding, on a
    model TRAINED TO MEMORIZE its corpus (the round-3 measurement used a
    model that never memorized — near-zero acceptance tells nothing; see
    PERF.md/VERDICT r3 task 5). With acceptance a, speculation needs one
    target dispatch per (a+1) tokens — the decisive lever on this
    dispatch-latency-bound platform. Reports tokens/s both ways + the
    measured dispatch ratio."""
    import numpy as np
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.util import decoding
    from deeplearning4j_tpu.zoo import TextGenerationTransformer

    V, L, STEPS, GAMMA = 64, 96, 64, 4
    model = TextGenerationTransformer(vocab_size=V, embed_dim=128,
                                      n_heads=4, n_layers=2,
                                      max_length=256, positional="rope",
                                      seed=0)
    net = model.init()
    # a strongly periodic corpus the model can memorize quickly
    period = list(range(2, 18))
    seq = (period * (L // len(period) + 1))[:L + 1]
    x = np.zeros((1, V, L), np.float32)
    y = np.zeros((1, V, L), np.float32)
    x[0, seq[:-1], np.arange(L)] = 1.0
    y[0, seq[1:], np.arange(L)] = 1.0
    ds = DataSet(x, y)
    for _ in range(60):
        net.fit(ds)
    prompt = seq[:24]
    # memorization check: greedy continuation should follow the period
    cont = model.sample_stream(net, prompt, steps=8, top_k=1)
    acc_probe = sum(int(cont[24 + i] == seq[24 + i]) for i in range(8))

    proposer = decoding.prompt_lookup_proposer(3)
    model.sample_stream(net, prompt, steps=2, top_k=1)        # warm
    model.speculative_sample(net, proposer, prompt, steps=2, gamma=GAMMA,
                             top_k=1)
    t0 = time.perf_counter()
    plain = model.sample_stream(net, prompt, steps=STEPS, top_k=1)
    dt_plain = time.perf_counter() - t0
    calls = {"n": 0}
    orig = type(net).rnn_time_step

    def counting(self, *a, **k):
        calls["n"] += 1
        return orig(self, *a, **k)

    type(net).rnn_time_step = counting
    try:
        t0 = time.perf_counter()
        spec = model.speculative_sample(net, proposer, prompt,
                                        steps=STEPS, gamma=GAMMA, top_k=1)
        dt_spec = time.perf_counter() - t0
    finally:
        type(net).rnn_time_step = orig
    assert spec == plain, "speculative greedy must equal plain greedy"
    _print_line(json.dumps({
        "metric": "specdec_prompt_lookup",
        "value": round(STEPS / dt_spec, 1),
        "unit": "tokens/sec",
        "plain_tokens_per_sec": round(STEPS / dt_plain, 1),
        "speedup": round(dt_plain / dt_spec, 2),
        "target_dispatches": calls["n"],
        "plain_dispatch_equiv": 1 + STEPS,
        "memorization_probe_8": acc_probe}), flush=True)


def bench_specbatch():
    """Batched speculative decoding (per-row acceptance) vs per-prompt
    speculation vs batched plain decode — the composed serving
    multiplier (speculation's dispatch ratio x batching's rows per
    dispatch)."""
    import numpy as np
    from deeplearning4j_tpu.util import decoding
    from deeplearning4j_tpu.zoo import TextGenerationTransformer

    V, B, STEPS, GAMMA = 2048, 8, 48, 4
    model = TextGenerationTransformer(vocab_size=V, embed_dim=512,
                                      n_heads=8, n_layers=6,
                                      max_length=256, positional="rope")
    net = model.init()
    net.conf.dtype = "bfloat16"
    rng = np.random.default_rng(0)
    base = [list(rng.integers(1, V, 6)) for _ in range(B)]
    prompts = [b * 3 for b in base]        # repetition: lookup can hit
    proposer = decoding.prompt_lookup_proposer(3)
    for p in prompts:                       # warm chunk shapes
        model.speculative_sample(net, proposer, p, steps=2, gamma=GAMMA,
                                 top_k=1)
    model.speculative_sample_batch(net, proposer, prompts, steps=4,
                                   gamma=GAMMA, top_k=1)
    model.sample_stream_batch(net, prompts, steps=4, top_k=1)

    t0 = time.perf_counter()
    for p in prompts:
        model.speculative_sample(net, proposer, p, steps=STEPS,
                                 gamma=GAMMA, top_k=1)
    dt_seq = time.perf_counter() - t0
    t0 = time.perf_counter()
    model.speculative_sample_batch(net, proposer, prompts, steps=STEPS,
                                   gamma=GAMMA, top_k=1)
    dt_batch = time.perf_counter() - t0
    t0 = time.perf_counter()
    model.sample_stream_batch(net, prompts, steps=STEPS, top_k=1)
    dt_plainb = time.perf_counter() - t0
    total = B * STEPS
    _print_line(json.dumps({
        "metric": "specdec_batched8",
        "value": round(total / dt_batch, 1),
        "unit": "tokens/sec",
        "per_prompt_spec_tokens_per_sec": round(total / dt_seq, 1),
        "batched_plain_tokens_per_sec": round(total / dt_plainb, 1),
        "batch_speedup_vs_per_prompt_spec": round(dt_seq / dt_batch, 2),
        "spec_speedup_vs_batched_plain": round(dt_plainb / dt_batch, 2)}),
        flush=True)


def bench_serve_continuous():
    """Continuous-batching serving engine (serving/GenerationEngine) vs
    the static-batch baseline on the SAME staggered request trace:
    requests arrive every STAGGER seconds; the engine admits each into
    a free slot immediately and streams tokens per dispatch, while the
    static baseline waits for the full batch and returns everything at
    the end (one sample_stream_batch call — the pre-engine serving
    shape). Greedy, rope positions, bf16; the record carries how many
    rows agree across the two paths (bit-exact parity vs one-shot
    decoding is pinned by the f32 tier-1 suite). Reports tokens/s and
    mean/p95 time-to-first-token for both."""
    import numpy as np
    from deeplearning4j_tpu.serving import (
        GenerationEngine, ttft_attribution)
    from deeplearning4j_tpu.zoo import TextGenerationTransformer

    V, R, STEPS, SLOTS = 2048, 16, 32, 8
    STAGGER = 0.05      # arrivals spread over ~0.8s — a real trace, not
    # a burst (a zero-stagger burst is static batching's best case)
    model = TextGenerationTransformer(vocab_size=V, embed_dim=512,
                                      n_heads=8, n_layers=6,
                                      max_length=256, positional="rope")
    net = model.init()
    net.conf.dtype = "bfloat16"
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, V, int(n)))
               for n in rng.integers(8, 25, R)]

    # --- continuous batching -----------------------------------------
    eng = GenerationEngine(net, V, slots=SLOTS, queue_limit=R)
    eng.warmup(max_prompt_len=32)      # all prime buckets + decode shape
    eng.start()
    t0 = time.perf_counter()
    handles = []
    for i, p in enumerate(prompts):
        while time.perf_counter() < t0 + i * STAGGER:
            time.sleep(0.001)
        handles.append(eng.submit(p, steps=STEPS, top_k=1,
                                  rng=np.random.default_rng(i)))
    outs = [h.result(timeout=600) for h in handles]
    dt_engine = time.perf_counter() - t0
    eng.shutdown()
    gen_engine = sum(len(o) - len(p) for o, p in zip(outs, prompts))
    ttft_engine = [h.ttft_s for h in handles]

    # --- static batch baseline: wait for the whole trace, then ONE
    # batched decode; every request's first token arrives at batch end
    model.sample_stream_batch(net, prompts, steps=4, top_k=1)   # warm
    arrive = [i * STAGGER for i in range(R)]
    t0 = time.perf_counter()
    time.sleep(arrive[-1])         # the batch waits for its last member
    outs_s = model.sample_stream_batch(net, prompts, steps=STEPS,
                                       top_k=1)
    dt_static = time.perf_counter() - t0
    gen_static = sum(len(o) - len(p) for o, p in zip(outs_s, prompts))
    ttft_static = [dt_static - a for a in arrive]
    # bit-exact engine==one-shot parity is pinned by the f32 tier-1
    # suite; at bf16 the static batch's SHARED left-padded prime can
    # flip near-tie argmaxes vs the per-request prime, so the bench
    # reports agreement instead of asserting it
    match_rows = sum(int(a == b) for a, b in zip(outs, outs_s))

    def p95(v):
        return float(np.percentile(np.asarray(v), 95))

    _print_line(json.dumps({
        "metric": "serve_continuous",
        "value": round(gen_engine / dt_engine, 1),
        "unit": "tokens/sec",
        "static_tokens_per_sec": round(gen_static / dt_static, 1),
        "ttft_mean_ms": round(np.mean(ttft_engine) * 1e3, 1),
        "ttft_p95_ms": round(p95(ttft_engine) * 1e3, 1),
        "static_ttft_mean_ms": round(np.mean(ttft_static) * 1e3, 1),
        "static_ttft_p95_ms": round(p95(ttft_static) * 1e3, 1),
        "requests": R, "slots": SLOTS, "steps": STEPS,
        "stagger_ms": STAGGER * 1e3,
        "static_match_rows": match_rows,
        # where the engine's TTFT went, from the request traces
        # (ISSUE 15): queue wait vs prefill vs placement residue
        "ttft_attribution": ttft_attribution(
            [h.trace() for h in handles])}), flush=True)


def bench_serve_paged():
    """Serving engine v2 vs the PR 5 slot arena on the same staggered
    mixed short/long trace: the paged engine runs 4x the slot engine's
    admitted rows on a TOKEN budget equal to the slot arena's worst
    case (slots x cache_length — paging spends the same HBM, it just
    stops pinning it per slot), with the prefix cache fed by a shared
    system prompt on half the requests. Records tokens/s, mean/p95
    TTFT, p95 TPOT, peak admitted concurrency, and page utilization for
    both paths, plus a speculative sub-leg (prompt-lookup draft over
    repetitive prompts) with its measured acceptance rate.

    PR 10 A/B leg: the paged engine runs the SAME trace twice — the
    direct paged-decode path (kernel on TPU, XLA-fallback elsewhere; no
    per-step gather/scatter round trip) vs the legacy round trip
    (``direct=False``) — and records kv-bytes-moved per generated token
    for both, ASSERTING the round-trip elimination: the direct path's
    per-token KV traffic must be well under the round trip's
    O(2·S·L)-per-step accounting.

    ISSUE 18 leg: the same trace once more with ``kv_dtype="int8"``.
    Adjudicated on mechanism only (modeled kv-bytes per token <= 0.55x
    the bf16 leg; ~2x pages under the same byte budget) — CPU
    wall-clock deltas between these legs are noise and are recorded
    but never asserted. ``BENCH_CALIBRATE=1`` additionally records the
    int8-vs-bf16 verdict into the crossover store (the entry
    ``kv_dtype="auto"`` resolves through).

    The model is sized so a decode dispatch is LATENCY-bound rather
    than FLOP-bound — the TPU serving regime, where a [32,V,1] step
    costs about what an [8,V,1] step does and wider admission is free
    throughput; a CPU-FLOP-bound model would instead just pay 4x the
    arithmetic per step and bury the scheduling effect under matmul
    time."""
    import numpy as np
    from deeplearning4j_tpu.monitoring.events import set_events_enabled
    from deeplearning4j_tpu.monitoring.metrics import MetricsRegistry
    from deeplearning4j_tpu.serving import (
        GenerationEngine, PagedKVConfig, SpeculationConfig,
        ttft_attribution)
    from deeplearning4j_tpu.serving.health import SERVING_SPEC_ACCEPTANCE
    from deeplearning4j_tpu.util.decoding import prompt_lookup_proposer
    from deeplearning4j_tpu.zoo import TextGenerationTransformer

    V, R, STEPS, SLOTS, CONC = 512, 48, 24, 8, 32      # CONC = 4x SLOTS
    STAGGER, PS, L = 0.02, 16, 256
    model = TextGenerationTransformer(vocab_size=V, embed_dim=128,
                                      n_heads=4, n_layers=3,
                                      max_length=L, positional="rope")
    net = model.init()
    net.conf.dtype = "bfloat16"
    rng = np.random.default_rng(0)
    sys_prompt = list(rng.integers(1, V, 16))
    prompts = []
    for i in range(R):
        if i % 4 == 3:                     # 25% long
            p = list(rng.integers(1, V, int(rng.integers(48, 96))))
        else:                              # 75% short
            p = list(rng.integers(1, V, int(rng.integers(4, 16))))
        if i % 2:                          # half share the system prompt
            p = sys_prompt + p[:max(1, len(p) - 16)]
        prompts.append(p)

    import threading

    def run(engine, label):
        engine.warmup(max_prompt_len=112)
        engine.start()
        t0 = time.perf_counter()
        handles, peak, peak_util = [], [0], [0.0]
        tpot, consumers = [], []
        tpot_lock = threading.Lock()
        pool_total = (engine.page_pool.usable
                      if engine.page_pool is not None else 0)

        def watch():
            while not all(h.done for h in handles) or not handles:
                peak[0] = max(peak[0], engine.active_slots())
                if pool_total:
                    # sample utilization LIVE: after the drain every
                    # slot has released its pages and only prefix-cache
                    # residue would remain
                    peak_util[0] = max(
                        peak_util[0],
                        engine.page_pool.used_count() / pool_total)
                if all(h.done for h in handles) and handles:
                    return
                time.sleep(0.002)

        def consume(h):
            # exact host-side inter-token gaps (TPOT) per stream — the
            # engine's own histogram only keeps count/sum
            last = None
            for _ in h:
                now = time.perf_counter()
                if last is not None:
                    with tpot_lock:
                        tpot.append(now - last)
                last = now

        w = threading.Thread(target=watch, daemon=True)
        w.start()
        for i, p in enumerate(prompts):
            while time.perf_counter() < t0 + i * STAGGER:
                time.sleep(0.001)
            h = engine.submit(p, steps=STEPS, top_k=1,
                              rng=np.random.default_rng(i))
            handles.append(h)
            c = threading.Thread(target=consume, args=(h,), daemon=True)
            c.start()
            consumers.append(c)
        outs = [h.result(timeout=600) for h in handles]
        dt = time.perf_counter() - t0
        w.join(timeout=5)
        for c in consumers:
            c.join(timeout=5)
        engine.shutdown()
        gen = sum(len(o) - len(p) for o, p in zip(outs, prompts))
        ttft = [h.ttft_s for h in handles]
        out = {f"{label}_tokens_per_sec": round(gen / dt, 1),
               f"{label}_ttft_mean_ms":
                   round(float(np.mean(ttft)) * 1e3, 1),
               f"{label}_ttft_p95_ms":
                   round(float(np.percentile(ttft, 95)) * 1e3, 1),
               f"{label}_tpot_p95_ms": (
                   round(float(np.percentile(tpot, 95)) * 1e3, 2)
                   if tpot else None),
               f"{label}_peak_active": peak[0],
               f"{label}_page_util": (
                   round(peak_util[0], 3) if pool_total else None),
               f"{label}_ttft_attribution": ttft_attribution(
                   [h.trace() for h in handles])}
        kvt = engine.health().get("kv_traffic")
        if kvt:
            out[f"{label}_decode_path"] = kvt["decode_path"]
            out[f"{label}_kv_bytes_per_token"] = round(
                kvt["bytes_moved_total"] / max(1, gen), 1)
        return out

    # token budget == the slot arena's worst case: SLOTS x L tokens
    budget_pages = SLOTS * (L // PS)
    rec = {"metric": "serve_paged", "unit": "tokens/sec",
           "requests": R, "steps": STEPS, "stagger_ms": STAGGER * 1e3,
           "slot_rows": SLOTS, "paged_rows": CONC, "page_size": PS,
           "total_pages": budget_pages}
    rec.update(run(GenerationEngine(net, V, slots=SLOTS, queue_limit=R),
                   "slot"))
    rec.update(run(GenerationEngine(
        net, V, slots=CONC, queue_limit=R,
        paging=PagedKVConfig(page_size=PS, total_pages=budget_pages)),
        "paged"))
    # A/B: the SAME trace through the legacy gather/scatter round trip
    # (direct=False) — kernel/direct-vs-roundtrip is the PR 10 claim
    rec.update(run(GenerationEngine(
        net, V, slots=CONC, queue_limit=R,
        paging=PagedKVConfig(page_size=PS, total_pages=budget_pages,
                             direct=False)),
        "paged_rt"))
    # tracing overhead A/B (ISSUE 15): the SAME paged trace with the
    # structured-event layer disabled — request tracing is ON by
    # default, so its cost must be within run noise (≤2% is the
    # acceptance band; recorded, with the delta, either way)
    prev_enabled = set_events_enabled(False)
    try:
        rec.update(run(GenerationEngine(
            net, V, slots=CONC, queue_limit=R,
            paging=PagedKVConfig(page_size=PS,
                                 total_pages=budget_pages)),
            "paged_notrace"))
    finally:
        set_events_enabled(prev_enabled)
    rec["tracing_overhead_frac"] = round(
        1.0 - rec["paged_tokens_per_sec"]
        / max(1e-9, rec["paged_notrace_tokens_per_sec"]), 4)

    rec["value"] = rec["paged_tokens_per_sec"]
    rec["admitted_concurrency_x"] = round(
        rec["paged_peak_active"] / max(1, rec["slot_peak_active"]), 2)
    rec["kv_bytes_per_token_x"] = round(
        rec["paged_rt_kv_bytes_per_token"]
        / max(1.0, rec["paged_kv_bytes_per_token"]), 2)
    # the acceptance assertion: the full-arena round trip is GONE from
    # the steady-state step. The XLA fallback still materializes the
    # mapped view once inside the dispatch (the scatter half is
    # eliminated → < 0.7x incl. prefill commits); the kernel path reads
    # only live pages (O(active context) → < 0.5x)
    lim = 0.5 if rec["paged_decode_path"] == "direct-pallas" else 0.7
    assert rec["paged_kv_bytes_per_token"] < \
        lim * rec["paged_rt_kv_bytes_per_token"], rec

    if os.environ.get("BENCH_CALIBRATE") == "1" and \
            rec["paged_decode_path"] == "direct-pallas":
        # record the decode-side crossover (PERF.md: "record the
        # crossover so auto can learn it"): the kernel leg above vs a
        # forced direct-xla leg on the SAME trace, per-token ms into
        # the committed store. Only meaningful where the kernel
        # actually resolved (a CPU backend never runs it).
        eng = GenerationEngine(
            net, V, slots=CONC, queue_limit=R,
            paging=PagedKVConfig(page_size=PS,
                                 total_pages=budget_pages,
                                 decode_impl="xla"))
        rec.update(run(eng, "paged_xla"))
        from deeplearning4j_tpu.tuning import default_store
        store = default_store()
        store.record(eng._decode_key,
                     1e3 / rec["paged_tokens_per_sec"],
                     1e3 / rec["paged_xla_tokens_per_sec"])
        store.save()
        rec["store_decode_recorded"] = eng._decode_key

    # ISSUE 18 A/B leg: the SAME trace with the int8 KV page pool.
    # Adjudicated on MECHANISM, not wall-clock — on CPU the wall-clock
    # deltas between these legs flip sign run-to-run (PERF.md), so the
    # tokens/s numbers are recorded but never asserted. What IS
    # asserted is what quantization actually changes: the modeled
    # kv-bytes-moved per generated token (the engine's own dispatch
    # accounting) and the page-capacity arithmetic under a byte budget.
    eng8 = GenerationEngine(
        net, V, slots=CONC, queue_limit=R,
        paging=PagedKVConfig(page_size=PS, total_pages=budget_pages,
                             kv_dtype="int8"))
    rec.update(run(eng8, "paged_int8"))
    rec["int8_kv_bytes_per_token_frac"] = round(
        rec["paged_int8_kv_bytes_per_token"]
        / max(1.0, rec["paged_kv_bytes_per_token"]), 3)
    # the halving claim: int8 pool reads at 1 byte/element + the scale
    # sidecar must cut the per-token KV traffic to <= 0.55x the bf16
    # leg on whichever direct impl resolved here
    assert rec["int8_kv_bytes_per_token_frac"] <= 0.55, rec
    # capacity: the SAME byte budget admits ~2x the pages (exact
    # admission math — no wall-clock involved). Against a bf16-native
    # pool the ratio is 2x minus the scale sidecar (~2% of a page:
    # 4B x Hkv per half-page vs Hkv*ps*D payload), so the pin is 1.9.
    from deeplearning4j_tpu.serving.quant import kv_page_bytes
    dims = [(h, d) for _, h, d in eng8._quant_dims]
    budget_bytes = budget_pages * kv_page_bytes(dims, PS, "bf16",
                                                net.conf.dtype)
    pages8 = budget_bytes // kv_page_bytes(dims, PS, "int8",
                                           net.conf.dtype)
    rec["int8_capacity_x"] = round(pages8 / budget_pages, 2)
    assert rec["int8_capacity_x"] >= 1.9, rec

    if os.environ.get("BENCH_CALIBRATE") == "1":
        # the quant crossover: int8 is an accuracy trade, so
        # kv_dtype="auto" only turns it on where a calibrated entry
        # says the int8 leg measured faster — record this run's
        # verdict (kernel_ms = int8, fallback_ms = bf16) into the
        # committed store; the store stamps the platform so a CPU
        # verdict can never flip auto on TPU
        from deeplearning4j_tpu.tuning import default_store
        store = default_store()
        store.record(eng8._quant_key,
                     1e3 / rec["paged_int8_tokens_per_sec"],
                     1e3 / rec["paged_tokens_per_sec"])
        store.save()
        rec["store_quant_recorded"] = eng8._quant_key

    # speculative sub-leg: repetitive prompts so prompt-lookup drafts
    # actually land; acceptance rate from the engine's own histogram
    reg = MetricsRegistry()
    spec_prompts = [list(rng.integers(1, V, 6)) * 4 for _ in range(16)]
    eng = GenerationEngine(
        net, V, slots=SLOTS, queue_limit=len(spec_prompts),
        registry=reg, name="engine:spec_bench",
        paging=PagedKVConfig(page_size=PS, total_pages=budget_pages),
        speculation=SpeculationConfig(draft=prompt_lookup_proposer(3),
                                      gamma=4))
    eng.warmup(max_prompt_len=32)
    t0 = time.perf_counter()
    hs = [eng.submit(p, steps=STEPS, top_k=1,
                     rng=np.random.default_rng(i))
          for i, p in enumerate(spec_prompts)]
    eng.run_until_idle()
    outs = [h.result(timeout=0) for h in hs]
    dt = time.perf_counter() - t0
    eng.shutdown()
    gen = sum(len(o) - len(p) for o, p in zip(outs, spec_prompts))
    hist = reg.snapshot_compact().get(
        SERVING_SPEC_ACCEPTANCE + "{model=engine:spec_bench}", {})
    rec["spec_tokens_per_sec"] = round(gen / dt, 1)
    rec["spec_acceptance_rate"] = (
        round(hist["sum"] / hist["count"], 3) if hist.get("count")
        else None)
    rec["spec_tokens_per_dispatch"] = round(gen / max(1, eng._dispatches
                                                      ), 2)
    spec_kvt = eng.health()["kv_traffic"]
    rec["spec_decode_path"] = spec_kvt["decode_path"]
    rec["spec_kv_bytes_per_token"] = round(
        spec_kvt["bytes_moved_total"] / max(1, gen), 1)
    _print_line(json.dumps(rec), flush=True)


def bench_serve_chaos():
    """Serving survivability under fire: the staggered serve_continuous
    trace with (a) a mid-run injected decode fault — supervised
    recovery vs the legacy fail-all — and (b) an overload burst beyond
    queue capacity — SLO shedding vs admit-everything. Records the
    recovered-request count, p95 TTFT with/without recovery (the
    no-recovery column counts only requests that got ANY output), and
    goodput (requests finishing inside their deadline per second) with
    and without shedding. The survivability claim as numbers: a fault
    costs a rebuild, not the batch; shedding keeps admitted requests'
    latency flat instead of letting everyone breach together."""
    import numpy as np
    from deeplearning4j_tpu.resilience import chaos
    from deeplearning4j_tpu.resilience.retry import RestartBudget
    from deeplearning4j_tpu.serving import (
        EngineSupervisor, GenerationEngine, OverloadConfig,
        ServingOverloaded, ttft_attribution)
    from deeplearning4j_tpu.zoo import TextGenerationTransformer

    V, R, STEPS, SLOTS = 512, 24, 24, 4
    STAGGER = 0.02
    model = TextGenerationTransformer(vocab_size=V, embed_dim=128,
                                      n_heads=4, n_layers=3,
                                      max_length=128, positional="rope")
    net = model.init()
    net.conf.dtype = "bfloat16"
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, V, int(n)))
               for n in rng.integers(6, 20, R)]

    def trace(supervised: bool):
        """The same staggered trace; a FaultBurstInjector kills one
        mid-run decode dispatch. Supervised: arena rebuild, everyone
        finishes. Unsupervised: the legacy fail-all."""
        eng = GenerationEngine(
            net, V, slots=SLOTS, queue_limit=R,
            supervisor=(EngineSupervisor(budget=RestartBudget(3, 60.0))
                        if supervised else None))
        eng.warmup(max_prompt_len=32)
        # arm the fault AFTER warmup so it lands ~30 dispatches into
        # real traffic (warmup consumes dispatch indices too)
        eng._decode_chaos = chaos.FaultBurstInjector(
            n=eng._dispatches + 30, k=1)
        eng.start()
        t0 = time.perf_counter()
        handles = []
        for i, p in enumerate(prompts):
            while time.perf_counter() < t0 + i * STAGGER:
                time.sleep(0.001)
            try:
                handles.append(eng.submit(p, steps=STEPS, top_k=1,
                                          rng=np.random.default_rng(i)))
            except Exception:  # noqa: BLE001 — fail-all refuses late submits
                handles.append(None)
        done, failed = 0, 0
        ttft = []
        for h in handles:
            if h is None:
                failed += 1
                continue
            try:
                h.result(timeout=600)
                done += 1
                ttft.append(h.ttft_s)
            except Exception:  # noqa: BLE001 — the fail-all path
                failed += 1
                if h.ttft_s is not None:
                    ttft.append(h.ttft_s)
        dt = time.perf_counter() - t0
        sup = eng._supervisor
        rec = {
            "completed": done, "failed": failed,
            "wall_s": round(dt, 2),
            "ttft_p95_ms": (round(float(np.percentile(ttft, 95)) * 1e3,
                                  1) if ttft else None),
            "rebuilds": sup.rebuilds if sup else 0,
            "recovered_requests": sup.recovered_requests if sup else 0,
            # trace-derived attribution incl. rebuild counts: the
            # recovery column shows its rebuilds here, the fail-all
            # column its truncated TTFT window
            "ttft_attribution": ttft_attribution(
                [h.trace() for h in handles if h is not None]),
        }
        eng.shutdown()
        return rec

    def overload_burst(shedding: bool):
        """2x-capacity burst of deadline-carrying requests: shedding
        (tight SLO + early rejection) vs admit-everything. Goodput =
        requests that finished INSIDE their deadline, per second."""
        ov = OverloadConfig(queue_wait_slo_s=0.3, min_samples=4,
                            breach_window=8, shed_to_depth=SLOTS,
                            early_reject=True) if shedding else None
        eng = GenerationEngine(net, V, slots=SLOTS, queue_limit=4 * R,
                               overload=ov)
        eng.warmup(max_prompt_len=32)
        eng.start()
        t0 = time.perf_counter()
        handles, shed = [], 0
        for i, p in enumerate(prompts * 2):       # the burst: 2x trace
            try:
                handles.append((eng.submit(
                    p, steps=STEPS, top_k=1, timeout=8.0,
                    rng=np.random.default_rng(i)), i))
            except ServingOverloaded:
                shed += 1
        good, late, ttft = 0, 0, []
        for h, i in handles:
            try:
                h.result(timeout=600)
                good += 1
                ttft.append(h.ttft_s)
            except ServingOverloaded:
                shed += 1
            except Exception:  # noqa: BLE001 — deadline expiries
                late += 1
                if h.ttft_s is not None:   # admitted, prefilled, missed
                    ttft.append(h.ttft_s)
        dt = time.perf_counter() - t0
        eng.shutdown()
        return {
            "goodput_req_per_s": round(good / dt, 2),
            "good": good, "deadline_missed": late, "shed": shed,
            "admitted_ttft_p95_ms": (
                round(float(np.percentile(ttft, 95)) * 1e3, 1)
                if ttft else None),
        }

    rec = {"metric": "serve_chaos", "unit": "requests_recovered",
           "requests": R, "steps": STEPS, "slots": SLOTS,
           "stagger_ms": STAGGER * 1e3,
           "recovery": trace(supervised=True),
           "fail_all": trace(supervised=False),
           "shedding": overload_burst(shedding=True),
           "no_shedding": overload_burst(shedding=False)}
    rec["value"] = rec["recovery"]["recovered_requests"]
    _print_line(json.dumps(rec), flush=True)


def bench_serve_fleet():
    """The serving fleet (ISSUE 14): a staggered mixed trace with three
    shared system-prompt families over 1 -> 2 -> 3 replicas (p95 TTFT
    should stay flat as replicas join — the fleet absorbs the same
    trace with less queueing), a kill-one-replica-mid-trace sub-leg at
    3 replicas (every request completes; migrated-request count
    recorded), an affinity-on vs affinity-off A/B at 2 replicas
    (aggregate prefix-cache hit-rate delta — affinity routes families
    where their blocks are warm), and the zero-retraces-after-warmup
    delta across the whole 3-replica trace including migration."""
    import numpy as np
    from deeplearning4j_tpu import monitoring
    from deeplearning4j_tpu.monitoring import runtime
    from deeplearning4j_tpu.monitoring.metrics import MetricsRegistry
    from deeplearning4j_tpu.serving import (
        FleetConfig, FleetRouter, GenerationEngine, PagedKVConfig,
        ttft_attribution)
    from deeplearning4j_tpu.zoo import TextGenerationTransformer

    # the trace must OVERLOAD one replica (deep queue at 2 slots) so
    # the fleet's measured effect is queue relief; on this shared-CPU
    # A/B the replicas also contend for cores, which real fleets
    # (one chip per replica) don't — flat TTFT can only be judged with
    # one chip per replica, which neither fleet has run on yet
    V, R, STEPS, SLOTS, PS = 256, 24, 24, 2, 8
    STAGGER = 0.005
    model_kw = dict(vocab_size=V, embed_dim=64, n_heads=4, n_layers=2,
                    max_length=64, positional="rope")
    rng = np.random.default_rng(0)
    families = [list(rng.integers(1, V, 2 * PS)) for _ in range(3)]
    prompts = [families[i % 3] + list(rng.integers(1, V,
                                                   int(rng.integers(2, 8))))
               for i in range(R)]

    def factory(made):
        """Engine factory recording every engine it built into `made`
        — the dead-replica-inclusive aggregation base (a killed
        replica's prefix hits must still count in the trace totals
        after the router drops it from health())."""
        def make(rid):
            net = TextGenerationTransformer(**model_kw).init()
            net.conf.dtype = "bfloat16"
            eng = GenerationEngine(
                net, V, slots=SLOTS, queue_limit=R,
                paging=PagedKVConfig(page_size=PS))
            made.append(eng)
            return eng
        return make

    def compile_total():
        c = monitoring.global_registry().get(runtime.COMPILE_COUNTER)
        return 0.0 if c is None else c.total()

    def trace(n_replicas, affinity=True, kill=False):
        reg = MetricsRegistry()
        engines = []
        fleet = FleetRouter(
            factory(engines), replicas=n_replicas,
            config=FleetConfig(affinity=affinity), registry=reg,
            name=f"bench{n_replicas}")
        fleet.warmup(max_prompt_len=32)
        warm = compile_total()
        fleet.start()
        t0 = time.perf_counter()
        handles = []
        killed_at = None
        for i, p in enumerate(prompts):
            while time.perf_counter() < t0 + i * STAGGER:
                time.sleep(0.001)
            if kill and i == R // 2:
                victim = max(fleet.replicas(),
                             key=lambda r: r.engine.active_slots())
                victim.engine._stop.set()   # simulated process death
                killed_at = i
            handles.append(fleet.submit(p, steps=STEPS, top_k=1,
                                        rng=np.random.default_rng(i)))
        done, ttft = 0, []
        for h in handles:
            try:
                h.result(timeout=600)
                done += 1
                if h.ttft_s is not None:
                    ttft.append(h.ttft_s)
            except Exception:  # noqa: BLE001 — count completions
                pass
        dt = time.perf_counter() - t0
        gen = sum(len(h.ids) - len(h.prompt) for h in handles if h.done)
        # aggregate over every engine the trace created — health()
        # still answers on a killed replica, and its pre-death hits
        # belong in the totals
        healths = [e.health() for e in engines]
        hits = sum(h["prefix_cache"]["hits"] for h in healths)
        misses = sum(h["prefix_cache"]["misses"] for h in healths)
        rec = {
            "completed": done, "wall_s": round(dt, 2),
            "tokens_per_sec": round(gen / dt, 1),
            "ttft_p95_ms": (round(float(np.percentile(ttft, 95)) * 1e3,
                                  1) if ttft else None),
            "prefix_hit_rate": round(hits / max(1, hits + misses), 3),
            "retraces_after_warmup": compile_total() - warm,
            # per-request trace decomposition: at 1 replica the queue
            # term dominates; added replicas should move queue wait,
            # not prefill — the attribution names which
            "ttft_attribution": ttft_attribution(
                [h.trace() for h in handles]),
        }
        if kill:
            rec.update({"killed_at_request": killed_at,
                        "migrations": fleet.migrations,
                        "migrated_requests": fleet.migrated_requests,
                        "replicas_left": len(fleet.replicas())})
        fleet.shutdown()
        return rec

    by_size = {n: trace(n) for n in (1, 2, 3)}
    kill_rec = trace(3, kill=True)
    no_aff = trace(2, affinity=False)
    rec = {"metric": "serve_fleet", "unit": "requests_completed",
           "requests": R, "steps": STEPS,
           "slots_per_replica": SLOTS, "stagger_ms": STAGGER * 1e3,
           "families": len(families),
           "replicas": {str(n): by_size[n] for n in by_size},
           "kill_mid_trace": kill_rec,
           "affinity_off_2x": no_aff,
           "affinity_hit_rate_delta": round(
               by_size[2]["prefix_hit_rate"]
               - no_aff["prefix_hit_rate"], 3)}
    rec["value"] = kill_rec["completed"]
    _print_line(json.dumps(rec), flush=True)


def bench_serve_fleet_procs():
    """Cross-process serving fleet (ISSUE 19): the serve_fleet trace
    over 1 -> 2 -> 3 replica PROCESSES (real fleet_worker subprocesses,
    shared-fs transport, out-of-process router), plus a kill -9 sub-leg
    at 3 processes. Adjudicates on MECHANISM only — every request
    completes at every size, the kill-one leg completes all 24/24 on
    survivors, and each replica runs under its own pid (its own
    interpreter and GIL — the per-process independence an in-process
    fleet cannot have). The workers are forced onto the CPU backend
    (``fleet/worker.py`` has no device assignment, so N workers on one
    TPU host would each try to claim every local chip — neither fleet
    has been brought up on more than one chip); the record says
    ``"workers": "cpu"``. tok/s and p95 TTFT are recorded but NEVER
    asserted: on shared CPU the replica processes contend for the same
    cores."""
    import shutil
    import subprocess
    import tempfile
    import textwrap
    import threading

    import numpy as np
    from deeplearning4j_tpu.monitoring.metrics import MetricsRegistry
    from deeplearning4j_tpu.serving import ProcessFleetRouter
    from deeplearning4j_tpu.serving.fleet import FleetConfig
    from deeplearning4j_tpu.serving.fleet import worker as fleet_worker

    V, R, STEPS, PS = 256, 24, 24, 8
    STAGGER, TTL = 0.005, 1.0
    rng = np.random.default_rng(0)
    families = [list(rng.integers(1, V, 2 * PS)) for _ in range(3)]
    prompts = [families[i % 3] + list(rng.integers(1, V,
                                                   int(rng.integers(2, 8))))
               for i in range(R)]
    repo_root = os.path.dirname(os.path.abspath(__file__))

    def write_builder(dirpath):
        # the worker builder, self-contained: every process builds a
        # bit-identical engine (fixed init seed) — same shape as the
        # in-process serve_fleet leg's factory
        with open(os.path.join(dirpath, "procfleet_builder.py"),
                  "w") as f:
            f.write(textwrap.dedent('''
                def build(rid):
                    from deeplearning4j_tpu.serving import (
                        GenerationEngine, PagedKVConfig)
                    from deeplearning4j_tpu.zoo import (
                        TextGenerationTransformer)
                    net = TextGenerationTransformer(
                        vocab_size=256, embed_dim=64, n_heads=4,
                        n_layers=2, max_length=64,
                        positional="rope").init()
                    net.conf.dtype = "bfloat16"
                    return GenerationEngine(
                        net, 256, slots=2, queue_limit=24,
                        paging=PagedKVConfig(page_size=8))
            '''))

    def trace(n_procs, kill=False):
        td = tempfile.mkdtemp(prefix="procfleet_")
        root = os.path.join(td, "fleet")
        write_builder(td)
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = td + os.pathsep + repo_root \
            + os.pathsep + env.get("PYTHONPATH", "")
        procs, logs = {}, {}
        for rid in range(n_procs):
            logs[rid] = open(os.path.join(td, f"agent{rid}.log"), "w")
            procs[rid] = fleet_worker.spawn(
                root, rid, "procfleet_builder:build", warmup=True,
                ttl=TTL, env=env, cwd=repo_root, stdout=logs[rid],
                stderr=subprocess.STDOUT)
        router = ProcessFleetRouter(
            root, config=FleetConfig(lease_ttl_s=TTL),
            registry=MetricsRegistry(), name=f"procbench{n_procs}")
        try:
            deadline = time.monotonic() + 600
            while router.live_replicas() != list(range(n_procs)):
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"agents never came up: {router.live_replicas()}")
                time.sleep(0.1)
            pids = sorted(st["pid"] for st
                          in router.status.read_all().values())
            router.start()
            handles, submit_t, first_t = [], {}, {}
            stop = threading.Event()

            def watch():     # TTFT observer: first RELAYED token
                while not stop.is_set():
                    now = time.perf_counter()
                    for h in list(handles):
                        if id(h) not in first_t and h.generated:
                            first_t[id(h)] = now
                    time.sleep(0.001)

            watcher = threading.Thread(target=watch, daemon=True)
            watcher.start()
            t0 = time.perf_counter()
            killed_at = victim = None
            for i, p in enumerate(prompts):
                while time.perf_counter() < t0 + i * STAGGER:
                    time.sleep(0.001)
                if kill and i == R // 2:
                    placed = [rid for rid, _
                              in router.assignments().values()]
                    victim = max(set(placed) or {0}, key=placed.count)
                    procs[victim].kill()    # SIGKILL: a real corpse
                    procs[victim].wait(timeout=30)
                    killed_at = i
                h = router.submit(p, steps=STEPS, top_k=1,
                                  rng=np.random.default_rng(i))
                submit_t[id(h)] = time.perf_counter()
                handles.append(h)
            done = 0
            for h in handles:
                try:
                    h.result(timeout=600)
                    done += 1
                except Exception:  # noqa: BLE001 — count completions
                    pass
            dt = time.perf_counter() - t0
            stop.set()
            watcher.join(timeout=2)
            gen = sum(len(h.generated) for h in handles if h.done)
            ttft = [first_t[k] - submit_t[k] for k in first_t]
            rec = {"completed": done, "wall_s": round(dt, 2),
                   "tokens_per_sec": round(gen / dt, 1),
                   "ttft_p95_ms": (round(float(
                       np.percentile(ttft, 95)) * 1e3, 1)
                       if ttft else None),
                   # one OS process (own pid, own GIL) per replica
                   "pids": pids,
                   "distinct_pids": len(set(pids)) == n_procs
                   and os.getpid() not in pids}
            if kill:
                rec.update({"killed_at_request": killed_at,
                            "victim": victim,
                            "dead_replicas": router.dead_replicas,
                            "replaced_requests":
                                router.replaced_requests,
                            "replicas_left":
                                len(router.live_replicas())})
            return rec
        finally:
            try:
                router.shutdown(stop_agents=True)
            except Exception:  # noqa: BLE001 — teardown must not mask
                pass
            for rid, proc in procs.items():
                try:
                    proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    proc.kill()
                logs[rid].close()
            shutil.rmtree(td, ignore_errors=True)

    by_size = {n: trace(n) for n in (1, 2, 3)}
    kill_rec = trace(3, kill=True)
    rec = {"metric": "serve_fleet_procs", "unit": "requests_completed",
           "requests": R, "steps": STEPS, "stagger_ms": STAGGER * 1e3,
           "lease_ttl_s": TTL,
           # the replica processes never touch the chip (see docstring)
           "workers": "cpu",
           "processes": {str(n): by_size[n] for n in by_size},
           "kill_mid_trace": kill_rec}
    rec["value"] = kill_rec["completed"]
    _print_line(json.dumps(rec), flush=True)


def bench_serve_disagg():
    """Disaggregated prefill/decode serving (ISSUE 20): one mixed
    long/short-prompt trace served twice — unified (a single engine)
    and disaggregated (a ``role="prefill"`` agent + 2 decode replicas,
    KV pages shipped through the content-addressed page store, decode
    placement by page locality) — in-process and deterministic.
    Adjudicates on MECHANISM only, per PERF.md's CPU-noise policy:
    ``bit_exact`` (every stream identical across the two modes),
    ``prefill_routed`` == the long-prompt count, and
    ``decode_fresh_prefill_blocks`` == 0 (zero store misses — every
    shipped-prefix request re-primes from imported or locally-held
    pages, executing ZERO full-block prefill steps on a decode
    replica). Page-ship bytes and store hit/miss counts ride in every
    record; tok/s and wall_s are recorded for a later on-chip comparison
    but NEVER asserted."""
    import copy
    import shutil
    import tempfile

    import numpy as np
    from deeplearning4j_tpu.serving import (
        GenerationEngine, PagedKVConfig, PageStore, PrefillAgent,
        ProcessFleetRouter, ReplicaAgent)
    from deeplearning4j_tpu.serving.fleet import FleetConfig
    from deeplearning4j_tpu.zoo import TextGenerationTransformer

    V, R, STEPS, PS, TTL = 256, 24, 16, 8, 30.0
    rng = np.random.default_rng(0)
    # 3 shared prompt families (system prompts), each 3 full KV blocks;
    # 2 of every 3 requests are long (family + a short unique tail),
    # the rest short enough that no usable full block exists
    families = [list(rng.integers(1, V, 3 * PS)) for _ in range(3)]
    prompts = []
    for i in range(R):
        if i % 3 == 2:
            prompts.append(list(rng.integers(
                1, V, int(rng.integers(3, PS)))))
        else:
            prompts.append(families[i % 3] + list(rng.integers(
                1, V, int(rng.integers(1, 5)))))
    n_long = sum(1 for p in prompts if (len(p) - 1) // PS >= 1)
    net = TextGenerationTransformer(
        vocab_size=V, embed_dim=64, n_heads=4, n_layers=2,
        max_length=64, positional="rope").init()

    def engine():
        return GenerationEngine(
            copy.deepcopy(net), V, slots=4, queue_limit=R,
            paging=PagedKVConfig(page_size=PS, total_pages=96))

    def submit_all(target):
        hs = []
        for i, p in enumerate(prompts):
            kw = (dict(top_k=1) if i % 2 == 0
                  else dict(temperature=1.3, top_p=0.9))
            hs.append(target.submit(
                p, steps=STEPS, rng=np.random.default_rng(i), **kw))
        return hs

    # -- unified leg: ONE engine, same requests ------------------------
    eng = engine()
    t0 = time.perf_counter()
    hs = submit_all(eng)
    while not all(h.done for h in hs):
        eng.step()
    uni_dt = time.perf_counter() - t0
    uni_ids = [h.ids for h in hs]
    uni_gen = sum(len(h.generated) for h in hs)
    eng.shutdown()

    # -- disagg leg: prefill pool + decode pool + page store -----------
    td = tempfile.mkdtemp(prefix="disagg_")
    store = PageStore(td)
    pre = PrefillAgent(engine(), store, td, 10, ttl=TTL)
    decs = []
    for rid in range(2):
        e = engine()
        # lazy bf16 pools materialize at the first surviving prime;
        # one tiny unique-token request makes imports live from the
        # very first real admission (what --warmup gives a worker)
        h = e.submit([V - 1 - rid], steps=2, top_k=1,
                     rng=np.random.default_rng(10_000 + rid))
        while not h.done:
            e.step()
        decs.append(ReplicaAgent(e, td, rid, ttl=TTL,
                                 page_store=store, import_pages=True))
    for a in decs:
        a.write_status()
    pre.write_status()
    router = ProcessFleetRouter(
        td, config=FleetConfig(disagg=True, lease_ttl_s=TTL),
        name="disaggbench")
    try:
        t0 = time.perf_counter()
        hs = submit_all(router)
        deadline = t0 + 600
        while not all(h.done for h in hs):
            if time.perf_counter() > deadline:
                raise RuntimeError(
                    f"disagg leg stalled: "
                    f"{sum(h.done for h in hs)}/{R} done")
            pre.poll_once()
            for a in decs:
                a.poll_once()
                a.step()
                a.publish_progress()
                a.write_status()
            router.relay()
        dis_dt = time.perf_counter() - t0
        dis_gen = sum(len(h.generated) for h in hs)
        health = router.health()
        rec = {"metric": "serve_disagg", "unit": "requests_completed",
               "requests": R, "steps": STEPS, "page_size": PS,
               "long_prompts": n_long,
               "completed": sum(1 for h in hs if h.done
                                and h.error is None),
               # THE adjudicated mechanism pins
               "bit_exact": [h.ids for h in hs] == uni_ids,
               "prefill_routed": health["prefill_routed"],
               "locality_hits": health["locality_hits"],
               "decode_fresh_prefill_blocks":
                   sum(a.store_misses for a in decs),
               # page-ship accounting, in every record
               "store": {"published": store.published,
                         "publish_bytes": store.publish_bytes,
                         "hits": sum(a.store_hits for a in decs),
                         "misses": sum(a.store_misses for a in decs),
                         "imported": sum(a.pages_imported
                                         for a in decs),
                         "import_bytes": sum(a.import_bytes
                                             for a in decs),
                         "quarantined": store.corrupt},
               # recorded for comparison only — NEVER asserted on CPU
               "unified": {"wall_s": round(uni_dt, 2),
                           "tokens_per_sec": round(uni_gen / uni_dt,
                                                   1)},
               "disagg": {"wall_s": round(dis_dt, 2),
                          "tokens_per_sec": round(dis_gen / dis_dt,
                                                  1)}}
        rec["value"] = rec["completed"]
        _print_line(json.dumps(rec), flush=True)
    finally:
        try:
            router.shutdown()
        except Exception:  # noqa: BLE001 — teardown must not mask
            pass
        pre.close()
        for a in decs:
            a.close()
        shutil.rmtree(td, ignore_errors=True)


def _converge_run(net, x, y, steps, record_every):
    """Fixed-seed training loop recording the loss trajectory. Each
    recorded point is a scalar host fetch — a real sync — and since
    params change every step no two dispatches are identical."""
    import jax
    import jax.numpy as jnp
    step = net._get_train_step(False)
    if hasattr(net.conf, "network_inputs"):
        inputs = {net.conf.network_inputs[0]: jnp.asarray(x)}
        labels = {net.conf.network_outputs[0]: jnp.asarray(y)}
    else:
        inputs, labels = jnp.asarray(x), jnp.asarray(y)
    key = jax.random.PRNGKey(0)
    p, s, u = net.params, net.state, net.updater_state
    traj = []
    for i in range(1, steps + 1):
        p, s, u, loss = step(p, s, u, inputs, labels, key, None, None)
        if i <= 5 or i % record_every == 0 or i == steps:
            traj.append(round(float(loss), 6))
    net.params, net.state, net.updater_state = p, s, u
    return traj


def _converge_fixture_path(name):
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tests", "fixtures", f"convergence_{name}_cpu.json")


def _converge_report(name, traj, steps, extra=None):
    """Compare a trajectory against the committed CPU fixture (generated
    by running this entry with JAX_PLATFORMS=cpu BENCH_ALLOW_CPU=1
    BENCH_WRITE_FIXTURE=1)
    and print the one-line record. Tolerances: the first 5 steps are
    pre-chaos and must track within 5%; by the end the plans/platforms
    have decorrelated chaotically, so the bar is the mean of the last 3
    recorded losses within 15% plus a >50% total decrease on both sides
    — the honest envelope for 'same arithmetic, same convergence'."""
    import numpy as np
    import jax
    path = _converge_fixture_path(name)
    rec = {"metric": f"converge_{name}",
           "platform": jax.devices()[0].platform,
           "steps_recorded": len(traj), "first": traj[0],
           "final_mean3": round(float(np.mean(traj[-3:])), 6),
           **(extra or {})}
    if os.environ.get("BENCH_WRITE_FIXTURE") == "1":
        with open(path, "w") as f:
            json.dump({"trajectory": traj, "steps": steps,
                       **(extra or {})}, f)
        rec["fixture_written"] = path
    elif os.path.exists(path):
        with open(path) as f:
            ref = json.load(f)
        rt = ref["trajectory"]
        if ref.get("steps") != steps or len(rt) != len(traj):
            # a config mismatch is not chip-arithmetic divergence —
            # refuse the comparison rather than misattribute it
            rec["vs_cpu"] = (f"fixture mismatch: fixture steps="
                             f"{ref.get('steps')}/{len(rt)} pts vs run "
                             f"{steps}/{len(traj)} pts")
            _print_line(json.dumps(rec), flush=True)
            return
        early = [abs(a - b) / max(abs(b), 1e-9)
                 for a, b in zip(traj[:5], rt[:5])]
        fin_a = float(np.mean(traj[-3:]))
        fin_b = float(np.mean(rt[-3:]))
        final_dev = abs(fin_a - fin_b) / max(abs(fin_b), 1e-9)
        decreased = (traj[-1] < 0.5 * traj[0]
                     and rt[-1] < 0.5 * rt[0])
        # when BOTH runs collapsed the loss to noise level (<2% of the
        # starting loss) AND land within an order of magnitude of each
        # other, the relative final_dev is comparing bf16 noise against
        # bf16 noise — both-collapsed IS the parity verdict there. The
        # ratio cap keeps a plateau-at-floor bug (e.g. 0.12 vs 2e-4,
        # both technically under floor) from being waved through.
        floor = 0.02 * rt[0]
        lo, hi = sorted((max(fin_a, 1e-9), max(fin_b, 1e-9)))
        collapsed = fin_a < floor and fin_b < floor and hi <= 10 * lo
        rec["vs_cpu"] = {
            "max_early_dev": round(max(early), 4),
            "final_dev": round(final_dev, 4),
            "both_collapsed": collapsed,
            "ok": bool(max(early) < 0.05 and decreased
                       and (collapsed or final_dev < 0.15))}
    else:
        rec["vs_cpu"] = "no fixture (generate with BENCH_WRITE_FIXTURE=1 "
        rec["vs_cpu"] += "on cpu)"
    _print_line(json.dumps(rec), flush=True)


def bench_converge_lenet():
    """On-chip convergence evidence (VERDICT r5 task 3b): LeNet trained
    to accuracy on the deterministic synthetic MNIST stand-in (this
    build is zero-egress — no real IDX files; the parity claim is
    numerical: chip arithmetic trains exactly like CPU on identical
    data). ref: deeplearning4j-zoo/.../LeNet.java + BASELINE configs[0]."""
    import numpy as np
    from deeplearning4j_tpu.datasets.fetchers import MnistDataSetIterator
    from deeplearning4j_tpu.zoo import LeNet
    from deeplearning4j_tpu.nn.updater import Adam

    steps = int(os.environ.get("BENCH_CONV_STEPS", "300"))
    it = MnistDataSetIterator(batch_size=4096, synthetic=True,
                              num_examples=4096, shuffle=False, seed=11)
    ds = next(iter(it))
    x = np.asarray(ds.features).reshape(-1, 1, 28, 28)
    y = np.asarray(ds.labels)
    net = LeNet(num_classes=10, updater=Adam(0.001)).init()
    traj = _converge_run(net, x[:2048], y[:2048], steps, 10)
    # held-out accuracy on the remaining synthetic rows
    out = np.asarray(net.output(x[2048:]))
    acc = float((out.argmax(1) == y[2048:].argmax(1)).mean())
    _converge_report("lenet", traj, steps, {"holdout_acc": round(acc, 4)})


def bench_converge_resnet():
    """On-chip convergence evidence (VERDICT r5 task 3a): fixed-seed
    100-step ResNet50 loss trajectory, chip vs the committed CPU
    fixture. BENCH_FUSE=2 runs the fused-bottleneck plan (same
    comparison: the plans are equivalence-pinned; the chip run proves
    the arithmetic on real hardware). Reduced shapes (64x64, batch 16)
    keep the CPU fixture generable in minutes; the arithmetic exercised
    is the full ResNet50 graph."""
    import numpy as np
    from deeplearning4j_tpu.zoo import ResNet50
    from deeplearning4j_tpu.nn.updater import Nesterovs

    steps = int(os.environ.get("BENCH_CONV_STEPS", "100"))
    fuse = {"0": False, "1": True, "2": "bottleneck"}.get(
        os.environ.get("BENCH_FUSE", "0"), False)
    net = ResNet50(num_classes=100, height=64, width=64,
                   updater=Nesterovs(0.005, momentum=0.9),
                   data_format="NHWC", fuse=fuse).init()
    net.conf.dtype = "bfloat16"
    rng = np.random.default_rng(3)
    labels = rng.integers(0, 100, 16)
    x = (rng.standard_normal((16, 3, 64, 64))
         + labels[:, None, None, None] * 0.03).astype(np.float32)
    y = np.zeros((16, 100), np.float32)
    y[np.arange(16), labels] = 1.0
    traj = _converge_run(net, x, y, steps, 10)
    _converge_report("resnet", traj, steps, {"fuse": str(fuse)})


def bench_checkpoint_stall():
    """Durability tax, measured (ISSUE 7): per-step fit overhead with
    checkpointing off / sync / async at a fixed cadence. The async claim
    — "the fit loop blocks only for the device→host snapshot" — becomes
    a number: stall ms per save for each mode, plus bytes committed and
    the steps/s delta vs checkpointing off. Same net, same seed, same
    synthetic stream in all three legs."""
    import shutil
    import tempfile

    from deeplearning4j_tpu.datasets.iterators import \
        BenchmarkDataSetIterator
    from deeplearning4j_tpu.monitoring.metrics import global_registry
    from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Adam
    from deeplearning4j_tpu.resilience.durable import CKPT_BYTES
    from deeplearning4j_tpu.util.checkpoint import CheckpointListener

    steps = int(os.environ.get("BENCH_CKPT_STEPS", "60"))
    cadence = int(os.environ.get("BENCH_CKPT_EVERY", "10"))
    width = int(os.environ.get("BENCH_CKPT_WIDTH", "512"))

    def build():
        conf = (NeuralNetConfiguration.Builder()
                .seed(7).updater(Adam(0.001)).list()
                .layer(DenseLayer(n_out=width, activation="relu"))
                .layer(DenseLayer(n_out=width, activation="relu"))
                .layer(OutputLayer(n_out=10, loss="mcxent",
                                   activation="softmax"))
                .set_input_type(InputType.feed_forward(256))
                .build())
        return MultiLayerNetwork(conf).init()

    def bytes_total():
        c = global_registry().get(CKPT_BYTES)
        return 0.0 if c is None else c.total()

    def leg(mode):
        it = BenchmarkDataSetIterator((64, 256), 10, steps)
        net = build()
        ckdir = tempfile.mkdtemp(prefix=f"bench_ckpt_{mode}_")
        lst = None
        if mode != "off":
            lst = CheckpointListener(ckdir, save_every_n_iterations=cadence,
                                     keep_last=2, async_save=(mode == "async"))
            net.set_listeners(lst)
        net.fit(it, epochs=1, batch_size=64)  # warmup epoch: traces compile
        b0, t0 = bytes_total(), time.perf_counter()
        it2 = BenchmarkDataSetIterator((64, 256), 10, steps)
        net.fit(it2, epochs=1, batch_size=64)
        elapsed = time.perf_counter() - t0
        if lst is not None:
            lst.flush(timeout=120)
            lst.close()
        saves = max(1, steps // cadence) if mode != "off" else 0
        shutil.rmtree(ckdir, ignore_errors=True)
        return {"elapsed_s": round(elapsed, 4),
                "steps_per_s": round(steps / elapsed, 2),
                "saves": saves,
                "ckpt_bytes": int(bytes_total() - b0)}

    res = {m: leg(m) for m in ("off", "sync", "async")}
    for m in ("sync", "async"):
        extra = res[m]["elapsed_s"] - res["off"]["elapsed_s"]
        res[m]["stall_ms_per_save"] = round(
            max(0.0, extra) / res[m]["saves"] * 1000.0, 3)
        res[m]["steps_per_s_delta_pct"] = round(
            100.0 * (res[m]["steps_per_s"] / res["off"]["steps_per_s"] - 1),
            2)
    _print_line(json.dumps({
        "metric": "checkpoint_stall",
        "value": res["async"]["stall_ms_per_save"],
        "unit": "ms_per_save_async",
        "steps": steps, "cadence": cadence,
        "sync_stall_ms_per_save": res["sync"]["stall_ms_per_save"],
        "modes": res}))


ALL = {"resnet": bench_resnet, "lstm": bench_lstm, "lenet": bench_lenet,
       "vgg16": bench_vgg16, "inception": bench_keras_inception,
       "attention": bench_attention, "transformer": bench_transformer,
       "scaling": bench_scaling, "word2vec": bench_word2vec,
       "window": bench_window_attention, "quant": bench_quant,
       "decode": bench_decode, "specdec": bench_specdec,
       "specbatch": bench_specbatch,
       "train_plan": bench_train_plan,
       "serve_continuous": bench_serve_continuous,
       "serve_paged": bench_serve_paged,
       "serve_chaos": bench_serve_chaos,
       "serve_fleet": bench_serve_fleet,
       "serve_fleet_procs": bench_serve_fleet_procs,
       "serve_disagg": bench_serve_disagg,
       "checkpoint_stall": bench_checkpoint_stall,
       "converge_lenet": bench_converge_lenet,
       "converge_resnet": bench_converge_resnet}

if __name__ == "__main__":
    import jax

    from deeplearning4j_tpu import monitoring
    from deeplearning4j_tpu.util.compile_cache import (
        configure_compile_cache)
    configure_compile_cache()
    if jax.default_backend() != "tpu" \
            and os.environ.get("BENCH_ALLOW_CPU") != "1":
        _print_line(json.dumps({
            "metric": "bench_all", "value": None, "unit": None,
            "error": "tpu-unavailable",
            "detail": f"backend is {jax.default_backend()!r}; set "
                      "BENCH_ALLOW_CPU=1 for CPU smoke runs"}))
        sys.exit(3)
    # count jit compiles + declare span series before any bench runs
    monitoring.ensure_started()
    # scaling (8 devices) is not in the default set: it fails on the
    # 1- and 4-chip hosts these legs otherwise run on
    names = sys.argv[1:] or ["resnet", "lstm", "lenet", "vgg16",
                             "inception", "attention", "transformer",
                             "word2vec"]
    for n in names:
        ALL[n]()
