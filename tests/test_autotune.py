"""Kernel-crossover autotuning (tuning/): the measured per-shape store,
and execution-plan resolution on the fit loops.

Contracts pinned here (ISSUE 11 acceptance):
- store lifecycle: calibrate → persist → a FRESH store (fresh process
  stand-in) resolves "auto" (training plans) from the stored
  timings; no entry → current defaults; platform-mismatched
  entry → ignored with a warning;
- ratchet/prune: repeated records merge (running mean), entries from a
  stale kernel revision are dropped on load;
- fit-loop plan matrix: `net.fit(..., execution_plan="fused")` matches
  `"xla"` (params / opt-state / score trajectory) with the non-finite
  sentinel ON, including the fused K-step scan path, with zero
  retraces after warmup.
"""

import logging
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from deeplearning4j_tpu.monitoring.metrics import global_registry
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.graph_conf import ElementWiseVertex
from deeplearning4j_tpu.nn.conf.layers import (
    ActivationLayer, BatchNormalization, ConvolutionLayer, DenseLayer,
    GlobalPoolingLayer, OutputLayer, SubsamplingLayer, ZeroPaddingLayer)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater import Nesterovs
from deeplearning4j_tpu.tuning import (
    IMPL_REVS, KernelCrossoverStore, apply_execution_plan,
    bottleneck_fingerprint, calibrate_training_kernels, default_store,
    fingerprint, modeled_train_step_traffic, reset_default_store,
    stem_fingerprint)
from deeplearning4j_tpu.tuning import crossover as crossover_mod
from deeplearning4j_tpu.tuning.crossover import (
    AUTOTUNE_CALIBRATIONS, AUTOTUNE_DECISIONS)
from deeplearning4j_tpu.tuning.plan import _block_key, _stem_key


@pytest.fixture(autouse=True)
def _fresh_default_store():
    reset_default_store(KernelCrossoverStore(path="/nonexistent/none"))
    yield
    reset_default_store(None)


def tiny_resnet_graph(h=16, w=16, seed=3):
    """One fused-stem chain + one identity bottleneck — every fusable
    pattern at CPU-test sizes."""
    g = (NeuralNetConfiguration.Builder().seed(seed)
         .updater(Nesterovs(0.05, momentum=0.9)).weight_init("relu")
         .graph_builder().add_inputs("input")
         .set_input_types(InputType.convolutional(h, w, 3)))
    g.add_layer("stem_pad", ZeroPaddingLayer(padding=(3, 3, 3, 3)),
                "input")
    g.add_layer("stem_conv",
                ConvolutionLayer(n_out=8, kernel=(7, 7), stride=(2, 2),
                                 padding=(0, 0), activation="identity",
                                 has_bias=False), "stem_pad")
    g.add_layer("stem_bn", BatchNormalization(), "stem_conv")
    g.add_layer("stem_act", ActivationLayer(activation="relu"),
                "stem_bn")
    g.add_layer("stem_pool",
                SubsamplingLayer(pooling_type="max", kernel=(3, 3),
                                 stride=(2, 2), padding=(1, 1)),
                "stem_act")

    def conv_bn(name, n_out, kernel, pad, inp, act="relu"):
        g.add_layer(f"{name}_conv",
                    ConvolutionLayer(n_out=n_out, kernel=kernel,
                                     stride=(1, 1), padding=pad,
                                     activation="identity",
                                     has_bias=False), inp)
        g.add_layer(f"{name}_bn", BatchNormalization(), f"{name}_conv")
        if act:
            g.add_layer(f"{name}_act",
                        ActivationLayer(activation=act), f"{name}_bn")
            return f"{name}_act"
        return f"{name}_bn"

    x = conv_bn("b_a", 4, (1, 1), (0, 0), "stem_pool")
    x = conv_bn("b_b", 4, (3, 3), (1, 1), x)
    x = conv_bn("b_c", 8, (1, 1), (0, 0), x, act=None)
    g.add_vertex("b_add", ElementWiseVertex(op="add"), x, "stem_pool")
    g.add_layer("b_out", ActivationLayer(activation="relu"), "b_add")
    g.add_layer("avgpool", GlobalPoolingLayer(pooling_type="avg"),
                "b_out")
    g.add_layer("output", OutputLayer(n_out=5, loss="mcxent",
                                      activation="softmax"), "avgpool")
    conf = g.set_outputs("output").build()
    conf.use_cnn_data_format("NHWC")
    return ComputationGraph(conf).init()


def xor_mlp():
    conf = (NeuralNetConfiguration.Builder().seed(1)
            .updater(Nesterovs(0.1, momentum=0.9)).weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=2, loss="mcxent",
                               activation="softmax"))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


def small_batch(h=16, w=16, n=4, classes=5, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 3, h, w)).astype(np.float32)
    y = np.zeros((n, classes), np.float32)
    y[np.arange(n), rng.integers(0, classes, n)] = 1.0
    return x, y


# ---------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------
class TestFingerprint:
    def test_stable_and_sorted(self):
        a = fingerprint("d", "float32", b=2, a=1)
        b = fingerprint("d", "float32", a=1, b=2)
        assert a == b == "d|a=1,b=2|f32"

    def test_dtype_normalization(self):
        assert fingerprint("d", "bfloat16").endswith("|bf16")
        assert fingerprint("d", None).endswith("|any")

    def test_domain_helpers(self):
        k = bottleneck_fingerprint(14, 14, 1024, 256, 1024, 1, False,
                                   "bfloat16")
        assert k.startswith("train_bottleneck|")
        assert stem_fingerprint(224, 224, 3, 64, "bfloat16") \
            .startswith("train_stem|")


# ---------------------------------------------------------------------
# the store: roundtrip / ratchet / prune / platform guard / telemetry
# ---------------------------------------------------------------------
class TestStore:
    def test_record_save_load_roundtrip(self, tmp_path):
        p = str(tmp_path / "KERNEL_CROSSOVER.json")
        s = KernelCrossoverStore(path=p)
        key = fingerprint("train_bottleneck", "float32", h=4)
        s.record(key, 1.5, 3.0)
        s.save()
        s2 = KernelCrossoverStore.load(p)
        e = s2.lookup(key)
        assert e is not None
        assert e["kernel_ms"] == 1.5 and e["fallback_ms"] == 3.0
        assert e["platform"] == jax.default_backend()
        assert s2.choose(key) == "kernel"

    def test_ratchet_running_mean(self):
        s = KernelCrossoverStore(path="/nonexistent/none")
        key = fingerprint("train_stem", "float32", h=8)
        s.record(key, 1.0, 2.0)
        e = s.record(key, 3.0, 4.0)
        assert e["samples"] == 2
        assert e["kernel_ms"] == pytest.approx(2.0)
        assert e["fallback_ms"] == pytest.approx(3.0)

    def test_stale_impl_rev_pruned_on_load(self, tmp_path):
        p = str(tmp_path / "KERNEL_CROSSOVER.json")
        s = KernelCrossoverStore(path=p)
        key = fingerprint("train_bottleneck", "float32", h=4)
        s.record(key, 1.0, 2.0)
        s._entries[key]["impl_rev"] = IMPL_REVS["train_bottleneck"] - 1
        s.save()
        s2 = KernelCrossoverStore.load(p)
        assert len(s2) == 0
        assert s2.choose(key, default="fallback") == "fallback"

    def test_platform_mismatch_refused_with_warning(self, caplog):
        key = fingerprint("train_stem", "bfloat16", h=16)
        s = KernelCrossoverStore(entries={key: {
            "kernel_ms": 1.0, "fallback_ms": 2.0, "platform": "tpu",
            "device_kind": "TPU v5e",
            "impl_rev": IMPL_REVS["train_stem"], "samples": 1}})
        with caplog.at_level(logging.WARNING):
            assert s.lookup(key) is None
            assert s.choose(key, default="fallback") == "fallback"
        assert any("calibrated on tpu" in r.message
                   for r in caplog.records)

    def test_torn_store_file_is_uncalibrated(self, tmp_path):
        p = tmp_path / "KERNEL_CROSSOVER.json"
        p.write_text("{ torn json")
        s = KernelCrossoverStore.load(str(p))
        assert len(s) == 0

    def test_missing_entry_yields_default(self):
        s = KernelCrossoverStore(path="/nonexistent/none")
        assert s.choose("train_stem|h=1|f32") is None
        assert s.choose("train_stem|h=1|f32", default="kernel") \
            == "kernel"

    def test_invalid_timings_rejected(self):
        s = KernelCrossoverStore(path="/nonexistent/none")
        with pytest.raises(ValueError):
            s.record("d|x|f32", 0.0, 1.0)

    def test_decision_and_calibration_telemetry(self):
        reg = global_registry()
        dec = reg.counter(AUTOTUNE_DECISIONS, "", ("domain", "choice"))
        cal = reg.counter(AUTOTUNE_CALIBRATIONS, "",
                          ("domain", "choice"))
        d0 = dec.value(domain="train_stem", choice="kernel")
        c0 = cal.value(domain="train_stem", choice="kernel")
        u0 = dec.value(domain="train_stem", choice="default")
        s = KernelCrossoverStore(path="/nonexistent/none")
        key = fingerprint("train_stem", "float32", h=9)
        s.choose(key)                       # default (uncalibrated)
        s.record(key, 1.0, 5.0)             # calibration, kernel wins
        s.choose(key)                       # decision: kernel
        assert dec.value(domain="train_stem", choice="kernel") == d0 + 1
        assert cal.value(domain="train_stem", choice="kernel") == c0 + 1
        assert dec.value(domain="train_stem", choice="default") \
            == u0 + 1


class TestCalibrateHarness:
    def test_calibrate_records_and_persists(self, tmp_path,
                                            monkeypatch):
        times = iter([1.25, 4.0])
        monkeypatch.setattr(crossover_mod, "_time_thunk",
                            lambda fn, w, i: next(times))
        p = str(tmp_path / "KERNEL_CROSSOVER.json")
        s = KernelCrossoverStore(path=p)
        key = fingerprint("train_stem", "float32", h=8)
        e = s.calibrate(key, lambda: None, lambda: None, persist=True)
        assert e["kernel_ms"] == 1.25 and e["fallback_ms"] == 4.0
        assert os.path.exists(p)
        assert KernelCrossoverStore.load(p).choose(key) == "kernel"

    def test_training_kernel_harness_fills_every_shape(self, tmp_path):
        net = tiny_resnet_graph()
        s = KernelCrossoverStore(
            path=str(tmp_path / "KERNEL_CROSSOVER.json"))
        out = calibrate_training_kernels(net, batch_size=2, store=s,
                                         warmup=0, iters=1,
                                         persist=True)
        bc, sc = net.fusion_candidates()
        assert len(out) == len(bc) + len(sc)
        s2 = KernelCrossoverStore.load(s.path)
        for grp in bc.values():
            assert s2.lookup(_block_key(grp, "float32")) is not None
        for grp in sc.values():
            assert s2.lookup(_stem_key(grp, "float32")) is not None


# ---------------------------------------------------------------------
# execution-plan resolution
# ---------------------------------------------------------------------
class TestPlanResolution:
    def test_invalid_plan_raises(self):
        with pytest.raises(ValueError):
            apply_execution_plan(tiny_resnet_graph(), "fast")

    def test_none_leaves_plan_untouched(self):
        net = tiny_resnet_graph()
        net.set_fusion("bottleneck")
        assert apply_execution_plan(net, None) is None
        assert net.fuse_bn_act_conv == "bottleneck"

    def test_xla_and_fused(self):
        net = tiny_resnet_graph()
        s = KernelCrossoverStore(path="/nonexistent/none")
        r = apply_execution_plan(net, "fused", store=s)
        assert r["level"] == "bottleneck" and r["blocks"] == 1
        assert not r["stem"]          # stem is store-gated even here
        _, _, bplan = net._fusion()
        assert list(bplan) == ["b_out"]
        r = apply_execution_plan(net, "xla", store=s)
        assert r["level"] is False
        assert net.fuse_bn_act_conv is False

    def test_auto_uncalibrated_is_xla(self):
        net = tiny_resnet_graph()
        s = KernelCrossoverStore(path="/nonexistent/none")
        r = apply_execution_plan(net, "auto", store=s)
        assert r["level"] is False and r["blocks"] == 0
        assert all(v["choice"] == "fallback" for v in r["keys"].values())

    def test_auto_resolves_per_shape_from_store(self, tmp_path):
        """calibrate → persist → a FRESH store resolves auto: block +
        stem engage exactly where the stored timings say kernel."""
        net = tiny_resnet_graph()
        bc, sc = net.fusion_candidates()
        p = str(tmp_path / "KERNEL_CROSSOVER.json")
        s = KernelCrossoverStore(path=p)
        s.record(_block_key(bc["b_out"], "float32"), 1.0, 3.0)
        s.record(_stem_key(sc["stem_pool"], "float32"), 1.0, 3.0)
        s.save()
        fresh = KernelCrossoverStore.load(p)     # fresh-process stand-in
        r = apply_execution_plan(net, "auto", store=fresh)
        assert r["blocks"] == 1 and r["stem"]
        assert list(net._stem_plan()) == ["stem_pool"]
        # flip the verdicts: kernel loses both → back to the XLA plan
        for _ in range(9):
            s.record(_block_key(bc["b_out"], "float32"), 99.0, 3.0)
            s.record(_stem_key(sc["stem_pool"], "float32"), 99.0, 3.0)
        r = apply_execution_plan(net, "auto", store=s)
        assert r["level"] is False and not r["stem"]

    def test_fused_engages_stem_when_store_says_win(self):
        net = tiny_resnet_graph()
        _, sc = net.fusion_candidates()
        s = KernelCrossoverStore(path="/nonexistent/none")
        s.record(_stem_key(sc["stem_pool"], "float32"), 1.0, 3.0)
        r = apply_execution_plan(net, "fused", store=s)
        assert r["stem"] and r["blocks"] == 1

    def test_mln_plan_is_noop_but_validates(self):
        net = xor_mlp()
        r = apply_execution_plan(net, "fused")
        assert r["level"] is False and r["blocks"] == 0
        with pytest.raises(ValueError):
            apply_execution_plan(net, "bogus")

    def test_zoo_fuse_and_plan_mutually_exclusive(self):
        from deeplearning4j_tpu.zoo import ResNet50
        with pytest.raises(ValueError):
            ResNet50(num_classes=10, height=64, width=64,
                     fuse="bottleneck", execution_plan="fused",
                     data_format="NHWC").init()

    def test_candidates_recompute_on_dtype_flip(self):
        """The bench workflow: build at f32, flip conf.dtype to bf16,
        re-resolve — the dtype-dependent VMEM gates (224 stem passes at
        bf16, fails at f32) must see the NEW dtype, not a stale cache."""
        from deeplearning4j_tpu.zoo import ResNet50
        net = ResNet50(num_classes=10, height=224, width=224,
                       data_format="NHWC").init()
        _, sc_f32 = net.fusion_candidates()
        assert not sc_f32              # f32 stem exceeds the budget
        net.conf.dtype = "bfloat16"
        _, sc_bf16 = net.fusion_candidates()
        assert list(sc_bf16) == ["stem_pool"]
        # and the store-taught auto plan can actually engage it now
        s = KernelCrossoverStore(path="/nonexistent/none")
        s.record(_stem_key(sc_bf16["stem_pool"], "bfloat16"), 1.0, 3.0)
        r = apply_execution_plan(net, "auto", store=s)
        assert r["stem"]

    def test_traffic_model_shape(self):
        net = tiny_resnet_graph()
        t = modeled_train_step_traffic(net, 32)
        assert t["blocks"] == 1 and t["stems"] == 1
        assert 0 < t["fused_bytes"] < t["xla_bytes"]


# ---------------------------------------------------------------------
# fit-loop plan matrix: fused == xla, sentinel ON, scan path, retraces
# ---------------------------------------------------------------------
def _fit_and_capture(execution_plan, *, k=1, epochs=2, seed=3):
    net = tiny_resnet_graph(seed=seed)
    net.nonfinite_policy = "skip"           # the non-finite sentinel ON
    x, y = small_batch()
    net.fit(x, y, epochs=epochs, batch_size=2, steps_per_dispatch=k,
            execution_plan=execution_plan)
    score = float(net.score_value)
    return net, score


class TestFitPlanMatrix:
    def test_fused_matches_xla_per_batch(self):
        s = KernelCrossoverStore(path="/nonexistent/none")
        reset_default_store(s)
        net_x, score_x = _fit_and_capture("xla")
        net_f, score_f = _fit_and_capture("fused")
        assert net_f._fusion()[2], "fused plan did not engage"
        assert score_f == pytest.approx(score_x, rel=2e-5, abs=2e-6)
        for a, b in zip(jax.tree_util.tree_leaves(net_x.params),
                        jax.tree_util.tree_leaves(net_f.params)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=5e-5, rtol=5e-4)
        for a, b in zip(
                jax.tree_util.tree_leaves(net_x.updater_state),
                jax.tree_util.tree_leaves(net_f.updater_state)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=5e-5, rtol=5e-4)

    def test_fused_matches_xla_scan_path(self):
        net_x, score_x = _fit_and_capture("xla", k=2)
        net_f, score_f = _fit_and_capture("fused", k=2)
        assert score_f == pytest.approx(score_x, rel=2e-5, abs=2e-6)
        for a, b in zip(jax.tree_util.tree_leaves(net_x.params),
                        jax.tree_util.tree_leaves(net_f.params)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=5e-5, rtol=5e-4)

    def test_mln_fused_is_bit_identical_to_xla(self):
        """Sequential nets: the plan seam exists, nothing fuses — the
        two plans are the SAME compiled step, bit-identical."""
        rng = np.random.default_rng(0)
        x = rng.standard_normal((16, 4)).astype(np.float32)
        y = np.zeros((16, 2), np.float32)
        y[np.arange(16), rng.integers(0, 2, 16)] = 1.0
        nets = []
        for plan in ("xla", "fused"):
            net = xor_mlp()
            net.nonfinite_policy = "skip"
            net.fit(x, y, epochs=2, batch_size=8, execution_plan=plan)
            nets.append(net)
        for a, b in zip(jax.tree_util.tree_leaves(nets[0].params),
                        jax.tree_util.tree_leaves(nets[1].params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_zero_retraces_after_warmup(self):
        from deeplearning4j_tpu import monitoring
        from deeplearning4j_tpu.monitoring import runtime

        def compile_total():
            c = monitoring.global_registry().get(runtime.COMPILE_COUNTER)
            return 0.0 if c is None else c.total()

        monitoring.ensure_started()
        net = tiny_resnet_graph()
        x, y = small_batch()
        net.fit(x, y, epochs=1, batch_size=2, execution_plan="fused")
        warm = compile_total()
        net.fit(x, y, epochs=2, batch_size=2, execution_plan="fused")
        assert compile_total() == warm, (
            "re-resolving the same execution plan retraced the step")

    def test_plan_switch_rebuilds_then_stays_stable(self):
        net = tiny_resnet_graph()
        x, y = small_batch()
        net.fit(x, y, epochs=1, batch_size=2, execution_plan="fused")
        assert net._fusion()[2]
        net.fit(x, y, epochs=1, batch_size=2, execution_plan="xla")
        assert not net._fusion()[2]

    def test_parallel_wrapper_plan_seam(self):
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
        net = xor_mlp()
        pw = ParallelWrapper(net, training_mode="allreduce",
                             prefetch_buffer=0)
        rng = np.random.default_rng(0)
        x = rng.standard_normal((16, 4)).astype(np.float32)
        y = np.zeros((16, 2), np.float32)
        y[np.arange(16), rng.integers(0, 2, 16)] = 1.0
        pw.fit(x, y, epochs=1, batch_size=8, execution_plan="fused")
        assert np.isfinite(float(net.score_value))
