"""Resilience layer (ISSUE 4): the chaos lane.

Contracts proven here:

- `resilience.retry`: bounded exponential backoff with jitter; metrics.
- `resilience.chaos`: deterministic injectors with once-latch and
  batch-preserving raise semantics.
- Non-finite sentinel: each fit loop (MultiLayerNetwork per-batch AND
  fused-scan, ComputationGraph, ParallelWrapper) completes under a
  NaN-poisoned batch, ends within tolerance of a fault-free run, and
  the skipped-update counters are observable in the metrics registry —
  with zero added steady-state host syncs (test_input_pipeline's
  no-retrace guards run with the sentinel on by default).
- Recovery: prefetch-worker death and SIGTERM-style mid-epoch kill both
  finish via FaultTolerantTrainer restart; divergence triggers rollback
  to the last GOOD-tagged checkpoint with LR backoff.
- Prefetch worker shutdown audit: a worker error can never vanish —
  it reaches the consumer or (consumer gone) the logged stop path.
- Serving: per-request deadlines, fail_fast admission, error
  propagation to waiting output() callers in batched AND sequential
  modes, health/readiness gauges.
"""

import random
import threading
import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import (
    ArrayDataSetIterator, DataSetIterator)
from deeplearning4j_tpu.monitoring.metrics import (
    MetricsRegistry, global_registry)
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater import Adam, Sgd
from deeplearning4j_tpu.parallel.inference import (
    InferenceTimeout, ParallelInference, ServingQueueFull,
    SERVING_DEADLINE_EXCEEDED, SERVING_ERRORS, SERVING_HEALTHY,
    SERVING_QUEUE_REJECTED, SERVING_READY, SERVING_REQUESTS)
from deeplearning4j_tpu.pipeline.prefetch import DevicePrefetchIterator
from deeplearning4j_tpu.resilience import chaos, sentinel
from deeplearning4j_tpu.resilience.retry import (
    RETRIES, RETRY_EXHAUSTED, RetryPolicy, retry_call)
from deeplearning4j_tpu.resilience.watchdog import (
    DivergenceError, DivergenceWatchdog)
from deeplearning4j_tpu.util.checkpoint import (
    list_checkpoints, list_good_checkpoints, save_checkpoint)
from deeplearning4j_tpu.util.recovery import RESTARTS, FaultTolerantTrainer

RNG = np.random.default_rng(7)


def data(n=64, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 4)).astype(np.float32)
    y = np.zeros((n, 2), np.float32)
    y[np.arange(n), (x[:, 0] > 0).astype(int)] = 1.0
    return x, y


def mlp(seed=3, lr=0.01, updater=None):
    conf = (NeuralNetConfiguration.Builder()
            .seed(seed).updater(updater or Adam(lr)).weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=2, loss="mcxent", activation="softmax"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    return MultiLayerNetwork(conf).init()


def small_graph(seed=3):
    b = (NeuralNetConfiguration.Builder()
         .seed(seed).updater(Adam(0.01)).weight_init("xavier")
         .graph_builder()
         .add_inputs("in")
         .add_layer("d", DenseLayer(n_out=8, activation="tanh"), "in")
         .add_layer("out", OutputLayer(n_out=2, loss="mcxent",
                                       activation="softmax"), "d")
         .set_outputs("out")
         .set_input_types(InputType.feed_forward(4)))
    return ComputationGraph(b.build()).init()


def params_finite(net) -> bool:
    return all(bool(np.isfinite(np.asarray(l)).all())
               for l in jax.tree_util.tree_leaves(net.params))


def acct_of(net) -> sentinel.SentinelAccounting:
    acct = sentinel.flush_accounting(net)
    assert acct is not None, "sentinel accounting never materialized"
    return acct


# ---------------------------------------------------------------------
# retry helper
# ---------------------------------------------------------------------
class TestRetry:
    def test_delay_grows_and_caps(self):
        p = RetryPolicy(base_delay=0.1, multiplier=2.0, max_delay=0.5,
                        jitter=0.0)
        assert [p.delay(i) for i in (1, 2, 3, 4)] == [0.1, 0.2, 0.4, 0.5]

    def test_jitter_is_deterministic_with_rng(self):
        p = RetryPolicy(base_delay=1.0, jitter=0.5)
        assert p.delay(1, random.Random(0)) == \
            p.delay(1, random.Random(0))
        assert 0.5 <= p.delay(1, random.Random(1)) <= 1.0

    def test_policy_validation(self):
        with pytest.raises(ValueError):
            RetryPolicy(max_attempts=0)
        with pytest.raises(ValueError):
            RetryPolicy(jitter=1.5)
        with pytest.raises(ValueError):
            RetryPolicy(multiplier=0.5)

    def test_succeeds_after_transient_failures(self):
        reg = MetricsRegistry()
        calls = []
        sleeps = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        out = retry_call(flaky, policy=RetryPolicy(max_attempts=3,
                                                   jitter=0.0),
                         sleep=sleeps.append, registry=reg)
        assert out == "ok" and len(calls) == 3
        assert len(sleeps) == 2 and sleeps[1] > sleeps[0]  # backoff grew
        assert reg.get(RETRIES).total() == 2

    def test_exhaustion_reraises_and_counts(self):
        reg = MetricsRegistry()
        with pytest.raises(OSError, match="always"):
            retry_call(lambda: (_ for _ in ()).throw(OSError("always")),
                       policy=RetryPolicy(max_attempts=2, jitter=0.0),
                       sleep=lambda s: None, registry=reg, op="doomed")
        assert reg.get(RETRY_EXHAUSTED).value(op="doomed") == 1

    def test_retryable_decorator_passes_user_kwargs_through(self):
        seen = {}

        from deeplearning4j_tpu.resilience.retry import retryable

        @retryable(policy=RetryPolicy(max_attempts=1))
        def sample(path, rng=None, sleep=None):
            seen.update(path=path, rng=rng, sleep=sleep)
            return "done"

        # kwargs that shadow retry_call's own options must reach the
        # function, not the retry machinery
        assert sample("p", rng="user-rng", sleep="user-sleep") == "done"
        assert seen == {"path": "p", "rng": "user-rng",
                        "sleep": "user-sleep"}

    def test_non_retryable_passes_straight_through(self):
        calls = []

        def bad():
            calls.append(1)
            raise KeyError("not transient")

        with pytest.raises(KeyError):
            retry_call(bad, policy=RetryPolicy(retry_on=(OSError,)),
                       sleep=lambda s: None)
        assert len(calls) == 1


# ---------------------------------------------------------------------
# chaos injectors
# ---------------------------------------------------------------------
class TestChaosInjectors:
    def _base(self, n=32, batch=8):
        x, y = data(n)
        return ArrayDataSetIterator(x, y, batch, shuffle=False)

    def test_raise_on_batch_once_preserves_the_batch(self):
        it = chaos.RaiseOnBatch(self._base(), n=1)
        cur = iter(it)
        b0 = next(cur)
        with pytest.raises(chaos.InjectedFault):
            next(cur)
        # the raise did NOT consume the batch: retrying the same cursor
        # delivers batch 1, and the remaining stream is intact
        b1 = next(cur)
        rest = list(cur)
        assert len(rest) == 2
        ref = list(iter(self._base()))
        np.testing.assert_array_equal(b0.features, ref[0].features)
        np.testing.assert_array_equal(b1.features, ref[1].features)

    def test_once_latch_spans_passes(self):
        it = chaos.RaiseOnBatch(self._base(), n=2)
        with pytest.raises(chaos.InjectedFault):
            list(iter(it))
        # second pass (new epoch): the latch holds, stream is clean
        assert len(list(iter(it))) == 4

    def test_nan_poison_targets_one_batch(self):
        it = chaos.NaNPoisonIterator(self._base(), n=1)
        batches = list(iter(it))
        assert not np.isfinite(batches[1].features).any()
        assert np.isfinite(batches[0].features).all()
        assert np.isfinite(batches[2].features).all()
        assert batches[1].features.shape == batches[0].features.shape

    def test_nan_poison_labels_field(self):
        it = chaos.NaNPoisonIterator(self._base(), n=0, field="labels")
        b0 = next(iter(it))
        assert np.isfinite(b0.features).all()
        assert not np.isfinite(b0.labels).any()

    def test_preemption_and_latency(self):
        it = chaos.PreemptionIterator(self._base(), n=3)
        with pytest.raises(chaos.SimulatedPreemption):
            list(iter(it))
        assert len(list(iter(it))) == 4  # once

        lat = chaos.LatencyIterator(self._base(), seconds=0.01, every=2)
        t0 = time.perf_counter()
        assert len(list(iter(lat))) == 4
        assert time.perf_counter() - t0 >= 0.02


# ---------------------------------------------------------------------
# non-finite sentinel: unit semantics
# ---------------------------------------------------------------------
class TestSentinelUnits:
    def test_where_finite_merges_missing_leaves(self):
        import jax.numpy as jnp
        ok = jnp.asarray(False)
        new = {"0": {"W": jnp.ones((2,)), "h": jnp.full((3,), 9.0)}}
        old = {"0": {"W": jnp.zeros((2,))}}  # no "h" carry pre-step
        out = sentinel.where_finite(ok, new, old)
        np.testing.assert_array_equal(np.asarray(out["0"]["W"]),
                                      np.zeros(2))  # guarded: kept old
        # a first-materialization leaf (RNN carry on chunk 0) has no
        # pre-step value: a BAD step must zero it (the absent-carry
        # semantic), not smuggle the poisoned value through
        np.testing.assert_array_equal(np.asarray(out["0"]["h"]),
                                      np.zeros(3))
        good = sentinel.where_finite(jnp.asarray(True), new, old)
        np.testing.assert_array_equal(np.asarray(good["0"]["h"]),
                                      np.full(3, 9.0))

    def test_tree_finite(self):
        import jax.numpy as jnp
        good = {"a": jnp.ones((2, 2))}
        bad = {"a": jnp.asarray([1.0, jnp.nan])}
        assert bool(sentinel.tree_finite(jnp.asarray(1.0), good))
        assert not bool(sentinel.tree_finite(jnp.asarray(1.0), bad))
        assert not bool(sentinel.tree_finite(jnp.asarray(jnp.inf), good))

    def test_cadence_flush_never_waits_on_inflight_steps(self):
        """The auto-flush at flush_every settles only READY flags — an
        in-flight device computation is left pending (no dispatch-queue
        stall); force-flush (watchdog/checkpoint/end-of-fit) takes all."""
        class _Inflight:
            def __init__(self, v):
                self.v = v

            def is_ready(self):
                return False

            def __array__(self, dtype=None, copy=None):
                return np.asarray(self.v)

        a = sentinel.SentinelAccounting("M", flush_every=2,
                                        registry=MetricsRegistry())
        a.record(_Inflight(False), skipped=True)
        a.record(_Inflight(False), skipped=True)  # cadence hit: no-op
        assert a.total_steps == 0 and len(a._pending) == 2
        a.flush()  # sanctioned sync point takes everything
        assert a.total_steps == 2 and a.bad_steps == 2

    def test_accounting_flush_and_consecutive(self):
        reg = MetricsRegistry()
        a = sentinel.SentinelAccounting("M", flush_every=100, registry=reg)
        for ok in (True, False, False, True, False):
            a.record(np.asarray(ok), skipped=True)
        a.flush()
        assert (a.total_steps, a.bad_steps, a.skipped_updates) == (5, 3, 3)
        assert a.consecutive_bad == 1
        assert reg.get(sentinel.BAD_STEPS).value(model="M") == 3
        a.record(np.asarray(False), skipped=False)  # "record" policy
        a.flush()
        assert a.consecutive_bad == 2 and a.skipped_updates == 3

    def test_default_policy_roundtrip(self):
        prev = sentinel.set_default_nonfinite_policy("record")
        try:
            assert prev == "skip"
            assert sentinel.effective_policy(object()) == "record"
        finally:
            sentinel.set_default_nonfinite_policy(prev)
        with pytest.raises(ValueError):
            sentinel.set_default_nonfinite_policy("maybe")

    def test_off_policy_keeps_legacy_step_contract(self):
        net = mlp()
        net.nonfinite_policy = "off"
        x, y = data(32)
        net.fit(x, y, epochs=1, batch_size=16)
        assert getattr(net, "_sentinel_accounting", None) is None
        # the raw 4-tuple step (bench/distributed contract) still works
        step = net._get_train_step(False)
        out = step(net.params, net.state, net.updater_state,
                   x[:16], y[:16], net._next_rng(), None, None)
        assert len(out) == 4


# ---------------------------------------------------------------------
# sentinel through the three fit loops (chaos acceptance)
# ---------------------------------------------------------------------
class TestSentinelFitLoops:
    TOL = 0.15  # |loss - fault-free loss| after the one skipped update

    def _poisoned(self, x, y, batch=16, n=1):
        return chaos.NaNPoisonIterator(
            ArrayDataSetIterator(x, y, batch, shuffle=False), n=n)

    def test_mln_per_batch_skips_and_recovers(self):
        x, y = data(96)
        clean, hurt = mlp(), mlp()
        clean.fit(x, y, epochs=3, batch_size=16)
        hurt.fit(self._poisoned(x, y), epochs=3, batch_size=16)
        assert params_finite(hurt)
        acct = acct_of(hurt)
        assert acct.bad_steps == 1 and acct.skipped_updates == 1
        assert abs(hurt.score(features=x, labels=y)
                   - clean.score(features=x, labels=y)) < self.TOL

    def test_mln_fused_scan_skips_inside_the_dispatch(self):
        x, y = data(96)
        clean, hurt = mlp(), mlp()
        clean.fit(x, y, epochs=3, batch_size=16)
        hurt.fit(self._poisoned(x, y, n=2), epochs=3, batch_size=16,
                 steps_per_dispatch=3)
        assert params_finite(hurt)
        acct = acct_of(hurt)
        assert acct.bad_steps == 1 and acct.skipped_updates == 1
        assert abs(hurt.score(features=x, labels=y)
                   - clean.score(features=x, labels=y)) < self.TOL

    def test_fused_skip_equals_per_batch_skip(self):
        """The zeroed update inside the scan is the SAME math as the
        per-batch skip — poisoned run params match exactly."""
        x, y = data(64)
        a, b = mlp(), mlp()
        a.fit(self._poisoned(x, y), epochs=2, batch_size=16)
        b.fit(self._poisoned(x, y), epochs=2, batch_size=16,
              steps_per_dispatch=4)
        for la, lb in zip(jax.tree_util.tree_leaves(a.params),
                          jax.tree_util.tree_leaves(b.params)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=1e-5, atol=1e-6)

    def test_graph_fused_skips_and_recovers(self):
        x, y = data(96)
        clean, hurt = small_graph(), small_graph()
        clean.fit(x, y, epochs=3, batch_size=16)
        hurt.fit(self._poisoned(x, y), epochs=3, batch_size=16,
                 steps_per_dispatch=2)
        assert params_finite(hurt)
        assert acct_of(hurt).skipped_updates == 1
        assert abs(float(hurt.score(DataSet(x, y)))
                   - float(clean.score(DataSet(x, y)))) < self.TOL

    def test_parallel_wrapper_allreduce_skips_and_recovers(self):
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
        x, y = data(96)
        clean = ParallelWrapper(mlp(updater=Sgd(0.1)))
        hurt = ParallelWrapper(mlp(updater=Sgd(0.1)))
        clean.fit(x, y, epochs=3, batch_size=16)
        hurt.fit(self._poisoned(x, y), epochs=3, batch_size=16)
        m = hurt.model
        assert params_finite(m)
        assert acct_of(m).skipped_updates == 1
        assert abs(m.score(features=x, labels=y)
                   - clean.model.score(features=x, labels=y)) < self.TOL

    def test_parallel_wrapper_averaging_skips_bad_shard_step(self):
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
        x, y = data(128)
        hurt = ParallelWrapper(mlp(updater=Sgd(0.1)),
                               training_mode="averaging",
                               averaging_frequency=2)
        hurt.fit(self._poisoned(x, y, batch=8, n=3), epochs=2, batch_size=8)
        m = hurt.model
        assert params_finite(m)
        assert acct_of(m).bad_steps >= 1

    def test_record_policy_counts_but_applies(self):
        x, y = data(32)
        net = mlp()
        net.nonfinite_policy = "record"
        net.fit(self._poisoned(x, y, n=0), epochs=1, batch_size=16)
        acct = acct_of(net)
        # record mode lets the poison THROUGH: step 0 is bad from the
        # input, step 1 is bad because the params are now NaN — exactly
        # the cascade the default skip policy prevents
        assert acct.bad_steps == 2 and acct.skipped_updates == 0
        assert not params_finite(net)

    def test_registry_counters_are_global_observables(self):
        existing = global_registry().get(sentinel.SKIPPED_UPDATES)
        before = existing.total() if existing is not None else 0.0
        x, y = data(32)
        net = mlp()
        net.fit(self._poisoned(x, y, n=0), epochs=1, batch_size=16)
        sentinel.flush_accounting(net)
        after = global_registry().get(sentinel.SKIPPED_UPDATES).total()
        assert after == before + 1


# ---------------------------------------------------------------------
# recovery: worker death, mid-epoch kill, transient retry
# ---------------------------------------------------------------------
class TestChaosRecovery:
    def test_prefetch_worker_death_recovers_via_restart(self, tmp_path):
        """A fatal error inside the prefetch worker thread kills the
        epoch; FaultTolerantTrainer restarts and the run completes."""
        x, y = data(64)
        it = DevicePrefetchIterator(
            chaos.RaiseOnBatch(ArrayDataSetIterator(x, y, 16,
                                                    shuffle=False), n=2),
            prefetch=2)
        net = mlp()
        trainer = FaultTolerantTrainer(net, str(tmp_path / "ckpt"),
                                       retry_on=(RuntimeError,))
        trainer.fit(it, epochs=3, batch_size=16)
        assert net.epoch_count == 3 and params_finite(net)
        assert global_registry().get(RESTARTS).total() >= 1

    def test_mid_epoch_kill_resumes_to_straight_run(self, tmp_path):
        """SIGTERM-style kill inside epoch 2: restart restores the
        epoch-1 boundary state (incl. RNG) and the final params match a
        never-killed run."""
        x, y = data(64)
        a = mlp(seed=5)
        FaultTolerantTrainer(a, str(tmp_path / "a")).fit(
            x, y, epochs=4, batch_size=16)

        b = mlp(seed=5)
        killed = chaos.PreemptionIterator(
            ArrayDataSetIterator(x, y, 16, shuffle=False), n=6)
        FaultTolerantTrainer(b, str(tmp_path / "b")).fit(
            killed, epochs=4, batch_size=16)
        assert b.epoch_count == 4
        np.testing.assert_allclose(np.asarray(a.output(x)),
                                   np.asarray(b.output(x)), atol=1e-4)

    def test_transient_iterator_flake_retried_exactly(self):
        """A transient base-iterator error under the prefetch retry
        policy re-pulls the SAME batch: numerics equal a fault-free
        run, and nothing surfaces to the fit loop."""
        x, y = data(64)
        clean, hurt = mlp(), mlp()
        clean.fit(ArrayDataSetIterator(x, y, 16, shuffle=False),
                  epochs=2, batch_size=16)
        flaky = chaos.RaiseOnBatch(
            ArrayDataSetIterator(x, y, 16, shuffle=False), n=1,
            exc=lambda: OSError("blip"))
        it = DevicePrefetchIterator(
            flaky, prefetch=2,
            retry=RetryPolicy(max_attempts=3, base_delay=0.01,
                              retry_on=(OSError,)))
        hurt.fit(it, epochs=2, batch_size=16)
        for la, lb in zip(jax.tree_util.tree_leaves(clean.params),
                          jax.tree_util.tree_leaves(hurt.params)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=1e-6, atol=1e-7)

    def test_transient_retry_exhaustion_still_raises(self):
        x, y = data(32)
        always = chaos.RaiseOnBatch(
            ArrayDataSetIterator(x, y, 16, shuffle=False), n=1,
            exc=lambda: OSError("dead"), once=False, period=0)
        it = DevicePrefetchIterator(
            always, prefetch=1,
            retry=RetryPolicy(max_attempts=2, base_delay=0.01,
                              retry_on=(OSError,)))
        with pytest.raises(OSError, match="dead"):
            for _ in it:
                pass


# ---------------------------------------------------------------------
# divergence watchdog + rollback
# ---------------------------------------------------------------------
class TestWatchdogRollback:
    def test_blowup_detection(self):
        wd = DivergenceWatchdog(blowup_factor=10.0, min_history=3,
                                check_every=1)
        m = mlp()
        for s in (1.0, 1.1, 0.9, 1.0):
            wd.iteration_done(m, 0, s)
        with pytest.raises(DivergenceError, match="blew past"):
            wd.iteration_done(m, 5, 50.0)
        wd.reset()
        wd.iteration_done(m, 6, 50.0)  # fresh window: no history yet

    def test_blowup_detection_stays_live_for_negative_losses(self):
        """Log-likelihood-style objectives go negative; the additive
        limit must still catch an explosion a ratio check would miss."""
        wd = DivergenceWatchdog(blowup_factor=10.0, min_history=3,
                                check_every=1)
        m = mlp()
        for s in (-5.0, -4.8, -5.2, -5.0):
            wd.iteration_done(m, 0, s)
        with pytest.raises(DivergenceError, match="blew past"):
            wd.iteration_done(m, 5, 1000.0)

    def test_consecutive_bad_detection(self):
        wd = DivergenceWatchdog(max_consecutive_bad=2, check_every=1)
        m = mlp()
        acct = sentinel.accounting_for(m)
        for _ in range(3):
            acct.record(np.asarray(False), skipped=True)
        with pytest.raises(DivergenceError, match="consecutive"):
            wd.iteration_done(m, 0, 0.5)

    def test_divergence_handled_even_with_narrowed_retry_on(self, tmp_path):
        """retry_on=(OSError,) must not disable the divergence rollback
        the caller explicitly configured."""
        x, y = data(64)
        net = mlp()
        ckdir = str(tmp_path / "ck")
        FaultTolerantTrainer(net, ckdir).fit(x, y, epochs=1, batch_size=16)
        poisoned = chaos.NaNPoisonIterator(
            ArrayDataSetIterator(x, y, 16, shuffle=False),
            n=range(0, 10000))
        trainer = FaultTolerantTrainer(
            net, ckdir, max_restarts=1, retry_on=(OSError,),
            watchdog=DivergenceWatchdog(max_consecutive_bad=2,
                                        check_every=2),
            lr_backoff=0.5)
        with pytest.raises(DivergenceError):
            trainer.fit(poisoned, epochs=3, batch_size=16)
        # the rollback DID run before the final re-raise
        assert net.conf.updater.learning_rate == pytest.approx(0.005)
        assert params_finite(net)

    def test_checkpoints_tagged_by_sentinel_state(self, tmp_path):
        net = mlp()
        x, y = data(32)
        net.fit(x, y, epochs=1, batch_size=16)
        save_checkpoint(net, str(tmp_path), step=1)
        acct = sentinel.accounting_for(net)
        acct.record(np.asarray(False), skipped=True)
        save_checkpoint(net, str(tmp_path), step=2)  # saved mid-bad-run
        assert list_checkpoints(str(tmp_path)) == [1, 2]
        assert list_good_checkpoints(str(tmp_path)) == [1]

    def test_blowup_rollback_rewinds_past_high_score_saves(self, tmp_path):
        """A FINITE blowup leaves every bad-step tag GOOD; the rollback
        must use the recorded save-time scores to rewind past saves
        taken mid-divergence — and fall back to the newest save of any
        tag when nothing qualifies."""
        net = mlp()
        x, y = data(32)
        net.fit(x, y, epochs=1, batch_size=16)
        ckdir = str(tmp_path)
        net.score_value = 0.6
        save_checkpoint(net, ckdir, step=1)   # healthy-era save
        net.score_value = 480.0
        save_checkpoint(net, ckdir, step=2)   # mid-divergence save
        assert list_good_checkpoints(ckdir) == [1, 2]  # tags can't tell
        trainer = FaultTolerantTrainer(net, ckdir)
        err = DivergenceError("blew past", limit=15.0)
        assert trainer._pick_rollback_step(err) == 1
        # consecutive-bad divergence (no limit): newest good wins
        assert trainer._pick_rollback_step(DivergenceError("bad")) == 2
        # nothing under the limit and nothing tagged good: newest of any
        acct = sentinel.accounting_for(net)
        acct.record(np.asarray(False), skipped=True)
        net.score_value = 500.0
        save_checkpoint(net, ckdir, step=3)   # tagged BAD
        import shutil as _sh
        for s in (1, 2):
            _sh.rmtree(f"{ckdir}/step_{s}")
            import os as _os
            _os.unlink(f"{ckdir}/step_{s}.resilience.json")
        assert list_good_checkpoints(ckdir) == []
        assert trainer._pick_rollback_step(err) == 3

    def test_rollback_prunes_post_divergence_saves(self, tmp_path):
        """Saves newer than the rewind point are deleted: a later
        transient restart must not restore the diverged state, and
        keep-last pruning (highest steps win) must not evict the fresh
        post-rollback saves in favor of poisoned ones."""
        net = mlp()
        x, y = data(32)
        net.fit(x, y, epochs=1, batch_size=16)
        ckdir = str(tmp_path)
        net.score_value = 0.6
        save_checkpoint(net, ckdir, step=1)
        net.score_value = 480.0
        save_checkpoint(net, ckdir, step=2)
        trainer = FaultTolerantTrainer(net, ckdir)
        restored = trainer._rollback(DivergenceError("blew", limit=15.0))
        assert restored == 1
        assert list_checkpoints(ckdir) == [1]
        assert trainer.resume_if_possible() == 1  # transient path agrees

    def test_divergence_rolls_back_to_last_good_with_lr_backoff(
            self, tmp_path):
        x, y = data(64)
        net = mlp(lr=0.01)
        ckdir = str(tmp_path / "ck")
        # phase 1: healthy epochs, GOOD-tagged checkpoints on disk
        FaultTolerantTrainer(net, ckdir).fit(x, y, epochs=2, batch_size=16)
        good_params = jax.tree_util.tree_map(np.asarray, net.params)

        # phase 2: the input source goes permanently toxic
        poisoned = chaos.NaNPoisonIterator(
            ArrayDataSetIterator(x, y, 16, shuffle=False),
            n=range(0, 10000))
        trainer = FaultTolerantTrainer(
            net, ckdir, max_restarts=1,
            watchdog=DivergenceWatchdog(max_consecutive_bad=2,
                                        check_every=2),
            lr_backoff=0.5)
        with pytest.raises(DivergenceError):
            trainer.fit(poisoned, epochs=4, batch_size=16)
        # rollback restored the last GOOD state and cooled the LR
        assert params_finite(net)
        assert net.conf.updater.learning_rate == pytest.approx(0.005)
        for lname, lp in net.params.items():
            for pname, arr in lp.items():
                np.testing.assert_array_equal(np.asarray(arr),
                                              good_params[lname][pname])
        assert global_registry().get(RESTARTS).value(
            cause="divergence") >= 1


# ---------------------------------------------------------------------
# prefetch worker shutdown audit
# ---------------------------------------------------------------------
class _ErrorAfterN(DataSetIterator):
    """Yields `n` batches then dies — sized so the queue is FULL when
    the error fires and the sentinel cannot be admitted."""

    def __init__(self, n=1, exc=ValueError("decoder exploded")):
        x, y = data(16)
        self.n = n
        self.ds = DataSet(x, y)
        self.exc = exc

    def __iter__(self):
        for _ in range(self.n):
            yield self.ds
        raise self.exc


class TestPrefetchShutdownAudit:
    def test_worker_error_reaches_consumer_through_full_queue(self):
        it = DevicePrefetchIterator(_ErrorAfterN(n=3), prefetch=1)
        batches = []
        with pytest.raises(ValueError, match="decoder exploded"):
            for b in it:
                batches.append(b)
        assert len(batches) == 3

    def test_abandoned_consumer_never_loses_the_error(self):
        """Regression (worker shutdown audit): queue full, consumer
        closes the generator before the sentinel can be enqueued — the
        error must land on the stop path (last_worker_error + log), not
        vanish with a dropped q.put."""
        it = DevicePrefetchIterator(_ErrorAfterN(n=2), prefetch=1)
        gen = iter(it)
        next(gen)  # starts the worker; b2 then fills the 1-slot queue
        # worker: stages b2 (queue full again), pulls -> ERROR; its
        # sentinel can never be admitted while b2 sits unconsumed
        t0 = time.perf_counter()
        while not it._err_holder and time.perf_counter() - t0 < 5.0:
            time.sleep(0.01)
        assert it._err_holder, "worker never recorded its error"
        gen.close()  # consumer detaches; stop path takes over
        it._last_thread.join(timeout=5.0)
        assert not it._last_thread.is_alive()
        assert isinstance(it.last_worker_error, ValueError)

    def test_retry_over_generator_base_surfaces_the_error(self):
        """Regression: a generator-backed base iterator DIES on its
        first error, so a retried pull sees StopIteration — which must
        re-raise the original failure, not pass for a clean
        end-of-stream (silent epoch truncation)."""
        it = DevicePrefetchIterator(
            _ErrorAfterN(n=1, exc=OSError("flake")), prefetch=2,
            retry=RetryPolicy(max_attempts=3, base_delay=0.01,
                              retry_on=(OSError,)))
        batches = []
        with pytest.raises(OSError, match="flake"):
            for b in it:
                batches.append(b)
        assert len(batches) == 1  # the good batch arrived, then the truth

    def test_consumer_drains_fully_when_worker_predeceases(self):
        """The consumer's liveness check: even with the sentinel lost,
        a dead worker + empty queue ends the pass instead of hanging."""
        x, y = data(32)
        it = DevicePrefetchIterator(
            ArrayDataSetIterator(x, y, 16, shuffle=False), prefetch=2)
        out = list(it)
        assert len(out) == 2
        it._last_thread.join(timeout=5.0)
        assert not it._last_thread.is_alive()


# ---------------------------------------------------------------------
# serving robustness
# ---------------------------------------------------------------------
class _SlowModel:
    """Stand-in with the surface ParallelInference touches."""

    _initialized = True

    def __init__(self, delay=0.0, fail=False, gate=None):
        self.delay = delay
        self.fail = fail
        self.gate = gate

    def init(self):
        return self

    def output(self, x):
        if self.gate is not None:
            self.gate.wait(5.0)
        if self.delay:
            time.sleep(self.delay)
        if self.fail:
            raise RuntimeError("model exploded")
        return np.asarray(x) * 2.0


class TestServingRobustness:
    def _x(self, n=8):
        return np.ones((n, 4), np.float32)

    def test_deadline_exceeded_raises_and_counts(self):
        reg = MetricsRegistry()
        pi = ParallelInference(_SlowModel(delay=1.0), max_batch_size=8,
                               batch_timeout_ms=1.0, registry=reg)
        try:
            t0 = time.perf_counter()
            with pytest.raises(InferenceTimeout):
                pi.output(self._x(), timeout=0.05)
            # enforced near the budget, not at the next 200ms poll tick
            assert time.perf_counter() - t0 < 0.19
            assert reg.get(SERVING_DEADLINE_EXCEEDED).total() == 1
            assert reg.get(SERVING_REQUESTS).total() == 1
        finally:
            pi.shutdown()

    def test_no_deadline_still_waits_and_succeeds(self):
        pi = ParallelInference(_SlowModel(delay=0.05),
                               batch_timeout_ms=1.0)
        try:
            out = pi.output(self._x())
            np.testing.assert_allclose(out, self._x() * 2.0)
        finally:
            pi.shutdown()

    def test_fail_fast_queue_policy_rejects_at_limit(self):
        reg = MetricsRegistry()
        gate = threading.Event()
        pi = ParallelInference(_SlowModel(gate=gate), queue_limit=1,
                               max_batch_size=4, batch_timeout_ms=1.0,
                               queue_policy="fail_fast", registry=reg)
        try:
            results = []
            threads = [threading.Thread(
                target=lambda: results.append(pi.output(self._x(4))))
                for _ in range(2)]
            threads[0].start()
            time.sleep(0.3)  # t0 dequeued by the worker, now gated
            threads[1].start()
            time.sleep(0.3)  # t1 sits in the queue: at limit
            with pytest.raises(ServingQueueFull):
                pi.output(self._x(4))
            assert reg.get(SERVING_QUEUE_REJECTED).total() == 1
            gate.set()
            for t in threads:
                t.join(timeout=5.0)
            assert len(results) == 2
        finally:
            gate.set()
            pi.shutdown()

    def test_batched_error_fails_all_coalesced_waiters(self):
        pi = ParallelInference(_SlowModel(fail=True), max_batch_size=16,
                               batch_timeout_ms=20.0)
        try:
            errors = []

            def call():
                try:
                    pi.output(self._x(4), timeout=5.0)
                except Exception as e:  # noqa: BLE001 — asserting on it
                    errors.append(e)

            threads = [threading.Thread(target=call) for _ in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10.0)
            assert len(errors) == 3
            assert all("model exploded" in str(e) for e in errors)
        finally:
            pi.shutdown()

    def test_sequential_error_propagates_and_counts(self):
        reg = MetricsRegistry()
        pi = ParallelInference(_SlowModel(fail=True),
                               inference_mode="sequential", registry=reg)
        with pytest.raises(RuntimeError, match="model exploded"):
            pi.output(self._x(), timeout=5.0)
        assert reg.get(SERVING_ERRORS).total() == 1
        pi.shutdown()
        with pytest.raises(RuntimeError, match="shut down"):
            pi.output(self._x())

    def test_malformed_request_fails_its_batch_not_the_server(self):
        """Regression: shape-mismatched requests coalesced into one
        batch fail THEIR waiters; the serving loop survives and keeps
        answering well-formed requests."""
        pi = ParallelInference(_SlowModel(), max_batch_size=16,
                               batch_timeout_ms=500.0)
        results, errors = [], []

        def call(x):
            try:
                results.append(pi.output(x, timeout=10.0))
            except Exception as e:  # noqa: BLE001 — asserting on it
                errors.append(e)

        try:
            t1 = threading.Thread(target=call,
                                  args=(np.ones((4, 4), np.float32),))
            t2 = threading.Thread(target=call,
                                  args=(np.ones((4, 6), np.float32),))
            t1.start()
            time.sleep(0.1)  # inside t1's coalescing window
            t2.start()
            t1.join(timeout=10.0)
            t2.join(timeout=10.0)
            assert len(errors) == 2  # the mismatched batch failed both
            assert pi.is_healthy()   # ... but the server survived
            out = pi.output(self._x(4), timeout=10.0)
            np.testing.assert_allclose(out, self._x(4) * 2.0)
        finally:
            pi.shutdown()

    def test_graceful_shutdown_delivers_inflight_result(self):
        """Regression: a stop signal arriving while the worker is mid-
        dispatch must not make the waiting caller bail — the result is
        still coming and shutdown() joins the worker precisely so it
        can be delivered."""
        gate = threading.Event()
        pi = ParallelInference(_SlowModel(gate=gate), max_batch_size=4,
                               batch_timeout_ms=1.0)
        results, errors = [], []

        def call():
            try:
                results.append(pi.output(self._x(4)))
            except Exception as e:  # noqa: BLE001 — asserting on it
                errors.append(e)

        t = threading.Thread(target=call)
        t.start()
        time.sleep(0.3)    # request dequeued; worker gated mid-dispatch
        pi._stop.set()     # shutdown signal lands while in flight
        time.sleep(0.3)    # caller polls with stop set, worker alive
        gate.set()
        t.join(timeout=5.0)
        pi.shutdown()
        assert errors == [] and len(results) == 1

    def test_shutdown_fails_pending_and_refuses_new(self):
        gate = threading.Event()
        pi = ParallelInference(_SlowModel(gate=gate), queue_limit=4,
                               max_batch_size=4, batch_timeout_ms=1.0)
        errors = []

        def call():
            try:
                pi.output(self._x(4))
            except Exception as e:  # noqa: BLE001 — asserting on it
                errors.append(e)

        t = threading.Thread(target=call)
        t.start()
        time.sleep(0.2)
        gate.set()
        pi.shutdown()
        t.join(timeout=5.0)
        with pytest.raises(RuntimeError, match="shut down"):
            pi.output(self._x())

    def test_health_and_readiness_gauges(self):
        reg = MetricsRegistry()
        pi = ParallelInference(_SlowModel(), registry=reg,
                               batch_timeout_ms=1.0)
        name = "_SlowModel"
        assert pi.health()["healthy"] and pi.health()["ready"]
        assert reg.get(SERVING_HEALTHY).value(model=name) == 1.0
        assert reg.get(SERVING_READY).value(model=name) == 1.0
        pi.shutdown()
        assert not pi.is_healthy()
        assert reg.get(SERVING_HEALTHY).value(model=name) == 0.0
        assert reg.get(SERVING_READY).value(model=name) == 0.0

    def test_gauges_do_not_pin_a_shutdown_server(self):
        """Regression: the scrape-time health callbacks hold a WEAK ref
        — a dead serving stack (and the model params behind it) must be
        collectable, and its series scrape as down."""
        import gc
        import weakref

        reg = MetricsRegistry()
        pi = ParallelInference(_SlowModel(), registry=reg,
                               batch_timeout_ms=1.0)
        alive = weakref.ref(pi)
        pi.shutdown()
        del pi
        gc.collect()
        assert alive() is None, "registry callbacks pinned the server"
        assert reg.get(SERVING_HEALTHY).value(model="_SlowModel") == 0.0
        assert reg.get(SERVING_READY).value(model="_SlowModel") == 0.0

    def test_real_model_end_to_end_with_deadline(self):
        net = mlp()
        pi = ParallelInference(net, batch_timeout_ms=1.0)
        try:
            x, _ = data(16)
            out = pi.output(x, timeout=30.0)
            assert out.shape == (16, 2)
            np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-4)
        finally:
            pi.shutdown()
