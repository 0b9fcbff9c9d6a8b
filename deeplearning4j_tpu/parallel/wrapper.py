"""Data-parallel training over a device mesh.

TPU-native replacement for deeplearning4j-scaleout's ParallelWrapper
(deeplearning4j-scaleout-parallelwrapper/.../ParallelWrapper.java:58-898) and
its two training modes:

- TrainingMode.SHARED_GRADIENTS (:68, EncodedGradientsAccumulator /
  EncodingHandler threshold-compressed async exchange) → here the NORTH STAR
  (BASELINE.json): ONE jitted SPMD train step with the batch sharded over the
  mesh "data" axis and params replicated; XLA inserts a dense allreduce
  (psum) of gradients over ICI. No worker threads, no replicas, no
  compression — ICI bandwidth makes dense exchange faster than the
  reference's sparse codec path.

- TrainingMode.AVERAGING (:59-74, averageModels every averagingFrequency
  iters :251-257) → `shard_map` formulation: each mesh shard runs
  `averaging_frequency` LOCAL updater steps on its own microbatches
  (lax.scan), then params/updater-state are psum-averaged. Kept for parity
  testing (the reference invariant
  TestCompareParameterAveragingSparkVsSingleMachine: freq=1 averaging ==
  single-machine result holds here exactly for SGD).

The reference's worker thread pool, device pinning (attachThreadToDevice
:137) and MagicQueue feeding disappear: SPMD partitioning is the scheduler.
"""

from __future__ import annotations

import logging
import time
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator, AsyncDataSetIterator
from deeplearning4j_tpu.monitoring.listener import (
    finalize_fit_telemetry, maybe_record_fit_iteration)
from deeplearning4j_tpu.nn.updater import normalize_gradients
from deeplearning4j_tpu.optimize.listeners import close_listeners
from deeplearning4j_tpu.parallel.mesh import default_mesh
from deeplearning4j_tpu.resilience.durable import (
    capture_cursor_pass, consume_restored_cursor, dispatch_boundary)
from deeplearning4j_tpu.resilience.sentinel import (
    apply_step, effective_policy, guard_updates, tree_finite)

log = logging.getLogger(__name__)


def _strip_rnn_state(state):
    """Remove per-batch RNN carries (h/c) so pytree structure is stable
    across shard_map in/out specs."""
    return {k: {kk: vv for kk, vv in v.items() if kk not in ("h", "c")}
            if isinstance(v, dict) else v for k, v in state.items()}


class ParallelWrapper:
    """Multi-device trainer wrapping a MultiLayerNetwork or ComputationGraph
    (ref: ParallelWrapper.Builder / fit :468)."""

    def __init__(self, model, mesh: Optional[Mesh] = None,
                 training_mode: str = "allreduce",
                 averaging_frequency: int = 5,
                 prefetch_buffer: int = 2,
                 report_score_after_averaging: bool = True,
                 collect_stats: bool = False,
                 steps_per_dispatch: int = 1,
                 device_prefetch: bool = False):
        self.model = model
        self.mesh = mesh if mesh is not None else default_mesh()
        self.training_mode = training_mode
        self.averaging_frequency = max(1, averaging_frequency)
        self.prefetch_buffer = prefetch_buffer
        #: allreduce mode: fuse K same-shape batches into one lax.scan
        #: dispatch of the wrapped model's scan train step (SPMD: batch
        #: axis 1 sharded over the mesh). Epoch tails fall back to the
        #: per-batch allreduce step.
        self.steps_per_dispatch = max(1, int(steps_per_dispatch))
        #: replace the host-side AsyncDataSetIterator stage with a
        #: DevicePrefetchIterator that lands batches PRE-SHARDED on the
        #: mesh (NamedSharding over "data"), so the H2D copy overlaps
        #: compute instead of happening inside the fit step.
        self.device_prefetch = bool(device_prefetch)
        self.n_devices = int(np.prod(self.mesh.devices.shape))
        self._jit_cache: Dict[Any, Any] = {}
        self._warned_small_batch = False
        self._warned_remainder_drop = False
        # phase timing (ref: CommonSparkTrainingStats role)
        self.stats = None
        if collect_stats:
            from deeplearning4j_tpu.parallel.stats import TrainingStats
            self.stats = TrainingStats()
        if not model._initialized:
            model.init()

    # ------------------------------------------------------------------
    def _host_trim(self, arr):
        """Host half of batch sharding: make the batch divisible by
        n_devices. Non-divisible remainders are DROPPED (the reference
        drops/queues leftovers rather than duplicating examples —
        duplicate-padding would silently over-weight the repeated sample in
        the gradient). Batches smaller than the mesh still pad by repetition
        as the only way to occupy every device; that case is logged once."""
        # host-only by caller contract: _shard_batch/_shard_stack return
        # device (prefetched) arrays untouched before reaching this, so
        # this asarray never sees a device value
        # tpulint: disable=host-sync-in-hot-loop
        arr = np.asarray(arr)
        n = arr.shape[0]
        rem = n % self.n_devices
        if rem:
            if n >= self.n_devices:
                if not self._warned_remainder_drop:
                    log.warning(
                        "batch of %d not divisible by %d devices: dropping "
                        "the %d trailing example(s) each step (size batches "
                        "to a multiple of the mesh to use all data)",
                        n, self.n_devices, rem)
                    self._warned_remainder_drop = True
                arr = arr[:n - rem]
            else:
                if not self._warned_small_batch:
                    log.warning(
                        "batch of %d < %d devices: padding by repetition "
                        "(repeated examples are over-weighted this step)",
                        n, self.n_devices)
                    self._warned_small_batch = True
                pad = self.n_devices - n
                arr = np.concatenate(
                    [arr, np.repeat(arr[-1:], pad, axis=0)], axis=0)
        return arr

    def _trim_batch(self, ds: DataSet) -> DataSet:
        """DataSet-level _host_trim (DevicePrefetchIterator transform:
        the worker trims before the background device_put). Stashes the
        pre-transform effective count so listener/throughput stats match
        the unprefetched path (a below-mesh batch padded by repetition
        must still report its REAL rows)."""
        out = DataSet(
            self._host_trim(ds.features),
            None if ds.labels is None else self._host_trim(ds.labels),
            None if ds.features_mask is None
            else self._host_trim(ds.features_mask),
            None if ds.labels_mask is None
            else self._host_trim(ds.labels_mask))
        out.real_examples = self._effective_examples(ds)
        return out

    def _shard_batch(self, arr):
        """Trim to mesh divisibility and device_put sharded on the data
        axis. Batches already staged by the device-prefetch pipeline
        (committed jax.Arrays, pre-trimmed and pre-sharded by the
        worker) pass through untouched — np.asarray on them would be a
        D2H round-trip."""
        if isinstance(arr, jax.Array):
            return arr
        arr = self._host_trim(arr)
        sh = NamedSharding(self.mesh, P("data", *([None] * (arr.ndim - 1))))
        # the SPMD jit-boundary copy of the UNPREFETCHED compat path:
        # fit(device_prefetch=True) moves this into the background worker
        # tpulint: disable=device-transfer-in-hot-loop
        return jax.device_put(arr, sh)

    def _shard_stack(self, arrs):
        """Stack K same-shape batches to [K, B, ...] sharded
        P(None, "data", ...) for the fused scan step. Device-resident
        (prefetched) batches stack on device; host batches trim and
        transfer as ONE put."""
        if isinstance(arrs[0], jax.Array):
            return jnp.stack(arrs)
        a = np.stack([self._host_trim(x) for x in arrs])
        sh = NamedSharding(self.mesh,
                           P(None, "data", *([None] * (a.ndim - 2))))
        # same unprefetched-compat jit-boundary copy as _shard_batch,
        # fused to ONE put for the K-step group
        # tpulint: disable=device-transfer-in-hot-loop
        return jax.device_put(a, sh)

    def _effective_examples(self, ds: DataSet) -> int:
        """Examples that actually contribute to the step after the
        divisibility trim (listener stats must not count dropped or
        repetition-padded rows). Prefetched batches carry the count
        computed BEFORE the worker's trim/pad (see _trim_batch)."""
        pre = getattr(ds, "real_examples", None)
        if pre is not None:
            return int(pre)
        n = ds.num_examples()
        if n >= self.n_devices:
            return (n // self.n_devices) * self.n_devices
        return n

    def _replicate(self, tree):
        sh = NamedSharding(self.mesh, P())
        return jax.device_put(tree, sh)

    def _timer(self, phase: str):
        """Phase timer. With collect_stats the TrainingStats event list
        records (and forwards to the metrics registry itself); otherwise a
        monitoring span lands the phase directly in the registry — either
        way every ParallelWrapper phase shows up at /metrics."""
        if self.stats is not None:
            return self.stats.time_phase(phase)
        from deeplearning4j_tpu.monitoring.tracing import span
        return span(phase)

    def _stash_batch_for_viz(self, ds: DataSet):
        m = self.model
        # hoisted capability flag (set at fit start); falls back to the
        # per-call scan when the batch path is driven directly
        stash = getattr(m, "_stash_features", None)
        if stash is None:
            stash = any(getattr(l, "needs_batch_features", False)
                        for l in m.listeners)
        if stash:
            m._last_batch_features = ds.features

    # ------------------------------------------------------------------
    # allreduce mode (north star)
    # ------------------------------------------------------------------
    def _fit_batch_allreduce(self, ds: DataSet):
        """One global SPMD step: inputs sharded, params replicated — the
        jitted step from the wrapped model works unchanged, XLA partitions
        it and inserts the ICI allreduce."""
        t0 = time.perf_counter()
        m = self.model
        policy = effective_policy(m)
        step = m._get_train_step(False, policy)
        rng = m._next_rng()
        self._stash_batch_for_viz(ds)
        with self._timer("step"):
            x = self._shard_batch(ds.features)
            y = self._shard_batch(ds.labels)
            fmask = None if ds.features_mask is None else self._shard_batch(ds.features_mask)
            lmask = None if ds.labels_mask is None else self._shard_batch(ds.labels_mask)
            from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
            if isinstance(m, MultiLayerNetwork):
                args = (x, y, rng, fmask, lmask)
            else:
                inputs = {m.conf.network_inputs[0]: x}
                labels = {m.conf.network_outputs[0]: y}
                fmasks = None if fmask is None else {m.conf.network_inputs[0]: fmask}
                lmasks = None if lmask is None else {m.conf.network_outputs[0]: lmask}
                args = (inputs, labels, rng, fmasks, lmasks)
            m.params, m.state, m.updater_state, loss = apply_step(
                m, policy, step, m.params, m.state, m.updater_state, *args)
            m.score_value = loss  # raw device scalar, float() on access
        with self._timer("listener"):
            for lst in m.listeners:
                if hasattr(lst, "record_batch"):
                    lst.record_batch(self._effective_examples(ds))
                # raw score: see multilayer's listener loop
                lst.iteration_done(m, m.iteration_count, m._score_raw)
        m.iteration_count += 1
        maybe_record_fit_iteration(m, self._effective_examples(ds),
                                   time.perf_counter() - t0)

    def _fit_group_allreduce(self, batches):
        """Fused multi-step SPMD dispatch: K batches stacked to
        [K, B, ...] (batch axis sharded over the mesh) through the
        wrapped model's scan train step — K allreduce steps, ONE
        Python→XLA round-trip. Listeners fire per logical step with
        lazy slices of the per-step loss vector."""
        t0 = time.perf_counter()
        m = self.model
        k = len(batches)
        policy = effective_policy(m)
        step = m._get_scan_train_step(k, policy)
        with self._timer("step"):
            rngs = jnp.stack([m._next_rng() for _ in range(k)])
            xs = self._shard_stack([b.features for b in batches])
            ys = self._shard_stack([b.labels for b in batches])
            fm = None if batches[0].features_mask is None else \
                self._shard_stack([b.features_mask for b in batches])
            lm = None if batches[0].labels_mask is None else \
                self._shard_stack([b.labels_mask for b in batches])
            from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
            if isinstance(m, MultiLayerNetwork):
                args = (xs, ys, rngs, fm, lm)
            else:
                inputs = {m.conf.network_inputs[0]: xs}
                labels = {m.conf.network_outputs[0]: ys}
                fms = None if fm is None else {m.conf.network_inputs[0]: fm}
                lms = None if lm is None else {m.conf.network_outputs[0]: lm}
                args = (inputs, labels, rngs, fms, lms)
            m.params, m.state, m.updater_state, losses = apply_step(
                m, policy, step, m.params, m.state, m.updater_state, *args)
            m.score_value = losses[-1]  # raw device scalar
        with self._timer("listener"):
            for i, b in enumerate(batches):
                loss_i = losses[i]  # lazy device slice, no sync
                # per LOGICAL step, so viz listeners pair each
                # iteration_done with its own batch's features
                self._stash_batch_for_viz(b)
                for lst in m.listeners:
                    if hasattr(lst, "record_batch"):
                        lst.record_batch(self._effective_examples(b))
                    lst.iteration_done(m, m.iteration_count, loss_i)
                m.iteration_count += 1
        maybe_record_fit_iteration(
            m, sum(self._effective_examples(b) for b in batches),
            time.perf_counter() - t0, n_batches=k)

    # ------------------------------------------------------------------
    # averaging mode (parity with ParameterAveraging semantics)
    # ------------------------------------------------------------------
    def _get_averaging_step(self, policy: str = "off"):
        key = ("avg", policy)
        if key in self._jit_cache:
            return self._jit_cache[key]
        m = self.model
        conf = m.conf
        mesh = self.mesh
        freq = self.averaging_frequency
        nd = self.n_devices

        def local_round(params, state, upd_state, xs, ys, rngs):
            """Runs on ONE shard: `freq` sequential local steps over the
            leading microbatch axis, then cross-shard param average. The
            non-finite sentinel skips per (shard, local step): a shard
            whose microbatch NaNs contributes its PRE-step params to the
            average instead of a poisoned tree."""

            def one(carry, inp):
                p, s, u = carry
                x, y, rng = inp
                rng = rng.reshape(2)  # per-shard slice [1,2] -> legacy key (2,)
                (loss, s2), grads = jax.value_and_grad(
                    lambda pp: m._loss(pp, s, x, y, rng, None, None, train=True),
                    has_aux=True)(p)
                ok = None if policy == "off" else tree_finite(loss, grads)
                grads = normalize_gradients(grads, conf.gradient_normalization,
                                            conf.gradient_normalization_threshold)
                steps, u2 = conf.updater.update(grads, u, p)
                p2 = jax.tree_util.tree_map(lambda a, b: a - b, p, steps)
                s2 = _strip_rnn_state(s2)
                if policy != "off":
                    p2, u2, s2 = guard_updates(
                        ok, policy, (p2, p), (u2, u), (s2, s))
                out = loss if policy == "off" else (loss, ok)
                return (p2, s2, u2), out

            (p_f, s_f, u_f), out = jax.lax.scan(one, (params, state, upd_state),
                                                (xs, ys, rngs))
            s_f = _strip_rnn_state(s_f)
            # parameter averaging across the mesh (ref: averageModels :339)
            p_avg = jax.tree_util.tree_map(lambda a: jax.lax.pmean(a, "data"), p_f)
            u_avg = jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a.astype(jnp.float32), "data").astype(a.dtype)
                if jnp.issubdtype(a.dtype, jnp.integer) else jax.lax.pmean(a, "data"),
                u_f)
            s_avg = jax.tree_util.tree_map(lambda a: jax.lax.pmean(a, "data"), s_f)
            if policy == "off":
                return p_avg, s_avg, u_avg, jnp.mean(out)
            losses, oks = out
            # per-local-step flag, ANDed over shards (replicated output)
            oks_all = jax.lax.pmin(oks.astype(jnp.int32), "data")
            return p_avg, s_avg, u_avg, jnp.mean(losses), oks_all

        def rep(x):
            return jax.tree_util.tree_map(lambda _: P(), x)

        def rounds(params, state, upd_state, xs, ys, rngs):
            outs = (rep(params), rep(state), rep(upd_state), P())
            if policy != "off":
                outs = outs + (P(),)
            fn = shard_map(
                local_round, mesh=mesh,
                in_specs=(rep(params), rep(state), rep(upd_state),
                          P(None, "data"), P(None, "data"), P(None, "data")),
                out_specs=outs,
                check_vma=False)
            return fn(params, state, upd_state, xs, ys, rngs)

        self._jit_cache[key] = jax.jit(rounds)
        return self._jit_cache[key]

    def _fit_round_averaging(self, batches):
        """Consume `averaging_frequency * n_devices` microbatches as one
        round (ref: ParameterAveragingTrainingMaster split sizing :287-298)."""
        t0 = time.perf_counter()
        m = self.model
        self._stash_batch_for_viz(batches[-1])
        freq = len(batches) // self.n_devices
        xs = np.stack([np.stack([b.features for b in
                                 batches[f * self.n_devices:(f + 1) * self.n_devices]],
                                axis=0) for f in range(freq)], axis=0)
        ys = np.stack([np.stack([b.labels for b in
                                 batches[f * self.n_devices:(f + 1) * self.n_devices]],
                                axis=0) for f in range(freq)], axis=0)
        # xs: [freq, n_dev, B, ...] — shard axis 1, scan axis 0, flatten device dim
        xs = xs.reshape((freq, self.n_devices * xs.shape[2]) + xs.shape[3:])
        ys = ys.reshape((freq, self.n_devices * ys.shape[2]) + ys.shape[3:])
        # one rng per (scan step, shard): [freq, n_dev, 2], shard axis = 1
        # (reshaped on device — round-tripping the keys through numpy was
        # a host sync in the per-round hot path)
        rngs = jax.random.split(
            m._next_rng(), freq * self.n_devices
        ).reshape(freq, self.n_devices, -1)
        policy = effective_policy(m)
        step = self._get_averaging_step(policy)
        with self._timer("step"):
            m.state = _strip_rnn_state(m.state)
            m.params, m.state, m.updater_state, loss = apply_step(
                m, policy, step, m.params, m.state, m.updater_state,
                jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(rngs))
            m.score_value = loss  # raw device scalar, float() on access
        round_examples = sum(b.num_examples() for b in batches)
        with self._timer("listener"):
            for lst in m.listeners:
                if hasattr(lst, "record_batch"):
                    # the whole round's examples: a MetricsListener (or
                    # PerformanceListener) must see the true throughput,
                    # not zero samples per round
                    lst.record_batch(round_examples)
                # raw score: see multilayer's listener loop
                lst.iteration_done(m, m.iteration_count, m._score_raw)
        m.iteration_count += freq
        maybe_record_fit_iteration(m, round_examples,
                                   time.perf_counter() - t0, n_batches=freq)

    # ------------------------------------------------------------------
    def fit(self, data, labels=None, epochs: int = 1, batch_size: int = 32,
            *, execution_plan=None):
        """Train across the mesh (ref: ParallelWrapper.fit :468). The
        iterator is wrapped in async prefetch like the reference's
        ADSI-per-device feeding — host-side by default, or the
        device-side pipeline stage when ``device_prefetch=True``
        (batches land pre-trimmed and pre-sharded on the mesh). With
        ``steps_per_dispatch=K``, allreduce mode fuses runs of K
        same-shape batches into single scan dispatches.

        ``execution_plan`` ("auto" | "fused" | "xla") resolves the fused
        training-kernel plan onto the wrapped model ONCE per fit, same
        seam as the single-device fit loops (tuning/plan.py)."""
        from deeplearning4j_tpu.monitoring import ensure_started
        from deeplearning4j_tpu.pipeline.padding import group_signature
        ensure_started()
        m = self.model
        if execution_plan is not None:
            from deeplearning4j_tpu.tuning.plan import apply_execution_plan
            sig0 = (getattr(m, "fuse_bn_act_conv", None),
                    getattr(m, "_fuse_stem", None),
                    getattr(m, "_fusion_only", None))
            apply_execution_plan(m, execution_plan)
            if sig0 != (getattr(m, "fuse_bn_act_conv", None),
                        getattr(m, "_fuse_stem", None),
                        getattr(m, "_fusion_only", None)):
                # averaging mode traces m._loss into the WRAPPER's
                # cache — a changed plan must rebuild it (allreduce
                # mode uses the model's cache, which set_fusion clears)
                self._jit_cache.clear()
        if labels is not None:
            it = ArrayDataSetIterator(data, labels, batch_size)
        elif isinstance(data, DataSet):
            it = ArrayDataSetIterator(data.features, data.labels, batch_size)
        else:
            it = data
        if it is not data:
            # align the internal iterator's pass counter with the
            # absolute epoch count — see MultiLayerNetwork.fit
            it.restore_state({"epoch": m.epoch_count, "pos": 0})
        # listener capability scan hoisted out of the per-batch path
        m._stash_features = any(getattr(l, "needs_batch_features", False)
                                for l in m.listeners)
        # restored data-pipeline cursor applies to the BASE iterator —
        # the per-epoch prefetch wrapper below is a fresh 1:1 stage each
        # pass, so fast-forwarding the base fast-forwards the stream
        consume_restored_cursor(m, it)
        capture_cursor_pass(m, it)
        try:
            for _ in range(epochs):
                # device prefetch serves the allreduce (SPMD) path only:
                # the averaging round builds its [freq, dev*B] stack
                # host-side, so pre-sharded device batches would force a
                # D2H gather per round, and the divisibility trim would
                # silently drop rows the averaging path trains on
                if self.device_prefetch and \
                        self.training_mode != "averaging":
                    from deeplearning4j_tpu.pipeline.prefetch import \
                        DevicePrefetchIterator
                    src = DevicePrefetchIterator(
                        it, prefetch=max(1, self.prefetch_buffer),
                        mesh=self.mesh, data_axis="data",
                        transform=self._trim_batch)
                elif self.prefetch_buffer:
                    src = AsyncDataSetIterator(it,
                                               prefetch=self.prefetch_buffer)
                else:
                    src = it
                averaging = self.training_mode == "averaging"
                round_size = self.averaging_frequency * self.n_devices
                k = self.steps_per_dispatch
                pend = []
                group, sig = [], None
                src_it = iter(src)
                while True:
                    with self._timer("etl"):
                        ds = next(src_it, None)
                    if ds is None:
                        break
                    if averaging:
                        pend.append(ds)
                        if len(pend) == round_size:
                            self._fit_round_averaging(pend)  # times itself
                            m._dispatched_in_epoch += round_size
                            dispatch_boundary(m)
                            pend = []
                    elif k > 1:
                        s = group_signature(ds)
                        if group and s != sig:
                            for b in group:  # unfusable run: per-batch
                                self._fit_batch_allreduce(b)
                                m._dispatched_in_epoch += 1
                                dispatch_boundary(m)
                            group = []
                        sig = s
                        group.append(ds)
                        if len(group) == k:
                            self._fit_group_allreduce(group)  # times itself
                            m._dispatched_in_epoch += k
                            dispatch_boundary(m)
                            group = []
                    else:
                        self._fit_batch_allreduce(ds)  # times itself
                        m._dispatched_in_epoch += 1
                        dispatch_boundary(m)
                # trailing partial averaging round / scan group:
                # allreduce per-batch steps
                for ds in pend + group:
                    self._fit_batch_allreduce(ds)
                    m._dispatched_in_epoch += 1
                    dispatch_boundary(m)
                m.epoch_count += 1
                m._dispatched_in_epoch = 0
                m._cursor_pass += 1
            # one allowed sync, after the final batch (see multilayer.fit)
            finalize_fit_telemetry(m)
        finally:
            m._stash_features = None
            m._cursor_pass = None
            close_listeners(m.listeners)
        return m
