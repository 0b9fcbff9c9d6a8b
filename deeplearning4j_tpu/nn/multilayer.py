"""MultiLayerNetwork — sequential network runtime.

TPU-native equivalent of deeplearning4j-nn/.../nn/multilayer/
MultiLayerNetwork.java (3156 LoC): fit(:1156), computeGradientAndScore(:2206),
feedForward(:852-964), output(:1866), doTruncatedBPTT(:1393), rnnTimeStep.

Design (SURVEY §7 stance): the reference's Solver/ConvexOptimizer/Updater-view
machinery collapses into ONE jitted train step — `jax.value_and_grad` over the
whole forward replaces per-layer backpropGradient; the updater is a pure
pytree transform; XLA buffer assignment replaces workspaces; `donate_argnums`
donates param/opt-state buffers so the step is in-place on device.

State (BN running stats, RNN carried h/c, center-loss centers) is an explicit
pytree threaded through the step — the functional formulation of the
reference's mutable layer fields.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator, DataSetIterator
from deeplearning4j_tpu.nn.conf.layers import (
    STREAM_STATE_KEYS,
    check_stream_budget,
    last_position,
    narrows_to_last,
    AutoEncoder,
    BaseOutputLayerConf,
    CenterLossOutputLayer,
    FrozenLayer,
)
from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
from deeplearning4j_tpu.nn.score import LazyScore
from deeplearning4j_tpu.nn.updater import normalize_gradients
from deeplearning4j_tpu.monitoring import ensure_started
from deeplearning4j_tpu.monitoring.listener import (
    finalize_fit_telemetry, maybe_record_fit_iteration)
from deeplearning4j_tpu.monitoring.tracing import span
from deeplearning4j_tpu.optimize.listeners import close_listeners
from deeplearning4j_tpu.pipeline.padding import (
    group_signature, num_real_examples, pad_batch, with_example_weights)
from deeplearning4j_tpu.resilience.durable import (
    capture_cursor_pass, consume_restored_cursor, dispatch_boundary)
from deeplearning4j_tpu.resilience.sentinel import (
    apply_step, effective_policy, guard_updates, tree_finite)

log = logging.getLogger(__name__)


def _tree_sub(params, steps):
    return jax.tree_util.tree_map(lambda p, s: p - s, params, steps)


def _strip_stream_state(state):
    """Drop transient streaming carries (RNN h/c, attention KV caches —
    STREAM_STATE_KEYS) from a state pytree. The fused lax.scan fit path
    needs the carry structure identical on every step, and non-carry
    training already ignores these keys at read (_forward strips them),
    so the scan path keeps them out of the carry entirely — same rule
    ParallelWrapper's averaging scan applies."""
    return {k: ({kk: vv for kk, vv in v.items()
                 if kk not in STREAM_STATE_KEYS}
                if isinstance(v, dict) else v)
            for k, v in state.items()}


class MultiLayerNetwork(LazyScore):
    """Sequential network with fit/output/evaluate (ref: MultiLayerNetwork.java)."""

    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers = conf.layers
        self.params: Dict[str, Any] = {}
        self.state: Dict[str, Any] = {}
        self.updater_state: Dict[str, Any] = {}
        self.listeners: List = []
        self.iteration_count = 0
        self.epoch_count = 0
        self.score_value = float("nan")
        self._rng = None
        self._jit_cache: Dict[Any, Any] = {}
        self._initialized = False
        # listener capability flags, hoisted to fit-loop setup (None =
        # not inside fit(): _fit_batch recomputes for direct callers)
        self._stash_features: Optional[bool] = None
        # non-finite sentinel policy override (None = process default;
        # see resilience/sentinel.py)
        self.nonfinite_policy: Optional[str] = None
        # durable-state plumbing (resilience/durable.py): the data-
        # pipeline cursor a checkpoint captures (batches DISPATCHED this
        # epoch + the canonical pad width), a restored cursor awaiting
        # application at the next fit, and the armed preemption guard
        self._dispatched_in_epoch = 0
        self._canon_in_epoch: Optional[int] = None
        self._restored_pipeline_state: Optional[Dict[str, Any]] = None
        self._cursor_pass: Optional[int] = None  # pass index mid-fit
        self._preemption_guard = None

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def init(self):
        """Initialize params/state (ref: MultiLayerNetwork.init())."""
        if self.conf.input_type is None:
            # try to infer from first layer's n_in
            first = self.layers[0]
            n_in = getattr(first, "n_in", None)
            if n_in is None:
                raise ValueError("set conf.input_type or first layer n_in")
            from deeplearning4j_tpu.nn.conf.inputs import InputType
            self.conf.input_type = InputType.feed_forward(n_in)
        from deeplearning4j_tpu.nn.conf.network import _infer_shapes_and_preprocessors
        _infer_shapes_and_preprocessors(self.conf)

        key = jax.random.PRNGKey(self.conf.seed)
        self._rng = jax.random.PRNGKey(self.conf.seed + 1)
        its = self.conf.layer_input_types()
        keys = jax.random.split(key, max(2, len(self.layers)))
        self.params, self.state = {}, {}
        for i, layer in enumerate(self.layers):
            p, s = layer.init(keys[i], its[i])
            self.params[str(i)] = p
            self.state[str(i)] = s
        self.updater_state = self.conf.updater.init_state(self.params)
        self._initialized = True
        return self

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listener(self, listener):
        self.listeners.append(listener)
        return self

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(self.params))

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------
    def _forward(self, params, state, x, *, train, rng, fmask=None,
                 carry_rnn=False, stream=False, pad=None,
                 upto: Optional[int] = None, last_only=False):
        """Pure forward pass. Returns (activation_list, new_state).

        activation_list[i] is the OUTPUT of layer i (post preprocessor+layer).

        `pad` (traced scalar) marks a left-padded streaming chunk:
        non-streaming layers (LSTM h/c carry-through on masked steps) see
        an ordinary key mask, while streaming cache layers get pad_left
        for packed slot accounting (pads never enter caches).

        `last_only` (rnn_time_step's: the caller will read the chunk's
        last position only) hands a per-position head
        (``layers.narrows_to_last``, no preprocessor before it) the last
        position of its input, so no [N, V, T] block exists.
        """
        acts = []
        new_state = {}
        mask = fmask
        # inference honors the bf16 compute policy too (training gets it
        # in _loss; double application is a no-op): bf16 activations +
        # weights halve HBM traffic and the carried KV-cache memory. The
        # public output() / rnn_time_step cast the final activation back
        # to f32 at the jit boundary.
        params, x = self._cast_compute(params, x)
        if pad is not None:
            mask = jnp.broadcast_to(jnp.arange(x.shape[-1]) >= pad,
                                    (x.shape[0], x.shape[-1]))
        its = self.conf.layer_input_types()
        h = x
        n = len(self.layers) if upto is None else upto
        for i in range(n):
            layer = self.layers[i]
            pre = self.conf.preprocessors.get(i)
            if pre is not None:
                h = pre.apply(h, mask)
                mask = pre.output_mask(mask, its[i])
            li_state = state.get(str(i), {})
            if not carry_rnn:
                li_state = {k: v for k, v in li_state.items()
                            if k not in STREAM_STATE_KEYS}
            rng_i = None
            if rng is not None:
                rng_i = jax.random.fold_in(rng, i)
            p_i = params[str(i)]
            wn = getattr(layer, "weight_noise", None)
            if wn is not None and train and rng_i is not None and \
                    isinstance(p_i, dict):
                p_i = wn.apply_to_params(
                    p_i, jax.random.fold_in(rng_i, 987))
            # stream (inference KV-cache decode) is distinct from
            # carry_rnn (tbptt h/c carry during training): tbptt trains
            # attention full-context per chunk
            extra = {}
            m_i = mask
            if getattr(layer, "supports_streaming", False):
                extra["stream"] = stream
                if pad is not None:
                    # packed accounting replaces the mask for cache layers
                    extra["pad_left"] = pad
                    m_i = None
            if last_only and i == len(self.layers) - 1 and pre is None \
                    and narrows_to_last(layer):
                h = h[:, :, -1:]
            h, s_new = layer.apply(p_i, h, li_state, train=train,
                                   rng=rng_i, mask=m_i, **extra)
            mask = layer.output_mask(mask, its[i])
            new_state[str(i)] = s_new
            acts.append(h)
        # pass through untouched state of layers beyond `upto`
        for i in range(n, len(self.layers)):
            new_state[str(i)] = state.get(str(i), {})
        return acts, new_state

    def _dequantized(self, params):
        """Materialize int8 QuantizedTensor leaves (W8A16 serving,
        optimize/quantization.py) as float32 — the inference paths run
        activations in f32 (conf.dtype is a TRAINING-cast policy), and
        _cast_compute re-casts to bf16 after this when scoring under a
        bf16 conf. XLA fuses the int8 convert into each consumer either
        way, which is where the HBM saving lives."""
        from deeplearning4j_tpu.optimize.quantization import dequantize_tree
        return dequantize_tree(params, jnp.float32)

    def _cast_compute(self, params, x):
        """Mixed precision: when conf.dtype is bfloat16, run forward in bf16
        (master params stay fp32 — grads flow back through the cast). On TPU
        this keeps matmuls/convs on the MXU bf16 path with fp32 accumulation
        (XLA default), the same fp16-compute policy the reference's cuDNN
        helpers select (BaseCudnnHelper dataType)."""
        from deeplearning4j_tpu.nn.compute import bf16_cast, bf16_cast_tree
        if getattr(self, "_quantized", False):
            params = self._dequantized(params)
        if self.conf.dtype in ("bfloat16", "bf16"):
            return bf16_cast_tree(params), bf16_cast(x)
        return params, x

    def _loss(self, params, state, x, y, rng, fmask, lmask, *, train=True,
              carry_rnn=False):
        """Scalar loss (data loss + L1/L2) and new state
        (ref: computeGradientAndScore :2206 + calcL1/L2 terms)."""
        params, x = self._cast_compute(params, x)
        out_idx = len(self.layers) - 1
        out_layer = self.layers[out_idx]
        acts, new_state = self._forward(params, state, x, train=train, rng=rng,
                                        fmask=fmask, carry_rnn=carry_rnn,
                                        upto=out_idx)
        h = acts[-1] if acts else x
        mask = lmask
        pre = self.conf.preprocessors.get(out_idx)
        if pre is not None:
            h = pre.apply(h, fmask)
        rng_o = jax.random.fold_in(rng, out_idx) if rng is not None else None
        if not hasattr(out_layer, "compute_score"):
            raise ValueError("last layer must be an output layer to compute loss")
        preout = out_layer.preout(params[str(out_idx)], h, train=train, rng=rng_o)
        # loss in >=fp32 under mixed precision (keeps f64 for gradient checks)
        preout = preout.astype(jnp.promote_types(preout.dtype, jnp.float32))
        score = out_layer.compute_score(y, preout, mask)
        o_state = state.get(str(out_idx), {})
        if isinstance(out_layer, CenterLossOutputLayer):
            score = score + out_layer.center_loss(h, y, o_state)
            o_state = out_layer.update_centers(jax.lax.stop_gradient(h), y, o_state)
        new_state[str(out_idx)] = o_state
        score = score + self._reg_loss(params)
        return score, new_state

    def _reg_loss(self, params):
        reg = 0.0
        for i, layer in enumerate(self.layers):
            l1c = layer.l1_coeffs()
            l2c = layer.l2_coeffs()
            if not l1c and not l2c:
                continue
            p = params[str(i)]
            for k, coeff in l1c.items():
                if k in p:
                    reg = reg + coeff * jnp.sum(jnp.abs(p[k]))
            for k, coeff in l2c.items():
                if k in p:
                    reg = reg + 0.5 * coeff * jnp.sum(p[k] ** 2)
        return reg

    # ------------------------------------------------------------------
    # jitted steps (cached per (carry_rnn, mask presence) signature)
    # ------------------------------------------------------------------
    def _get_train_step(self, carry_rnn: bool, policy: str = "off"):
        """One jitted optimizer step. With the non-finite sentinel
        (policy "skip"/"record" — resilience/sentinel.py) the step also
        returns a raw device ok-flag, and under "skip" a bad step
        applies a where-zeroed update: params/opt-state/BN-stats keep
        their pre-step values, all on device, no host sync. Returns a
        4-tuple under "off" (the pre-resilience contract the
        distributed workers rely on), a 5-tuple otherwise."""
        if getattr(self, "_quantized", False):
            raise RuntimeError(
                "this network was quantized for inference "
                "(quantize_for_inference) — int8 weights have no "
                "gradient path; train the fp checkpoint and re-quantize")
        # conf.dtype is baked into the trace: key it (stale compiled
        # steps would silently keep the old precision); ditto policy
        key = ("train", carry_rnn, self.conf.dtype, policy)
        if key not in self._jit_cache:
            conf = self.conf

            def step(params, state, upd_state, x, y, rng, fmask, lmask):
                (loss, new_state), grads = jax.value_and_grad(
                    lambda p: self._loss(p, state, x, y, rng, fmask, lmask,
                                         train=True, carry_rnn=carry_rnn),
                    has_aux=True)(params)
                # sentinel reads RAW grads: normalization (clipping)
                # must not mask an Inf by rescaling it
                ok = None if policy == "off" else tree_finite(loss, grads)
                grads = normalize_gradients(grads, conf.gradient_normalization,
                                            conf.gradient_normalization_threshold)
                steps, new_upd = conf.updater.update(grads, upd_state, params)
                new_params = _tree_sub(params, steps)
                if any(getattr(l, "constraints", None) for l in self.layers):
                    from deeplearning4j_tpu.nn.conf.constraints import \
                        apply_constraints
                    new_params = apply_constraints(self.layers, new_params)
                if policy == "off":
                    return new_params, new_state, new_upd, loss
                new_params, new_upd, new_state = guard_updates(
                    ok, policy, (new_params, params),
                    (new_upd, upd_state), (new_state, state))
                return new_params, new_state, new_upd, loss, ok

            self._jit_cache[key] = jax.jit(step, donate_argnums=(0, 2))
        return self._jit_cache[key]

    def _get_scan_train_step(self, k: int, policy: str = "off"):
        """Fused multi-step dispatch: K optimizer steps in ONE jitted,
        buffer-donating call via lax.scan over stacked batches
        ([K, B, ...]), returning the per-step loss vector as a single
        device array. Each scan iteration is exactly the _get_train_step
        body, so K Python→XLA round-trips (and K listener-side dispatch
        gaps) collapse into one — the micro-batch fusion μ-cuDNN applies
        to framework overhead (PAPERS.md). Streaming carries are
        stripped from the scanned state (see _strip_stream_state).

        With the non-finite sentinel on (policy != "off") each scan
        iteration checks its own loss/grads and (under "skip") zeroes
        its own update, so one poisoned batch cannot corrupt the other
        K-1 fused steps; the per-step ok-flags come back as a [K] device
        vector alongside the losses."""
        if getattr(self, "_quantized", False):
            raise RuntimeError(
                "this network was quantized for inference "
                "(quantize_for_inference) — int8 weights have no "
                "gradient path; train the fp checkpoint and re-quantize")
        key = ("scan", k, self.conf.dtype, policy)
        if key not in self._jit_cache:
            conf = self.conf

            def stepk(params, state, upd_state, xs, ys, rngs, fmasks, lmasks):
                def one(carry, inp):
                    p, s, u = carry
                    x, y, rng, fm, lm = inp
                    (loss, s2), grads = jax.value_and_grad(
                        lambda pp: self._loss(pp, s, x, y, rng, fm, lm,
                                              train=True, carry_rnn=False),
                        has_aux=True)(p)
                    ok = None if policy == "off" else \
                        tree_finite(loss, grads)
                    grads = normalize_gradients(
                        grads, conf.gradient_normalization,
                        conf.gradient_normalization_threshold)
                    steps, u2 = conf.updater.update(grads, u, p)
                    p2 = _tree_sub(p, steps)
                    if any(getattr(l, "constraints", None)
                           for l in self.layers):
                        from deeplearning4j_tpu.nn.conf.constraints import \
                            apply_constraints
                        p2 = apply_constraints(self.layers, p2)
                    s2 = _strip_stream_state(s2)
                    if policy != "off":
                        p2, u2, s2 = guard_updates(
                            ok, policy, (p2, p), (u2, u), (s2, s))
                    out = loss if policy == "off" else (loss, ok)
                    return (p2, s2, u2), out

                (p, s, u), out = jax.lax.scan(
                    one, (params, _strip_stream_state(state), upd_state),
                    (xs, ys, rngs, fmasks, lmasks))
                if policy == "off":
                    return p, s, u, out
                losses, oks = out
                return p, s, u, losses, oks

            self._jit_cache[key] = jax.jit(stepk, donate_argnums=(0, 2))
        return self._jit_cache[key]

    def _get_output_fn(self, train: bool, carry_rnn: bool,
                       stream: bool = False, padded: bool = False,
                       donate: bool = False, last_only: bool = False):
        # the process-wide stream-cache sharding config is part of the
        # key: flipping it retraces the step for EVERY net on next use
        # (a stale compiled step would silently keep the old layout);
        # so is the page-pool read this net's own attention layers hold
        # (xla fallback vs pallas kernel: what the engine serving THIS
        # net chose)
        from deeplearning4j_tpu.nn.compute import f32_head as head
        from deeplearning4j_tpu.nn.conf import layers as _L
        # donation only means anything where XLA aliases buffers; on CPU
        # it would just warn, so resolve it off there and share the
        # non-donating trace
        donate = donate and jax.default_backend() != "cpu"
        key = ("out", train, carry_rnn, stream, padded, donate, last_only,
               self.conf.dtype,
               _L._STREAM_CACHE_SHARDING if stream else None,
               _L.paged_reads(self.layers) if stream else None)
        if key not in self._jit_cache:
            read = last_position if last_only else (lambda y: y)
            if padded:
                # left-padded packed chunk: pad count is a TRACED scalar,
                # so every prompt length shares this one compiled shape
                def fwd(params, state, x, rng, pad):
                    acts, new_state = self._forward(
                        params, state, x, train=train, rng=rng, fmask=None,
                        carry_rnn=carry_rnn, stream=stream, pad=pad,
                        last_only=last_only)
                    return head(read(acts[-1])), new_state
            else:
                def fwd(params, state, x, rng, fmask):
                    acts, new_state = self._forward(
                        params, state, x, train=train, rng=rng, fmask=fmask,
                        carry_rnn=carry_rnn, stream=stream,
                        last_only=last_only)
                    return head(read(acts[-1])), new_state

            self._jit_cache[key] = jax.jit(
                fwd, donate_argnums=(1,) if donate else ())
        return self._jit_cache[key]

    def _get_score_fn(self):
        key = ("score", self.conf.dtype)
        if key not in self._jit_cache:
            def sf(params, state, x, y, fmask, lmask):
                loss, _ = self._loss(params, state, x, y, None, fmask, lmask,
                                     train=False)
                return loss

            self._jit_cache[key] = jax.jit(sf)
        return self._jit_cache[key]

    # ------------------------------------------------------------------
    # training
    # ------------------------------------------------------------------
    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def fit(self, data, labels=None, epochs: int = 1, batch_size: int = 32,
            *, steps_per_dispatch: int = 1, prefetch: int = 0,
            pad_tail: Optional[bool] = None,
            execution_plan: Optional[str] = None):
        """Train (ref: MultiLayerNetwork.fit(DataSetIterator) :1156).

        Accepts a DataSetIterator, a DataSet, or (features, labels) arrays.

        ``execution_plan`` ("auto" | "fused" | "xla", tuning/plan.py)
        selects how eligible chains execute — resolved ONCE here, never
        inside a step builder. Sequential nets have no fused graph
        chains, so every plan runs the XLA step; the kwarg validates
        and keeps the fit-loop API uniform across the step builders.

        Dispatch-overhead knobs (pipeline/ — see ARCHITECTURE.md "Input
        pipeline & fused dispatch"):

        - ``steps_per_dispatch=K``: fuse K optimizer steps into one
          jitted lax.scan dispatch (_get_scan_train_step). Listeners
          still fire once per LOGICAL step, receiving a lazy slice of
          the per-step loss vector (no sync unless they float() it).
          Epoch-trailing groups smaller than K run per-batch.
        - ``prefetch=N``: stage batches through DevicePrefetchIterator
          so H2D transfer overlaps compute, N batches deep.
        - ``pad_tail``: pad the ragged last batch to the canonical batch
          shape with an example-weight mask folded into the loss (exact
          for row-wise layers; approximate under batch-stat layers like
          BatchNormalization — pipeline/padding.py). Defaults to ON when
          steps_per_dispatch > 1, OFF otherwise.
        """
        if not self._initialized:
            self.init()
        ensure_started()
        if execution_plan is not None:
            from deeplearning4j_tpu.tuning.plan import apply_execution_plan
            apply_execution_plan(self, execution_plan)
        if labels is not None:
            it: DataSetIterator = ArrayDataSetIterator(data, labels, batch_size)
        elif isinstance(data, DataSet):
            it = ArrayDataSetIterator(data.features, data.labels, batch_size,
                                      data.features_mask, data.labels_mask)
        else:
            it = data
        if it is not data:
            # internally-built iterator: align its pass counter with the
            # ABSOLUTE epoch count, so shuffle orders are a function of
            # the global epoch (a fresh per-fit iterator replays the
            # same stream an uninterrupted single fit would produce —
            # what makes checkpoint cursors transplant across fits)
            it.restore_state({"epoch": self.epoch_count, "pos": 0})
        k = max(1, int(steps_per_dispatch))
        pad = (k > 1) if pad_tail is None else bool(pad_tail)
        if prefetch:
            from deeplearning4j_tpu.pipeline.prefetch import \
                DevicePrefetchIterator
            # pad in the worker, BEFORE the transfer (padding a
            # device-resident batch in the fit loop would be a D2H
            # round-trip)
            it = DevicePrefetchIterator(
                it, prefetch=prefetch, pad_to="auto" if pad else None,
                pad_when=lambda ds: ds.labels is not None)
        # listener capability scan hoisted out of the per-batch path
        self._stash_features = any(getattr(l, "needs_batch_features", False)
                                   for l in self.listeners)
        # a restored checkpoint's data-pipeline cursor fast-forwards the
        # iterator so a mid-epoch resume continues at the exact batch an
        # uninterrupted run would see next (resilience/durable.py);
        # _cursor_pass pins the iterator's OWN pass index (the shuffle
        # seed) for the duration of each pass
        consume_restored_cursor(self, it)
        capture_cursor_pass(self, it)
        try:
            for epoch in range(epochs):
                for lst in self.listeners:
                    lst.on_epoch_start(self, self.epoch_count)
                self._fit_epoch(it, k, pad)
                # increment BEFORE listeners fire: a CheckpointListener save
                # in on_epoch_end must record this epoch as COMPLETED, or
                # resume re-trains it (off-by-one). Listeners still receive
                # the pre-increment epoch index.
                epoch_idx = self.epoch_count
                self.epoch_count += 1
                self._dispatched_in_epoch = 0
                self._canon_in_epoch = None
                self._cursor_pass += 1
                for lst in self.listeners:
                    lst.on_epoch_end(self, epoch_idx)
            # the steady-state loop above never blocks on the device; the
            # one allowed sync is here, after the final batch
            finalize_fit_telemetry(self)
        finally:
            self._stash_features = None
            self._cursor_pass = None
            close_listeners(self.listeners)
        return self

    def _fit_epoch(self, it, k: int, pad: bool):
        """One pass over the iterator: pad ragged batches to the
        canonical (first-batch) row count when `pad`, and fuse runs of
        `k` same-signature batches into single scan dispatches when
        k > 1. Anything unfusable (tbptt sequences, signature changes,
        the trailing partial group) falls back to the per-batch step.

        After every dispatch fully retires, ``dispatch_boundary`` runs:
        deferred checkpoint-cadence saves and a pending preemption are
        honored THERE, where params/counters/RNG/cursor are mutually
        consistent. ``_dispatched_in_epoch``/``_canon_in_epoch`` feed
        the checkpoint's data-pipeline cursor (a resumed fit re-enters
        here with both restored by consume_restored_cursor)."""
        canon = self._canon_in_epoch
        group: List[DataSet] = []
        sig = None

        def flush():
            nonlocal sig
            if not group:
                sig = None
                return
            if len(group) == k:
                self._fit_group(group)
            else:
                for b in group:
                    self._fit_batch(b)
            self._dispatched_in_epoch += len(group)
            group.clear()
            sig = None
            dispatch_boundary(self)

        for ds in it:
            if self.conf.tbptt and ds.features.ndim == 3:
                flush()
                self._fit_tbptt(ds)
                self._dispatched_in_epoch += 1
                dispatch_boundary(self)
                continue
            if canon is None:
                canon = ds.num_examples()
                self._canon_in_epoch = canon
            if pad and ds.labels is not None:
                if ds.num_examples() < canon:
                    ds = pad_batch(ds, canon)
                # every batch carries an example-weight mask so the padded
                # tail shares the full batches' jit signature (exact:
                # ones-masked mean == plain mean)
                ds = with_example_weights(ds)
            if k == 1:
                self._fit_batch(ds)
                self._dispatched_in_epoch += 1
                dispatch_boundary(self)
                continue
            s = group_signature(ds)
            if group and s != sig:
                flush()
            sig = s
            group.append(ds)
            if len(group) == k:
                flush()
        flush()

    def _fit_group(self, group: Sequence[DataSet]):
        """Dispatch one fused K-step scan over stacked batches. Listeners
        fire per logical step with a LAZY slice of the device loss
        vector — the sync-free steady-state contract holds."""
        t0 = time.perf_counter()
        k = len(group)
        with span("etl"):
            rngs = jnp.stack([self._next_rng() for _ in range(k)])
            # jnp.stack is a device-side concat for prefetched (already
            # device-resident) batches and one fused H2D copy otherwise
            xs = jnp.stack([b.features for b in group])
            ys = jnp.stack([b.labels for b in group])
            fmasks = None if group[0].features_mask is None else \
                jnp.stack([b.features_mask for b in group])
            lmasks = None if group[0].labels_mask is None else \
                jnp.stack([b.labels_mask for b in group])
        policy = effective_policy(self)
        step = self._get_scan_train_step(k, policy)
        with span("step"):
            # apply_step absorbs the [K] sentinel flag vector (recorded
            # lazily — accounting syncs at its own cadence)
            self.params, self.state, self.updater_state, losses = \
                apply_step(self, policy, step, self.params, self.state,
                           self.updater_state, xs, ys, rngs, fmasks, lmasks)
        # raw device scalar: float() (the host sync) deferred to access
        self.score_value = losses[-1]
        with span("listener"):
            for i, b in enumerate(group):
                loss_i = losses[i]  # lazy device slice, no sync
                if self._stash_features:
                    # per LOGICAL step, so viz listeners pair each
                    # iteration_done with its own batch's features
                    self._last_batch_features = b.features
                for lst in self.listeners:
                    if hasattr(lst, "record_batch"):
                        lst.record_batch(num_real_examples(b))
                    lst.iteration_done(self, self.iteration_count, loss_i)
                self.iteration_count += 1
        maybe_record_fit_iteration(
            self, sum(num_real_examples(b) for b in group),
            time.perf_counter() - t0, n_batches=k)

    def _fit_batch(self, ds: DataSet, carry_rnn: bool = False):
        t0 = time.perf_counter()
        stash = self._stash_features
        if stash is None:  # direct call outside fit(): no hoisted scan
            stash = any(getattr(l, "needs_batch_features", False)
                        for l in self.listeners)
        if stash:
            self._last_batch_features = ds.features  # for viz listeners
        with span("etl"):
            rng = self._next_rng()
            # jnp.asarray here is the jit-boundary copy of the
            # UNPREFETCHED compat path (baselined for tpulint
            # device-transfer-in-hot-loop): fit(prefetch=N) moves these
            # H2D copies into the background pipeline stage
            fmask = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
            lmask = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
            x = jnp.asarray(ds.features)
            y = jnp.asarray(ds.labels)
        policy = effective_policy(self)
        step = self._get_train_step(carry_rnn, policy)
        with span("step"):
            self.params, self.state, self.updater_state, loss = \
                apply_step(self, policy, step, self.params, self.state,
                           self.updater_state, x, y, rng, fmask, lmask)
        # raw device scalar: float() (the host sync) deferred to access
        self.score_value = loss
        with span("listener"):
            # num_real_examples: a padded tail batch reports its true
            # row count to throughput stats, not the bucket size
            n_real = num_real_examples(ds)
            for lst in self.listeners:
                if hasattr(lst, "record_batch"):
                    lst.record_batch(n_real)
                # raw score, NOT the float property: listeners that use the
                # score sync at their own cadence, the rest never sync
                lst.iteration_done(self, self.iteration_count,
                                   self._score_raw)
        self.iteration_count += 1
        maybe_record_fit_iteration(self, n_real,
                                   time.perf_counter() - t0)

    def _fit_tbptt(self, ds: DataSet):
        """Truncated BPTT: split the sequence into tbptt_fwd_length chunks,
        carrying RNN state across chunks within the batch
        (ref: doTruncatedBPTT :1393)."""
        t = ds.features.shape[2]
        L = self.conf.tbptt_fwd_length
        self.rnn_clear_previous_state()
        for s in range(0, t, L):
            chunk = DataSet(
                ds.features[:, :, s:s + L],
                ds.labels[:, :, s:s + L] if ds.labels is not None and ds.labels.ndim == 3
                else ds.labels,
                ds.features_mask[:, s:s + L] if ds.features_mask is not None else None,
                ds.labels_mask[:, s:s + L] if ds.labels_mask is not None else None,
            )
            self._fit_batch(chunk, carry_rnn=True)

    # ------------------------------------------------------------------
    # inference / scoring
    # ------------------------------------------------------------------
    def output(self, x, train: bool = False, mask=None):
        """Forward pass returning output activations (ref: output :1866)."""
        if not self._initialized:
            self.init()
        fn = self._get_output_fn(train, False)
        rng = self._next_rng() if train else jax.random.PRNGKey(0)
        fmask = None if mask is None else jnp.asarray(mask)
        out, _ = fn(self.params, self.state, jnp.asarray(x), rng, fmask)
        return out

    def feed_forward(self, x, train: bool = False):
        """All layer activations (ref: feedForward :852). Public outputs
        follow the same f32 boundary as output()."""
        from deeplearning4j_tpu.nn.compute import f32_head
        acts, _ = self._forward(self.params, self.state, jnp.asarray(x),
                                train=train, rng=jax.random.PRNGKey(0))
        return [f32_head(a) for a in acts]

    def score(self, ds: DataSet = None, features=None, labels=None) -> float:
        """Loss on a dataset (ref: MultiLayerNetwork.score(DataSet))."""
        if ds is None:
            ds = DataSet(np.asarray(features), np.asarray(labels))
        fn = self._get_score_fn()
        fmask = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
        lmask = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
        return float(fn(self.params, self.state, jnp.asarray(ds.features),
                        jnp.asarray(ds.labels), fmask, lmask))

    def evaluate(self, iterator):
        """Classification evaluation (ref: MultiLayerNetwork.evaluate)."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        e = Evaluation()
        if isinstance(iterator, DataSet):
            iterator = ArrayDataSetIterator(iterator.features, iterator.labels, 128)
        for ds in iterator:
            out = self.output(ds.features, mask=ds.features_mask)
            e.eval(ds.labels, np.asarray(out), mask=ds.labels_mask)
        return e

    def evaluate_regression(self, iterator):
        from deeplearning4j_tpu.eval.evaluation import RegressionEvaluation
        e = RegressionEvaluation()
        if isinstance(iterator, DataSet):
            iterator = ArrayDataSetIterator(iterator.features, iterator.labels, 128)
        for ds in iterator:
            out = self.output(ds.features, mask=ds.features_mask)
            e.eval(ds.labels, np.asarray(out), mask=ds.labels_mask)
        return e

    # ------------------------------------------------------------------
    # RNN streaming state (ref: rnnTimeStep :~2300, rnnClearPreviousState)
    # ------------------------------------------------------------------
    def rnn_time_step(self, x, mask=None, pad_left=None,
                      donate_state=False, last_only=False):
        """Stateful streaming inference: feeds one (or more) timesteps,
        carrying h/c (and attention KV caches) across calls
        (ref: rnnTimeStep). `mask` is this chunk's [N, T] key mask for
        padded variable-length batches; attention layers carry it in the
        KV cache so padded positions stay masked on later steps.

        `pad_left` (int, mutually exclusive with mask) marks the first
        pad_left positions as LEFT padding with packed accounting: pads
        never enter caches nor consume streaming positions, so an
        arbitrary-length prompt primes in ONE dispatch at a bucketed
        shape (util/decoding pads to a power of two) with results
        identical to unpadded chunked priming. The pad count rides the
        jit as a traced scalar — one compiled shape per bucket.

        `donate_state=True` donates the carried state's buffers to the
        dispatch (TPU/GPU; a no-op on CPU): the serving engine's
        direct-paged decode path sets it so the page pools update IN
        PLACE (the O(one-token) append) instead of being copied each
        step. The caller must hold no references to the pre-call state
        leaves — the returned state is the only live copy.

        `last_only=True` is the caller saying what it will read: the
        chunk's last position and nothing else (a prime reads the
        distribution that follows the prompt; a verify chunk reads every
        position and does not ask). An output over a time axis then comes
        back as [N, C] and a per-position head (``RnnOutputLayer``) is
        handed the last position of its input, so neither the program
        nor the host holds an [N, C, T] block of which one column is
        read. A program of its own per shape (part of the jit key); the
        state the call leaves is the same either way, and a head that
        answers [N, C] already (``LastStepOutputLayer``) compiles the
        program it compiled before."""
        x = jnp.asarray(x)
        last_only = bool(last_only)
        if pad_left is not None:
            if mask is not None:
                raise ValueError("pad_left and mask are mutually exclusive")
            pad_left = int(pad_left)
            if not 0 <= pad_left < x.shape[-1]:
                raise ValueError(f"pad_left {pad_left} out of range for a "
                                 f"chunk of {x.shape[-1]} positions")
            new_pos = check_stream_budget(self, x.shape[-1], self.layers,
                                          pad=pad_left)
            fn = self._get_output_fn(False, True, stream=True, padded=True,
                                     donate=donate_state,
                                     last_only=last_only)
            out, new_state = fn(self.params, self.state, x,
                                jax.random.PRNGKey(0),
                                jnp.asarray(pad_left, jnp.int32))
        else:
            new_pos = check_stream_budget(self, x.shape[-1], self.layers)
            fn = self._get_output_fn(False, True, stream=True,
                                     donate=donate_state,
                                     last_only=last_only)
            out, new_state = fn(self.params, self.state, x,
                                jax.random.PRNGKey(0),
                                None if mask is None else jnp.asarray(mask))
        consumed = new_pos - getattr(self, "_stream_pos", 0)
        self._stream_pos = new_pos
        rows = getattr(self, "_stream_pos_rows", None)
        if rows is not None:     # per-row positions (after per-row rewind)
            self._stream_pos_rows = rows + consumed
            self._stream_pos = int(self._stream_pos_rows.max())
        self.state = new_state
        return out


    def set_stream_cache_sharding(self, mesh, axis: str = "data"):
        """Shard streaming attention KV caches over the sequence axis of
        `mesh` (None reverts to single-device caches). PROCESS-WIDE, like
        use_cnn_data_format: the setting applies to every net, and since
        it is part of each streaming step's jit key, any net retraces
        with the new layout on its next streaming call — no stale
        compiled steps. Streaming decode (rnn_time_step / sample_stream /
        beam_search) then runs sequence-parallel: per-device cache memory
        is O(cache_length / n_devices) and XLA inserts the cross-device
        softmax combine."""
        from deeplearning4j_tpu.nn.conf.layers import (
            set_stream_cache_sharding)
        set_stream_cache_sharding(mesh, axis)
        return self

    def rnn_clear_previous_state(self):
        self._stream_pos = 0
        self._stream_pos_rows = None
        for k, s in self.state.items():
            self.state[k] = {kk: vv for kk, vv in s.items()
                             if kk not in STREAM_STATE_KEYS}

    # ------------------------------------------------------------------
    # layerwise pretraining (ref: MultiLayerNetwork.pretrain :220)
    # ------------------------------------------------------------------
    def pretrain(self, iterator, epochs: int = 1):
        """Greedy layerwise pretraining of AutoEncoder/VAE layers."""
        if getattr(self, "_quantized", False):
            raise RuntimeError(
                "this network was quantized for inference "
                "(quantize_for_inference) — int8 weights have no "
                "gradient path; train the fp checkpoint and re-quantize")
        if not self._initialized:
            self.init()
        for i, layer in enumerate(self.layers):
            if not isinstance(layer, AutoEncoder) and not hasattr(layer, "pretrain_loss"):
                continue
            self._pretrain_layer(i, layer, iterator, epochs)
        return self

    def _pretrain_layer(self, idx, layer, iterator, epochs):
        upd = self.conf.updater
        upd_state = upd.init_state(self.params[str(idx)])

        @jax.jit
        def pstep(p_i, all_params, u_state, x, rng):
            def loss_fn(p):
                params2 = dict(all_params)
                params2[str(idx)] = p
                acts, _ = self._forward(params2, self.state, x, train=False,
                                        rng=None, upto=idx)
                h = acts[-1] if acts else x
                return layer.pretrain_loss(p, h, rng)

            loss, grads = jax.value_and_grad(loss_fn)(p_i)
            steps, new_u = upd.update(grads, u_state, p_i)
            return _tree_sub(p_i, steps), new_u, loss

        for _ in range(epochs):
            if isinstance(iterator, DataSet):
                batches = ArrayDataSetIterator(iterator.features, iterator.labels, 32)
            else:
                batches = iterator
            for ds in batches:
                rng = self._next_rng()
                p_new, upd_state, loss = pstep(self.params[str(idx)], self.params,
                                               upd_state, jnp.asarray(ds.features), rng)
                self.params[str(idx)] = p_new

    # ------------------------------------------------------------------
    # info
    # ------------------------------------------------------------------
    def summary(self) -> str:
        """Layer table (ref: MultiLayerNetwork.summary())."""
        its = self.conf.layer_input_types()
        lines = ["=" * 72,
                 f"{'idx':<4}{'layer':<28}{'out type':<24}{'params':<12}",
                 "-" * 72]
        total = 0
        for i, layer in enumerate(self.layers):
            nparams = sum(int(np.prod(p.shape))
                          for p in jax.tree_util.tree_leaves(self.params.get(str(i), {})))
            total += nparams
            ot = layer.output_type(its[i])
            lines.append(f"{i:<4}{type(layer).__name__:<28}{str(ot.to_dict()):<24}"
                         f"{nparams:<12}")
        lines.append("-" * 72)
        lines.append(f"Total params: {total}")
        lines.append("=" * 72)
        return "\n".join(lines)

    def clone(self) -> "MultiLayerNetwork":
        import copy
        net = MultiLayerNetwork(MultiLayerConfiguration.from_dict(self.conf.to_dict()))
        if self._initialized:
            net.init()
            net.params = jax.tree_util.tree_map(lambda a: jnp.array(a), self.params)
            net.state = jax.tree_util.tree_map(lambda a: jnp.array(a), self.state)
        return net
