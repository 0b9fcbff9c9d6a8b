"""ComputationGraph — DAG network runtime.

TPU-native equivalent of deeplearning4j-nn/.../nn/graph/ComputationGraph.java
(3363 LoC): topologicalSortOrder :1190, fit :837, feedForward :1361 (topo-order
vertex loop), calcBackpropGradients :1629 (replaced by jax.grad), output :1532.

The whole DAG forward compiles into one XLA program under jit; the reference's
LOOP_* workspaces (:100-126) are replaced by XLA buffer assignment + donation.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator
from deeplearning4j_tpu.nn.conf.graph_conf import LayerVertex
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    STREAM_STATE_KEYS, BaseOutputLayerConf, CenterLossOutputLayer,
    last_position, narrows_to_last, paged_reads, stream_capacity)
from deeplearning4j_tpu.nn.conf.network import ComputationGraphConfiguration
from deeplearning4j_tpu.nn.score import LazyScore
from deeplearning4j_tpu.nn.updater import normalize_gradients
from deeplearning4j_tpu.monitoring import ensure_started
from deeplearning4j_tpu.monitoring.listener import (
    finalize_fit_telemetry, maybe_record_fit_iteration)
from deeplearning4j_tpu.monitoring.tracing import span
from deeplearning4j_tpu.nn.multilayer import _strip_stream_state, _tree_sub
from deeplearning4j_tpu.optimize.listeners import close_listeners
from deeplearning4j_tpu.pipeline.padding import (
    group_signature, num_real_examples, pad_batch, with_example_weights)
from deeplearning4j_tpu.resilience.durable import (
    capture_cursor_pass, consume_restored_cursor, dispatch_boundary)
from deeplearning4j_tpu.resilience.sentinel import (
    apply_step, effective_policy, guard_updates, tree_finite)

log = logging.getLogger(__name__)


from deeplearning4j_tpu.nn.compute import f32_head as _f32_head  # noqa: E402


class ComputationGraph(LazyScore):
    """DAG network with fit/output/evaluate (ref: ComputationGraph.java)."""

    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
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
        self._topo = conf.topological_order()
        #: the layers that read a page pool (``paged_read``): what
        #: ``_paged_reads`` keys every streaming dispatch on, found once
        #: (a walk over ~250 vertices a dispatch cost 0.25 ms on the
        #: chip's host)
        self._paged_layers = [
            v.layer for v in conf.vertices.values()
            if hasattr(getattr(v, "layer", None), "paged_read")]
        self._vertex_input_types: Dict[str, List[InputType]] = {}
        self.fuse_bn_act_conv = False
        self._fusion_cache = None
        # execution-plan refinements (tuning/plan.py): restrict the
        # bottleneck plan to a chosen block subset and/or engage the
        # fused space-to-depth stem (nn/layers/stem.py)
        self._fusion_only = None
        self._fuse_stem = False
        # the matchers' VMEM gates consult conf.dtype, so both plan
        # caches are dtype-stamped: flipping dtype after construction
        # (the bench builds at f32 then sets bf16) recomputes them
        self._fusion_dtype = None
        self._candidates_cache = None
        # listener capability flags, hoisted to fit-loop setup (None =
        # not inside fit(): _fit_batch recomputes for direct callers)
        self._stash_features: Optional[bool] = None
        # non-finite sentinel policy override (None = process default;
        # see resilience/sentinel.py)
        self.nonfinite_policy: Optional[str] = None
        # durable-state plumbing (resilience/durable.py) — see
        # MultiLayerNetwork.__init__
        self._dispatched_in_epoch = 0
        self._canon_in_epoch: Optional[int] = None
        self._restored_pipeline_state: Optional[Dict[str, Any]] = None
        self._cursor_pass: Optional[int] = None  # pass index mid-fit
        self._preemption_guard = None

    # ------------------------------------------------------------------
    # bn→act→conv1x1 fusion (execution-plan optimization, see
    # nn/layers/fused.py — params/state stay keyed by the original vertex
    # names, so serialization/import/transfer are unaffected)
    # ------------------------------------------------------------------
    def set_fusion(self, enabled=True, *, stem=False, only=None):
        """Select the fused execution plan: False (unfused — the
        measured-best default), True (bn→act→1×1-conv groups,
        nn/layers/fused.py), or "bottleneck" (whole identity-bottleneck
        chains through the Pallas kernel cascade,
        nn/layers/bottleneck.py). Changes how eligible chains execute,
        not what they compute (equivalence is test-pinned); jitted steps
        are rebuilt only when the resolved plan actually changes, so
        re-resolving the same plan per fit() call never retraces.

        ``only`` (bottleneck level) restricts fusion to the named block
        output vertices — the per-shape "auto" resolution seam
        (tuning/plan.py): the crossover store decides block by block and
        passes the winners here. ``stem`` additionally engages the fused
        space-to-depth stem (nn/layers/stem.py) on a matching
        pad→7×7/2-conv→BN→relu→3×3/2-maxpool chain."""
        if enabled not in (False, True, "bottleneck"):
            raise ValueError(
                f"unknown fusion level {enabled!r}: expected False, True "
                "or 'bottleneck'")
        if stem and enabled != "bottleneck":
            raise ValueError(
                "stem=True rides the 'bottleneck' fusion level (the "
                "fused-kernel execution plan)")
        only = None if only is None else frozenset(only)
        sig = (enabled, bool(stem), only)
        if sig != (self.fuse_bn_act_conv, self._fuse_stem,
                   self._fusion_only):
            self.fuse_bn_act_conv = enabled
            self._fuse_stem = bool(stem)
            self._fusion_only = only
            self._jit_cache.clear()
            self._fusion_cache = None
        return self

    def _fusion(self):
        """(plan, skip): plan maps a 1×1-conv vertex name to the fused
        group executing (bn → activation → conv) in one op; skip maps the
        absorbed bn/activation vertex names to their consuming conv.

        Eligibility (conservative — anything else runs unfused): a
        BatchNormalization vertex, optionally followed by an
        ActivationLayer (or its own activation), feeding a kernel-1×1 /
        stride-1 / pad-0 / dilation-1 ConvolutionLayer; every
        intermediate has a single consumer, no preprocessors/dropout, is
        not a network output, and the prologue activation is relu or
        identity (the Pallas kernel's fast set)."""
        if not self.fuse_bn_act_conv:
            return {}, {}, {}
        if self._fusion_cache is not None and \
                self._fusion_dtype == self.conf.dtype:
            return self._fusion_cache[:3]
        self._fusion_dtype = self.conf.dtype
        if self.fuse_bn_act_conv == "bottleneck":
            skip, bplan = self._bottleneck_fusion(self._fusion_only)
            splan = self._stem_fusion() if self._fuse_stem else {}
            for out_name, group in splan.items():
                for m in group["members"]:
                    skip[m] = out_name
            self._fusion_cache = ({}, skip, bplan, splan)
            return self._fusion_cache[:3]
        from deeplearning4j_tpu.nn.conf.layers import (
            ActivationLayer, BatchNormalization, ConvolutionLayer)
        consumers, layer_of = self._fusion_graph_view()
        plan: Dict[str, Tuple[str, str, str]] = {}
        skip: Dict[str, str] = {}
        for bn_name in self._topo:
            bn = layer_of(bn_name, BatchNormalization)
            if bn is None:
                continue
            if len(self.conf.vertex_inputs.get(bn_name, [])) != 1:
                continue
            if self._vertex_input_types[bn_name][0].kind != "cnn":
                continue
            cons = consumers.get(bn_name, [])
            if len(cons) != 1:
                continue
            nxt, act_vertex = cons[0], None
            act = bn.activation or "identity"
            al = layer_of(nxt, ActivationLayer)
            if al is not None:
                if act != "identity":
                    continue
                acons = consumers.get(nxt, [])
                if len(acons) != 1:
                    continue
                act_vertex, act, nxt = nxt, al.activation, acons[0]
            conv = layer_of(nxt, ConvolutionLayer)
            if (conv is None or act not in ("relu", "identity")
                    or tuple(conv.kernel) != (1, 1)
                    or tuple(conv.stride) != (1, 1)
                    or tuple(conv.padding) != (0, 0)
                    or tuple(conv.dilation) != (1, 1)
                    or conv.convolution_mode not in ("truncate", "same")
                    or conv.data_format != bn.data_format):
                continue
            if self.conf.vertex_inputs.get(nxt) != [act_vertex or bn_name]:
                continue
            src = self.conf.vertex_inputs[bn_name][0]
            plan[nxt] = (bn_name, act, src)
            skip[bn_name] = nxt
            if act_vertex is not None:
                skip[act_vertex] = nxt
        self._fusion_cache = (plan, skip, {}, {})
        return self._fusion_cache[:3]

    def _fusion_graph_view(self):
        """Shared matcher scaffolding for the fusion plans: the
        (consumers map, layer_of helper) both pattern matchers walk.
        layer_of(n, cls) returns the vertex n's layer iff it is a plain
        LayerVertex of exactly `cls` with no preprocessor/dropout and is
        not a network output — anything else is ineligible for fusion."""
        self._infer_types()
        consumers: Dict[str, List[str]] = {}
        for cname, srcs in self.conf.vertex_inputs.items():
            for s in srcs:
                consumers.setdefault(s, []).append(cname)
        outputs = set(self.conf.network_outputs)

        def layer_of(n, cls):
            v = self.conf.vertices.get(n)
            if (not isinstance(v, LayerVertex) or v.preprocessor is not None
                    or n in outputs):
                return None
            l = v.layer
            return l if type(l) is cls and not l.dropout else None

        return consumers, layer_of

    def _stem_plan(self):
        """splan for the fused space-to-depth stem: output (pool) vertex
        name → group. Populated only at level "bottleneck" with
        stem=True (set_fusion)."""
        self._fusion()          # populate the cache
        return self._fusion_cache[3] if self._fusion_cache else {}

    def _bottleneck_fusion(self, only=None):
        """(skip, bplan) for fuse level "bottleneck": bplan maps the
        final relu vertex of each IDENTITY bottleneck (conv1x1→bn→relu→
        conv3x3→bn→relu→conv1x1→bn→add(x)→relu, all stride 1, identity
        skip, NHWC) to its vertex group; skip maps every absorbed
        intermediate to that output vertex. Anything unmatched — entry
        blocks, other strides/layouts — runs unfused
        (nn/layers/bottleneck.py holds the kernels + eligibility
        rationale). ``only`` (a set of output-vertex names) keeps just
        the named blocks — the per-shape "auto" plan resolution."""
        from deeplearning4j_tpu.nn.conf.graph_conf import ElementWiseVertex
        from deeplearning4j_tpu.nn.conf.layers import (
            ActivationLayer, BatchNormalization, ConvolutionLayer)
        from deeplearning4j_tpu.nn.layers.bottleneck import (
            fused_bottleneck_supported)
        consumers, layer_of = self._fusion_graph_view()
        outputs = set(self.conf.network_outputs)

        def sole_consumer(n):
            c = consumers.get(n, [])
            return c[0] if len(c) == 1 else None

        def chain_next(n):
            """The one consumer of n, which must also have n as its ONE
            input (a second input would make the unfused vertex read a
            different xs[0] than the fused chain convolves). The residual
            add is the only legitimately multi-input consumer and is
            checked explicitly below."""
            c = sole_consumer(n)
            if c is None or self.conf.vertex_inputs.get(c, []) != [n]:
                return None
            return c

        def conv_ok(l, kernel, padding, stride=(1, 1)):
            return (l is not None and tuple(l.kernel) == kernel
                    and tuple(l.stride) == stride
                    and tuple(l.padding) == padding
                    and tuple(l.dilation) == (1, 1)
                    and not l.has_bias
                    and l.activation in (None, "identity")
                    and l.data_format == "NHWC")

        def walk_bn_act(name):
            """name is a conv; its single consumer must be bn (+ relu
            act vertex or bn relu activation). Returns (bn, act_vertex,
            following vertex) or None."""
            bn_name = chain_next(name)
            bn = bn_name and layer_of(bn_name, BatchNormalization)
            if bn is None or \
                    len(self.conf.vertex_inputs.get(bn_name, [])) != 1:
                return None
            nxt = chain_next(bn_name)
            if nxt is None:
                return None
            act = bn.activation or "identity"
            act_vertex = None
            al = layer_of(nxt, ActivationLayer)
            if al is not None and act == "identity":
                act_vertex, act = nxt, al.activation
                nxt = chain_next(act_vertex)
            if act != "relu" or nxt is None:
                return None
            return bn_name, act_vertex, nxt

        bplan: Dict[str, Dict[str, str]] = {}
        skip: Dict[str, str] = {}
        for ca_name in self._topo:
            conv_a = layer_of(ca_name, ConvolutionLayer)
            if conv_a is None:
                continue
            stride = tuple(conv_a.stride)
            if stride not in ((1, 1), (2, 2)) or \
                    not conv_ok(conv_a, (1, 1), (0, 0), stride):
                continue
            srcs = self.conf.vertex_inputs.get(ca_name, [])
            if len(srcs) != 1:
                continue
            src = srcs[0]
            it = self._vertex_input_types[ca_name][0]
            if it.kind != "cnn":
                continue
            w1 = walk_bn_act(ca_name)
            if w1 is None:
                continue
            bn_a, act_a, cb_name = w1
            conv_b = layer_of(cb_name, ConvolutionLayer)
            if not conv_ok(conv_b, (3, 3), (1, 1)):
                continue
            w2 = walk_bn_act(cb_name)
            if w2 is None:
                continue
            bn_b, act_b, cc_name = w2
            conv_c = layer_of(cc_name, ConvolutionLayer)
            if not conv_ok(conv_c, (1, 1), (0, 0)):
                continue
            bn_c_name = chain_next(cc_name)
            bn_c = bn_c_name and layer_of(bn_c_name, BatchNormalization)
            if bn_c is None or (bn_c.activation or "identity") != "identity":
                continue
            add_name = sole_consumer(bn_c_name)
            addv = add_name and self.conf.vertices.get(add_name)
            if (not isinstance(addv, ElementWiseVertex)
                    or addv.op.lower() != "add" or add_name in outputs):
                continue
            add_ins = self.conf.vertex_inputs.get(add_name, [])
            skip_group = {}
            if sorted(add_ins) == sorted([bn_c_name, src]):
                if stride != (1, 1):
                    continue          # strided main path needs a conv skip
            else:
                # downsample form: the other add input is src -> conv_skip
                # (1x1, same stride) -> bn_skip (identity activation)
                others = [i for i in add_ins if i != bn_c_name]
                if len(add_ins) != 2 or len(others) != 1:
                    continue
                bn_s_name = others[0]
                bn_s = layer_of(bn_s_name, BatchNormalization)
                if bn_s is None or \
                        (bn_s.activation or "identity") != "identity" or \
                        sole_consumer(bn_s_name) != add_name:
                    continue
                cs_in = self.conf.vertex_inputs.get(bn_s_name, [])
                if len(cs_in) != 1:
                    continue
                cs_name = cs_in[0]
                conv_s = layer_of(cs_name, ConvolutionLayer)
                if not conv_ok(conv_s, (1, 1), (0, 0), stride) or \
                        chain_next(cs_name) != bn_s_name or \
                        self.conf.vertex_inputs.get(cs_name, []) != [src]:
                    continue
                skip_group = {"conv_skip": cs_name, "bn_skip": bn_s_name}
            out_name = chain_next(add_name)
            out_act = out_name and layer_of(out_name, ActivationLayer)
            if out_act is None or out_act.activation != "relu":
                continue
            bns = [self.conf.vertices[n].layer
                   for n in ((bn_a, bn_b, bn_c_name)
                             + ((skip_group["bn_skip"],)
                                if skip_group else ()))]
            if len({(b.eps, b.decay) for b in bns}) != 1:
                continue
            if len({b.data_format for b in bns} | {"NHWC"}) != 1:
                continue
            # runtime-shape VMEM gate from the statically inferred types
            if not fused_bottleneck_supported(
                    (1, it.height, it.width, it.channels),
                    conv_a.n_out, conv_c.n_out,
                    self.conf.dtype or "float32",
                    stride=stride[0], has_skip=bool(skip_group)):
                continue
            if only is not None and out_name not in only:
                continue
            group = {"src": src, "conv_a": ca_name, "bn_a": bn_a,
                     "conv_b": cb_name, "bn_b": bn_b, "conv_c": cc_name,
                     "bn_c": bn_c_name, "add": add_name,
                     "stride": stride[0],
                     # shape metadata for the crossover fingerprint
                     # (tuning/plan.py) — unused by the apply path
                     "h": it.height, "w": it.width, "cin": it.channels,
                     "cmid": conv_a.n_out, "cout": conv_c.n_out,
                     **skip_group}
            members = [ca_name, bn_a, cb_name, bn_b, cc_name, bn_c_name,
                       add_name] + list(skip_group.values())
            if act_a:
                members.append(act_a)
            if act_b:
                members.append(act_b)
            if any(m in skip for m in members):
                continue
            bplan[out_name] = group
            for m in members:
                skip[m] = out_name
        return skip, bplan

    def _stem_fusion(self):
        """splan for the fused space-to-depth stem (nn/layers/stem.py):
        maps the maxpool vertex closing a
        [ZeroPadding(3,3,3,3) →] 7×7/2 pad-3 conv → BN → relu →
        3×3/2 pad-1 max-pool chain (NHWC, no bias, single consumers) to
        its vertex group. At most one chain matches (the stem consumes
        a network input resolution); everything else runs unfused."""
        from deeplearning4j_tpu.nn.conf.layers import (
            ActivationLayer, BatchNormalization, ConvolutionLayer,
            SubsamplingLayer, ZeroPaddingLayer)
        from deeplearning4j_tpu.nn.layers.stem import fused_stem_supported
        consumers, layer_of = self._fusion_graph_view()

        def sole_consumer(n):
            c = consumers.get(n, [])
            return c[0] if len(c) == 1 else None

        def chain_next(n):
            c = sole_consumer(n)
            if c is None or self.conf.vertex_inputs.get(c, []) != [n]:
                return None
            return c

        splan: Dict[str, Dict[str, Any]] = {}
        for cv_name in self._topo:
            conv = layer_of(cv_name, ConvolutionLayer)
            if (conv is None or tuple(conv.kernel) != (7, 7)
                    or tuple(conv.stride) != (2, 2)
                    or tuple(conv.dilation) != (1, 1)
                    or conv.has_bias
                    or conv.activation not in (None, "identity")
                    or conv.data_format != "NHWC"
                    or conv.convolution_mode != "truncate"):
                continue
            srcs = self.conf.vertex_inputs.get(cv_name, [])
            if len(srcs) != 1:
                continue
            members = [cv_name]
            pad_name = pre_vertex = None
            outputs = set(self.conf.network_outputs)
            if tuple(conv.padding) == (0, 0):
                # ZeroPadding(3,3,3,3) form (the zoo ResNet50 layout).
                # Matched by hand rather than layer_of: the pad vertex
                # legitimately carries the graph's input preprocessor
                # (FeedForwardToCnn), which the fused group absorbs.
                pad_name = srcs[0]
                pv = self.conf.vertices.get(pad_name)
                padl = pv.layer if (
                    isinstance(pv, LayerVertex)
                    and type(pv.layer) is ZeroPaddingLayer
                    and pad_name not in outputs
                    and not pv.layer.dropout) else None
                if (padl is None or tuple(padl._pads()) != (3, 3, 3, 3)
                        or padl.data_format != "NHWC"
                        or chain_next(pad_name) != cv_name):
                    continue
                if pv.preprocessor is not None:
                    pre_vertex = pad_name
                pin = self.conf.vertex_inputs.get(pad_name, [])
                if len(pin) != 1:
                    continue
                src = pin[0]
                it = self._vertex_input_types[pad_name][0]
                members.append(pad_name)
            elif tuple(conv.padding) == (3, 3):
                src = srcs[0]
                it = self._vertex_input_types[cv_name][0]
            else:
                continue
            if it.kind != "cnn":
                continue
            bn_name = chain_next(cv_name)
            bn = bn_name and layer_of(bn_name, BatchNormalization)
            if bn is None or \
                    len(self.conf.vertex_inputs.get(bn_name, [])) != 1:
                continue
            members.append(bn_name)
            nxt = chain_next(bn_name)
            act = bn.activation or "identity"
            if nxt is not None:
                al = layer_of(nxt, ActivationLayer)
                if al is not None and act == "identity":
                    members.append(nxt)
                    act = al.activation
                    nxt = chain_next(nxt)
            if act != "relu" or nxt is None:
                continue
            pool = layer_of(nxt, SubsamplingLayer)
            if (pool is None or pool.pooling_type.lower() != "max"
                    or tuple(pool.kernel) != (3, 3)
                    or tuple(pool.stride) != (2, 2)
                    or tuple(pool.padding) != (1, 1)
                    or pool.convolution_mode != "truncate"
                    or pool.data_format != "NHWC"):
                continue
            if not fused_stem_supported(
                    (1, it.height, it.width, it.channels), conv.n_out,
                    self.conf.dtype or "float32"):
                continue
            splan[nxt] = {"src": src, "conv": cv_name, "bn": bn_name,
                          "pre_vertex": pre_vertex,
                          "h": it.height, "w": it.width,
                          "cin": it.channels, "cout": conv.n_out,
                          "members": members}
        return splan

    def fusion_candidates(self):
        """Everything the fused execution plans COULD engage on this
        graph, independent of the currently selected plan: (bottleneck
        block groups, stem groups), each with the shape metadata the
        crossover fingerprints need (tuning/plan.py resolves
        ``execution_plan="auto"`` per candidate from the store). Pure
        read — no plan state is touched and no jitted step rebuilt;
        memoised per conf.dtype (the graph is fixed after construction
        but the VMEM gates are dtype-dependent), so per-fit plan
        re-resolution never re-walks the matchers."""
        cache = getattr(self, "_candidates_cache", None)
        if cache is None or cache[0] != self.conf.dtype:
            _, bplan = self._bottleneck_fusion(None)
            self._candidates_cache = (self.conf.dtype, bplan,
                                      self._stem_fusion())
        return self._candidates_cache[1:]

    # ------------------------------------------------------------------
    def _infer_types(self) -> Dict[str, InputType]:
        """Output InputType of every vertex, walking topo order.
        Memoised — the graph is fixed after construction, and per-token
        decode loops call this host-side."""
        if getattr(self, "_out_types_cache", None) is not None:
            return self._out_types_cache
        out_types: Dict[str, InputType] = {}
        for name, it in self.conf.input_types.items():
            out_types[name] = it
        for name in self._topo:
            ins = self.conf.vertex_inputs.get(name, [])
            its = [out_types[i] for i in ins if i in out_types]
            if len(its) != len(ins):
                missing = [i for i in ins if i not in out_types]
                raise ValueError(f"vertex {name}: missing input types for {missing} "
                                 "(call set_input_types on the builder)")
            self._vertex_input_types[name] = its
            out_types[name] = self.conf.vertices[name].output_type(its)
        self._out_types_cache = out_types
        return out_types

    def init(self):
        self._infer_types()
        key = jax.random.PRNGKey(self.conf.seed)
        self._rng = jax.random.PRNGKey(self.conf.seed + 1)
        keys = jax.random.split(key, max(2, len(self._topo)))
        self.params, self.state = {}, {}
        for i, name in enumerate(self._topo):
            v = self.conf.vertices[name]
            p, s = v.init(keys[i], self._vertex_input_types[name])
            self.params[name] = p
            self.state[name] = s
        self.updater_state = self.conf.updater.init_state(self.params)
        self._initialized = True
        return self

    def add_listener(self, listener):
        """Append a training listener (parity with MultiLayerNetwork)."""
        self.listeners.append(listener)
        return self

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(self.params))

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------
    def _forward(self, params, state, inputs: Dict[str, Any], *, train, rng,
                 fmasks: Optional[Dict[str, Any]] = None, carry_rnn=False,
                 stream=False, pad=None, preout_of=None, last_only=False):
        """Topo-order forward (ref: feedForward :1361). Returns
        (vertex_activations dict, new_state, masks dict). `preout_of` is a
        vertex name or a collection of names whose output layers should
        yield pre-activation outputs — the loss computes every output's
        preout in this ONE pass (ref: computeGradientAndScore :1298 runs a
        single feedForward for all outputs).

        `pad` (traced scalar) marks a left-padded streaming chunk
        (single-input graphs): non-streaming vertices see an ordinary key
        mask; streaming cache layers get pad_left for packed slot
        accounting (pads never enter caches) — see
        SelfAttentionLayer._stream_attend.

        `last_only` (rnn_time_step's: the caller will read the chunk's
        last position only) hands each head of `_last_only_heads` the
        last position of its input, so its product and its softmax run
        over one column and no [N, V, T] block exists."""
        preout_set = ({preout_of} if isinstance(preout_of, str)
                      else set(preout_of or ()))
        # inference honors the bf16 compute policy too (also applied by
        # _loss for reg in f32 — double application is a no-op): bf16
        # activations + weights halve HBM traffic and carried KV-cache
        # memory; output() / rnn_time_step cast final activations back
        # to f32 (f32_head)
        params, inputs = self._cast_compute(params, inputs)
        fused_plan, fused_skip, bneck_plan = self._fusion()
        stem_plan = self._stem_plan()
        acts: Dict[str, Any] = dict(inputs)
        masks: Dict[str, Any] = dict(fmasks or {})
        if pad is not None:
            masks = {name: jnp.broadcast_to(
                jnp.arange(a.shape[-1]) >= pad, (a.shape[0], a.shape[-1]))
                for name, a in inputs.items()}
        new_state: Dict[str, Any] = {}
        narrowed = self._last_only_heads() if last_only else ()
        for i, name in enumerate(self._topo):
            v = self.conf.vertices[name]
            ins = self.conf.vertex_inputs.get(name, [])
            in_masks = [masks.get(i_) for i_ in ins]
            if name in fused_skip:
                # absorbed into a downstream fused conv: produce no
                # activation; masks still propagate, bn state is written
                # by the fused step
                masks[name] = v.output_mask(
                    in_masks, self._vertex_input_types[name])
                new_state[name] = state.get(name, {})
                continue
            if name in fused_plan:
                bn_name, p_act, src = fused_plan[name]
                self._apply_fused(name, bn_name, p_act, acts[src], params,
                                  state, new_state, acts, train=train)
                masks[name] = v.output_mask(
                    in_masks, self._vertex_input_types[name])
                continue
            if name in bneck_plan:
                self._apply_fused_bottleneck(
                    name, bneck_plan[name], params, state, new_state,
                    acts, train=train)
                masks[name] = v.output_mask(
                    in_masks, self._vertex_input_types[name])
                continue
            if name in stem_plan:
                self._apply_fused_stem(
                    name, stem_plan[name], params, state, new_state,
                    acts, train=train)
                masks[name] = v.output_mask(
                    in_masks, self._vertex_input_types[name])
                continue
            xs = [acts[i_] for i_ in ins]
            if getattr(v, "wants_all_masks", False):
                mask = in_masks      # e.g. cross attention: keys = input 1
            else:
                mask = next((m for m in in_masks if m is not None), None)
            v_state = state.get(name, {})
            if not carry_rnn:
                v_state = {k: val for k, val in v_state.items()
                           if k not in STREAM_STATE_KEYS}
            rng_i = jax.random.fold_in(rng, i) if rng is not None else None
            if name in preout_set and isinstance(v, LayerVertex) and \
                    hasattr(v.layer, "compute_score"):
                x = xs[0]
                if v.preprocessor is not None:
                    x = v.preprocessor.apply(x, mask)
                acts[name] = v.layer.preout(v.layer and params[name], x,
                                            train=train, rng=rng_i)
                new_state[name] = v_state
            else:
                # stream (inference KV-cache decode) is distinct from
                # carry_rnn (tbptt h/c carry)
                extra = {}
                m_i = mask
                if getattr(v, "supports_streaming", False):
                    extra["stream"] = stream
                    if pad is not None:
                        # packed accounting replaces the mask (see
                        # MultiLayerNetwork._forward)
                        extra["pad_left"] = pad
                        m_i = None
                if name in narrowed:
                    xs = [xs[0][:, :, -1:]]
                y, s_new = v.apply(params[name], xs, v_state, train=train,
                                   rng=rng_i, mask=m_i, **extra)
                acts[name] = y
                new_state[name] = s_new
            masks[name] = v.output_mask(in_masks, self._vertex_input_types[name])
        return acts, new_state, masks

    def _apply_fused(self, conv_name, bn_name, p_act, y, params, state,
                     new_state, acts, *, train):
        """Execute one fused bn→act→conv1x1 group (see nn/layers/fused.py):
        y is the RAW activation feeding the bn vertex; writes the conv
        output into acts[conv_name] and the bn running stats into
        new_state[bn_name]."""
        from deeplearning4j_tpu.nn.layers.fused import bn_act_conv1x1
        from deeplearning4j_tpu.nn import activations as _act
        bn = self.conf.vertices[bn_name].layer
        conv = self.conf.vertices[conv_name].layer
        bn_params = params.get(bn_name, {})
        bn_state = state.get(bn_name, {})
        nf = bn_state["mean"].shape[0]
        gamma = bn_params.get("gamma", jnp.full((nf,), bn.gamma, y.dtype))
        beta = bn_params.get("beta", jnp.full((nf,), bn.beta, y.dtype))
        out, new_mean, new_var = bn_act_conv1x1(
            y, gamma, beta, bn_state["mean"], bn_state["var"],
            params[conv_name]["W"], params[conv_name].get("b"),
            train=train, eps=bn.eps, decay=bn.decay, act=p_act,
            data_format=conv.data_format)
        acts[conv_name] = _act.get(conv.activation)(out)
        new_state[bn_name] = ({"mean": new_mean, "var": new_var}
                              if train else bn_state)
        new_state[conv_name] = state.get(conv_name, {})

    def _apply_fused_bottleneck(self, out_name, group, params, state,
                                new_state, acts, *, train):
        """Execute one fused identity-bottleneck group (see
        nn/layers/bottleneck.py): reads the block input activation,
        writes the final relu output into acts[out_name] and each BN's
        running stats into new_state; params/state stay keyed by the
        original vertex names (serialization/import unaffected)."""
        from deeplearning4j_tpu.nn.layers.bottleneck import (
            BnParams, fused_bottleneck)
        x = acts[group["src"]]

        def bn_params(bn_name):
            bn = self.conf.vertices[bn_name].layer
            p = params.get(bn_name, {})
            s = state.get(bn_name, {})
            nf = s["mean"].shape[0]
            gamma = p.get("gamma", jnp.full((nf,), bn.gamma, x.dtype))
            beta = p.get("beta", jnp.full((nf,), bn.beta, x.dtype))
            # quantize through x.dtype exactly like the unfused
            # BatchNormalization.apply (fused.py precision-chain note):
            # the persistent running stats must round identically under
            # bf16 or the two execution plans train diverging state
            return bn, BnParams(
                gamma=gamma.astype(x.dtype),
                beta=beta.astype(x.dtype),
                running_mean=s["mean"].astype(x.dtype)
                .astype(jnp.float32),
                running_var=s["var"].astype(x.dtype)
                .astype(jnp.float32))

        bn_a, pa = bn_params(group["bn_a"])
        bn_b, pb = bn_params(group["bn_b"])
        bn_c, pc = bn_params(group["bn_c"])
        wa4 = params[group["conv_a"]]["W"]        # [O, I, 1, 1]
        wb4 = params[group["conv_b"]]["W"]        # [O, I, 3, 3]
        wc4 = params[group["conv_c"]]["W"]
        wa = wa4.reshape(wa4.shape[0], wa4.shape[1]).T
        wc = wc4.reshape(wc4.shape[0], wc4.shape[1]).T
        # tap-major [9, Cin, Cout]: tap t = kh*3+kw matches the kernel's
        # shifted-window order (cross-correlation, like lax.conv)
        wb = wb4.transpose(2, 3, 1, 0).reshape(9, wb4.shape[1],
                                               wb4.shape[0])
        if "conv_skip" in group:                  # downsample (entry) form
            ps = bn_params(group["bn_skip"])[1]
            ws4 = params[group["conv_skip"]]["W"]
            ws = ws4.reshape(ws4.shape[0], ws4.shape[1]).T
        else:
            ps = ws = None
        out, new_stats = fused_bottleneck(
            x, wa, pa, wb, pb, wc, pc, w_skip=ws, bn_skip=ps,
            stride=group.get("stride", 1), train=train, eps=bn_a.eps,
            decay=bn_a.decay,
            interpret=jax.default_backend() != "tpu")
        acts[out_name] = out
        # absorbed members already got pass-through state from the
        # fused_skip branch; only the trained BN stats and the output
        # vertex are written here
        if train:
            mua, vara, mub, varb, muc, varc = new_stats[:6]
            new_state[group["bn_a"]] = {"mean": mua, "var": vara}
            new_state[group["bn_b"]] = {"mean": mub, "var": varb}
            new_state[group["bn_c"]] = {"mean": muc, "var": varc}
            if ws is not None:
                new_state[group["bn_skip"]] = {"mean": new_stats[6],
                                               "var": new_stats[7]}
        new_state[out_name] = state.get(out_name, {})

    def _apply_fused_stem(self, out_name, group, params, state,
                          new_state, acts, *, train):
        """Execute the fused space-to-depth stem group (see
        nn/layers/stem.py): reads the raw network input activation,
        writes the pooled output into acts[out_name] and the stem BN's
        running stats into new_state; params/state stay keyed by the
        original vertex names (serialization/import unaffected)."""
        from deeplearning4j_tpu.nn.layers.bottleneck import BnParams
        from deeplearning4j_tpu.nn.layers.stem import fused_stem
        x = acts[group["src"]]
        if group.get("pre_vertex"):
            # the absorbed pad vertex's input preprocessor (e.g.
            # FeedForwardToCnn under the NHWC internal layout) still
            # runs — the kernel sees the same NHWC image the unfused
            # chain would
            x = self.conf.vertices[group["pre_vertex"]] \
                .preprocessor.apply(x, None)
        bn = self.conf.vertices[group["bn"]].layer
        p = params.get(group["bn"], {})
        s = state.get(group["bn"], {})
        nf = s["mean"].shape[0]
        gamma = p.get("gamma", jnp.full((nf,), bn.gamma, x.dtype))
        beta = p.get("beta", jnp.full((nf,), bn.beta, x.dtype))
        # same precision chain as the bottleneck plumbing: running stats
        # round through x.dtype so both execution plans train identical
        # persistent state under bf16
        bnp = BnParams(
            gamma=gamma.astype(x.dtype), beta=beta.astype(x.dtype),
            running_mean=s["mean"].astype(x.dtype).astype(jnp.float32),
            running_var=s["var"].astype(x.dtype).astype(jnp.float32))
        out, (nm, nv) = fused_stem(
            x, params[group["conv"]]["W"], bnp, train=train,
            eps=bn.eps, decay=bn.decay,
            interpret=jax.default_backend() != "tpu")
        acts[out_name] = out
        if train:
            new_state[group["bn"]] = {"mean": nm, "var": nv}
        new_state[out_name] = state.get(out_name, {})

    def _as_mask_dict(self, masks, default_key=None) -> Optional[Dict[str, Any]]:
        """Normalize a masks argument: a dict maps vertex name -> mask
        (None entries dropped); a bare array masks `default_key` (the
        first network input unless given, e.g. an output for label
        masks); None/all-None -> None."""
        if masks is None:
            return None
        if not isinstance(masks, dict):
            key = default_key or self.conf.network_inputs[0]
            # jit-boundary copy of the unprefetched compat path (the
            # multilayer._fit_batch twin lives in TPULINT_BASELINE):
            # fit(prefetch=N) stages these in the background worker, and
            # asarray on an already-device array is a no-op reference
            # tpulint: disable=device-transfer-in-hot-loop
            return {key: jnp.asarray(masks)}
        # tpulint: disable=device-transfer-in-hot-loop (same compat copy)
        out = {k: jnp.asarray(v) for k, v in masks.items() if v is not None}
        return out or None

    def _as_input_dict(self, inputs) -> Dict[str, Any]:
        if isinstance(inputs, dict):
            # jit-boundary copy of the unprefetched compat path — see
            # _as_mask_dict
            # tpulint: disable=device-transfer-in-hot-loop
            return {k: jnp.asarray(v) for k, v in inputs.items()}
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        # tpulint: disable=device-transfer-in-hot-loop (same compat copy)
        return {name: jnp.asarray(x)
                for name, x in zip(self.conf.network_inputs, inputs)}

    def _dequantized(self, params):
        """Materialize int8 QuantizedTensor leaves (W8A16 serving,
        optimize/quantization.py) as float32; XLA fuses the int8 convert
        into each consumer, which is where the HBM saving lives.
        Mirrors MultiLayerNetwork._dequantized."""
        from deeplearning4j_tpu.optimize.quantization import dequantize_tree
        return dequantize_tree(params, jnp.float32)

    def _cast_compute(self, params, inputs):
        """Dequantize int8 leaves, then apply the bf16 compute cast to
        params + the input dict (mirrors MultiLayerNetwork._cast_compute;
        conf.dtype sits in every jit key, so the policy can't go stale)."""
        from deeplearning4j_tpu.nn.compute import bf16_cast, bf16_cast_tree
        if getattr(self, "_quantized", False):
            params = self._dequantized(params)
        if self.conf.dtype in ("bfloat16", "bf16"):
            params = bf16_cast_tree(params)
            inputs = {k: bf16_cast(jnp.asarray(v))
                      for k, v in inputs.items()}
        return params, inputs

    def _loss(self, params, state, inputs, labels: Dict[str, Any], rng,
              fmasks, lmasks, *, train=True, carry_rnn=False):
        """Sum of output-layer losses + regularization."""
        # _forward applies the compute cast; dequantize here only so the
        # reg term below never sees int8 leaves (scoring path — training
        # itself is refused in _get_train_step)
        if getattr(self, "_quantized", False):
            params = self._dequantized(params)
        # ONE forward pass yields every output layer's preout (stateful
        # vertices update exactly once per step, matching the reference's
        # single feedForward in computeGradientAndScore :1298)
        total = 0.0
        acts, new_state, masks = self._forward(
            params, state, inputs, train=train, rng=rng, fmasks=fmasks,
            carry_rnn=carry_rnn, preout_of=self.conf.network_outputs)
        for out_name in self.conf.network_outputs:
            v = self.conf.vertices[out_name]
            if not (isinstance(v, LayerVertex) and
                    hasattr(v.layer, "compute_score")):
                raise ValueError(f"output vertex {out_name} is not an output layer")
            y = labels[out_name]
            lmask = (lmasks or {}).get(out_name)
            if lmask is None:
                ins = self.conf.vertex_inputs[out_name]
                lmask = next((masks.get(i_) for i_ in ins if masks.get(i_) is not None),
                             None)
            a_out = acts[out_name]
            a_out = a_out.astype(jnp.promote_types(a_out.dtype, jnp.float32))
            total = total + v.layer.compute_score(y, a_out, lmask)
            if isinstance(v.layer, CenterLossOutputLayer):
                ins = self.conf.vertex_inputs[out_name]
                feats = acts[ins[0]]
                o_state = new_state.get(out_name, {})
                total = total + v.layer.center_loss(feats, y, o_state)
                new_state[out_name] = v.layer.update_centers(
                    jax.lax.stop_gradient(feats), y, o_state)
        total = total + self._reg_loss(params)
        return total, new_state

    def _reg_loss(self, params):
        reg = 0.0
        for name, v in self.conf.vertices.items():
            if not isinstance(v, LayerVertex):
                continue
            l1c = v.layer.l1_coeffs()
            l2c = v.layer.l2_coeffs()
            p = params.get(name, {})
            for k, coeff in l1c.items():
                if k in p:
                    reg = reg + coeff * jnp.sum(jnp.abs(p[k]))
            for k, coeff in l2c.items():
                if k in p:
                    reg = reg + 0.5 * coeff * jnp.sum(p[k] ** 2)
        return reg

    # ------------------------------------------------------------------
    # training
    # ------------------------------------------------------------------
    def _get_train_step(self, carry_rnn: bool, policy: str = "off"):
        """One jitted step — sentinel semantics as in
        MultiLayerNetwork._get_train_step (5-tuple with a raw ok-flag
        when policy != "off"; "skip" where-zeroes bad updates)."""
        if getattr(self, "_quantized", False):
            raise RuntimeError(
                "this network was quantized for inference "
                "(quantize_for_inference) — int8 weights have no "
                "gradient path; train the fp checkpoint and re-quantize")
        key = ("train", carry_rnn, self.conf.dtype, policy)
        if key not in self._jit_cache:
            conf = self.conf

            def step(params, state, upd_state, inputs, labels, rng, fmasks, lmasks):
                (loss, new_state), grads = jax.value_and_grad(
                    lambda p: self._loss(p, state, inputs, labels, rng, fmasks,
                                         lmasks, train=True, carry_rnn=carry_rnn),
                    has_aux=True)(params)
                ok = None if policy == "off" else tree_finite(loss, grads)
                grads = normalize_gradients(grads, conf.gradient_normalization,
                                            conf.gradient_normalization_threshold)
                steps, new_upd = conf.updater.update(grads, upd_state, params)
                new_params = _tree_sub(params, steps)
                if policy == "off":
                    return new_params, new_state, new_upd, loss
                new_params, new_upd, new_state = guard_updates(
                    ok, policy, (new_params, params),
                    (new_upd, upd_state), (new_state, state))
                return new_params, new_state, new_upd, loss, ok

            self._jit_cache[key] = jax.jit(step, donate_argnums=(0, 2))
        return self._jit_cache[key]

    def _get_scan_train_step(self, k: int, policy: str = "off"):
        """Fused multi-step dispatch — the ComputationGraph twin of
        MultiLayerNetwork._get_scan_train_step: K optimizer updates in
        one jitted, buffer-donating lax.scan over stacked (dict-keyed)
        batches, returning the per-step loss vector (plus the per-step
        sentinel ok-flags when policy != "off")."""
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
                    ins, lbs, rng, fm, lm = inp
                    (loss, s2), grads = jax.value_and_grad(
                        lambda pp: self._loss(pp, s, ins, lbs, rng, fm, lm,
                                              train=True),
                        has_aux=True)(p)
                    ok = None if policy == "off" else \
                        tree_finite(loss, grads)
                    grads = normalize_gradients(
                        grads, conf.gradient_normalization,
                        conf.gradient_normalization_threshold)
                    steps, u2 = conf.updater.update(grads, u, p)
                    p2 = _tree_sub(p, steps)
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

    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def fit(self, data, labels=None, epochs: int = 1, batch_size: int = 32,
            *, steps_per_dispatch: int = 1, prefetch: int = 0,
            pad_tail: Optional[bool] = None,
            execution_plan: Optional[str] = None):
        """Train (ref: ComputationGraph.fit :837). Accepts a DataSetIterator
        (single-input/single-output), a DataSet, (features, labels), or dicts
        keyed by input/output names (MultiDataSet equivalent).

        ``execution_plan`` ("auto" | "fused" | "xla") selects how the
        eligible fused chains (bottleneck blocks, the space-to-depth
        stem) execute — "auto" resolves per shape from the measured
        kernel-crossover store with the XLA plan as the uncalibrated
        default (tuning/plan.py). Resolution happens ONCE here;
        re-resolving the same plan never rebuilds jitted steps, so the
        zero-retrace contract holds. None leaves an explicitly
        set_fusion'd plan untouched.

        `steps_per_dispatch` / `prefetch` / `pad_tail` are the fused
        multi-step dispatch and device-prefetch knobs — see
        MultiLayerNetwork.fit and ARCHITECTURE.md "Input pipeline &
        fused dispatch". Tail padding is skipped for feature-masked
        batches without an explicit labels mask: there the loss falls
        back to the PROPAGATED feature mask (see _loss), which a
        synthesized example-weight mask would shadow."""
        if not self._initialized:
            self.init()
        ensure_started()
        if execution_plan is not None:
            from deeplearning4j_tpu.tuning.plan import apply_execution_plan
            apply_execution_plan(self, execution_plan)
        if labels is not None:
            it = ArrayDataSetIterator(data, labels, batch_size)
        elif isinstance(data, DataSet):
            it = ArrayDataSetIterator(data.features, data.labels, batch_size,
                                      data.features_mask, data.labels_mask)
        else:
            it = data
        if it is not data:
            # align the internal iterator's pass counter with the
            # absolute epoch count — see MultiLayerNetwork.fit
            it.restore_state({"epoch": self.epoch_count, "pos": 0})
        k = max(1, int(steps_per_dispatch))
        pad = (k > 1) if pad_tail is None else bool(pad_tail)
        if prefetch:
            from deeplearning4j_tpu.pipeline.prefetch import \
                DevicePrefetchIterator
            # pad in the worker, BEFORE the transfer (padding a
            # device-resident batch in the fit loop would be a D2H
            # round-trip); pad_when carries the mask-shadowing
            # exemption the loop below applies to unprefetched batches
            it = DevicePrefetchIterator(
                it, prefetch=prefetch, pad_to="auto" if pad else None,
                pad_when=lambda ds: ds.labels is not None and (
                    ds.labels_mask is not None or ds.features_mask is None))
        # listener capability scan hoisted out of the per-batch path
        self._stash_features = any(getattr(l, "needs_batch_features", False)
                                   for l in self.listeners)
        # restored data-pipeline cursor: see MultiLayerNetwork.fit
        consume_restored_cursor(self, it)
        capture_cursor_pass(self, it)
        try:
            for _ in range(epochs):
                for lst in self.listeners:
                    lst.on_epoch_start(self, self.epoch_count)
                self._fit_epoch(it, k, pad)
                # completed-epoch ordering: see multilayer.py fit
                epoch_idx = self.epoch_count
                self.epoch_count += 1
                self._dispatched_in_epoch = 0
                self._canon_in_epoch = None
                self._cursor_pass += 1
                for lst in self.listeners:
                    lst.on_epoch_end(self, epoch_idx)
            # one allowed sync, after the final batch (see multilayer.fit)
            finalize_fit_telemetry(self)
        finally:
            self._stash_features = None
            self._cursor_pass = None
            close_listeners(self.listeners)
        return self

    def _fit_epoch(self, it, k: int, pad: bool):
        """One pass over the iterator — the graph twin of
        MultiLayerNetwork._fit_epoch: pad ragged batches to the
        canonical row count when `pad` and fuse runs of `k`
        same-signature batches into single scan dispatches; anything
        unfusable falls back to the per-batch step.

        Dispatch boundaries + cursor counters: see
        MultiLayerNetwork._fit_epoch."""
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
            if canon is None:
                canon = ds.num_examples()
                self._canon_in_epoch = canon
            # feature-masked batches without an explicit labels mask use
            # the PROPAGATED mask in _loss; a synthesized example-weight
            # mask would shadow it, so those stay unpadded
            if pad and ds.labels is not None and (
                    ds.labels_mask is not None or ds.features_mask is None):
                if ds.num_examples() < canon:
                    ds = pad_batch(ds, canon)
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
        """One fused K-step scan dispatch over stacked dict-keyed
        batches; listeners fire per logical step with lazy loss slices
        (see MultiLayerNetwork._fit_group)."""
        t0 = time.perf_counter()
        k = len(group)
        out0 = self.conf.network_outputs[0]
        with span("etl"):
            rngs = jnp.stack([self._next_rng() for _ in range(k)])
            ins = [self._as_input_dict(b.features) for b in group]
            lbs = [{out0: b.labels} if not isinstance(b.labels, dict)
                   else b.labels for b in group]
            fms = [self._as_mask_dict(b.features_mask) for b in group]
            lms = [self._as_mask_dict(b.labels_mask, default_key=out0)
                   for b in group]

            def stack_dicts(ds_list):
                if ds_list[0] is None:
                    return None
                return {kk: jnp.stack([d[kk] for d in ds_list])
                        for kk in ds_list[0]}

            xs = stack_dicts(ins)
            ys = stack_dicts(lbs)
            fmasks = stack_dicts(fms)
            lmasks = stack_dicts(lms)
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

    def _fit_batch(self, ds: DataSet):
        t0 = time.perf_counter()
        # listener parity with MultiLayerNetwork._fit_batch: viz listeners
        # (needs_batch_features) get the raw batch stashed here too
        stash = self._stash_features
        if stash is None:  # direct call outside fit(): no hoisted scan
            stash = any(getattr(l, "needs_batch_features", False)
                        for l in self.listeners)
        if stash:
            self._last_batch_features = ds.features
        with span("etl"):
            rng = self._next_rng()
            # jnp.asarray here is the jit-boundary copy of the
            # UNPREFETCHED compat path (baselined for tpulint
            # device-transfer-in-hot-loop): fit(prefetch=N) moves these
            # H2D copies into the background pipeline stage
            inputs = self._as_input_dict(ds.features)
            labels = {self.conf.network_outputs[0]: jnp.asarray(ds.labels)} \
                if not isinstance(ds.labels, dict) else \
                {k: jnp.asarray(v) for k, v in ds.labels.items()}
            fmasks = self._as_mask_dict(ds.features_mask)
            lmasks = self._as_mask_dict(ds.labels_mask,
                                        default_key=self.conf.network_outputs[0])
        policy = effective_policy(self)
        step = self._get_train_step(False, policy)
        with span("step"):
            self.params, self.state, self.updater_state, loss = \
                apply_step(self, policy, step, self.params, self.state,
                           self.updater_state, inputs, labels, rng,
                           fmasks, lmasks)
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

    # ------------------------------------------------------------------
    # inference
    # ------------------------------------------------------------------
    def output(self, *inputs, train: bool = False, masks=None):
        """Output activations (ref: output :1532). Returns a single array if
        the graph has one output, else a list."""
        if not self._initialized:
            self.init()
        key = ("out", train, self.conf.dtype)
        if key not in self._jit_cache:
            def fwd(params, state, ins, rng, fmasks):
                acts, new_state, _ = self._forward(params, state, ins, train=train,
                                                   rng=rng, fmasks=fmasks)
                return [_f32_head(acts[o])
                        for o in self.conf.network_outputs], new_state

            self._jit_cache[key] = jax.jit(fwd)
        if len(inputs) == 1 and isinstance(inputs[0], dict):
            ins = self._as_input_dict(inputs[0])
        else:
            ins = self._as_input_dict(list(inputs))
        fmasks = self._as_mask_dict(masks)
        rng = self._next_rng() if train else jax.random.PRNGKey(0)
        outs, _ = self._jit_cache[key](self.params, self.state, ins, rng, fmasks)
        return outs[0] if len(outs) == 1 else outs

    def score(self, ds: DataSet) -> float:
        inputs = self._as_input_dict(ds.features)
        labels = {self.conf.network_outputs[0]: jnp.asarray(ds.labels)} \
            if not isinstance(ds.labels, dict) else \
            {k: jnp.asarray(v) for k, v in ds.labels.items()}
        loss, _ = self._loss(self.params, self.state, inputs, labels, None,
                             None, None, train=False)
        return float(loss)

    def evaluate(self, iterator):
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        e = Evaluation()
        if isinstance(iterator, DataSet):
            iterator = ArrayDataSetIterator(iterator.features, iterator.labels, 128)
        for ds in iterator:
            out = self.output(ds.features, masks=ds.features_mask)
            e.eval(ds.labels, np.asarray(out), mask=ds.labels_mask)
        return e


    def rnn_time_step(self, *inputs, masks=None, pad_left=None,
                      donate_state=False, last_only=False):
        """Stateful streaming inference over the graph, carrying RNN h/c in
        self.state across calls (ref: ComputationGraph.rnnTimeStep).
        `masks` maps network-input name -> this chunk's [N, T] key mask
        for padded variable-length batches; attention vertices carry it
        in the KV cache so padded positions stay masked on later steps.

        `pad_left` (int, mutually exclusive with masks; single-input
        graphs only) marks the first pad_left positions as LEFT padding
        with packed accounting — pads never enter caches nor consume
        streaming positions, so any prompt length primes in one dispatch
        at a bucketed shape (see MultiLayerNetwork.rnn_time_step).

        `last_only=True` says the caller will read the chunk's last
        position only (a prime; see MultiLayerNetwork.rnn_time_step):
        every output over a time axis comes back as [N, C], and a
        per-position head (`_last_only_heads`) computes that one column
        alone. The state the call leaves is the same either way."""
        # the process-wide stream-cache sharding keys the cache
        # (flipping it retraces for every net on next use), and so does
        # the page-pool read this net's own attention layers hold.
        # donate_state (TPU/GPU only — a no-op on CPU) aliases the
        # carried state buffers into the dispatch: the serving engine's
        # paged decode sets it so the page pools update in place (see
        # MultiLayerNetwork.rnn_time_step).
        from deeplearning4j_tpu.nn.conf import layers as _L
        padded = pad_left is not None
        donate = donate_state and jax.default_backend() != "cpu"
        last_only = bool(last_only)
        key = ("rnn_step", padded, donate, last_only, self.conf.dtype,
               _L._STREAM_CACHE_SHARDING, self._paged_reads())
        if key not in self._jit_cache:
            read = last_position if last_only else (lambda y: y)

            if padded:
                def fwd(params, state, ins, rng, pad):
                    acts, new_state, _ = self._forward(
                        params, state, ins, train=False, rng=rng,
                        fmasks=None, carry_rnn=True, stream=True, pad=pad,
                        last_only=last_only)
                    return [_f32_head(read(acts[o])) for o in
                            self.conf.network_outputs], new_state
            else:
                def fwd(params, state, ins, rng, fmasks):
                    acts, new_state, _ = self._forward(
                        params, state, ins, train=False, rng=rng,
                        fmasks=fmasks, carry_rnn=True, stream=True,
                        last_only=last_only)
                    return [_f32_head(read(acts[o])) for o in
                            self.conf.network_outputs], new_state

            self._jit_cache[key] = jax.jit(
                fwd, donate_argnums=(1,) if donate else ())
        if len(inputs) == 1 and isinstance(inputs[0], dict):
            ins = self._as_input_dict(inputs[0])
        else:
            ins = self._as_input_dict(list(inputs))
        if padded:
            if masks is not None:
                raise ValueError("pad_left and masks are mutually exclusive")
            if len(ins) != 1:
                raise ValueError("pad_left needs a single-input graph "
                                 "(the pad applies to THE streamed input)")
            pad_left = int(pad_left)
            t = next(iter(ins.values())).shape[-1]
            if not 0 <= pad_left < t:
                raise ValueError(f"pad_left {pad_left} out of range for a "
                                 f"chunk of {t} positions")
            new_pos_map = self._check_graph_stream_budget(ins, pad=pad_left)
            outs, new_state = self._jit_cache[key](
                self.params, self.state, ins, jax.random.PRNGKey(0),
                jnp.asarray(pad_left, jnp.int32))
        else:
            fmasks = self._as_mask_dict(masks)
            new_pos_map = self._check_graph_stream_budget(ins)
            outs, new_state = self._jit_cache[key](
                self.params, self.state, ins, jax.random.PRNGKey(0), fmasks)
        self.state = new_state
        old_max = max(getattr(self, "_stream_pos_map", {}).values(),
                      default=0)
        self._stream_pos_map = new_pos_map
        rows = getattr(self, "_stream_pos_rows", None)
        if rows is not None:     # per-row positions (after per-row rewind)
            consumed = max(new_pos_map.values(), default=0) - old_max
            self._stream_pos_rows = rows + consumed
        return outs[0] if len(outs) == 1 else outs

    def _vertex_time_lengths(self, ins):
        """Propagate each vertex's output TIME length (None when
        non-temporal) through the topo order for this call's inputs.
        Temporality comes from the statically inferred output InputTypes
        (kind == "rnn"), so time-collapsing layers/vertices (LastTimeStep,
        GlobalPooling, …) propagate None without per-class special cases;
        the length itself is this call's runtime chunk length, taken from
        the first temporal input (DuplicateToTimeSeries re-expands from
        its reference sequence, which that rule also picks: its first —
        collapsed — input is non-temporal)."""
        out_types = self._infer_types()
        # a sequence of ids [N, T] (SequenceEmbeddingLayer's input) is
        # temporal too: its declared type says so, its rank does not
        lens = {name: (int(a.shape[-1]) if getattr(a, "ndim", 0) == 3
                       or (getattr(a, "ndim", 0) == 2
                           and self.conf.input_types[name].kind == "rnn")
                       else None)
                for name, a in ins.items()}
        for name in self._topo:
            if out_types[name].kind != "rnn":
                lens[name] = None
                continue
            slens = [lens.get(s)
                     for s in self.conf.vertex_inputs.get(name, [])]
            lens[name] = next((l for l in slens if l is not None), None)
        return lens

    def _check_graph_stream_budget(self, ins, pad: int = 0):
        """Per-vertex streaming budget: each streaming layer is charged
        the time length of the activation actually reaching it — in a
        multi-input graph (e.g. seq2seq decode re-feeding the full
        encoder sequence each step, or an encoder path collapsed through
        LastTimeStep+DuplicateToTimeSeries) different caches advance by
        different amounts. `pad` left-pad positions (packed padded
        priming; single-input graphs, so every temporal length carries
        the same pad) are free. Validates every vertex, returning the
        counter updates; the caller commits them after the forward
        succeeds."""
        lens = self._vertex_time_lengths(ins)
        pos = getattr(self, "_stream_pos_map", {})
        updates = {}
        for name, v in self.conf.vertices.items():
            layer = getattr(v, "layer", None)
            if layer is None or not getattr(layer, "supports_streaming",
                                            False):
                continue
            srcs = self.conf.vertex_inputs.get(name, [])
            t = next((lens[s] for s in srcs if lens.get(s) is not None),
                     None)
            if t is None:
                continue
            new_pos = pos.get(name, 0) + t - pad
            cap = stream_capacity([layer])
            if cap is not None and new_pos > cap:
                raise ValueError(
                    f"vertex '{name}' streamed {new_pos} positions, "
                    f"exceeding its streaming capacity ({cap}); call "
                    "rnn_clear_previous_state() or raise "
                    "cache_length/max_length")
            updates[name] = new_pos
        return {**pos, **updates}


    def _last_only_heads(self):
        """The output vertices that `rnn_time_step(last_only=True)` hands
        the last position of their input: per-position heads
        (``layers.narrows_to_last``) with no preprocessor before them
        that feed no other vertex. Any other output over a time axis is
        computed whole and its last position taken from the result."""
        fed = {s for ins in self.conf.vertex_inputs.values() for s in ins}
        return {o for o in self.conf.network_outputs
                if o not in fed
                and isinstance(self.conf.vertices[o], LayerVertex)
                and self.conf.vertices[o].preprocessor is None
                and narrows_to_last(self.conf.vertices[o].layer)}

    def _paged_reads(self):
        """This graph's part of its streaming jit keys: how each of its
        attention layers reads a page pool (``layers.paged_reads``)."""
        return paged_reads(self._paged_layers)

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
        """ref: ComputationGraph.rnnClearPreviousState."""
        self._stream_pos_map = {}
        self._stream_pos_rows = None
        for k, s in self.state.items():
            if isinstance(s, dict):
                self.state[k] = {kk: vv for kk, vv in s.items()
                                 if kk not in STREAM_STATE_KEYS}

    def summary(self) -> str:
        self._infer_types()
        lines = ["=" * 80,
                 f"{'vertex':<24}{'type':<26}{'inputs':<20}{'params':<10}",
                 "-" * 80]
        total = 0
        for name in self._topo:
            v = self.conf.vertices[name]
            nparams = sum(int(np.prod(p.shape))
                          for p in jax.tree_util.tree_leaves(self.params.get(name, {})))
            total += nparams
            tname = type(v.layer).__name__ if isinstance(v, LayerVertex) \
                else type(v).__name__
            ins = ",".join(self.conf.vertex_inputs.get(name, []))
            lines.append(f"{name:<24}{tname:<26}{ins:<20}{nparams:<10}")
        lines.append("-" * 80)
        lines.append(f"Total params: {total}")
        lines.append("=" * 80)
        return "\n".join(lines)

