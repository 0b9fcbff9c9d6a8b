"""Layer configuration classes.

TPU-native equivalent of deeplearning4j-nn/.../nn/conf/layers/* — one typed,
JSON-round-trippable dataclass per layer type. Unlike the reference (which
splits declarative conf classes from imperative impl classes in nn/layers/*),
each conf here owns its functional ``init``/``apply``: apply is a pure
function of (params, inputs, state, rng), so `jax.grad` provides every
backward pass the reference hand-writes, and `jax.jit` compiles the whole
network into one XLA program.

Shape inference mirrors InputTypeUtil.java; parameter initialization mirrors
nn/params/* (DefaultParamInitializer, ConvolutionParamInitializer,
LSTMParamInitializer...). Param names follow the reference ("W", "b", "RW",
"gamma", "beta"...) so DL4J checkpoint import maps 1:1.
"""

from __future__ import annotations

import contextlib
import dataclasses
from dataclasses import dataclass, field
from typing import (Any, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import activations as _act
from deeplearning4j_tpu.nn import losses as _losses
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import convolution as _conv
from deeplearning4j_tpu.nn.layers import linear_attention as _la
from deeplearning4j_tpu.nn.layers import normalization as _norm
from deeplearning4j_tpu.nn.layers import recurrent as _rnn
from deeplearning4j_tpu.nn.layers import routed_experts as _re
from deeplearning4j_tpu.nn.layers import sparse_latent as _sl
from deeplearning4j_tpu.nn.weights import init_weights

import numpy as np

#: per-layer state keys carried only by the streaming rnn_time_step path
#: (stripped on ordinary forwards; cleared by rnn_clear_previous_state):
#: LSTM h/c, attention KV cache, positional-embedding offset, and the
#: direct-paged-decode view (pool pair + page table) the serving engine
#: installs around its decode dispatches (serving/paged_kernel.py)
#: (kv_page_scale_k/v: the int8 pool's [P, Hkv] amax-scale sidecars —
#: serving/quant.py; kv_page_prime: the engine's prime-through-the-
#: pool marker — its presence routes a prefill chunk through the
#: paged path on the folded-gather read, see _stream_attend_paged)
#: (kv_c / kv_r / kv_i and their kv_page_* pool leaves: the latent, the
#: rotated key and the index key LatentAttentionLayer caches per token;
#: moe_stats: RoutedExpertsLayer's router-load counters; attn_stats: the
#: positions LatentAttentionLayer's streaming forms scored)
#: (gdn_s / gdn_conv: GatedDeltaNetLayer's float32 state a head and the
#: K - 1 inputs before its convolution, one row a stream whatever its
#: length; gdn_stats: the positions its two forms computed)
STREAM_STATE_KEYS = frozenset(
    {"h", "c", "kv_k", "kv_v", "kv_pos", "kv_abs", "kv_mask",
     "pos_offset", "kv_page_k", "kv_page_v", "kv_page_table",
     "kv_page_scale_k", "kv_page_scale_v", "kv_page_prime",
     "kv_c", "kv_r", "kv_i", "kv_page_c", "kv_page_r", "kv_page_i",
     "moe_stats", "attn_stats", "gdn_s", "gdn_conv", "gdn_stats"})

#: streaming-state keys whose LEADING axis is the batch dimension (beam
#: search gathers these when pruning beams; kv_pos/kv_abs/pos_offset are
#: batch-independent scalars/vectors)
BATCHED_STREAM_KEYS = frozenset({"h", "c", "kv_k", "kv_v", "kv_mask",
                                 "kv_c", "kv_r", "kv_i", "gdn_s",
                                 "gdn_conv"})


def reorder_stream_state(net, indices) -> None:
    """Gather the batch dimension of every carried streaming-state array
    (beam-search pruning: surviving beam b continues from parent
    indices[b]'s caches/RNN state). `indices`: int array [new_batch].
    kv_pos is normally a batch-independent scalar, but a per-row rewind
    (rewind_stream_state with an array) promotes it to [N] — gathered
    here like the caches so reordering keeps each row's own position
    (same for a rolling cache's kv_abs once promoted to [N, L])."""
    idx = jnp.asarray(indices)
    for name, s in net.state.items():
        if not isinstance(s, dict):
            continue
        net.state[name] = {
            kk: (vv[idx] if kk in BATCHED_STREAM_KEYS
                 or (kk == "kv_pos" and getattr(vv, "ndim", 0) >= 1)
                 or (kk == "kv_abs" and getattr(vv, "ndim", 0) >= 2)
                 else vv)
            for kk, vv in s.items()}
    rows = getattr(net, "_stream_pos_rows", None)
    if rows is not None:         # host row-position mirror follows
        net._stream_pos_rows = np.asarray(rows)[np.asarray(indices)]


def rewind_stream_state(net, n) -> None:
    """Rewind the last `n` streamed positions (speculative-decoding
    rollback, util/decoding.speculative_sample): position counters
    (attention kv_pos, positional-embedding pos_offset) move back by n —
    the rejected cache slots become invisible to the position-validity
    masks and are overwritten by the next write, so a rewound stream is
    exactly the stream that never saw those tokens (test-pinned).

    `n` may be an int (all rows rewind together) or an int array [N]
    (PER-ROW rewind — batched speculative decoding, where each row
    accepts a different prefix). A per-row rewind promotes kv_pos from a
    shared scalar to a [N] vector; the attention streaming path then
    writes each row's next chunk at its own slots (SelfAttentionLayer.
    _stream_attend vector-pos branch). Per-row rewind is attention-only:
    PositionalEmbeddingLayer's pos_offset stays scalar, so nets with
    learned positional tables reject array rewinds.

    Only position-indexed state can rewind: recurrent state (LSTM h/c,
    linear-attention state) carries the rejected steps irreversibly, so
    nets with such layers raise. Rolling (windowed) caches additionally need
    cache_length >= window + n — a rejected write may have evicted the
    slot n positions short of the window edge."""
    per_row = np.ndim(n) > 0
    if not per_row and n == 0:
        return
    if per_row:
        n = np.asarray(n, np.int32)
        if not n.any():
            return
    check_rewindable(net, int(np.max(n)) if per_row else n)
    # ONE device dispatch for every counter (speculative decoding calls
    # this per round — per-counter updates would pay dispatch latency
    # once per layer per round)
    refs, vals = [], []
    for name, s in net.state.items():
        if not isinstance(s, dict):
            continue
        for k in ("kv_pos", "pos_offset"):
            if k in s:
                if per_row and k == "pos_offset":
                    raise ValueError(
                        "per-row rewind is attention-only: learned "
                        "positional tables carry a shared pos_offset "
                        "(use a rope or position-free model)")
                refs.append((name, k))
                vals.append(s[k])
    if refs:
        # the rewind amount is data-dependent per call (accepted-token
        # counts differ every speculative step): a tiny scalar/[S] int
        # upload is inherent to the rejection walk, not a missed cache
        # tpulint: disable=device-transfer-in-hot-loop
        new_vals = _rewind_counters(vals, jnp.asarray(n, jnp.int32))
        for (name, k), v in zip(refs, new_vals):
            s = dict(net.state[name])
            s[k] = v
            net.state[name] = s
    if per_row:
        # exact host-side row positions: the budget counters must track
        # max-over-rows (a min-subtraction would drift them upward and
        # trip check_stream_budget spuriously once rows diverge; a
        # max-subtraction would under-count and overrun the cache)
        rows = getattr(net, "_stream_pos_rows", None)
        if rows is None or len(rows) != len(n):
            base = getattr(net, "_stream_pos", None)
            if base is None:
                pm0 = getattr(net, "_stream_pos_map", None) or {}
                base = max(pm0.values(), default=0)
            rows = np.full(len(n), base, np.int64)
        new_rows = np.maximum(rows - n, 0)
        net._stream_pos_rows = new_rows
        n_scalar = int(rows.max()) - int(new_rows.max())
    else:
        n_scalar = n
        rows = getattr(net, "_stream_pos_rows", None)
        if rows is not None:
            net._stream_pos_rows = np.maximum(rows - n, 0)
    if getattr(net, "_stream_pos", None) is not None:
        net._stream_pos = max(0, net._stream_pos - n_scalar)
    pm = getattr(net, "_stream_pos_map", None)
    if pm:
        net._stream_pos_map = {k: max(0, v - n_scalar)
                               for k, v in pm.items()}


@jax.jit
def _rewind_counters(vals, n):
    return [jnp.maximum(v - n, 0) for v in vals]


_NO_REWIND = ("rewind_stream_state: recurrent state (LSTM h/c, "
              "linear-attention state) is a function of every token fed "
              "and cannot be rewound: such layers do not support "
              "speculative rollback")


def check_rewindable(net, n: int) -> None:
    """Validate that `net` can rewind up to `n` streamed positions
    (rewind_stream_state preconditions) — speculative_sample calls this
    ONCE at entry with n = gamma, so a non-rewindable net fails fast
    instead of mid-generation at the first data-dependent rejection."""
    if n < 0:
        raise ValueError(f"rewind must be >= 0, got {n}")
    for s in net.state.values():
        if isinstance(s, dict) and ("h" in s or "c" in s or "gdn_s" in s):
            raise ValueError(_NO_REWIND)
    layers = list(getattr(net, "layers", None) or []) or [
        getattr(v, "layer", None)
        for v in (getattr(net.conf, "vertices", None) or {}).values()]
    for l in layers:
        # static check too: a freshly-cleared stream has no h/c in state
        # yet, but the layer WILL carry it as soon as it streams
        if getattr(l, "carries_recurrent_state", False):
            raise ValueError(_NO_REWIND)
        w = getattr(l, "window", None)
        if w and getattr(l, "supports_streaming", False):
            L = getattr(l, "cache_length", 0)
            if L < w + n:
                raise ValueError(
                    f"rewinding {n} positions on a rolling cache needs "
                    f"cache_length >= window + n ({L} < {w + n}) — the "
                    "rejected writes evicted still-in-window slots")


#: (mesh, axis) sharding the streaming KV caches over their slot axis, or
#: None (single-device caches). Module-level like use_cnn_data_format —
#: set through MultiLayerNetwork/ComputationGraph.set_stream_cache_sharding,
#: which also invalidates the nets' jit caches.
_STREAM_CACHE_SHARDING: Optional[Tuple[Any, str]] = None


def set_stream_cache_sharding(mesh, axis: str = "data") -> None:
    """Shard streaming attention KV caches over the sequence (slot) axis
    of `mesh` (None disables).

    With this set, the carried kv_k/kv_v ([N,Hkv,L,D]) and kv_mask
    ([N,L]) get a sharding constraint partitioning L across the mesh —
    per-device cache memory is O(L/n). XLA partitions the incremental
    cache writes and the cache attention accordingly, inserting the
    cross-device combine for the softmax — the jit-native form of
    sequence-parallel streaming decode (sample_stream / rnn_time_step
    work unchanged; SURVEY §5 long-context)."""
    global _STREAM_CACHE_SHARDING
    _STREAM_CACHE_SHARDING = None if mesh is None else (mesh, axis)


def paged_reads(layers) -> Tuple[Tuple[str, bool], ...]:
    """The ``paged_read`` of every layer among `layers` that has one: a
    net's streaming jit keys hold it, so a net whose engine chose the
    kernel and a net whose engine chose the folded gather each trace,
    and keep, their own program."""
    return tuple(l.paged_read for l in layers if hasattr(l, "paged_read"))


def _shard_cache(x, n_lead: int):
    """Sharding-constrain a streaming-cache array whose slot axis sits at
    position n_lead (kc/vc: 2, kv_mask: 1). No-op when unconfigured."""
    if _STREAM_CACHE_SHARDING is None or x is None:
        return x
    mesh, axis = _STREAM_CACHE_SHARDING
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = P(*([None] * n_lead), axis, *([None] * (x.ndim - n_lead - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def stream_capacity(layers):
    """Smallest streaming-position capacity over `layers` (None if
    unbounded): max_length always caps; cache_length caps only for
    non-windowed layers (a rolling window cache never fills up)."""
    limit = None
    for l in layers:
        if not getattr(l, "supports_streaming", False):
            continue
        windowed = getattr(l, "window", None) is not None
        caps = [getattr(l, "max_length", 0)]
        if not windowed:
            caps.append(getattr(l, "cache_length", 0))
        for cap in caps:
            if cap:
                limit = cap if limit is None else min(limit, cap)
    return limit


def check_stream_budget(net, t: int, layers, pad: int = 0) -> int:
    """Host-side guard for streaming inference: dynamic_update_slice
    CLAMPS out-of-range starts, so streaming past a layer's KV-cache /
    positional capacity would silently corrupt instead of erroring.
    Tracks net._stream_pos (reset by rnn_clear_previous_state).

    `pad` left-pad positions (packed padded priming) are free: they
    never enter a cache nor advance a position.

    Validates only — returns the would-be position; the caller commits
    it to net._stream_pos AFTER the forward succeeds, so neither a
    rejected oversized call nor a forward-raised error (e.g. a
    mid-stream mask) inflates the counter past the real cache state."""
    new_pos = getattr(net, "_stream_pos", 0) + int(t) - int(pad)
    limit = stream_capacity(layers)
    if limit is not None and new_pos > limit:
        raise ValueError(
            f"streamed {new_pos} positions, exceeding the smallest "
            f"streaming capacity ({limit}); call rnn_clear_previous_state() "
            "or raise cache_length/max_length")
    return new_pos

# ---------------------------------------------------------------------------
# registry + serde
# ---------------------------------------------------------------------------

LAYER_REGISTRY: Dict[str, type] = {}


def register_layer(cls):
    LAYER_REGISTRY[cls.__name__] = cls
    return cls


def layer_to_dict(layer) -> dict:
    d = {"@class": type(layer).__name__}
    for f in dataclasses.fields(layer):
        v = getattr(layer, f.name)
        if f.name == "constraints" and v:  # list OR tuple of constraints
            v = [c.to_dict() for c in v]
        elif hasattr(v, "to_dict") and f.name in ("dropout", "weight_noise"):
            v = v.to_dict()
        elif isinstance(v, tuple):
            v = list(v)
        d[f.name] = v
    return d


def layer_from_dict(d: dict):
    d = dict(d)
    cls_name = d.pop("@class")
    cls = LAYER_REGISTRY.get(cls_name)
    if cls is None:
        raise ValueError(f"Unknown layer class '{cls_name}'")
    names = {f.name for f in dataclasses.fields(cls)}
    kwargs = {k: v for k, v in d.items() if k in names}
    if isinstance(kwargs.get("dropout"), dict):
        from deeplearning4j_tpu.nn.conf.dropout import dropout_from_dict
        kwargs["dropout"] = dropout_from_dict(kwargs["dropout"])
    if isinstance(kwargs.get("weight_noise"), dict):
        from deeplearning4j_tpu.nn.conf.dropout import weight_noise_from_dict
        kwargs["weight_noise"] = weight_noise_from_dict(kwargs["weight_noise"])
    if kwargs.get("constraints"):
        from deeplearning4j_tpu.nn.conf.constraints import constraint_from_dict
        kwargs["constraints"] = [
            constraint_from_dict(c) if isinstance(c, dict) else c
            for c in kwargs["constraints"]]
    return cls(**kwargs)


def _pair(v) -> Tuple[int, int]:
    if isinstance(v, (list, tuple)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


# ---------------------------------------------------------------------------
# base classes
# ---------------------------------------------------------------------------


@dataclass
class LayerConf:
    """Base for all layer configs (ref: nn/conf/layers/Layer.java)."""

    name: Optional[str] = None
    # DL4J semantics: `dropout` is the RETAIN probability applied to the layer
    # INPUT during training (ref: conf/dropout/Dropout.java); 0.0 = disabled.
    # Also accepts an IDropout object (AlphaDropout, GaussianDropout, ...).
    dropout: Any = 0.0
    # optional IWeightNoise (DropConnect/WeightNoise) applied to this
    # layer's params during training (ref: conf/weightnoise/)
    weight_noise: Any = None
    # weight constraints projected after each update (ref: conf/constraint/)
    constraints: Any = None

    # -- protocol ----------------------------------------------------------
    def output_type(self, it: InputType) -> InputType:
        return it

    def init(self, key, it: InputType) -> Tuple[dict, dict]:
        """Return (params, state) pytrees for this layer."""
        return {}, {}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        """Return (y, new_state). Must be pure/traceable."""
        raise NotImplementedError

    def output_mask(self, mask, it: InputType):
        """Propagate a [batch, time] mask through this layer (ref: feedForwardMaskArray)."""
        return mask

    # regularization coefficients collected by the network loss
    def l1_coeffs(self) -> Dict[str, float]:
        return {}

    def l2_coeffs(self) -> Dict[str, float]:
        return {}

    def maybe_dropout_input(self, x, train, rng):
        if not train or rng is None:
            return x
        if hasattr(self.dropout, "apply_dropout"):  # IDropout object
            return self.dropout.apply_dropout(x, rng)
        if isinstance(self.dropout, (int, float)) and 0.0 < self.dropout < 1.0:
            keep = self.dropout
            m = jax.random.bernoulli(rng, keep, x.shape)
            return jnp.where(m, x / keep, 0.0)
        return x

    def to_dict(self):
        return layer_to_dict(self)


@dataclass
class BaseLayerConf(LayerConf):
    """Base for parameterized layers (ref: conf/layers/BaseLayer.java):
    activation / weight init / bias init / L1-L2 regularization."""

    activation: str = "identity"
    weight_init: str = "xavier"
    dist: Optional[dict] = None
    bias_init: float = 0.0
    l1: float = 0.0
    l2: float = 0.0
    l1_bias: float = 0.0
    l2_bias: float = 0.0
    learning_rate: Optional[float] = None  # per-layer LR override
    updater: Optional[dict] = None  # per-layer updater override

    def l1_coeffs(self):
        d = {}
        if self.l1:
            d["W"] = self.l1
            d["RW"] = self.l1
        if self.l1_bias:
            d["b"] = self.l1_bias
        return d

    def l2_coeffs(self):
        d = {}
        if self.l2:
            d["W"] = self.l2
            d["RW"] = self.l2
        if self.l2_bias:
            d["b"] = self.l2_bias
        return d


@dataclass
class FeedForwardLayerConf(BaseLayerConf):
    """Base for layers with nIn/nOut (ref: conf/layers/FeedForwardLayer.java)."""

    n_in: Optional[int] = None
    n_out: Optional[int] = None

    def infer_n_in(self, it: InputType):
        if self.n_in is None:
            self.n_in = it.flat_size()


# ---------------------------------------------------------------------------
# feed-forward layers
# ---------------------------------------------------------------------------


@register_layer
@dataclass
class DenseLayer(FeedForwardLayerConf):
    """Fully-connected layer (ref: conf/layers/DenseLayer.java;
    impl nn/layers/feedforward/dense/DenseLayer.java via BaseLayer W·x+b)."""

    has_bias: bool = True

    def output_type(self, it):
        return InputType.feed_forward(self.n_out)

    def init(self, key, it):
        self.infer_n_in(it)
        w = init_weights(key, (self.n_in, self.n_out), self.n_in, self.n_out,
                         self.weight_init, self.dist)
        p = {"W": w}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, jnp.float32)
        return p, {}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        y = x @ params["W"]
        if self.has_bias:
            y = y + params["b"]
        return _act.get(self.activation)(y), state


@register_layer
@dataclass
class EmbeddingLayer(FeedForwardLayerConf):
    """Embedding lookup (ref: conf/layers/EmbeddingLayer.java; impl
    feedforward/embedding/EmbeddingLayer.java — input is a column of indices)."""

    has_bias: bool = True

    def output_type(self, it):
        return InputType.feed_forward(self.n_out)

    def init(self, key, it):
        if self.n_in is None:
            self.n_in = it.flat_size()
        w = init_weights(key, (self.n_in, self.n_out), self.n_in, self.n_out,
                         self.weight_init, self.dist)
        p = {"W": w}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, jnp.float32)
        return p, {}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 2:
            idx = idx[:, 0]
        y = params["W"][idx]
        if self.has_bias:
            y = y + params["b"]
        return _act.get(self.activation)(y), state


@register_layer
@dataclass
class ActivationLayer(LayerConf):
    """Standalone activation (ref: conf/layers/ActivationLayer.java)."""

    activation: str = "relu"

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        return _act.get(self.activation)(x), state


@register_layer
@dataclass
class DropoutLayer(LayerConf):
    """Dropout as its own layer (ref: conf/layers/DropoutLayer.java).
    `dropout` field = retain probability (DL4J semantics)."""

    def __post_init__(self):
        if self.dropout == 0.0:
            self.dropout = 0.5

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        return self.maybe_dropout_input(x, train, rng), state


# ---------------------------------------------------------------------------
# convolutional layers
# ---------------------------------------------------------------------------


@register_layer
@dataclass
class ConvolutionLayer(FeedForwardLayerConf):
    """2-D convolution, NCHW (ref: conf/layers/ConvolutionLayer.java; native
    path CudnnConvolutionHelper.java:54 → here `lax.conv_general_dilated`)."""

    kernel: Sequence[int] = (3, 3)
    stride: Sequence[int] = (1, 1)
    padding: Sequence[int] = (0, 0)
    dilation: Sequence[int] = (1, 1)
    convolution_mode: str = "truncate"  # truncate | strict | same
    has_bias: bool = True
    data_format: str = "NCHW"  # internal activation layout; NHWC = TPU-fast

    def output_type(self, it):
        if it.kind != "cnn":
            raise ValueError(f"ConvolutionLayer needs CNN input, got {it}")
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        dh, dw = _pair(self.dilation)
        oh = _conv.conv_out_size(it.height, kh, sh, ph, dh, self.convolution_mode)
        ow = _conv.conv_out_size(it.width, kw, sw, pw, dw, self.convolution_mode)
        return InputType.convolutional(oh, ow, self.n_out)

    def init(self, key, it):
        if self.n_in is None:
            self.n_in = it.channels
        kh, kw = _pair(self.kernel)
        fan_in = self.n_in * kh * kw
        fan_out = self.n_out * kh * kw
        w = init_weights(key, (self.n_out, self.n_in, kh, kw), fan_in, fan_out,
                         self.weight_init, self.dist)
        p = {"W": w}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, jnp.float32)
        return p, {}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        y = _conv.conv2d(x, params["W"], params.get("b"), _pair(self.stride),
                         _pair(self.padding), _pair(self.dilation),
                         self.convolution_mode, self.data_format)
        return _act.get(self.activation)(y), state


@register_layer
@dataclass
class Convolution1DLayer(FeedForwardLayerConf):
    """1-D convolution over [N, C, W] (ref: conf/layers/Convolution1DLayer.java)."""

    kernel: int = 3
    stride: int = 1
    padding: int = 0
    dilation: int = 1
    convolution_mode: str = "truncate"
    has_bias: bool = True

    def output_type(self, it):
        ow = _conv.conv_out_size(it.timesteps, self.kernel, self.stride,
                                 self.padding, self.dilation, self.convolution_mode) \
            if it.timesteps is not None else None
        return InputType.recurrent(self.n_out, ow)

    def init(self, key, it):
        if self.n_in is None:
            self.n_in = it.size
        fan_in = self.n_in * self.kernel
        fan_out = self.n_out * self.kernel
        w = init_weights(key, (self.n_out, self.n_in, self.kernel), fan_in, fan_out,
                         self.weight_init, self.dist)
        p = {"W": w}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, jnp.float32)
        return p, {}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        y = _conv.conv1d(x, params["W"], params.get("b"), self.stride, self.padding,
                         self.dilation, self.convolution_mode)
        return _act.get(self.activation)(y), state


@register_layer
@dataclass
class Deconvolution2DLayer(ConvolutionLayer):
    """Transposed convolution (ref: later-DL4J Deconvolution2D; included for
    completeness of the conv family)."""

    def output_type(self, it):
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        if self.convolution_mode == "same":
            oh, ow = it.height * sh, it.width * sw
        else:
            oh = sh * (it.height - 1) + kh - 2 * ph
            ow = sw * (it.width - 1) + kw - 2 * pw
        return InputType.convolutional(oh, ow, self.n_out)

    def init(self, key, it):
        if self.n_in is None:
            self.n_in = it.channels
        kh, kw = _pair(self.kernel)
        fan_in = self.n_in * kh * kw
        fan_out = self.n_out * kh * kw
        # conv_transpose with transpose_kernel expects [O, I, kH, kW] flipped use
        w = init_weights(key, (self.n_out, self.n_in, kh, kw), fan_in, fan_out,
                         self.weight_init, self.dist)
        p = {"W": w}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, jnp.float32)
        return p, {}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        y = _conv.deconv2d(x, params["W"], params.get("b"), _pair(self.stride),
                           _pair(self.padding), self.convolution_mode,
                           self.data_format)
        return _act.get(self.activation)(y), state


@register_layer
@dataclass
class SubsamplingLayer(LayerConf):
    """2-D pooling (ref: conf/layers/SubsamplingLayer.java; native path
    CudnnSubsamplingHelper.java → here `lax.reduce_window`)."""

    pooling_type: str = "max"  # max | avg | pnorm | sum
    kernel: Sequence[int] = (2, 2)
    stride: Sequence[int] = (2, 2)
    padding: Sequence[int] = (0, 0)
    convolution_mode: str = "truncate"
    pnorm: float = 2.0
    data_format: str = "NCHW"

    def output_type(self, it):
        kh, kw = _pair(self.kernel)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        oh = _conv.conv_out_size(it.height, kh, sh, ph, 1, self.convolution_mode)
        ow = _conv.conv_out_size(it.width, kw, sw, pw, 1, self.convolution_mode)
        return InputType.convolutional(oh, ow, it.channels)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        k, s, p = _pair(self.kernel), _pair(self.stride), _pair(self.padding)
        df = self.data_format
        pt = self.pooling_type.lower()
        if pt == "max":
            y = _conv.max_pool2d(x, k, s, p, self.convolution_mode,
                                 data_format=df)
        elif pt == "avg":
            y = _conv.avg_pool2d(x, k, s, p, self.convolution_mode,
                                 data_format=df)
        elif pt == "pnorm":
            y = _conv.pnorm_pool2d(x, k, s, p, self.pnorm,
                                   self.convolution_mode, data_format=df)
        elif pt == "sum":
            y = _conv.avg_pool2d(x, k, s, p, self.convolution_mode,
                                 data_format=df) * (k[0] * k[1])
        else:
            raise ValueError(f"unknown pooling type {self.pooling_type}")
        return y, state


@register_layer
@dataclass
class Subsampling1DLayer(LayerConf):
    """1-D pooling over [N, C, W] (ref: conf/layers/Subsampling1DLayer.java)."""

    pooling_type: str = "max"
    kernel: int = 2
    stride: int = 2
    padding: int = 0
    convolution_mode: str = "truncate"

    def output_type(self, it):
        ow = _conv.conv_out_size(it.timesteps, self.kernel, self.stride,
                                 self.padding, 1, self.convolution_mode) \
            if it.timesteps is not None else None
        return InputType.recurrent(it.size, ow)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        x4 = x[:, :, None, :]  # [N,C,1,W]
        k, s, p = (1, self.kernel), (1, self.stride), (0, self.padding)
        if self.pooling_type.lower() == "max":
            y = _conv.max_pool2d(x4, k, s, p, self.convolution_mode)
        else:
            y = _conv.avg_pool2d(x4, k, s, p, self.convolution_mode)
        return y[:, :, 0, :], state


@register_layer
@dataclass
class Upsampling2DLayer(LayerConf):
    """Nearest-neighbour upsampling (ref: conf/layers/Upsampling2D.java)."""

    size: Sequence[int] = (2, 2)
    data_format: str = "NCHW"

    def output_type(self, it):
        sh, sw = _pair(self.size)
        return InputType.convolutional(it.height * sh, it.width * sw, it.channels)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        return _conv.upsample2d(x, _pair(self.size), self.data_format), state


@register_layer
@dataclass
class Upsampling1DLayer(LayerConf):
    """Nearest-neighbour upsampling along time, [N, C, T] → [N, C, T*size]
    (ref: conf/layers/Upsampling1D.java; Keras UpSampling1D)."""

    size: int = 2

    def output_type(self, it):
        t = it.timesteps * self.size if it.timesteps is not None else None
        return InputType.recurrent(it.size, t)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        return jnp.repeat(x, self.size, axis=2), state


@register_layer
@dataclass
class ZeroPadding1DLayer(LayerConf):
    """Zero padding along time, [N, C, T] → [N, C, left+T+right]
    (ref: conf/layers/ZeroPadding1DLayer.java; Keras ZeroPadding1D)."""

    padding: Sequence[int] = (1, 1)  # (left, right); int means symmetric

    def _pads(self):
        p = self.padding
        if isinstance(p, int):
            return (p, p)
        p = list(p)
        if len(p) == 1:
            return (int(p[0]), int(p[0]))
        return (int(p[0]), int(p[1]))

    def output_type(self, it):
        l, r = self._pads()
        t = it.timesteps + l + r if it.timesteps is not None else None
        return InputType.recurrent(it.size, t)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        l, r = self._pads()
        return jnp.pad(x, ((0, 0), (0, 0), (l, r))), state


@register_layer
@dataclass
class ZeroPaddingLayer(LayerConf):
    """Zero padding [top, bottom, left, right] (ref: conf/layers/ZeroPaddingLayer.java)."""

    padding: Sequence[int] = (0, 0, 0, 0)
    data_format: str = "NCHW"

    def _pads(self):
        p = list(self.padding)
        if len(p) == 2:
            p = [p[0], p[0], p[1], p[1]]
        return p

    def output_type(self, it):
        t, b, l, r = self._pads()
        return InputType.convolutional(it.height + t + b, it.width + l + r, it.channels)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        return _conv.zero_pad2d(x, self._pads(), self.data_format), state


@register_layer
@dataclass
class GlobalPoolingLayer(LayerConf):
    """Global pooling over time or spatial dims (ref: conf/layers/
    GlobalPoolingLayer.java; impl pooling/GlobalPoolingLayer.java). Mask-aware
    for RNN input like the reference (MaskedReductionUtil)."""

    pooling_type: str = "max"  # max | avg | sum | pnorm
    pnorm: float = 2.0
    collapse_dimensions: bool = True
    data_format: str = "NCHW"  # layout of 4-D (CNN) input

    def output_type(self, it):
        if it.kind == "rnn":
            return InputType.feed_forward(it.size)
        if it.kind == "cnn":
            return InputType.feed_forward(it.channels)
        return it

    def output_mask(self, mask, it):
        return None  # pooling over time consumes the mask

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        pt = self.pooling_type.lower()
        if x.ndim == 3:  # [N, C, T] — pool over time, honoring mask
            axes = (2,)
            if mask is not None:
                m = mask[:, None, :].astype(x.dtype)
                if pt == "max":
                    y = jnp.max(jnp.where(m > 0, x, -jnp.inf), axis=2)
                elif pt == "avg":
                    y = jnp.sum(x * m, axis=2) / jnp.clip(jnp.sum(m, axis=2), 1e-8, None)
                elif pt == "sum":
                    y = jnp.sum(x * m, axis=2)
                else:
                    y = jnp.sum(jnp.abs(x * m) ** self.pnorm, axis=2) ** (1.0 / self.pnorm)
                return y, state
        elif x.ndim == 4:  # [N, C, H, W] (or [N, H, W, C] internal NHWC)
            axes = (2, 3) if self.data_format == "NCHW" else (1, 2)
        else:
            axes = tuple(range(1, x.ndim))
        if pt == "max":
            y = jnp.max(x, axis=axes)
        elif pt == "avg":
            y = jnp.mean(x, axis=axes)
        elif pt == "sum":
            y = jnp.sum(x, axis=axes)
        elif pt == "pnorm":
            y = jnp.sum(jnp.abs(x) ** self.pnorm, axis=axes) ** (1.0 / self.pnorm)
        else:
            raise ValueError(f"unknown pooling type {self.pooling_type}")
        return y, state


# ---------------------------------------------------------------------------
# normalization layers
# ---------------------------------------------------------------------------


@register_layer
@dataclass
class BatchNormalization(FeedForwardLayerConf):
    """Batch norm with running stats as explicit state (ref: conf/layers/
    BatchNormalization.java, native path CudnnBatchNormalizationHelper.java).
    Defaults match the reference: eps=1e-5, decay=0.9, gamma=1, beta=0."""

    eps: float = 1e-5
    decay: float = 0.9
    lock_gamma_beta: bool = False
    gamma: float = 1.0
    beta: float = 0.0
    data_format: str = "NCHW"

    def output_type(self, it):
        return it

    def _nf(self, it):
        return it.channels if it.kind == "cnn" else it.flat_size()

    def init(self, key, it):
        nf = self._nf(it)
        self.n_in = self.n_out = nf
        params = {}
        if not self.lock_gamma_beta:
            params["gamma"] = jnp.full((nf,), self.gamma, jnp.float32)
            params["beta"] = jnp.full((nf,), self.beta, jnp.float32)
        state = {"mean": jnp.zeros((nf,), jnp.float32),
                 "var": jnp.ones((nf,), jnp.float32)}
        return params, state

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        nf = state["mean"].shape[0]
        gamma = params.get("gamma", jnp.full((nf,), self.gamma, x.dtype))
        beta = params.get("beta", jnp.full((nf,), self.beta, x.dtype))
        ch_axis = 3 if (self.data_format == "NHWC" and x.ndim == 4) else 1
        y, new_mean, new_var = _norm.batch_norm(
            x, gamma.astype(x.dtype), beta.astype(x.dtype),
            state["mean"].astype(x.dtype), state["var"].astype(x.dtype),
            train, self.eps, self.decay, channel_axis=ch_axis
        )
        if train:  # running stats kept in fp32 regardless of compute dtype
            new_state = {"mean": new_mean.astype(jnp.float32),
                         "var": new_var.astype(jnp.float32)}
        else:
            new_state = state
        return _act.get(self.activation)(y), new_state


@register_layer
@dataclass
class LayerNormalization(FeedForwardLayerConf):
    """Layer normalization over the feature axis, per example (and per
    timestep for RNN-format input [N,F,T]). A post-parity layer the
    transformer stack needs (the reference predates it); gain/bias
    params follow the BatchNormalization naming.
    """

    eps: float = 1e-5

    def output_type(self, it):
        if it.kind == "cnn":
            raise ValueError(
                "LayerNormalization supports FF [N,F] and RNN [N,F,T] "
                "input (per-feature axis 1); use BatchNormalization for "
                "CNN activations")
        return it

    def init(self, key, it):
        nf = it.size if it.kind == "rnn" else it.flat_size()
        self.n_in = self.n_out = nf
        return {"gamma": jnp.ones((nf,), jnp.float32),
                "beta": jnp.zeros((nf,), jnp.float32)}, {}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        # feature axis is 1 for both [N,F] and [N,F,T]
        acc = jnp.promote_types(x.dtype, jnp.float32)
        xf = x.astype(acc)
        mean = xf.mean(axis=1, keepdims=True)
        var = jnp.maximum((xf * xf).mean(axis=1, keepdims=True)
                          - mean * mean, 0.0)
        y = ((xf - mean) * jax.lax.rsqrt(var + self.eps)).astype(x.dtype)
        shape = [1] * x.ndim
        shape[1] = -1
        y = y * params["gamma"].astype(x.dtype).reshape(shape) + \
            params["beta"].astype(x.dtype).reshape(shape)
        return _act.get(self.activation)(y), state


@register_layer
@dataclass
class PositionalEmbeddingLayer(FeedForwardLayerConf):
    """Adds a learned positional embedding to RNN-format input [N,F,T]
    (post-parity; attention is position-agnostic without it). Params:
    P [F, max_length]; a full-sequence forward longer than max_length is
    rejected at trace time.

    Streaming (rnn_time_step): carries "pos_offset" so each chunk gets
    the embeddings for its absolute positions — the attention-era
    equivalent of LSTM h/c carry (MultiLayerNetwork.rnnTimeStep). The
    dynamic slice CLAMPS past max_length, so the network-level
    check_stream_budget guard enforces the capacity host-side."""

    max_length: int = 1024

    supports_streaming = True

    def output_type(self, it):
        if it.kind != "rnn":
            raise ValueError("PositionalEmbeddingLayer needs RNN input")
        return it

    def init(self, key, it):
        self.n_in = self.n_out = it.size
        p = 0.02 * jax.random.normal(key, (it.size, self.max_length))
        return {"P": p.astype(jnp.float32)}, {}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None,
              stream=False, pad_left=None):
        t = x.shape[2]
        if t > self.max_length:
            raise ValueError(f"sequence length {t} exceeds max_length "
                             f"{self.max_length}")
        if pad_left is not None and not stream:
            raise ValueError("pad_left is only meaningful for streaming")
        if stream:
            off = state.get("pos_offset")
            if off is None:
                off = jnp.zeros((), jnp.int32)
            if pad_left is None:
                z = jnp.zeros((), off.dtype)
                emb = jax.lax.dynamic_slice(
                    params["P"], (z, off), (params["P"].shape[0], t))
                new_off = off + t
            else:
                # left-padded packed chunk: chunk position i holds the
                # (cumsum-1)-th REAL token, so it gathers that absolute
                # position's embedding; pads (clamped to 0) are garbage
                # rows discarded downstream and never advance the offset
                m0 = jnp.arange(t) >= pad_left
                cum = jnp.cumsum(m0.astype(off.dtype))
                idx = jnp.clip(off + cum - 1, 0, self.max_length - 1)
                emb = params["P"][:, idx]
                new_off = off + cum[-1]
            y = x + emb[None].astype(x.dtype)
            new_state = {**state, "pos_offset": new_off}
        else:
            y = x + params["P"][None, :, :t].astype(x.dtype)
            new_state = state
        return _act.get(self.activation)(y), new_state


def _paged_append(pool, page, off, rows):
    """Write a chunk's rows into a [P, Hkv, page_size, D] pool leaf:
    ``rows[n, t, h]`` (a [D] vector) lands at ``pool[page[n, t], h,
    off[n, t]]``. One scatter of N·T·Hkv rows into the leaf seen as
    rows, [P·Hkv·page_size, D] (a bitcast of the row-major leaf): XLA
    then keeps the leaf in the layout the Mosaic kernel and the
    program's entry and result hold it in, and updates the donated leaf
    in place. (Indexed over page and row-in-page alone —
    ``pool.at[page, :, off, :]``, a [Hkv, D] window a token — the TPU
    compiler lays the leaf out head-minor for the scatter and copies
    the whole leaf before and after it, every layer, every step.)
    Duplicate targets (idle and masked rows on the null page 0) are as
    harmless as any write there: nothing reads it."""
    rows_of, row = _paged_rows(pool, page, off)
    return rows_of.at[row].set(rows).reshape(pool.shape)


def _paged_rows(pool, page, off):
    """A [P, Hkv, page_size, D] leaf seen as rows, [P·Hkv·page_size, D],
    and the rows ``[..., Hkv]`` that hold the tokens at ``(page, off)``."""
    p, hkv, ps, d = pool.shape
    row = (page[..., None] * hkv + jnp.arange(hkv)) * ps + off[..., None]
    return pool.reshape(p * hkv * ps, d), row


#: the least width of a cache leaf's rows that the TPU runtime keeps
#: row-major on the device. A narrower leaf (an index key of 64) is given a
#: page-minor layout, and every program that appends to it or gathers from
#: it copies the whole leaf there and back (seen in a compile for a
#: described v5e: two pool-shaped copies a layer a decode step), so such a
#: leaf is kept this wide, zeros past its own width
_LEAF_LANES = 128


#: the most key slots a block of the masked form scores at once. One
#: product-mask-softmax over a block's [Hkv, reps, 128, S] float32 scores
#: is a single fusion on the TPU, and past some S between 6,144 and 8,192
#: it falls off a cliff (alone on a v5e, 32 heads of 128: 0.04 ms a block
#: at 6,144 slots, 11.7 ms at 8,192; a prime of 8,192 took 208 ms a layer
#: of which 188 were the blocks of its last causal group), so a longer
#: span goes in pieces of this many slots joined by the running maximum
_KEY_SPAN = 4096


#: the widest table, in multiples of ``index_topk``, whose paged decode
#: reads every slot under the selection's mask (``"masked"``); a wider
#: one sorts its scores and gathers the kept positions (``"gathered"``).
#: The masked read grows with the table, the gathered one past its sort
#: does not. A layer alone on a v5e (16 rows, 32 heads on 4 KV heads of
#: 128, topk 2,048), masked against gathered: 2.16 / 4.25 ms at 12,544
#: slots (6.1 x topk), 5.05 / 6.24 at 25,088 (12.25 x), 11.89 / 10.05 at
#: 50,176 (24.5 x), 22.96 / 18.66 at 100,352 (49 x): they cross near 17 x
_MASKED_READ_RATIO = 16


def _paged_gather(pool, page, off):
    """Tokens out of a [P, Hkv, page_size, D] pool leaf: ``[..., Hkv, D]``
    for ``page`` / ``off`` [...], the token at ``pool[page, :, off]`` —
    read as rows of the leaf seen as [P·Hkv·page_size, D], the view
    ``_paged_append`` writes through. (Indexed over page and row-in-page
    alone, a [Hkv, D] window a token, the TPU compiler copies the whole
    leaf into a head-minor layout first: two pool-shaped copies a layer a
    step, seen in a compile for a described v5e.)"""
    rows_of, row = _paged_rows(pool, page, off)
    return rows_of[row]


def _attend_spans(score, value, width):
    """Attention over ``width`` key slots in spans of ``_KEY_SPAN`` joined
    by the running maximum: ``score(lo, hi)`` gives a span's masked
    float32 scores [..., hi - lo], ``value(e, lo, hi)`` the product of
    their exponentials with the span's values. A span whose slots are all
    masked weighs exp(MASKED - max) = 0 once a later or earlier span holds
    a kept slot."""
    o = m = z = None
    for lo in range(0, width, _KEY_SPAN):
        hi = min(width, lo + _KEY_SPAN)
        s = score(lo, hi)
        top = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - top)
        part, total = value(e, lo, hi), jnp.sum(e, axis=-1, keepdims=True)
        if o is None:
            o, m, z = part, top, total
        else:
            new = jnp.maximum(m, top)
            was, now = jnp.exp(m - new), jnp.exp(top - new)
            o, z, m = o * was + part * now, z * was + total * now, new
    return o / z


@register_layer
@dataclass
class SelfAttentionLayer(FeedForwardLayerConf):
    """Multi-head self-attention over RNN-format input [N,F,T] (a
    post-parity layer — the 2017 reference has no attention). The
    attention core is the flash-style blockwise kernel
    (parallel/sequence.blockwise_attention), so long sequences run in
    O(T·block) memory on one chip; under a mesh the same layer math is
    what ring/Ulysses parallelize.

    Params: Wq/Wk/Wv/Wo [F,F] + bq/bk/bv/bo. `causal` masks the future
    (LM decoding); `n_heads` must divide n_out.

    Streaming (rnn_time_step): set `cache_length` and the layer carries a
    KV cache ("kv_k"/"kv_v"/"kv_pos") across calls — incremental decoding
    attends each new token against the cached keys instead of re-running
    the full context, the attention-era counterpart of the reference's
    stored-state rnnTimeStep (MultiLayerNetwork.java rnnTimeStep).

    `n_kv_heads` < n_heads selects grouped-query attention: K/V carry
    only n_kv_heads heads (each shared by n_heads/n_kv_heads query
    heads), shrinking Wk/Wv and — the point — the streaming KV cache by
    the same factor. n_kv_heads == n_heads (default None) is standard
    MHA; n_kv_heads == 1 is multi-query attention.

    `rope=True` applies rotary position embeddings to q/k (RoFormer):
    positions enter through rotation of the head channels, so scores
    depend only on RELATIVE offsets — no learned position table, clean
    extrapolation, and streaming decode rotates by absolute kv_pos
    (cached keys are rotated at insert time). Head dim must be even.
    ``rope=False`` (the default) rotates nothing: a decoder whose other
    layers carry order (linear attention) runs its full-attention layers
    that way.

    ``has_bias=False`` drops bq/bk/bv/bo (the leaves do not exist).
    ``qk_norm=True`` passes the projected queries and keys, each over its
    whole projected width and before the split into heads, through an
    RMSNorm with a gain (leaves ``q_norm`` [n_out], ``k_norm``
    [Hkv * D]; statistics in float32, ``qk_norm_eps``) — the decoders
    that bound their attention logits this way. Both off by default: a
    saved configuration and its programs stay as they were.
    ``qk_norm="head"`` norms each head of both over its own ``D`` channels
    instead (one gain [D] for queries, one for keys).
    ``stream_query_block`` bounds what a long prime keeps of its scores.
    ``head_dim`` gives the heads a width of their own (``Wq`` [F, H * D],
    ``Wo`` [H * D, n_out]) where it is not ``n_out / n_heads``.

    Learned sparse selection (``index_topk`` > 0; off by default, and
    then no leaf, no cache and no program differs): an indexer beside the
    projections — ``q^I = x Wiq`` (``index_n_heads`` x ``index_head_dim``),
    one index key a token ``k^I = LayerNorm(x Wik)`` (``ik_gamma``,
    ``ik_beta``, eps ``qk_norm_eps``), both rotated over their whole width where ``rope`` is on
    (half-split pairs, ``rope_base``), ``w = x Wiw Hi^-1/2 Di^-1/2`` —
    scores every earlier position ``I(t, s) = sum_j w_tj relu(q^I_tj .
    k^I_s)``, and query t attends the ``min(index_topk, t + 1)`` of highest
    I, ties to the lower index (``nn/layers/sparse_latent.py``). The index
    key is a third cache leaf, ``kv_i`` [N, 1, L, max(Di, 128)] (zeros
    past Di: ``_LEAF_LANES``), beside ``kv_k`` and ``kv_v``. Two forms of one function. MASKED (training forward, dense
    streaming, so a serving engine's prime): queries in blocks of
    ``stream_query_block``, a block's index scores against the key slots,
    the exact top-k as a mask, grouped-query attention under it; a
    stream's first chunk attends its own keys, slot for query, in up to
    four causal groups; behind a page table (paged decode) the same
    selection and attention over the table's whole mapped view, its
    pages read whole. GATHERED (paged decode, where ``selected_read``
    says so: a table wider than ``_MASKED_READ_RATIO`` x
    ``index_topk``): the index keys of a row's whole context are read
    through the table and scored, and only the selected positions' keys
    and values are gathered out of the pool and attended. Products take
    operands as they come and accumulate in float32; index scores and softmax are float32.
    Either streaming form adds to ``attn_stats`` the positions whose
    attention scores it computed (``stream_counters()``).
    """

    n_heads: int = 4
    causal: bool = True
    block_size: int = 512
    cache_length: int = 0
    n_kv_heads: Optional[int] = None
    rope: bool = False
    rope_base: float = 10000.0
    #: sliding-window width (causal only): each query sees its `window`
    #: most recent positions (Mistral-style local attention; the Pallas
    #: kernel skips out-of-window blocks). None = full attention.
    window: Optional[int] = None
    has_bias: bool = True
    #: False | True (over the whole projected width) | "head" (each head
    #: over its own channels)
    qk_norm: Any = False
    qk_norm_eps: float = 1e-6
    #: a head's width where it is not ``n_out // n_heads``
    head_dim: Optional[int] = None
    #: the indexer (``index_topk`` 0: none): its heads, their width, and
    #: how many positions a query keeps
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    #: queries a streaming chunk attends at once (None: all of them, one
    #: [H, T, L] float32 score tensor). A prime of T positions against a
    #: cache of L keeps T * L * H * 4 bytes of scores and as many of
    #: probabilities; in blocks of this many queries (a ``lax.map``, the
    #: same arithmetic a row) it keeps a block's.
    stream_query_block: Optional[int] = None

    supports_streaming = True
    #: (impl, interpret): how a decode step reads the page pool when a
    #: page table rides the state. ("xla", False) folds the pool[table]
    #: gather into the attention op (any backend); ("pallas", i) runs the
    #: serving/paged_kernel.py paged-attention kernel (i = interpret
    #: mode, for CPU exactness tests). No configuration field: the
    #: serving engine records here, on the layers of the net it serves,
    #: what ``paged_kernel.choose_paged_read`` answered, and the net's
    #: streaming jit keys hold it (``paged_reads``).
    paged_read = ("xla", False)

    def output_type(self, it):
        if it.kind != "rnn":
            raise ValueError("SelfAttentionLayer needs RNN input [N,F,T]")
        return InputType.recurrent(self.n_out or it.size, it.timesteps)

    @property
    def head_width(self) -> int:
        return self.head_dim or self.n_out // self.n_heads

    @property
    def selects(self) -> bool:
        return self.index_topk > 0

    def paged_leaves(self):
        """The cache leaves a page pool holds for this layer: keys and
        values, [Hkv, D] a token, tokens on axis 1 ([P, Hkv, page, D]);
        with an indexer also the index key, [1, Di] a token."""
        hkv = self.n_kv_heads or self.n_heads
        d = self.head_width
        leaves = (PagedLeaf("kv_k", (hkv, d), 1),
                  PagedLeaf("kv_v", (hkv, d), 1))
        if self.selects:
            leaves += (PagedLeaf("kv_i", (1, self._index_leaf_width), 1),)
        return leaves

    @property
    def _index_leaf_width(self) -> int:
        return max(self.index_head_dim, _LEAF_LANES)

    def _index_leaf(self, ki):
        """An index key [..., Di] as its cache leaf holds it."""
        pad = self._index_leaf_width - ki.shape[-1]
        return jnp.pad(ki, [(0, 0)] * (ki.ndim - 1) + [(0, pad)])

    def stream_counters(self):
        """With an indexer, ``LatentAttentionLayer``'s declaration: a net
        has one kind of selecting layer, so one ``health()`` key."""
        if not self.selects:
            return None
        return _selection_counters(self)

    @property
    def selected_read(self) -> Optional[str]:
        """How a paged decode reads the positions the indexer kept
        (None without an indexer): ``"masked"``, the whole mapped view
        under the selection's mask, where the table is at most
        ``_MASKED_READ_RATIO`` times ``index_topk``; else
        ``"gathered"``, the kept positions alone."""
        if not self.selects:
            return None
        return "masked" if self.cache_length <= \
            _MASKED_READ_RATIO * self.index_topk else "gathered"

    def paged_read_tokens(self) -> Dict[str, int]:
        """Tokens of each leaf one row's paged decode reads where the
        layer selects: the whole context's index keys; the whole
        context's keys and values too where it reads them masked, the
        selected positions' where it gathers them."""
        if not self.selects:
            return {}
        top = self.cache_length if self.selected_read == "masked" else \
            min(self.index_topk, self.cache_length)
        return {"kv_k": top, "kv_v": top, "kv_i": self.cache_length}

    def init(self, key, it):
        if self.n_in is None:
            self.n_in = it.size
        if self.n_out is None:
            self.n_out = self.n_in
        if self.head_dim is None and self.n_out % self.n_heads:
            raise ValueError(f"n_out {self.n_out} not divisible by "
                             f"n_heads {self.n_heads}")
        if self.qk_norm not in (False, True, "head"):
            raise ValueError(f"qk_norm is False, True or 'head', got "
                             f"{self.qk_norm!r}")
        if self.n_kv_heads is not None and self.n_kv_heads < 1:
            raise ValueError(f"n_kv_heads must be >= 1, got "
                             f"{self.n_kv_heads}")
        hkv = self.n_kv_heads or self.n_heads
        if self.n_heads % hkv:
            raise ValueError(f"n_heads {self.n_heads} not divisible by "
                             f"n_kv_heads {hkv}")
        d = self.head_width
        if self.rope and d % 2:
            raise ValueError(f"rope needs an even head dim, got {d} "
                             f"(n_out {self.n_out} / n_heads "
                             f"{self.n_heads})")
        if self.window is not None:
            if not self.causal:
                raise ValueError("window attention requires causal=True")
            if self.window < 1:
                raise ValueError(f"window must be >= 1, got {self.window}")
        if self.selects:
            if not self.causal or self.window is not None:
                raise ValueError("an indexer selects among the earlier "
                                 "positions: causal=True, no window")
            if self.index_n_heads < 1 or self.index_head_dim < 1 or (
                    self.rope and self.index_head_dim % 2):
                raise ValueError(
                    f"index_topk {self.index_topk} needs index_n_heads "
                    f"and an (even, with rope) index_head_dim, got "
                    f"{self.index_n_heads} x {self.index_head_dim}")
        keys = jax.random.split(key, 4)
        p = {}
        for i, name in enumerate(("q", "k", "v", "o")):
            n_in = self.n_in if name != "o" else self.n_heads * d
            n_out = hkv * d if name in ("k", "v") else \
                self.n_out if name == "o" else self.n_heads * d
            p["W" + name] = init_weights(keys[i], (n_in, n_out), n_in,
                                         n_out, self.weight_init, self.dist)
            if self.has_bias:
                p["b" + name] = jnp.zeros((n_out,), jnp.float32)
        if self.qk_norm == "head":
            p["q_norm"] = jnp.ones((d,), jnp.float32)
            p["k_norm"] = jnp.ones((d,), jnp.float32)
        elif self.qk_norm:
            p["q_norm"] = jnp.ones((self.n_heads * d,), jnp.float32)
            p["k_norm"] = jnp.ones((hkv * d,), jnp.float32)
        if self.selects:
            hi, di = self.index_n_heads, self.index_head_dim
            ikeys = jax.random.split(jax.random.fold_in(key, 1), 3)
            for k, (name, width) in zip(ikeys, (("Wiq", hi * di),
                                                ("Wik", di), ("Wiw", hi))):
                p[name] = init_weights(k, (self.n_in, width), self.n_in,
                                       width, self.weight_init, self.dist)
            p["ik_gamma"] = jnp.ones((di,), jnp.float32)
            p["ik_beta"] = jnp.zeros((di,), jnp.float32)
        return p, {}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None,
              stream=False, pad_left=None):
        from deeplearning4j_tpu.parallel.sequence import blockwise_attention
        if pad_left is not None and not stream:
            raise ValueError("pad_left is only meaningful for streaming")
        x = self.maybe_dropout_input(x, train, rng)
        n, f, t = x.shape
        h = self.n_heads
        hkv = self.n_kv_heads or h
        d = self.head_width
        xt = jnp.transpose(x, (0, 2, 1))                    # [N,T,F]
        per_head = self.qk_norm == "head"

        def proj(name, heads):
            y = xt @ params["W" + name]
            if self.has_bias:
                y = y + params["b" + name]
            normed = self.qk_norm and name in ("q", "k")
            if normed and not per_head:
                with jax.named_scope("attn.qk_norm"):
                    y = _rms_norm(y, params[name + "_norm"],
                                  self.qk_norm_eps)
            y = y.reshape(n, t, heads, d)
            if normed and per_head:
                with jax.named_scope("attn.qk_norm"):
                    y = _rms_norm(y, params[name + "_norm"],
                                  self.qk_norm_eps)
            return y.transpose(0, 2, 1, 3)

        # the scopes name the selecting layer's parts in a profile; a
        # layer without an indexer lowers as it always did
        with self._scope("gqa.project"):
            q = proj("q", h)                                # [N,H,T,D]
            k, v = proj("k", hkv), proj("v", hkv)           # [N,Hkv,T,D]
        idx = self._index_project(params, xt) if self.selects else None
        if self.rope and not stream:
            pos = jnp.arange(t)
            q = self._rope(q, pos)
            k = self._rope(k, pos)
        if stream:
            # cache the Hkv-sized K/V (the GQA memory win), expand at
            # attend time inside _stream_attend
            o, state = self._stream_attend(q, k, v, state, mask,
                                           pad_left=pad_left, idx=idx)
        elif self.selects:
            pos = jnp.arange(t, dtype=jnp.int32)[None]
            qi, ki, w = idx
            key_valid = None if mask is None else \
                jnp.asarray(mask).reshape(n, t).astype(bool)
            o, _ = self._attend_selected(
                q, k, v, (self._rope_index(qi, pos), w),
                self._rope_index(ki, pos)[:, None], pos, key_valid,
                aligned=True)
        else:
            k, v = self._expand_kv(k, v)
            # variable-length batches: mask KEYS with -inf score bias
            # (zeroed K/V would still receive softmax mass)
            o = blockwise_attention(q, k, v, causal=self.causal,
                                    block_size=self.block_size,
                                    key_mask=mask, window=self.window)
        with self._scope("gqa.project"):
            o = o.transpose(0, 2, 1, 3).reshape(n, t, h * d)
            o = o @ params["Wo"]
            if self.has_bias:
                o = o + params["bo"]
        y = jnp.transpose(o, (0, 2, 1))                     # [N,F,T]
        return _act.get(self.activation)(y), state

    def _scope(self, name: str):
        return jax.named_scope(name) if self.selects \
            else contextlib.nullcontext()

    # -- learned sparse selection (index_topk > 0) -------------------------
    def _index_project(self, params, xt):
        """The indexer's parts of a chunk before positions enter:
        ``(q^I [N,T,Hi,Di], k^I [N,T,Di], w [N,T,Hi])``."""
        n, t, _ = xt.shape
        hi, di = self.index_n_heads, self.index_head_dim
        with jax.named_scope("gqa.index"):
            qi = (xt @ params["Wiq"]).reshape(n, t, hi, di)
            ki = _sl.layer_norm(xt @ params["Wik"], params["ik_gamma"],
                                params["ik_beta"], self.qk_norm_eps)
            w = (xt @ params["Wiw"]) * (hi ** -0.5 * di ** -0.5)
        return qi, ki, w.astype(xt.dtype)

    def _rope_index(self, x, positions):
        """The layer's rotation over the whole index width: x
        [N, T, (Hi,) Di], positions [N|1, T] (pads' -1 clamped)."""
        if not self.rope:
            return x
        half = x.shape[-1] // 2
        inv = self.rope_base ** (-jnp.arange(half, dtype=jnp.float32)
                                 / half)
        cos, sin = _sl.rope_tables(jnp.maximum(positions, 0), inv)
        return _sl.rope_half(x, cos, sin)

    def _attend_selected(self, q, kc, vc, idx, ic, q_pos, key_valid=None,
                         aligned=False):
        """The masked form: queries q [N,H,T,D] with their index parts
        ``idx`` = (q^I [N,T,Hi,Di], w [N,T,Hi]) against kc / vc
        [N,Hkv,L,D] and the index keys ic [N,1,L,Di]; query t may see the
        slots ``<= q_pos[t]`` (q_pos [N|1, T]) that ``key_valid`` [N|1, L]
        admits, and attends the ``index_topk`` of them its index scores
        put first. Queries go in blocks of ``stream_query_block``
        (``sparse_latent.QUERY_BLOCK`` where unset), a block's key slots
        in spans of ``_KEY_SPAN`` joined by the running maximum: a
        block's [N, H, B, span] scores exist at once, the chunk's never. ``aligned``
        says the keys are the chunk's own, slot for query: the blocks
        then go in up to four groups, each against the prefix of the keys
        that ends where its last query stands. Returns ``(o [N,H,T,D],
        scored)``, ``scored`` the (query row, slot) pairs whose attention
        scores it computed."""
        qi, w = idx
        n, h, t, d = q.shape
        hkv, L = kc.shape[1], kc.shape[2]
        reps = h // hkv
        slot = jnp.arange(L, dtype=jnp.int32)
        q_pos = jnp.broadcast_to(q_pos, (n, t)).astype(jnp.int32)
        b, pad, groups = _sl.query_groups(
            t, L, aligned, self.stream_query_block or _sl.QUERY_BLOCK)

        def blocks(a, axis):
            width = [(0, 0)] * a.ndim
            width[axis] = (0, pad)
            a = jnp.pad(a, width)
            a = a.reshape(a.shape[:axis] + ((t + pad) // b, b)
                          + a.shape[axis + 1:])
            return jnp.moveaxis(a, axis, 0)

        def attend(args, seen):
            """One block of queries against the first ``seen`` slots."""
            qb, qib, wb, pos = args
            valid = slot[None, None, :seen] <= pos[..., None]   # [N,B,s]
            if key_valid is not None:
                valid = valid & key_valid[:, None, :seen]
            with jax.named_scope("gqa.index"):
                scores = _sl.index_scores(
                    qib, ic[:, 0, :seen, :qib.shape[-1]], wb)
            with jax.named_scope("gqa.select"):
                sel = _sl.top_k_mask(scores, valid, self.index_topk)
            with jax.named_scope("gqa.attend"):
                qg = qb.reshape(n, hkv, reps, b, d)

                def score(lo, hi):
                    s = jnp.einsum("ngrtd,ngld->ngrtl", qg, kc[:, :, lo:hi],
                                   preferred_element_type=jnp.float32)
                    return jnp.where(sel[:, None, None, :, lo:hi],
                                     s * d ** -0.5, _sl.MASKED)

                o = _attend_spans(score, lambda e, lo, hi: jnp.einsum(
                    "ngrtl,ngld->ngrtd", e.astype(vc.dtype), vc[:, :, lo:hi],
                    preferred_element_type=jnp.float32), seen)
                return o.reshape(n, h, b, d).astype(q.dtype)

        parts = (blocks(q, 2), blocks(qi, 1), blocks(w, 1),
                 blocks(q_pos, 1))
        out, at = [], 0
        for per, seen in groups:
            out.append(jax.lax.map(
                lambda args, seen=seen: attend(args, seen),
                tuple(a[at:at + per] for a in parts)))
            at += per
        o = jnp.moveaxis(jnp.concatenate(out), 0, 2)     # [N,H,nb,B,D]
        return (o.reshape(n, h, t + pad, d)[:, :, :t],
                n * sum(per * b * seen for per, seen in groups))

    def _attend_gathered(self, q, kp, vp, ip, table, idx, q_pos):
        """The gathered form behind a page table: every row scores the
        index keys of its whole context (read through ``table``), keeps
        the ``index_topk`` best of the positions ``<= q_pos``, gathers
        those positions' keys and values out of the pools kp / vp
        [P,Hkv,ps,D] and attends them and nothing else. Returns
        ``(o [N,H,T,D], scored)``."""
        qi, w = idx
        n, h, t, d = q.shape
        hkv, ps = kp.shape[1], kp.shape[2]
        n_blk, L = table.shape[1], self.cache_length
        with jax.named_scope("gqa.index"):
            ki = ip[table].reshape(n, n_blk * ps, -1)[:, :L,
                                                      :qi.shape[-1]]
            scores = _sl.index_scores(qi, ki, w)                 # [N,T,L]
        with jax.named_scope("gqa.select"):
            live = jnp.arange(L)[None, None, :] <= q_pos[..., None]
            top = min(self.index_topk, L)
            best, sel = jax.lax.top_k(
                jnp.where(live, scores, -jnp.inf), top)          # [N,T,k]
            chosen = best > -jnp.inf
        with jax.named_scope("gqa.gather"):
            rows = jnp.arange(n)[:, None, None]
            page = table[rows, jnp.minimum(sel // ps, n_blk - 1)]
            off = sel % ps
            kg = _paged_gather(kp, page, off)               # [N,T,k,Hkv,D]
            vg = _paged_gather(vp, page, off)
        with jax.named_scope("gqa.attend"):
            qg = q.reshape(n, hkv, h // hkv, t, d)
            s = jnp.einsum("ngrtd,ntkgd->ngrtk", qg, kg,
                           preferred_element_type=jnp.float32)
            s = jnp.where(chosen[:, None, None], s * d ** -0.5, _sl.MASKED)
            a = jax.nn.softmax(s, axis=-1).astype(vg.dtype)
            o = jnp.einsum("ngrtk,ntkgd->ngrtd", a, vg,
                           preferred_element_type=jnp.float32)
        return o.reshape(n, h, t, d).astype(q.dtype), n * t * top

    def _attend_paged_masked(self, q, kp, vp, ip, table, idx, q_pos):
        """The masked form behind a page table: ``_attend_gathered``'s
        arguments and result, the selection a mask (``top_k_mask``) over
        the table's whole mapped view and no position gathered. Keys and
        values are read a page at a time as the pool holds it, all heads
        of a page in one slice, and taken as rows of the view
        [N, n_blk·Hkv·page_size, D]: every query head is scored against
        every row, the rows of the other heads masked with the unselected
        positions, in spans of ``_KEY_SPAN`` rows joined by the running
        maximum (``_attend_spans``, the prime's join). Scoring four heads'
        rows where one is kept costs less than laying the view out head
        by head: alone on a v5e at the longdocs cell's shapes a page
        gather takes 0.64 ms a pool this way, 0.92 a page of one head at
        a time (four times the slices), and 0.64 plus a 0.61 copy with
        the heads moved forward after it."""
        qi, w = idx
        n, h, t, d = q.shape
        hkv, ps = kp.shape[1], kp.shape[2]
        n_blk, L = table.shape[1], self.cache_length
        rows = n_blk * hkv * ps
        with jax.named_scope("gqa.index"):
            ki = ip[table].reshape(n, n_blk * ps, -1)[:, :L, :qi.shape[-1]]
            scores = _sl.index_scores(qi, ki, w)                 # [N,T,L]
        with jax.named_scope("gqa.select"):
            live = jnp.arange(L)[None, None, :] <= q_pos[..., None]
            sel = jnp.pad(_sl.top_k_mask(scores, live, self.index_topk),
                          ((0, 0), (0, 0), (0, n_blk * ps - L)))
            # a row of the view is (page, head, offset)
            sel = jnp.broadcast_to(
                sel.reshape(n, t, n_blk, 1, ps),
                (n, t, n_blk, hkv, ps)).reshape(n, 1, t, rows)
            own = (jnp.arange(rows) // ps % hkv)[None, :] == \
                (jnp.arange(h) // (h // hkv))[:, None]           # [H, R]
        with jax.named_scope("gqa.gather"):
            kr = kp[table].reshape(n, rows, d)
            vr = vp[table].reshape(n, rows, d)
        with jax.named_scope("gqa.attend"):
            def score(lo, hi):
                s = jnp.einsum("nhtd,nrd->nhtr", q, kr[:, lo:hi],
                               preferred_element_type=jnp.float32)
                ok = sel[..., lo:hi] & own[None, :, None, lo:hi]
                return jnp.where(ok, s * d ** -0.5, _sl.MASKED)

            o = _attend_spans(score, lambda e, lo, hi: jnp.einsum(
                "nhtr,nrd->nhtd", e.astype(vr.dtype), vr[:, lo:hi],
                preferred_element_type=jnp.float32), rows)
        return o.astype(q.dtype), n * t * L

    def _stream_attend(self, q, k, v, state, mask=None, pad_left=None,
                       idx=None):
        """Incremental decode: append k/v to the carried cache, attend q
        against it. Positions past cache_length are a caller error (the
        dynamic_update_slice would clamp) — size cache_length to the max
        generation length.

        A key mask ([N, T] per chunk, like the non-stream path's) is
        carried in the cache as kv_mask so padded positions stay masked
        on every later step. Masked streaming must start masked: the
        kv_mask buffer is created on the first chunk (a mask appearing
        mid-stream would leave earlier chunks' validity unrecorded).

        `pad_left` (traced scalar) selects PACKED accounting for a
        left-padded chunk (util/decoding's single-dispatch priming): the
        first pad_left positions never enter the cache (their writes
        route to an out-of-range dump slot and are dropped), real tokens
        take consecutive slots/positions as if the pads did not exist —
        so one bucketed jit shape serves every prompt length with
        results identical to unpadded chunked priming. Pad queries
        attend nothing and produce discarded rows. Mutually exclusive
        with `mask` (pads are non-existent, not masked-but-resident)."""
        if self.cache_length <= 0:
            raise ValueError(
                "SelfAttentionLayer streaming needs cache_length > 0")
        if not self.causal:
            raise ValueError("streaming decode requires causal=True")
        if state.get("kv_page_table") is not None:
            # direct paged decode: the serving engine installed the page
            # pool + table in place of a dense cache — read through the
            # table, append one token per row in place
            return self._stream_attend_paged(q, k, v, state, mask=mask,
                                             pad_left=pad_left, idx=idx)
        n, _, t, d = q.shape
        hkv = k.shape[1]                 # cache holds n_kv_heads heads
        L = self.cache_length
        kc = state.get("kv_k")
        fresh = kc is None
        if fresh:
            kc = jnp.zeros((n, hkv, L, d), q.dtype)
            vc = jnp.zeros((n, hkv, L, d), q.dtype)
            pos = jnp.zeros((), jnp.int32)
        else:
            vc, pos = state["kv_v"], state["kv_pos"]
        vec = getattr(pos, "ndim", 0) >= 1    # [N] per-row positions
        # (after a per-row rewind_stream_state — batched speculation)
        if pad_left is not None:
            if mask is not None:
                raise ValueError("pad_left and mask are mutually "
                                 "exclusive in streaming attention")
            if state.get("kv_mask") is not None:
                raise ValueError(
                    "left-padded (packed) priming cannot follow masked "
                    "streaming — packed writes would leave the carried "
                    "kv_mask unset for their slots; restart the stream "
                    "(rnn_clear_previous_state)")
            if vec:
                raise ValueError(
                    "packed (pad_left) priming cannot follow a per-row "
                    "rewind — restart the stream")
            m0 = jnp.arange(t) >= pad_left              # [T] valid flags
            cum = jnp.cumsum(m0.astype(pos.dtype))
            q_pos = pos + cum - 1                       # pads: pos-1
            n_new = cum[-1]
        else:
            m0 = None
            steps_t = jnp.arange(t, dtype=pos.dtype)
            # [N,T] when per-row, [T] when shared
            q_pos = pos[:, None] + steps_t if vec else pos + steps_t
            n_new = t
        if self.rope:
            abs_pos = q_pos if m0 is None else jnp.maximum(q_pos, 0)
            q = self._rope(q, abs_pos)
            k = self._rope(k, abs_pos)
        if self.window is not None:
            return self._stream_attend_rolling(
                q, k, v, state, kc, vc, pos, mask, fresh=fresh,
                m0=m0, q_pos=q_pos, n_new=n_new, vec=vec)
        if self.selects:
            if mask is not None or vec or state.get("kv_mask") is not None:
                raise ValueError(
                    "a SelfAttentionLayer with an indexer streams "
                    "maskless chunks at a shared position (left-pad with "
                    "pad_left), and per-row positions only behind a page "
                    "table (the serving engine's paged decode)")
            return self._stream_attend_selecting(
                q, k, v, idx, state, kc, vc, pos, m0=m0, q_pos=q_pos,
                n_new=n_new,
                fresh=fresh and state.get("kv_pos") is None)
        z = jnp.zeros((), pos.dtype)
        if vec:
            # per-row scatter at each row's own slots (advanced indexing
            # puts the two index axes first: value is [N,T,Hkv,D]);
            # out-of-range rows (past cache_length) drop their writes
            bidx = jnp.arange(n)[:, None]
            kc = kc.at[bidx, :, q_pos, :].set(
                k.transpose(0, 2, 1, 3).astype(kc.dtype), mode="drop")
            vc = vc.at[bidx, :, q_pos, :].set(
                v.transpose(0, 2, 1, 3).astype(vc.dtype), mode="drop")
        elif m0 is None:
            kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                              (z, z, pos, z))
            vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                              (z, z, pos, z))
        else:
            # packed scatter: pads route to the out-of-range dump slot L
            # and are DROPPED — they never occupy cache capacity
            slots = jnp.where(m0, q_pos, L)
            kc = kc.at[:, :, slots, :].set(k.astype(kc.dtype), mode="drop")
            vc = vc.at[:, :, slots, :].set(v.astype(vc.dtype), mode="drop")
        kc, vc = _shard_cache(kc, 2), _shard_cache(vc, 2)
        if vec:
            km = self._stream_mask_update(
                state, mask, n, t, L, fresh=fresh,
                write=lambda km, m: km.at[jnp.arange(n)[:, None],
                                          q_pos].set(m, mode="drop"))
            km = _shard_cache(km, 1)
        elif m0 is None:
            km = self._stream_mask_update(
                state, mask, n, t, L, fresh=fresh,
                write=lambda km, m: jax.lax.dynamic_update_slice(
                    km, m, (z, pos)))
            km = _shard_cache(km, 1)
        else:
            km = None
        # grouped attend against the UN-expanded cache: q reshaped to
        # [N, Hkv, reps, T, D] — materializing a repeated cache would
        # forfeit GQA's decode bandwidth win
        # query at absolute position p sees cache slots <= p
        k_idx = jnp.arange(L)
        if vec:
            valid = k_idx[None, None, :] <= q_pos[..., None]  # [N, T, L]
        else:
            valid = (k_idx[None, :] <= q_pos[:, None])[None]  # [1, T, L]
        if km is not None:
            valid = valid & km[:, None, :]                    # [N, T, L]
        o = self._grouped_attend(q, kc, vc, valid)
        out = {**state, "kv_k": kc, "kv_v": vc, "kv_pos": pos + n_new}
        if km is not None:
            out["kv_mask"] = km
        return o, out

    def _stream_attend_selecting(self, q, k, v, idx, state, kc, vc, pos, *,
                                 m0, q_pos, n_new, fresh):
        """Dense streaming with an indexer: the chunk's keys, values and
        index keys go to the [N, ., L, .] caches at the shared position
        (left pads dropped), then the masked form: of a stream's FIRST
        chunk (``fresh``: a prime) against the chunk's own keys, slot for
        query, the pads masked; of a later chunk against the whole
        cache."""
        n, _, t, _ = q.shape
        L = self.cache_length
        qi, ki, w = idx
        ipos = (q_pos if m0 is None else jnp.maximum(q_pos, 0))[None]
        qi, ki = self._rope_index(qi, ipos), self._rope_index(ki, ipos)
        ki = self._index_leaf(ki)[:, None]               # [N, 1, T, W]
        ic = state.get("kv_i")
        if ic is None:
            ic = jnp.zeros((n, 1, L, ki.shape[-1]), ki.dtype)
        slots = q_pos if m0 is None else jnp.where(m0, q_pos, L)
        kc, vc, ic = (c.at[:, :, slots, :].set(new.astype(c.dtype),
                                               mode="drop")
                      for c, new in ((kc, k), (vc, v), (ic, ki)))
        if fresh:
            o, scored = self._attend_selected(
                q, k, v, (qi, w), ki,
                jnp.arange(t, dtype=jnp.int32)[None],
                None if m0 is None else m0[None], aligned=True)
        else:
            o, scored = self._attend_selected(q, kc, vc, (qi, w), ic,
                                              q_pos[None])
        return o, {**_attended(state, scored), "kv_k": kc, "kv_v": vc,
                   "kv_i": ic, "kv_pos": pos + n_new}

    def _stream_attend_paged(self, q, k, v, state, mask=None,
                             pad_left=None, idx=None):
        """Direct paged decode: K/V live in the block-paged pool
        (``kv_page_k``/``kv_page_v`` — [P, Hkv, page_size, D]) and the
        per-row page table (``kv_page_table`` — [N, n_max], 0 = null
        page), installed by the serving engine around its decode
        dispatches. The chunk's new tokens append with one scatter a
        leaf (``_paged_append``): N·T·Hkv rows of [D], each at the row
        its ``(page, head, offset)`` names — an O(one-token) write into
        the donated leaf, in the layout it already has — then the
        queries attend against the pool through the table, by this
        layer's ``paged_read``:

        - ``"xla"`` impl (any backend): the ``pool[table]`` gather is
          folded into this dispatch and feeds the SAME
          ``_grouped_attend`` the dense arena runs — outputs are
          bit-identical to the slot arena by construction (valid
          positions hold the exact bytes the dense cache would; masked
          positions are finite garbage ``-1e30`` hides, the dense
          path's own idle-slot argument).
        - ``"pallas"`` impl: serving/paged_kernel.py — the kernel walks
          the scalar-prefetched table and copies the mapped pages out
          of the pool itself, so only live pages are read
          (O(active context), the true paged-attention read path);
          width T = 1 + gamma runs the same kernel for the widened
          speculative verify dispatch.

        Contract (the engine's decode shape): per-row ``kv_pos``
        vector, packed maskless chunks, no rolling window. Appends past
        a row's allocation or capacity route to the null page 0 —
        transient speculative overflow (rewound before it is ever
        visible) and idle-slot coasting both land where nothing reads.
        Prefix-shared read-only blocks are safe by block alignment: a
        row appends only at positions ≥ its own fresh blocks.

        Two state-structure extensions ride the same dispatch (both
        Python-level — pytree structure keys the jit cache, so each
        combination is its own trace and the plain bf16 decode graph
        is untouched):

        - ``kv_page_prime`` present: this chunk is the engine's
          PRIME-THROUGH-THE-POOL prefill (batch 1, the int8 path —
          quantize-once means the prompt's pool bytes must be written
          by the same quantized append the decode steps use, never
          densely primed and converted). ``pad_left`` is then allowed
          with the dense path's packed accounting, pads and
          prefix-shared positions route to the null page
          (``q_pos < pos``), and the read is FORCED onto the folded
          XLA gather regardless of the live impl — the kernel's
          uniform-width causality has no notion of packed pads, and a
          rebuild's re-prime must retrace the identical read math.
        - ``kv_page_scale_k``/``_v`` present: the pool is int8 with
          per-(page, head) amax-scale sidecars (serving/quant.py) —
          appends quantize under the page base's scale, reads
          dequantize in the gather (XLA) or in VMEM (the kernel, with
          scales riding the scalar prefetch)."""
        prime = state.get("kv_page_prime") is not None
        if mask is not None or (pad_left is not None and not prime):
            raise ValueError(
                "direct paged decode is packed/maskless (the engine's "
                "decode dispatch shape) — masked or left-padded chunks "
                "must prime through the dense path")
        if self.window is not None:
            raise ValueError("rolling (windowed) caches are not "
                             "pageable (no stable token->page map)")
        kp, vp = state["kv_page_k"], state["kv_page_v"]
        table = state["kv_page_table"]
        ksc = state.get("kv_page_scale_k")
        quant = ksc is not None
        if self.selects and (prime or quant):
            raise ValueError(
                "a SelfAttentionLayer with an indexer keeps a third, "
                "unquantized leaf a token (kv_i): the int8 pool and its "
                "prime through the pool know keys and values only")
        pos = state.get("kv_pos")
        if pos is None or getattr(pos, "ndim", 0) < 1:
            raise ValueError(
                "direct paged decode needs the per-row kv_pos vector "
                "(the engine arena carries one; a scalar-position "
                "stream has no per-slot pages to address)")
        n, hkv, t, d = k.shape
        L = self.cache_length
        ps = kp.shape[2]
        n_blk = table.shape[1]
        if prime and pad_left is not None:
            # packed pad accounting, the dense prime's (_stream_attend):
            # pads take q_pos = pos - 1 and never advance the stream
            m0 = jnp.arange(t) >= pad_left                  # [T] valid
            cum = jnp.cumsum(m0.astype(pos.dtype))
            q_pos = pos[:, None] + (cum - 1)[None, :]       # [N, T]
            n_new = cum[-1]
            chunk0 = pad_left
        else:
            q_pos = pos[:, None] + jnp.arange(t, dtype=pos.dtype)
            n_new = t
            chunk0 = 0
        if self.rope:
            abs_pos = jnp.maximum(q_pos, 0) if prime else q_pos
            q = self._rope(q, abs_pos)
            k = self._rope(k, abs_pos)
        # -- O(one-token) append at (page, offset) ---------------------
        blk = jnp.clip(q_pos // ps, 0, n_blk - 1).astype(jnp.int32)
        page = jnp.take_along_axis(table, blk, axis=1)
        page = jnp.where(q_pos < L, page, 0)    # past capacity: null
        if prime:
            # pads (q_pos = pos - 1) and prefix-shared positions
            # (q_pos < pos = the hit length) must not write real pages:
            # route them to the null page like past-capacity appends
            page = jnp.where(q_pos >= pos[:, None], page, 0)
        off = (q_pos % ps).astype(jnp.int32)
        kt = k.transpose(0, 2, 1, 3)                    # [N, T, Hkv, D]
        vt = v.transpose(0, 2, 1, 3)
        if quant:
            from deeplearning4j_tpu.serving.quant import quantize_chunk
            vsc = state["kv_page_scale_v"]
            writable = q_pos < L
            if prime:
                writable = writable & (q_pos >= pos[:, None])
            kq, ksc = quantize_chunk(kt, ksc, page, q_pos, pos,
                                     writable, page_size=ps,
                                     chunk0=chunk0)
            vq, vsc = quantize_chunk(vt, vsc, page, q_pos, pos,
                                     writable, page_size=ps,
                                     chunk0=chunk0)
            kp = _paged_append(kp, page, off, kq)
            vp = _paged_append(vp, page, off, vq)
        else:
            kp = _paged_append(kp, page, off, kt.astype(kp.dtype))
            vp = _paged_append(vp, page, off, vt.astype(vp.dtype))
        impl, interpret = self.paged_read
        if self.selects:
            # one of the two forms of ``selected_read``, whatever
            # ``paged_read`` says: the kernel cannot read the index leaf
            qi, ki, w = idx
            ip = _paged_append(
                state["kv_page_i"], page, off,
                self._index_leaf(self._rope_index(ki, q_pos))[
                    :, :, None].astype(state["kv_page_i"].dtype))
            attend = self._attend_paged_masked \
                if self.selected_read == "masked" else self._attend_gathered
            o, scored = attend(
                q, kp, vp, ip, table, (self._rope_index(qi, q_pos), w),
                q_pos)
            return o, {**_attended(state, scored), "kv_page_k": kp,
                       "kv_page_v": vp, "kv_page_i": ip,
                       "kv_pos": pos + n_new}
        if impl == "pallas" and not prime:
            from deeplearning4j_tpu.serving.paged_kernel import (
                paged_attention)
            reps = self.n_heads // hkv
            qg = q.reshape(n, hkv, reps * t, d)
            o = paged_attention(qg, kp, vp, table,
                                (pos + t).astype(jnp.int32),
                                query_width=t, interpret=interpret,
                                k_scales=ksc if quant else None,
                                v_scales=vsc if quant else None)
            o = o.reshape(n, self.n_heads, t, d)
        else:
            kg = kp[table]                    # [N, n_blk, Hkv, ps, D]
            vg = vp[table]
            if quant:
                # dequant folded into the gather: q * sigma is exact
                # (power-of-two sigma, serving/quant.py), so a page
                # reads back the same values on every dispatch
                kg = kg.astype(jnp.float32) * \
                    ksc[table][:, :, :, None, None]
                vg = vg.astype(jnp.float32) * \
                    vsc[table][:, :, :, None, None]
                kg = kg.astype(q.dtype)
                vg = vg.astype(q.dtype)
            kd = jnp.moveaxis(kg, 2, 1
                              ).reshape(n, hkv, n_blk * ps, d)[:, :, :L]
            vd = jnp.moveaxis(vg, 2, 1
                              ).reshape(n, hkv, n_blk * ps, d)[:, :, :L]
            valid = jnp.arange(L)[None, None, :] <= q_pos[..., None]
            o = self._grouped_attend(q, kd, vd, valid)
        out = {**state, "kv_page_k": kp, "kv_page_v": vp,
               "kv_pos": pos + n_new}
        if quant:
            out["kv_page_scale_k"] = ksc
            out["kv_page_scale_v"] = vsc
        return o, out

    def _stream_mask_update(self, state, mask, n, t, L, *, fresh, write):
        """Maintain the [N, L] cached-key validity buffer. Returns the
        updated buffer, or None when this stream has never seen a mask."""
        km = state.get("kv_mask")
        if mask is None and km is None:
            return None
        if km is None:
            if not fresh:
                raise ValueError(
                    "mask passed mid-stream to a SelfAttentionLayer that "
                    "started streaming unmasked — earlier chunks' key "
                    "validity was never recorded; restart the stream "
                    "(rnn_clear_previous_state) with the mask from the "
                    "first chunk")
            km = jnp.zeros((n, L), jnp.bool_)
        m = (jnp.ones((n, t), jnp.bool_) if mask is None
             else jnp.asarray(mask).reshape(n, t).astype(jnp.bool_))
        return write(km, m)

    def _grouped_attend(self, q, kc, vc, valid):
        """Masked attention of [N,H,T,D] queries against the un-expanded
        [N,Hkv,L,D] cache (GQA groups share KV heads); valid: [N|1, T, L]."""
        n, _, t, d = q.shape
        blk = self.stream_query_block
        if blk and t > blk and t % blk == 0:
            nb, bound = t // blk, valid.shape[0]
            qb = jnp.moveaxis(q.reshape(n, self.n_heads, nb, blk, d), 2, 0)
            vb = jnp.moveaxis(valid.reshape(bound, nb, blk, -1), 1, 0)
            o = jax.lax.map(
                lambda x: self._grouped_attend(x[0], kc, vc, x[1]),
                (qb, vb))                          # [nb, N, H, blk, D]
            return jnp.moveaxis(o, 0, 2).reshape(n, self.n_heads, t, d)
        hkv = kc.shape[1]
        reps = self.n_heads // hkv
        qg = q.astype(jnp.float32).reshape(n, hkv, reps, t, d)
        s = jnp.einsum("ngrtd,ngld->ngrtl", qg,
                       kc.astype(jnp.float32)) / np.sqrt(d)
        s = jnp.where(valid[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("ngrtl,ngld->ngrtd", p, vc.astype(jnp.float32))
        return o.reshape(n, self.n_heads, t, d).astype(q.dtype)

    def _stream_attend_rolling(self, q, k, v, state, kc, vc, pos,
                               mask=None, *, fresh, m0=None, q_pos=None,
                               n_new=None, vec=False):
        """Windowed streaming with a ROLLING cache: slots are reused
        modulo cache_length, so generation length is unbounded with
        bounded memory (cache_length >= window keeps every in-window key
        resident; evicted keys are out of the window by construction).
        kv_abs tracks each slot's absolute position (-1 = empty).

        `m0`/`q_pos`/`n_new` arrive from _stream_attend when the chunk is
        left-padded (packed accounting — see there): pad writes route to
        the dump slot L and are dropped, so pads consume neither slots
        nor positions. The static chunk-size guards below use the padded
        length t (conservative: a padded chunk needs its full bucket to
        fit, so pick a bucket <= cache_length).

        vec=True is the per-row-positions regime (after a per-row
        rewind_stream_state — batched speculation): q_pos is [N,T], each
        row writes at its own modular slots, and kv_abs promotes from
        the shared [L] to [N,L] on the first per-row write (exact:
        before rows diverge every row's slot->abs map is identical).
        The validity test stays the same per-row recency arithmetic, so
        a rewound row's stale future entries are invisible to that row
        while other rows keep seeing their accepted keys."""
        n, _, t, d = q.shape
        hkv = k.shape[1]
        L = self.cache_length
        if L < self.window:
            raise ValueError(
                f"rolling window streaming needs cache_length >= window "
                f"({L} < {self.window})")
        if fresh:
            # empty cache: writes never evict needed keys; any t <= L ok
            if t > L:
                raise ValueError(f"priming chunk of {t} positions exceeds "
                                 f"cache_length {L}")
        elif t > L - self.window + 1:
            # mid-stream, a larger chunk would overwrite slots still
            # inside earlier queries' windows BEFORE they attend
            raise ValueError(
                f"mid-stream chunk of {t} positions would evict in-window "
                f"keys; max is cache_length - window + 1 = "
                f"{L - self.window + 1} (or raise cache_length)")
        kv_abs = state.get("kv_abs")
        if kv_abs is None:
            kv_abs = jnp.full((L,), -1, jnp.int32)
        if q_pos is None:
            steps_t = jnp.arange(t, dtype=pos.dtype)
            q_pos = pos[:, None] + steps_t if vec else pos + steps_t
            n_new = t
        if vec:
            if m0 is not None:
                raise ValueError(
                    "packed (pad_left) priming cannot follow a per-row "
                    "rewind — restart the stream")
            if kv_abs.ndim == 1:
                kv_abs = jnp.broadcast_to(kv_abs, (n, L))
            slots = q_pos % L                              # [N, T]
            bidx = jnp.arange(n)[:, None]
            kc = kc.at[bidx, :, slots, :].set(
                k.transpose(0, 2, 1, 3).astype(kc.dtype))
            vc = vc.at[bidx, :, slots, :].set(
                v.transpose(0, 2, 1, 3).astype(vc.dtype))
            kv_abs = kv_abs.at[bidx, slots].set(
                q_pos.astype(kv_abs.dtype))
            km = self._stream_mask_update(
                state, mask, n, t, L, fresh=fresh,
                write=lambda km, m: km.at[bidx, slots].set(m))
        elif m0 is None:
            slots = q_pos % L
            kc = kc.at[:, :, slots, :].set(k.astype(kc.dtype))
            vc = vc.at[:, :, slots, :].set(v.astype(vc.dtype))
            kv_abs = kv_abs.at[slots].set(q_pos.astype(kv_abs.dtype))
            km = self._stream_mask_update(
                state, mask, n, t, L, fresh=fresh,
                write=lambda km, m: km.at[:, slots].set(m))
        else:
            slots = jnp.where(m0, q_pos % L, L)      # pads -> dump, dropped
            kc = kc.at[:, :, slots, :].set(k.astype(kc.dtype), mode="drop")
            vc = vc.at[:, :, slots, :].set(v.astype(vc.dtype), mode="drop")
            kv_abs = kv_abs.at[slots].set(q_pos.astype(kv_abs.dtype),
                                          mode="drop")
            km = None
        kc, vc = _shard_cache(kc, 2), _shard_cache(vc, 2)
        km = _shard_cache(km, 1)
        reps = self.n_heads // hkv
        qg = q.astype(jnp.float32).reshape(n, hkv, reps, t, d)
        scale = 1.0 / np.sqrt(d)
        s = jnp.einsum("ngrtd,ngld->ngrtl", qg,
                       kc.astype(jnp.float32)) * scale
        if vec:
            abs_r = kv_abs[:, None, :]                       # [N, 1, L]
            valid = ((abs_r >= 0)
                     & (abs_r <= q_pos[..., None])
                     & (q_pos[..., None] - abs_r < self.window))
            # [N, T, L] — each row against its own slot->abs map
        else:
            valid = ((kv_abs[None, :] >= 0)
                     & (kv_abs[None, :] <= q_pos[:, None])
                     & (q_pos[:, None] - kv_abs[None, :] < self.window))
            valid = valid[None]                              # [1, T, L]
        if km is not None:
            valid = valid & km[:, None, :]                   # [N, T, L]
        s = jnp.where(valid[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("ngrtl,ngld->ngrtd", p, vc.astype(jnp.float32))
        o = o.reshape(n, self.n_heads, t, d).astype(q.dtype)
        out = {**state, "kv_k": kc, "kv_v": vc, "kv_abs": kv_abs,
               "kv_pos": pos + n_new}
        if km is not None:
            out["kv_mask"] = km
        return o, out

    def _rope(self, x, positions):
        """Rotary position embedding (RoFormer rotate-half convention):
        x [N,H,T,D], positions [T] absolute — or [N,T] when rows carry
        their own streaming positions (per-row rewind). Pairs channel i
        with channel i + D/2 and rotates by positions * base^(-2i/D)."""
        d = x.shape[-1]
        if d % 2:
            raise ValueError(f"rope needs an even head dim, got {d}")
        half = d // 2
        inv = self.rope_base ** (-jnp.arange(half, dtype=jnp.float32)
                                 / half)
        ang = positions.astype(jnp.float32)[..., None] * inv  # [...,T,half]
        if ang.ndim == 2:           # shared positions: [T,half]
            cos = jnp.cos(ang)[None, None].astype(x.dtype)
            sin = jnp.sin(ang)[None, None].astype(x.dtype)
        else:                       # per-row positions: [N,T,half]
            cos = jnp.cos(ang)[:, None].astype(x.dtype)
            sin = jnp.sin(ang)[:, None].astype(x.dtype)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x1 * sin + x2 * cos], axis=-1)

    def _expand_kv(self, k, v):
        """Repeat K/V heads up to n_heads for grouped-query attention
        (no-op for standard MHA)."""
        reps = self.n_heads // k.shape[1]
        if reps == 1:
            return k, v
        return (jnp.repeat(k, reps, axis=1), jnp.repeat(v, reps, axis=1))


@register_layer
@dataclass
class LocalResponseNormalization(LayerConf):
    """LRN across channels (ref: conf/layers/LocalResponseNormalization.java;
    native path CudnnLocalResponseNormalizationHelper.java). Defaults k=2,
    n=5, alpha=1e-4, beta=0.75 match the reference."""

    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75
    data_format: str = "NCHW"

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        ch_axis = 3 if self.data_format == "NHWC" else 1
        return _norm.lrn(x, self.k, self.n, self.alpha, self.beta,
                         channel_axis=ch_axis), state


# ---------------------------------------------------------------------------
# recurrent layers
# ---------------------------------------------------------------------------


@register_layer
@dataclass
class LSTM(FeedForwardLayerConf):
    """LSTM without peepholes (ref: conf/layers/LSTM.java; impl via
    LSTMHelpers.java / CudnnLSTMHelper.java → here lstm_scan). Params:
    W [nIn,4nOut], RW [nOut,4nOut], b [4nOut]; gate order (i,f,c,o);
    forget-gate bias init (ref: forgetGateBiasInit, default 1.0)."""

    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"
    activation: str = "tanh"

    _peephole = False
    #: streams via irreversible h/c carry — cannot rewind (speculative
    #: decoding rollback); see check_rewindable
    carries_recurrent_state = True

    def output_type(self, it):
        return InputType.recurrent(self.n_out, it.timesteps)

    def init(self, key, it):
        if self.n_in is None:
            self.n_in = it.size
        h = self.n_out
        k1, k2, k3 = jax.random.split(key, 3)
        fan_in, fan_out = self.n_in, h
        w = init_weights(k1, (self.n_in, 4 * h), fan_in + h, h, self.weight_init, self.dist)
        rw = init_weights(k2, (h, 4 * h), fan_in + h, h, self.weight_init, self.dist)
        b = jnp.zeros((4 * h,), jnp.float32)
        b = b.at[h:2 * h].set(self.forget_gate_bias_init)
        p = {"W": w, "RW": rw, "b": b}
        if self._peephole:
            p["P"] = jnp.zeros((3, h), jnp.float32)
        return p, {}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        out, h_fin, c_fin = _rnn.lstm_scan(
            x, params["W"], params["RW"], params["b"],
            h0=state.get("h"), c0=state.get("c"),
            peephole=params.get("P"), mask=mask,
            gate_act=self.gate_activation, cell_act=self.activation,
        )
        return out, {**state, "h": h_fin, "c": c_fin}


@register_layer
@dataclass
class GravesLSTM(LSTM):
    """LSTM with peephole connections (ref: conf/layers/GravesLSTM.java;
    peephole columns per LSTMParamInitializer)."""

    _peephole = True


@register_layer
@dataclass
class GravesBidirectionalLSTM(FeedForwardLayerConf):
    """Bidirectional Graves LSTM; forward+backward outputs SUMMED
    (ref: GravesBidirectionalLSTM.java:219)."""

    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"
    activation: str = "tanh"

    def output_type(self, it):
        return InputType.recurrent(self.n_out, it.timesteps)

    def init(self, key, it):
        if self.n_in is None:
            self.n_in = it.size
        h = self.n_out
        keys = jax.random.split(key, 4)
        p = {}
        for tag, kw, kr in (("F", keys[0], keys[1]), ("B", keys[2], keys[3])):
            w = init_weights(kw, (self.n_in, 4 * h), self.n_in + h, h,
                             self.weight_init, self.dist)
            rw = init_weights(kr, (h, 4 * h), self.n_in + h, h,
                              self.weight_init, self.dist)
            b = jnp.zeros((4 * h,), jnp.float32).at[h:2 * h].set(
                self.forget_gate_bias_init)
            p[f"W{tag}"] = w
            p[f"RW{tag}"] = rw
            p[f"b{tag}"] = b
            p[f"P{tag}"] = jnp.zeros((3, h), jnp.float32)
        return p, {}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        y = _rnn.bidirectional_sum(
            x, params["WF"], params["RWF"], params["bF"],
            params["WB"], params["RWB"], params["bB"],
            peep_f=params["PF"], peep_b=params["PB"], mask=mask,
            gate_act=self.gate_activation, cell_act=self.activation,
        )
        return y, state


@register_layer
@dataclass
class SimpleRnn(FeedForwardLayerConf):
    """Vanilla RNN h_t = act(xW + hRW + b)."""

    activation: str = "tanh"

    def output_type(self, it):
        return InputType.recurrent(self.n_out, it.timesteps)

    def init(self, key, it):
        if self.n_in is None:
            self.n_in = it.size
        h = self.n_out
        k1, k2 = jax.random.split(key)
        w = init_weights(k1, (self.n_in, h), self.n_in + h, h, self.weight_init, self.dist)
        rw = init_weights(k2, (h, h), self.n_in + h, h, self.weight_init, self.dist)
        return {"W": w, "RW": rw, "b": jnp.zeros((h,), jnp.float32)}, {}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        out, _ = _rnn.simple_rnn_scan(x, params["W"], params["RW"], params["b"],
                                      mask=mask, act=self.activation)
        return out, state


@register_layer
@dataclass
class LastTimeStepLayer(LayerConf):
    """Extract last (unmasked) timestep: [N,C,T] -> [N,C]
    (ref: graph vertex rnn/LastTimeStepVertex.java, usable as a layer)."""

    def output_type(self, it):
        return InputType.feed_forward(it.size)

    def output_mask(self, mask, it):
        return None

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        if mask is None:
            return x[:, :, -1], state
        idx = jnp.sum(mask > 0, axis=1).astype(jnp.int32) - 1  # [N]
        idx = jnp.clip(idx, 0, x.shape[2] - 1)
        y = jnp.take_along_axis(x, idx[:, None, None], axis=2)[:, :, 0]
        return y, state


# ---------------------------------------------------------------------------
# output layers
# ---------------------------------------------------------------------------


@dataclass
class BaseOutputLayerConf(FeedForwardLayerConf):
    """Base for output layers carrying a loss function
    (ref: conf/layers/BaseOutputLayer.java)."""

    loss: str = "mcxent"
    activation: str = "softmax"

    def compute_score(self, labels, preout, mask=None):
        return _losses.score(labels, preout, self.loss, self.activation, mask)


@register_layer
@dataclass
class OutputLayer(BaseOutputLayerConf):
    """Dense + loss output layer (ref: conf/layers/OutputLayer.java)."""

    has_bias: bool = True

    def output_type(self, it):
        return InputType.feed_forward(self.n_out)

    def init(self, key, it):
        self.infer_n_in(it)
        w = init_weights(key, (self.n_in, self.n_out), self.n_in, self.n_out,
                         self.weight_init, self.dist)
        p = {"W": w}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, jnp.float32)
        return p, {}

    def preout(self, params, x, *, train=False, rng=None):
        x = self.maybe_dropout_input(x, train, rng)
        y = x @ params["W"]
        if self.has_bias:
            y = y + params["b"]
        return y

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        return _act.get(self.activation)(self.preout(params, x, train=train, rng=rng)), state


@register_layer
@dataclass
class RnnOutputLayer(BaseOutputLayerConf):
    """Per-timestep dense + loss over [N,C,T] (ref: conf/layers/RnnOutputLayer.java)."""

    has_bias: bool = True
    #: whether the STREAMING form answers for the last position only,
    #: whatever the caller asked (``LastStepOutputLayer``)
    last_step_only = False

    def output_type(self, it):
        return InputType.recurrent(self.n_out, it.timesteps)

    def init(self, key, it):
        if self.n_in is None:
            self.n_in = it.size
        w = init_weights(key, (self.n_in, self.n_out), self.n_in, self.n_out,
                         self.weight_init, self.dist)
        p = {"W": w}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, jnp.float32)
        return p, {}

    def preout(self, params, x, *, train=False, rng=None):
        x = self.maybe_dropout_input(x, train, rng)
        y = jnp.einsum("nct,co->not", x, params["W"])
        if self.has_bias:
            y = y + params["b"][None, :, None]
        return y

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        pre = self.preout(params, x, train=train, rng=rng)
        a = _act.get(self.activation)
        if str(self.activation).lower() == "softmax":
            y = jax.nn.softmax(pre, axis=1)
        else:
            y = a(pre)
        return y, state

    def compute_score(self, labels, preout, mask=None):
        # fold time into batch: [N,C,T] -> [N*T, C]; mask [N,T] -> [N*T]
        n, c, t = preout.shape
        p2 = jnp.transpose(preout, (0, 2, 1)).reshape(n * t, c)
        l2 = jnp.transpose(labels, (0, 2, 1)).reshape(n * t, c)
        m2 = mask.reshape(n * t) if mask is not None else None
        return _losses.score(l2, p2, self.loss, self.activation, m2)


@register_layer
@dataclass
class LossLayer(BaseOutputLayerConf):
    """Parameterless loss layer (ref: conf/layers/LossLayer.java)."""

    def output_type(self, it):
        return it

    def preout(self, params, x, *, train=False, rng=None):
        return x

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        return _act.get(self.activation)(x), state


@register_layer
@dataclass
class CenterLossOutputLayer(OutputLayer):
    """Output layer with center loss (ref: conf/layers/CenterLossOutputLayer.java;
    impl nn/layers/training/CenterLossOutputLayer.java). Per-class feature
    centers are non-gradient state updated by EMA (alpha), loss adds
    lambda * ||features - center_y||^2."""

    alpha: float = 0.05
    lambda_: float = 2e-4

    def init(self, key, it):
        p, s = super().init(key, it)
        s = dict(s)
        s["centers"] = jnp.zeros((self.n_out, self.n_in), jnp.float32)
        return p, s

    def center_loss(self, features, labels, state):
        centers = state["centers"]
        cls = jnp.argmax(labels, axis=-1)
        diff = features - centers[cls]
        return self.lambda_ * 0.5 * jnp.mean(jnp.sum(diff * diff, axis=-1))

    def update_centers(self, features, labels, state):
        centers = state["centers"]
        cls = jnp.argmax(labels, axis=-1)  # [N]
        onehot = jax.nn.one_hot(cls, centers.shape[0], dtype=features.dtype)  # [N,K]
        counts = jnp.sum(onehot, axis=0)[:, None]  # [K,1]
        sums = onehot.T @ features  # [K, F]
        batch_mean = sums / jnp.clip(counts, 1.0, None)
        updated = centers + self.alpha * (batch_mean - centers)
        new_centers = jnp.where(counts > 0, updated, centers)
        return {**state, "centers": new_centers}


# ---------------------------------------------------------------------------
# misc layers
# ---------------------------------------------------------------------------


@register_layer
@dataclass
class FrozenLayer(LayerConf):
    """Wrapper marking an inner layer's params as non-trainable
    (ref: nn/conf/layers/misc/FrozenLayer.java, nn/layers/FrozenLayer.java).
    The network applies stop_gradient to its params during training."""

    inner: Optional[dict] = None  # serialized inner layer conf

    def __post_init__(self):
        if isinstance(self.inner, LayerConf):
            self._inner_obj = self.inner
            self.inner = layer_to_dict(self._inner_obj)
        elif self.inner is not None:
            self._inner_obj = layer_from_dict(self.inner)
        else:
            self._inner_obj = None

    @property
    def layer(self) -> LayerConf:
        return self._inner_obj

    def output_type(self, it):
        return self._inner_obj.output_type(it)

    def output_mask(self, mask, it):
        return self._inner_obj.output_mask(mask, it)

    def init(self, key, it):
        return self._inner_obj.init(key, it)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        params = jax.lax.stop_gradient(params)
        return self._inner_obj.apply(params, x, state, train=train, rng=rng, mask=mask)


@register_layer
@dataclass
class AutoEncoder(FeedForwardLayerConf):
    """Denoising autoencoder pretrain layer (ref: conf/layers/AutoEncoder.java;
    impl feedforward/autoencoder/AutoEncoder.java). Params W, b (hidden bias),
    vb (visible bias); decode uses W^T (tied weights)."""

    corruption_level: float = 0.3
    sparsity: float = 0.0
    loss: str = "mse"
    activation: str = "sigmoid"

    def output_type(self, it):
        return InputType.feed_forward(self.n_out)

    def init(self, key, it):
        self.infer_n_in(it)
        w = init_weights(key, (self.n_in, self.n_out), self.n_in, self.n_out,
                         self.weight_init, self.dist)
        return {"W": w, "b": jnp.zeros((self.n_out,), jnp.float32),
                "vb": jnp.zeros((self.n_in,), jnp.float32)}, {}

    def encode(self, params, x):
        return _act.get(self.activation)(x @ params["W"] + params["b"])

    def decode(self, params, h):
        return _act.get(self.activation)(h @ params["W"].T + params["vb"])

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        return self.encode(params, x), state

    def pretrain_loss(self, params, x, rng):
        """Denoising reconstruction loss for layerwise pretraining
        (ref: AutoEncoder.computeGradientAndScore)."""
        xc = x
        if rng is not None and self.corruption_level > 0:
            keep = jax.random.bernoulli(rng, 1.0 - self.corruption_level, x.shape)
            xc = jnp.where(keep, x, 0.0)
        recon = self.decode(params, self.encode(params, xc))
        return jnp.mean(jnp.sum((recon - x) ** 2, axis=-1))


@register_layer
@dataclass
class RBM(FeedForwardLayerConf):
    """Restricted Boltzmann machine with CD-k pretraining (ref:
    conf/layers/RBM.java + layers/feedforward/rbm/RBM.java:68).

    Params follow PretrainParamInitializer: W [nIn,nOut], hidden bias b,
    visible bias vb. Forward activation = propUp (same as the reference's
    use as a feedforward layer once pretrained).

    Pretraining uses the standard free-energy formulation of contrastive
    divergence: loss = mean(F(v0) - F(v_k)) with the chain sample v_k under
    stop_gradient, so jax.grad yields exactly the CD-k update
    (⟨v h⟩_data − ⟨v h⟩_model) that the reference hand-codes. Gibbs chain
    runs in probability space when sample=False (deterministic; used by
    gradient checks) or with Bernoulli sampling when an rng is given.

    hidden_unit: "binary" | "rectified"; visible_unit: "binary" | "gaussian"
    (reference HiddenUnit/VisibleUnit enums, the two pairs it actually
    supports in practice)."""

    hidden_unit: str = "binary"
    visible_unit: str = "binary"
    k: int = 1  # CD-k Gibbs steps
    sparsity: float = 0.0
    activation: str = "sigmoid"
    loss: str = "mse"

    def output_type(self, it):
        return InputType.feed_forward(self.n_out)

    def init(self, key, it):
        self.infer_n_in(it)
        w = init_weights(key, (self.n_in, self.n_out), self.n_in, self.n_out,
                         self.weight_init, self.dist)
        return {"W": w, "b": jnp.zeros((self.n_out,), jnp.float32),
                "vb": jnp.zeros((self.n_in,), jnp.float32)}, {}

    def prop_up(self, params, v):
        z = v @ params["W"] + params["b"]
        if self.hidden_unit == "rectified":
            return jax.nn.relu(z)
        return jax.nn.sigmoid(z)

    def prop_down(self, params, h):
        z = h @ params["W"].T + params["vb"]
        if self.visible_unit == "gaussian":
            return z  # mean of unit-variance Gaussian
        return jax.nn.sigmoid(z)

    def free_energy(self, params, v):
        """F(v) = -v·vb + 0.5|v-vb|² (gaussian) − Σ softplus(b + vW).

        Closed form is exact for BINARY hidden units only; rectified-hidden
        pretraining uses the energy-statistic loss in pretrain_loss instead."""
        hidden_term = jnp.sum(jax.nn.softplus(v @ params["W"] + params["b"]),
                              axis=-1)
        if self.visible_unit == "gaussian":
            visible_term = 0.5 * jnp.sum((v - params["vb"]) ** 2, axis=-1)
            return visible_term - hidden_term
        return -(v @ params["vb"]) - hidden_term

    def _energy_statistic(self, params, v):
        """E(v, h(v)) with the hidden activations under stop_gradient: its
        parameter gradient is the CD sufficient statistic (v⊗h, h, v) for
        any hidden nonlinearity (how the reference accumulates wGradient/
        hBiasGradient/vBiasGradient in RBM.java computeGradientAndScore)."""
        h = jax.lax.stop_gradient(self.prop_up(params, v))
        if self.visible_unit == "gaussian":
            visible = 0.5 * jnp.sum((v - params["vb"]) ** 2, axis=-1)
        else:
            visible = -(v @ params["vb"])
        return visible - jnp.sum((v @ params["W"]) * h, axis=-1) \
            - (h @ params["b"])

    def gibbs_step(self, params, v, rng):
        h = self.prop_up(params, v)
        if rng is not None and self.hidden_unit == "binary":
            k1, k2 = jax.random.split(rng)
            h = jax.random.bernoulli(k1, h).astype(v.dtype)
        else:
            k2 = rng
        v_new = self.prop_down(params, h)
        if k2 is not None and self.visible_unit == "gaussian":
            v_new = v_new + jax.random.normal(k2, v_new.shape, v_new.dtype)
        return v_new

    def contrastive_divergence(self, params, v0, rng, sample: bool = True):
        """Run the CD-k chain, return v_k (no gradient flows through it)."""
        v = v0
        for i in range(max(1, self.k)):
            step_rng = (jax.random.fold_in(rng, i)
                        if (rng is not None and sample) else None)
            v = self.gibbs_step(params, v, step_rng)
        return jax.lax.stop_gradient(v)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        return _act.get(self.activation)(x @ params["W"] + params["b"]), state

    def pretrain_loss(self, params, x, rng, sample: bool = True):
        vk = self.contrastive_divergence(params, x, rng, sample=sample)
        energy = (self.free_energy if self.hidden_unit == "binary"
                  else self._energy_statistic)
        loss = jnp.mean(energy(params, x) - energy(params, vk))
        if self.sparsity > 0:
            h_mean = jnp.mean(self.prop_up(params, x), axis=0)
            loss = loss + self.sparsity * jnp.sum((h_mean - 0.01) ** 2)
        return loss


# ---------------------------------------------------------------------------
# the decoder vocabulary of latent-attention / routed-expert models
# ---------------------------------------------------------------------------


class PagedLeaf(NamedTuple):
    """One leaf of a streaming attention layer's cache as the layer
    declares it to a page pool. The dense streaming leaf is
    ``[N, *token_shape with L at token_axis]`` under ``key`` (``kv_...``),
    the pool leaf ``[P, *token_shape with page_size at token_axis]`` under
    ``page_key`` (``kv_page_...``); one page table a row serves every
    leaf of a layer."""

    key: str
    token_shape: Tuple[int, ...]
    token_axis: int

    @property
    def page_key(self) -> str:
        return "kv_page_" + self.key[len("kv_"):]

    def shape(self, lead: int, length: int) -> Tuple[int, ...]:
        """``[lead, ...]`` with ``length`` tokens at the token axis."""
        s = tuple(self.token_shape)
        return ((lead,) + s[:self.token_axis] + (int(length),)
                + s[self.token_axis:])

    @property
    def token_elements(self) -> int:
        return int(np.prod(self.token_shape, dtype=np.int64))


def paged_leaves(layer) -> Tuple[PagedLeaf, ...]:
    """What a streaming layer keeps per token behind a page table: its own
    ``paged_leaves()``, or none (a layer with no per-token cache)."""
    declare = getattr(layer, "paged_leaves", None)
    return tuple(declare()) if declare is not None else ()


class SlotLeaf(NamedTuple):
    """One leaf of the state a streaming layer keeps a STREAM and not a
    token (recurrent state): ``[N, *row_shape]`` under ``key`` in the
    layer's streaming state, ``dtype`` (None: the dtype activations come
    in). A serving engine keeps one row a slot beside its page pool and
    seats a primed row whole; the size does not grow with the context."""

    key: str
    row_shape: Tuple[int, ...]
    dtype: Optional[str] = None

    def row_bytes(self, compute_dtype) -> int:
        return int(np.prod(self.row_shape, dtype=np.int64)) \
            * jnp.dtype(self.dtype or compute_dtype).itemsize


def slot_leaves(layer) -> Tuple[SlotLeaf, ...]:
    """What a streaming layer keeps per stream: its own
    ``slot_leaves()``, or none."""
    declare = getattr(layer, "slot_leaves", None)
    return tuple(declare()) if declare is not None else ()


class StreamCounters(NamedTuple):
    """The counters a layer's streaming forms keep in their state: an
    integer vector (or a scalar, for one field) under ``key``, one number
    a ``fields`` name, added to by every call (``maxima``: fields that
    take the maximum instead). A serving engine takes them out of the
    state each dispatch returns, joins them on the device and shows the
    sums over the layers of a ``kind`` under ``health()[kind]``.
    ``host``, where given, is called with the contexts (positions seen,
    itself included) of each real query of a dispatch and returns what
    that dispatch adds to further fields of the same key, counted on the
    host from the rows alone."""

    key: str
    kind: str
    fields: Tuple[str, ...]
    maxima: Tuple[str, ...] = ()
    host: Optional[Any] = None


def stream_counters(layer) -> Optional[StreamCounters]:
    """A layer's own ``stream_counters()``, or None."""
    declare = getattr(layer, "stream_counters", None)
    return declare() if declare is not None else None


def _selection_counters(layer) -> StreamCounters:
    """What a layer that attends ``layer.index_topk`` selected positions
    counts (one declaration for every such layer: a net has one kind of
    them, so one ``health()`` key): on the device the positions whose
    attention scores its forms computed (``_attended``); on the host, from
    a dispatch's queries by the positions each may see, how many there
    were, how many positions lay before them, and how many of those the
    selection keeps."""
    def selected(contexts) -> Dict[str, int]:
        contexts = np.asarray(contexts, np.int64)
        return {"query_positions": len(contexts),
                "context_positions": int(contexts.sum()),
                "selected_positions": int(
                    np.minimum(contexts, layer.index_topk).sum())}

    return StreamCounters("attn_stats", "sparse_attn",
                          ("attended_positions",), host=selected)


def _attended(state, scored: int):
    """``state`` with ``scored`` more positions in ``attn_stats``."""
    return {**state, "attn_stats": jnp.asarray(scored, jnp.int32)
            + state.get("attn_stats", 0)}


def _rms_norm(x, gamma, eps: float):
    """RMSNorm over the last axis, float32 statistics."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(x.dtype)


@register_layer
@dataclass
class RMSNorm(FeedForwardLayerConf):
    """Root-mean-square normalisation over the feature axis (axis 1 of
    [N,F] and [N,F,T]) with a gain and no bias, statistics in float32
    (Zhang & Sennrich 2019): the norm of the decoders that dropped
    LayerNorm's mean and bias."""

    eps: float = 1e-6

    def output_type(self, it):
        if it.kind == "cnn":
            raise ValueError("RMSNorm supports FF [N,F] and RNN [N,F,T] "
                             "input (feature axis 1)")
        return it

    def init(self, key, it):
        nf = it.size if it.kind == "rnn" else it.flat_size()
        self.n_in = self.n_out = nf
        return {"gamma": jnp.ones((nf,), jnp.float32)}, {}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        y = jnp.moveaxis(_rms_norm(jnp.moveaxis(x, 1, -1), params["gamma"],
                                   self.eps), -1, 1)
        return _act.get(self.activation)(y), state


@register_layer
@dataclass
class GatedFeedForward(FeedForwardLayerConf):
    """Position-wise gated feed-forward ``(silu(x W_g) * x W_u) W_d`` over
    [N,F] or [N,F,T], no biases: ``hidden`` wide inside, ``n_out``
    (default: the input's width) out. Streaming needs no state: every
    position is its own."""

    hidden: int = 256

    def output_type(self, it):
        n_out = self.n_out or it.size
        return (InputType.recurrent(n_out, it.timesteps)
                if it.kind == "rnn" else InputType.feed_forward(n_out))

    def init(self, key, it):
        if self.n_in is None:
            self.n_in = it.size
        if self.n_out is None:
            self.n_out = self.n_in
        kg, ku, kd = jax.random.split(key, 3)
        f, i, o = self.n_in, self.hidden, self.n_out
        return {"Wg": init_weights(kg, (f, i), f, i, self.weight_init,
                                   self.dist),
                "Wu": init_weights(ku, (f, i), f, i, self.weight_init,
                                   self.dist),
                "Wd": init_weights(kd, (i, o), i, o, self.weight_init,
                                   self.dist)}, {}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        y = _re.gated_ffn(jnp.moveaxis(x, 1, -1), params["Wg"], params["Wu"],
                      params["Wd"]).astype(x.dtype)
        return _act.get(self.activation)(jnp.moveaxis(y, -1, 1)), state


@register_layer
@dataclass
class SequenceEmbeddingLayer(FeedForwardLayerConf):
    """Token embedding over a sequence of ids: ``[N, T]`` integers in,
    ``[N, E, T]`` out (``W`` [V, E], row ``id``; no bias). A net whose
    input feeds this layer takes ids and no one-hot tensor
    (``takes_ids``): the decoders of ``util/decoding`` and the serving
    engine ask the net and feed it accordingly. The declared input type
    stays ``InputType.recurrent(vocab, T)``: the size is the range of the
    ids."""

    #: the dtype the rows are handed on in (None: the table's own). A
    #: lookup has no float input to promote against, so a net that
    #: computes wider than it stores its table says so here
    out_dtype: Optional[str] = None

    takes_ids = True

    def output_type(self, it):
        if it.kind != "rnn":
            raise ValueError("SequenceEmbeddingLayer needs a sequence "
                             "input (InputType.recurrent(vocab, T))")
        return InputType.recurrent(self.n_out, it.timesteps)

    def init(self, key, it):
        if self.n_in is None:
            self.n_in = it.size
        w = init_weights(key, (self.n_in, self.n_out), self.n_in,
                         self.n_out, self.weight_init, self.dist)
        return {"W": w}, {}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        if x.ndim != 2:
            raise ValueError(
                f"SequenceEmbeddingLayer takes ids [N, T], got an array "
                f"of {x.ndim} axes (a one-hot [N, V, T] goes through a "
                f"kernel-1 convolution instead)")
        with jax.named_scope("embed.ids"):
            y = jnp.take(params["W"], x.astype(jnp.int32), axis=0)
            if self.out_dtype is not None:
                y = y.astype(self.out_dtype)
        return _act.get(self.activation)(jnp.moveaxis(y, -1, 1)), state


@register_layer
@dataclass
class TokenProjectionLayer(Convolution1DLayer):
    """The token projection of a sequence model whose leaves are a
    kernel-1 ``Convolution1DLayer``'s — ``W`` [n_out, V, 1], ``b``
    [n_out], that layer's ``init`` and ``output_type`` — and whose
    ``apply`` looks at its input: integer ids ``[N, T]`` give column
    ``id`` of ``W`` plus ``b`` as ``[N, n_out, T]``, which is what the
    convolution makes of their one-hot (one column times ``W`` is that
    column; in the dtype the convolution hands on); a float ``[N, V, T]``
    (one-hot training data, soft distributions) goes through the
    convolution. A net whose input feeds this layer ``takes_ids``, as with
    ``SequenceEmbeddingLayer``; a saved configuration that names
    ``Convolution1DLayer`` here stays the one-hot net it was."""

    kernel: int = 1
    convolution_mode: str = "same"

    takes_ids = True

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        if x.ndim == 2:
            if not jnp.issubdtype(x.dtype, jnp.integer):
                raise ValueError(
                    f"TokenProjectionLayer takes integer ids [N, T] or a "
                    f"float [N, V, T], got {x.dtype} [N, T]")
            # the one-hot the host used to build and upload, made inside
            # the program in the leaves' dtype: XLA feeds the comparison
            # to the product as its operand. A column gather of W
            # [E, V, 1] copies all of W transposed first, every dispatch:
            # 0.95 ms against 0.42 for 32 decode rows on a v5e (PERF.md,
            # PR 32), so the product is the one form
            with jax.named_scope("embed.ids"):
                x = jax.nn.one_hot(x, params["W"].shape[1], axis=1,
                                   dtype=params["W"].dtype)
        return super().apply(params, x, state, train=train, rng=rng,
                             mask=mask)


@register_layer
@dataclass
class LastStepOutputLayer(RnnOutputLayer):
    """``RnnOutputLayer`` whose STREAMING form answers for the chunk's
    last position only: ``[N, V]`` out of ``rnn_time_step``, the
    distribution that follows the chunk, and no ``[N, V, T]`` block of
    which a prime reads one column. The training forward and the loss
    are ``RnnOutputLayer``'s, over every position. (A left-padded
    chunk's last position is always a real token.)"""

    supports_streaming = True
    last_step_only = True

    def apply(self, params, x, state, *, train=False, rng=None, mask=None,
              stream=False, pad_left=None):
        if not stream:
            return super().apply(params, x, state, train=train, rng=rng,
                                 mask=mask)
        with jax.named_scope("head.last"):
            y, _ = super().apply(params, x[:, :, -1:], state)
        return y[:, :, 0], state


def narrows_to_last(layer) -> bool:
    """Whether a streaming call whose caller reads the chunk's last
    position only (``rnn_time_step(last_only=True)``) may hand `layer`
    the last position of its input: an ``RnnOutputLayer`` answers
    position t from position t alone. A ``LastStepOutputLayer`` has
    narrowed itself already and is left as it is."""
    return isinstance(layer, RnnOutputLayer) and not layer.last_step_only


def last_position(y):
    """``[N, C]`` of an output over a time axis ``[N, C, T]``: its last
    position. An output that has no time axis is returned as it is."""
    return y[:, :, -1] if y.ndim == 3 else y


@register_layer
@dataclass
class LatentAttentionLayer(FeedForwardLayerConf):
    """Multi-head latent attention with learned sparse selection, over
    [N,F,T] (DeepSeek-V2's MLA, arXiv:2405.04434, with DeepSeek-V3.2's
    indexer inside it: one layer, because the indexer reads the query
    latent).

    ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` -> H x (nope | rope);
    ``[c_kv | k_r] = x W_kva``, ``c_kv <- RMSNorm(c_kv)``; the rope parts
    are rotated (interleaved pairs, YaRN frequencies). What a token leaves
    behind is ``c_kv`` (``kv_lora_rank``), the one rotated key ``k_r``
    shared by all heads, and the index key ``k^I`` — the three cache
    leaves, ``kv_c`` / ``kv_r`` / ``kv_i`` [N, L, .], which
    ``paged_leaves()`` declares to the serving engine's page pool.

    Selection: ``q^I = c_q W_iq`` (Hi x Di), ``k^I = LayerNorm(x W_ik)``,
    the first ``qk_rope_head_dim`` dims of both rotated (half-split
    pairs), ``w = x W_iw Hi^-1/2 Di^-1/2``; query t scores every earlier
    position ``I(t, s) = sum_j w_tj relu(q^I_tj . k^I_s)`` and attends the
    ``min(index_topk, t + 1)`` of highest I, ties to the lower index.

    Two forms of one function. PER-HEAD (training forward, dense
    streaming, so the serving engine's prefill): ``[k_nope | v] = c_kv
    W_kvb`` for every cached position, queries in blocks of
    ``sparse_latent.QUERY_BLOCK``, each block's scores against all cache
    slots masked to its rows' selected sets. ABSORBED (paged decode, a page table in
    the state): ``q' = q_nope W_UK`` against the gathered ``c_kv`` of the
    row's selected positions only, ``o = (p . c_kv) W_UV``; the index keys
    of the row's whole context are scored every step, read from the pool
    through the table.

    Streaming: ``cache_length`` > 0; scalar ``kv_pos`` with optional
    ``pad_left`` (packed accounting, as ``SelfAttentionLayer``), or the
    engine's paged view (per-row ``kv_pos``, ``kv_page_table``). A
    per-row rewind without a page table is not implemented. Either
    streaming form adds to ``attn_stats`` (int32, in its state) the cache
    positions whose attention scores the call computed, whatever the
    selection kept of them: the form is chosen here, so it is counted
    here (the serving engine's ``health()["sparse_attn"]``).
    """

    n_heads: int = 4
    q_lora_rank: int = 32
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    index_n_heads: int = 2
    index_head_dim: int = 16
    index_topk: int = 16
    eps: float = 1e-6
    rope_theta: float = 10000.0
    #: YaRN (``rope_factor`` 1.0 = plain rope): see sparse_latent.py
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 0.0
    cache_length: int = 0

    supports_streaming = True
    causal = True

    # -- what the layer is -------------------------------------------------
    def output_type(self, it):
        if it.kind != "rnn":
            raise ValueError("LatentAttentionLayer needs RNN input [N,F,T]")
        return InputType.recurrent(self.n_out or it.size, it.timesteps)

    def paged_leaves(self):
        return (PagedLeaf("kv_c", (self.kv_lora_rank,), 0),
                PagedLeaf("kv_r", (self.qk_rope_head_dim,), 0),
                PagedLeaf("kv_i", (self.index_head_dim,), 0))

    def stream_counters(self):
        return _selection_counters(self)

    def paged_read_tokens(self) -> Dict[str, int]:
        """Tokens of each leaf one row's paged decode reads (the serving
        engine's modeled KV traffic): the whole context's index keys,
        the selected positions' latents and rotated keys."""
        top = min(self.index_topk, self.cache_length)
        return {"kv_c": top, "kv_r": top, "kv_i": self.cache_length}

    #: how a paged decode reads the selected positions
    #: (``SelfAttentionLayer.selected_read``): it gathers them
    selected_read = "gathered"

    def _query_groups(self, t: int, slots: int, aligned: bool):
        """``sparse_latent.query_groups`` in blocks of ``QUERY_BLOCK``."""
        return _sl.query_groups(t, slots, aligned, _sl.QUERY_BLOCK)

    @property
    def softmax_scale(self) -> float:
        m = 1.0
        if self.rope_factor > 1.0:
            m = 0.1 * self.rope_mscale_all_dim * np.log(self.rope_factor) \
                + 1.0
        return float((self.qk_nope_head_dim + self.qk_rope_head_dim)
                     ** -0.5 * m * m)

    def _inv_freq(self):
        return _sl.yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta,
                             self.rope_factor, self.rope_original_max,
                             self.rope_beta_fast, self.rope_beta_slow)

    def init(self, key, it):
        if self.n_in is None:
            self.n_in = it.size
        if self.n_out is None:
            self.n_out = self.n_in
        dr = self.qk_rope_head_dim
        if dr % 2 or dr > self.index_head_dim:
            raise ValueError(
                f"qk_rope_head_dim {dr} must be even and at most "
                f"index_head_dim {self.index_head_dim} (the index key's "
                f"first rope dims are rotated)")
        h, e = self.n_heads, self.n_in
        shapes = {
            "Wqa": (e, self.q_lora_rank),
            "Wqb": (self.q_lora_rank,
                    h * (self.qk_nope_head_dim + dr)),
            "Wkva": (e, self.kv_lora_rank + dr),
            "Wkvb": (self.kv_lora_rank,
                     h * (self.qk_nope_head_dim + self.v_head_dim)),
            "Wo": (h * self.v_head_dim, self.n_out),
            "Wiq": (self.q_lora_rank,
                    self.index_n_heads * self.index_head_dim),
            "Wik": (e, self.index_head_dim),
            "Wiw": (e, self.index_n_heads)}
        keys = jax.random.split(key, len(shapes))
        p = {name: init_weights(k, s, s[0], s[1], self.weight_init,
                                self.dist)
             for k, (name, s) in zip(keys, shapes.items())}
        p["q_gamma"] = jnp.ones((self.q_lora_rank,), jnp.float32)
        p["kv_gamma"] = jnp.ones((self.kv_lora_rank,), jnp.float32)
        p["ik_gamma"] = jnp.ones((self.index_head_dim,), jnp.float32)
        p["ik_beta"] = jnp.zeros((self.index_head_dim,), jnp.float32)
        return p, {}

    # -- the forward -------------------------------------------------------
    def apply(self, params, x, state, *, train=False, rng=None, mask=None,
              stream=False, pad_left=None):
        if pad_left is not None and not stream:
            raise ValueError("pad_left is only meaningful for streaming")
        x = self.maybe_dropout_input(x, train, rng)
        xt = jnp.moveaxis(x, 1, 2)                              # [N,T,F]
        proj = self._project(params, xt)
        if not stream:
            n, t = xt.shape[:2]
            q_pos = jnp.arange(t, dtype=jnp.int32)[None]
            keys = self._rotate_keys(proj, q_pos)
            q = self._rotate_queries(proj, q_pos)
            key_valid = None if mask is None else \
                jnp.asarray(mask).reshape(n, t).astype(bool)
            o, _ = self._attend_per_head(params, q, q_pos, keys, key_valid,
                                         aligned=True)
        elif state.get("kv_page_table") is not None:
            if mask is not None or pad_left is not None:
                raise ValueError(
                    "direct paged decode is packed/maskless (the "
                    "engine's decode dispatch shape)")
            o, state = self._stream_paged(params, proj, state)
        else:
            o, state = self._stream_dense(params, proj, state, mask,
                                          pad_left)
        with jax.named_scope("mla.project"):
            y = (o @ params["Wo"]).astype(x.dtype)
        return _act.get(self.activation)(jnp.moveaxis(y, 2, 1)), state

    def _project(self, p, xt):
        """Everything a chunk's tokens give before positions enter: the
        query parts, the cache leaves unrotated, the indexer's parts."""
        n, t, _ = xt.shape
        h, dn, dr = self.n_heads, self.qk_nope_head_dim, \
            self.qk_rope_head_dim
        with jax.named_scope("mla.project"):
            cq = _rms_norm(xt @ p["Wqa"], p["q_gamma"], self.eps)
            q = (cq @ p["Wqb"]).reshape(n, t, h, dn + dr)
            kva = xt @ p["Wkva"]
            ckv = _rms_norm(kva[..., :self.kv_lora_rank], p["kv_gamma"],
                            self.eps)
        with jax.named_scope("dsa.index"):
            qi = (cq @ p["Wiq"]).reshape(n, t, self.index_n_heads,
                                         self.index_head_dim)
            ki = _sl.layer_norm(xt @ p["Wik"], p["ik_gamma"], p["ik_beta"],
                                self.eps)
            w = (xt @ p["Wiw"]) * (self.index_n_heads ** -0.5
                                   * self.index_head_dim ** -0.5)
        return {"q": q, "ckv": ckv, "kr": kva[..., self.kv_lora_rank:],
                "qi": qi, "ki": ki, "w": w.astype(xt.dtype)}

    def _tables(self, positions):
        return _sl.rope_tables(jnp.maximum(positions, 0), self._inv_freq())

    def _rotate_keys(self, proj, positions):
        """(c_kv, rotated k_r, rotated k^I) of the chunk, the three
        cache leaves in ``paged_leaves()`` order. positions [N|1, T]."""
        dr = self.qk_rope_head_dim
        cos, sin = self._tables(positions)
        ki = proj["ki"]
        ki = jnp.concatenate([_sl.rope_half(ki[..., :dr], cos, sin),
                              ki[..., dr:]], -1)
        return proj["ckv"], _sl.rope_interleaved(proj["kr"], cos, sin), ki

    def _rotate_queries(self, proj, positions):
        """(q_nope, rotated q_rope, rotated q^I, w)."""
        dn, dr = self.qk_nope_head_dim, self.qk_rope_head_dim
        cos, sin = self._tables(positions)
        q, qi = proj["q"], proj["qi"]
        qi = jnp.concatenate([_sl.rope_half(qi[..., :dr], cos, sin),
                              qi[..., dr:]], -1)
        return (q[..., :dn], _sl.rope_interleaved(q[..., dn:], cos, sin), qi,
                proj["w"])

    def index_selection(self, params, x):
        """What the indexer makes of a whole sequence x [N,F,T] (no
        cache): ``(scores [N,T,T] float32, selected [N,T,T] bool)``, the
        sets the training forward attends. For tests and diagnostics."""
        proj = self._project(params, jnp.moveaxis(x, 1, 2))
        t = x.shape[2]
        pos = jnp.arange(t, dtype=jnp.int32)
        _, _, qi, w = self._rotate_queries(proj, pos[None])
        scores = _sl.index_scores(qi, self._rotate_keys(proj, pos[None])[2], w)
        causal = (pos[None, :] <= pos[:, None])[None]
        return scores, _sl.top_k_mask(scores, jnp.broadcast_to(
            causal, scores.shape), self.index_topk)

    def _attend_per_head(self, p, q, q_pos, keys, key_valid=None,
                         aligned=False):
        """Per-head attention of a chunk's queries against ``keys`` =
        (c_kv, k_r, k^I), each [N, L, .]; query t may see the slots
        ``<= q_pos[t]`` (q_pos [N|1, T]) that ``key_valid`` [N|1, L]
        admits. Queries go in blocks of ``QUERY_BLOCK``: a block's
        [N, H, B, L] scores exist at once, the chunk's never. ``aligned``
        says the keys are the chunk's own, slot for query (q_pos[t] = t):
        the blocks then go in up to four groups, each against the prefix
        of the keys that ends where its last query stands, so that a
        block's scores span on average 5/8 of the chunk and not all of
        it. Returns ``([N, T, H * v_head_dim], scored)``, ``scored`` the
        (query row, slot) pairs whose attention scores it computed."""
        q_nope, q_rope, qi, w = q
        ckv, kr, ki = keys
        n, t = q_nope.shape[:2]
        L = ckv.shape[1]
        h, dn, dv = self.n_heads, self.qk_nope_head_dim, self.v_head_dim
        with jax.named_scope("mla.project"):
            kv = (ckv @ p["Wkvb"]).reshape(n, L, h, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        slot = jnp.arange(L, dtype=jnp.int32)
        q_pos = jnp.broadcast_to(q_pos, (n, t))
        b, pad, groups = self._query_groups(t, L, aligned)

        def blocks(a):
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            a = a.reshape((n, (t + pad) // b, b) + a.shape[2:])
            return jnp.moveaxis(a, 1, 0)

        def attend(args, seen):
            """One block of queries against the first ``seen`` slots."""
            qn, qr, qib, wb, pos = args
            valid = slot[None, None, :seen] <= pos[..., None]   # [N,B,s]
            if key_valid is not None:
                valid = valid & key_valid[:, None, :seen]
            with jax.named_scope("dsa.index"):
                scores = _sl.index_scores(qib, ki[:, :seen], wb)
            with jax.named_scope("dsa.select"):
                sel = _sl.top_k_mask(scores, valid, self.index_topk)
            with jax.named_scope("mla.attend"):
                s = jnp.einsum("nqhd,nshd->nhqs", qn, k_nope[:, :seen],
                               preferred_element_type=jnp.float32) \
                    + jnp.einsum("nqhd,nsd->nhqs", qr, kr[:, :seen],
                                 preferred_element_type=jnp.float32)
                s = jnp.where(sel[:, None], s * self.softmax_scale, _sl.MASKED)
                a = jax.nn.softmax(s, axis=-1).astype(v.dtype)
                return jnp.einsum("nhqs,nshd->nqhd", a, v[:, :seen],
                                  preferred_element_type=jnp.float32
                                  ).astype(v.dtype)

        parts = tuple(blocks(a) for a in (q_nope, q_rope, qi, w, q_pos))
        out, at = [], 0
        for per, seen in groups:
            out.append(jax.lax.map(
                lambda args, seen=seen: attend(args, seen),
                tuple(a[at:at + per] for a in parts)))
            at += per
        o = jnp.moveaxis(jnp.concatenate(out), 0, 1)
        return (o.reshape(n, t + pad, h * dv)[:, :t],
                n * sum(per * b * seen for per, seen in groups))

    def _stream_dense(self, p, proj, state, mask, pad_left):
        """Dense streaming: append the chunk's three leaves to the
        [N, L, .] caches at a shared scalar position (left pads dropped,
        ``SelfAttentionLayer._stream_attend``'s packed accounting), then
        per-head attention: of a stream's FIRST chunk (no cache in the
        state yet: a fresh prime) against the chunk's own keys, slot for
        query, the pads masked; of a later chunk against the whole
        cache."""
        if self.cache_length <= 0:
            raise ValueError(
                "LatentAttentionLayer streaming needs cache_length > 0")
        if mask is not None:
            raise ValueError(
                "LatentAttentionLayer streams maskless chunks (left-pad "
                "with pad_left, or stream rows of equal length)")
        n, t = proj["q"].shape[:2]
        L = self.cache_length
        leaves = self.paged_leaves()
        pos = state.get("kv_pos")
        if pos is None:
            pos = jnp.zeros((), jnp.int32)
        if getattr(pos, "ndim", 0) >= 1:
            raise ValueError(
                "LatentAttentionLayer streams per-row positions only "
                "behind a page table (the serving engine's paged decode)")
        if pad_left is not None:
            m0 = jnp.arange(t) >= pad_left
            cum = jnp.cumsum(m0.astype(pos.dtype))
            q_pos = pos + cum - 1                    # pads: pos - 1
            slots = jnp.where(m0, q_pos, L)          # pads: dropped
            n_new = cum[-1]
        else:
            q_pos = pos + jnp.arange(t, dtype=pos.dtype)
            slots, n_new = q_pos, t
        new = self._rotate_keys(proj, q_pos[None])
        fresh = all(state.get(leaf.key) is None for leaf in leaves)
        caches = []
        for leaf, chunk in zip(leaves, new):
            c = state.get(leaf.key)
            if c is None:
                c = jnp.zeros(leaf.shape(n, L), chunk.dtype)
            caches.append(c.at[:, slots].set(chunk.astype(c.dtype),
                                             mode="drop"))
        q = self._rotate_queries(proj, q_pos[None])
        if fresh and state.get("kv_pos") is None:
            real = None if pad_left is None else m0[None]
            o, scored = self._attend_per_head(
                p, q, jnp.arange(t, dtype=jnp.int32)[None], new, real,
                aligned=True)
        else:
            o, scored = self._attend_per_head(p, q, q_pos[None],
                                              tuple(caches))
        out = {**_attended(state, scored), "kv_pos": pos + n_new}
        out.update({leaf.key: c for leaf, c in zip(leaves, caches)})
        return o, out

    def _stream_paged(self, p, proj, state):
        """Direct paged decode in the absorbed form: the chunk's leaves
        append at each row's (page, offset); every row scores the index
        keys of its whole context through the table, keeps the
        ``index_topk`` best, gathers those positions' ``c_kv`` and ``k_r``
        from the pool and attends them and nothing else. Appends past a
        row's allocation or capacity land on the null page 0."""
        leaves = self.paged_leaves()
        pools = [state[leaf.page_key] for leaf in leaves]
        table = state["kv_page_table"]
        pos = state.get("kv_pos")
        if pos is None or getattr(pos, "ndim", 0) < 1:
            raise ValueError("direct paged decode needs the per-row "
                             "kv_pos vector (the engine arena's)")
        n, t = proj["q"].shape[:2]
        L, ps, n_blk = self.cache_length, pools[0].shape[1], table.shape[1]
        h, dn, dv = self.n_heads, self.qk_nope_head_dim, self.v_head_dim
        q_pos = pos[:, None] + jnp.arange(t, dtype=pos.dtype)      # [N,T]
        new = self._rotate_keys(proj, q_pos)
        blk = jnp.clip(q_pos // ps, 0, n_blk - 1).astype(jnp.int32)
        page = jnp.where(q_pos < L,
                         jnp.take_along_axis(table, blk, axis=1), 0)
        off = (q_pos % ps).astype(jnp.int32)
        pools = [pool.at[page, off].set(chunk.astype(pool.dtype))
                 for pool, chunk in zip(pools, new)]
        pool_c, pool_r, pool_i = pools
        q_nope, q_rope, qi, w = self._rotate_queries(proj, q_pos)
        with jax.named_scope("dsa.index"):
            ki = pool_i[table].reshape(n, n_blk * ps, -1)[:, :L]
            scores = _sl.index_scores(qi, ki, w)                     # [N,T,L]
        with jax.named_scope("dsa.select"):
            live = jnp.arange(L)[None, None, :] <= q_pos[..., None]
            top = min(self.index_topk, L)
            best, idx = jax.lax.top_k(
                jnp.where(live, scores, -jnp.inf), top)          # [N,T,k]
            chosen = best > -jnp.inf
        with jax.named_scope("mla.attend"):
            rows = jnp.arange(n)[:, None, None]
            sel_page = table[rows, jnp.minimum(idx // ps, n_blk - 1)]
            c = pool_c[sel_page, idx % ps]                     # [N,T,k,kl]
            r = pool_r[sel_page, idx % ps]                     # [N,T,k,dr]
            w_kvb = p["Wkvb"].reshape(self.kv_lora_rank, h, dn + dv)
            q_lat = jnp.einsum("nqhd,chd->nqhc", q_nope, w_kvb[..., :dn],
                               preferred_element_type=jnp.float32
                               ).astype(c.dtype)
            s = jnp.einsum("nqhc,nqkc->nhqk", q_lat, c,
                           preferred_element_type=jnp.float32) \
                + jnp.einsum("nqhd,nqkd->nhqk", q_rope, r,
                             preferred_element_type=jnp.float32)
            s = jnp.where(chosen[:, None], s * self.softmax_scale, _sl.MASKED)
            a = jax.nn.softmax(s, axis=-1).astype(c.dtype)
            o_lat = jnp.einsum("nhqk,nqkc->nqhc", a, c,
                               preferred_element_type=jnp.float32
                               ).astype(c.dtype)
            o = jnp.einsum("nqhc,chd->nqhd", o_lat, w_kvb[..., dn:],
                           preferred_element_type=jnp.float32
                           ).astype(c.dtype)
        out = {**_attended(state, n * t * top), "kv_pos": pos + t}
        out.update({leaf.page_key: pool
                    for leaf, pool in zip(leaves, pools)})
        return o.reshape(n, t, h * dv), out


@register_layer
@dataclass
class RoutedExpertsLayer(FeedForwardLayerConf):
    """A layer of routed experts that is told which experts it holds:
    the router is the model's (``router_experts`` outputs; ``scoring``
    ``"sigmoid"``, the default: grouped sigmoid choice with a selection
    bias ``br``; ``"softmax"``: softmax over all outputs, no groups and
    no bias leaf; ``top_k`` a token, the chosen gates renormalised where
    ``norm_topk`` and scaled by ``scale``; nn/layers/routed_experts.py),
    the expert matrices are
    those of ``held`` = (first, count) alone, and the layer gives
    ``shared(x) + sum over held i of g_i E_i(x)``, every expert a gated
    (SiLU) feed-forward ``hidden`` wide. What the experts held elsewhere
    would add is theirs to add: on one device the layer runs with no
    exchange, and with ``held`` = (0, router_experts) it is the whole
    layer.

    Streaming (``rnn_time_step``) computes the held part by the grouped
    product, tile by tile over tokens laid out by expert, dropping no
    token; left pads route nowhere. It carries ``moe_stats`` int32 [6] in
    its state — tokens routed, (token, held expert) pairs, rows the
    grouped product computed (whole tiles), the fullest expert's load in
    one call (a maximum, the others sums), and over the calls of one
    position a row (decode steps) how many there were and how many held
    experts got at least one token in them (the expert weights such a
    step cannot do without, whatever computes the product) — which the
    serving engine reads in ``health()["experts"]``. The training forward
    runs every held expert over every token (differentiable)."""

    hidden: int = 64
    router_experts: int = 8
    held: Any = (0, 8)
    top_k: int = 2
    groups: int = 1
    top_groups: int = 1
    scale: float = 1.0
    shared: int = 1
    scoring: str = "sigmoid"
    norm_topk: bool = True

    supports_streaming = True

    def __post_init__(self):
        if self.scoring not in ("sigmoid", "softmax") or (
                self.scoring == "softmax" and self.groups != 1):
            raise ValueError(
                f"scoring is 'sigmoid' or 'softmax' (which has no "
                f"groups), got {self.scoring!r} with {self.groups} groups")
        self.held = (int(self.held[0]), int(self.held[1]))
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.router_experts):
            raise ValueError(f"held {self.held} is no range of the "
                             f"router's {self.router_experts} experts")
        if self.router_experts % self.groups or \
                self.top_groups > self.groups:
            raise ValueError("groups must divide router_experts, and "
                             "top_groups cannot pass groups")

    def stream_counters(self):
        return StreamCounters(
            "moe_stats", "experts",
            ("tokens", "held_pairs", "rows_computed", "max_expert_load",
             "decode_calls", "decode_experts_touched"),
            maxima=("max_expert_load",))

    def output_type(self, it):
        if it.kind != "rnn":
            raise ValueError("RoutedExpertsLayer needs RNN input [N,F,T]")
        return InputType.recurrent(self.n_out or it.size, it.timesteps)

    def init(self, key, it):
        if self.n_in is None:
            self.n_in = it.size
        if self.n_out is None:
            self.n_out = self.n_in
        f, i, g = self.n_in, self.hidden, self.held[1]
        ks = jax.random.split(key, 7)

        def w(k, shape, a, b):
            return init_weights(k, shape, a, b, self.weight_init, self.dist)

        p = {"Wr": w(ks[0], (f, self.router_experts), f,
                     self.router_experts)}
        if self.scoring == "sigmoid":
            p["br"] = jnp.zeros((self.router_experts,), jnp.float32)
        p.update(Wg=w(ks[1], (g, f, i), f, i), Wu=w(ks[2], (g, f, i), f, i),
                 Wd=w(ks[3], (g, i, self.n_out), i, self.n_out))
        if self.shared:
            s = i * self.shared
            p.update(Ws_g=w(ks[4], (f, s), f, s), Ws_u=w(ks[5], (f, s), f, s),
                     Ws_d=w(ks[6], (s, self.n_out), s, self.n_out))
        return p, {}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None,
              stream=False, pad_left=None):
        x = self.maybe_dropout_input(x, train, rng)
        n, f, t = x.shape
        flat = jnp.moveaxis(x, 1, 2).reshape(n * t, f)
        valid = None
        if pad_left is not None:
            valid = jnp.broadcast_to(jnp.arange(t) >= pad_left, (n, t))
        elif mask is not None:
            valid = jnp.asarray(mask).reshape(n, t).astype(bool)
        first, count = self.held
        with jax.named_scope("moe.route"):
            gates = _re.router_gates(
                flat, params["Wr"], params.get("br"), groups=self.groups,
                top_groups=self.top_groups, top_k=self.top_k,
                scale=self.scale, scoring=self.scoring,
                norm_topk=self.norm_topk)[:, first:first + count]
            if valid is not None:
                gates = jnp.where(valid.reshape(-1, 1), gates, 0.0)
        with jax.named_scope("moe.experts"):
            if stream:
                tile = min(_re.GROUP_TILE,
                           max(16, 1 << (n * t - 1).bit_length()))
                y, stats = _re.grouped_experts(
                    flat, gates, params["Wg"], params["Wu"], params["Wd"],
                    tile=tile, max_per_token=self.top_k)
                tokens = n * t if valid is None else jnp.sum(valid)
                prev = state.get("moe_stats")
                if prev is None:
                    prev = jnp.zeros((6,), jnp.int32)
                # a call of one position a row is a decode step: the held
                # experts with a token in it are the weights it needs
                decode = int(t == 1)
                touched = jnp.sum(jnp.any(gates > 0, axis=0))
                state = {**state, "moe_stats": jnp.stack([
                    prev[0] + tokens, prev[1] + stats[0],
                    prev[2] + stats[1],
                    jnp.maximum(prev[3], stats[2]), prev[4] + decode,
                    prev[5] + decode * touched]).astype(jnp.int32)}
            else:
                y = _re.dense_experts(flat, gates, params["Wg"],
                                      params["Wu"], params["Wd"])
        if self.shared:
            with jax.named_scope("moe.shared"):
                y = y + _re.gated_ffn(flat, params["Ws_g"], params["Ws_u"],
                                      params["Ws_d"])
        y = jnp.moveaxis(y.astype(x.dtype).reshape(n, t, -1), 2, 1)
        return _act.get(self.activation)(y), state


@register_layer
@dataclass
class GatedDeltaNetLayer(FeedForwardLayerConf):
    """Linear attention by the gated delta rule over RNN-format input
    [N,F,T] (Yang, Kautz & Hatamizadeh 2024; the arithmetic and its two
    forms are nn/layers/linear_attention.py). ``n_heads`` heads, keys and
    queries ``key_dim`` wide, values ``value_dim``. For a token ``x``:

        q, k, v, z = x Wq, x Wk, x Wv, x Wz;    a, b = x Wa, x Wb
        (q, k, v) <- silu(causal depthwise conv, width conv_kernel)
        q <- q / |q| * key_dim^-1/2,  k <- k / |k|      (per head)
        beta = (2 if allow_neg_eigval else 1) * sigmoid(b)
        alpha = exp(-exp(A_log) * softplus(a + dt_bias))
        S <- alpha S + beta k (v - alpha S^T k)^T,   o = S^T q
        y = RMSNorm(o) * norm * silu(z)   (per head);   out = y Wo

    No biases. Leaves: ``Wq Wk`` [F, H*dk], ``Wv Wz`` [F, H*dv], ``Wa
    Wb`` [F, H], ``Wo`` [H*dv, n_out], ``conv`` [K, H*(2dk+dv)],
    ``A_log dt_bias`` [H], ``norm`` [dv]. Products take operands in the
    input's dtype and accumulate in float32; decays, beta, both norms'
    statistics and the state are float32.

    One form a shape of input: a chunk one position wide takes the
    one-step update, a wider one the chunked scan (whole chunks of
    ``linear_attention.CHUNK``, padded on the left). A masked position —
    ``pad_left`` of a packed prime, or a key ``mask`` — has alpha 1 and
    beta 0, feeds the convolution a zero and leaves its tail where it
    was, so a left-padded bucket primes to exactly the state of the
    unpadded prompt; with a ``mask`` the real positions are first moved
    to the right of their row, in order, and the outputs moved back.

    Streaming (``rnn_time_step``) carries the state a STREAM keeps and
    not a token (``carries_recurrent_state``; declared by
    ``slot_leaves()``): ``gdn_s`` [N, H, dk, dv] float32 and ``gdn_conv``
    [N, K-1, H*(2dk+dv)], the convolution's last inputs. It cannot be
    rewound, nor taken up at a prefix. ``gdn_stats`` int32 [3] counts
    what the forms computed (``stream_counters()``): positions the
    chunked scan went over, left pads and the fill to whole chunks
    included; the real ones among them; one-step updates (rows)."""

    n_heads: int = 4
    key_dim: int = 32
    value_dim: int = 64
    conv_kernel: int = 4
    allow_neg_eigval: bool = True
    eps: float = 1e-6
    l2_eps: float = 1e-6

    supports_streaming = True
    carries_recurrent_state = True

    @property
    def conv_channels(self) -> int:
        return self.n_heads * (2 * self.key_dim + self.value_dim)

    def output_type(self, it):
        if it.kind != "rnn":
            raise ValueError("GatedDeltaNetLayer needs RNN input [N,F,T]")
        return InputType.recurrent(self.n_out or it.size, it.timesteps)

    def slot_leaves(self):
        return (SlotLeaf("gdn_s", (self.n_heads, self.key_dim,
                                   self.value_dim), "float32"),
                SlotLeaf("gdn_conv", (self.conv_kernel - 1,
                                      self.conv_channels)))

    def stream_counters(self):
        return StreamCounters(
            "gdn_stats", "linear_attn",
            ("scanned_positions", "fed_positions", "state_updates"))

    def init(self, key, it):
        if self.n_in is None:
            self.n_in = it.size
        if self.n_out is None:
            self.n_out = self.n_in
        f, h = self.n_in, self.n_heads
        qk, vz = h * self.key_dim, h * self.value_dim
        ks = jax.random.split(key, 10)

        def w(k, a, b):
            return init_weights(k, (a, b), a, b, self.weight_init,
                                self.dist)

        # decay rates and time steps as the rule's authors draw them: A
        # uniform in (0, 16), a time step log-uniform in (1e-3, 0.1)
        dt = jnp.exp(jax.random.uniform(
            ks[9], (h,), jnp.float32, np.log(1e-3), np.log(0.1)))
        return {
            "Wq": w(ks[0], f, qk), "Wk": w(ks[1], f, qk),
            "Wv": w(ks[2], f, vz), "Wz": w(ks[3], f, vz),
            "Wa": w(ks[4], f, h), "Wb": w(ks[5], f, h),
            "Wo": w(ks[6], vz, self.n_out),
            "conv": jax.random.normal(
                ks[7], (self.conv_kernel, self.conv_channels),
                jnp.float32) / np.sqrt(self.conv_kernel),
            "A_log": jnp.log(jax.random.uniform(
                ks[8], (h,), jnp.float32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "norm": jnp.ones((self.value_dim,), jnp.float32)}, {}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None,
              stream=False, pad_left=None):
        if pad_left is not None and not stream:
            raise ValueError("pad_left is only meaningful for streaming")
        x = self.maybe_dropout_input(x, train, rng)
        n, _, t = x.shape
        h, dk, dv = self.n_heads, self.key_dim, self.value_dim
        cd = x.dtype
        xt = jnp.moveaxis(x, 1, 2)                             # [N, T, F]
        valid = order = None
        if pad_left is not None:
            if mask is not None:
                raise ValueError("pad_left and mask are mutually "
                                 "exclusive in streaming")
            valid = jnp.broadcast_to(jnp.arange(t) >= pad_left, (n, t))
        elif mask is not None:
            # real positions to the right of their row, in order: every
            # row is then left-padded, whatever the mask's shape was
            valid = jnp.asarray(mask).reshape(n, t) > 0
            order = jnp.argsort(valid, axis=1, stable=True)
            xt = jnp.take_along_axis(xt, order[..., None], axis=1)
            valid = jnp.take_along_axis(valid, order, axis=1)
        pad = (jnp.zeros((n,), jnp.int32) if valid is None
               else t - jnp.sum(valid, axis=1, dtype=jnp.int32))

        def proj(name):
            return jnp.dot(xt, params[name],
                           preferred_element_type=jnp.float32)

        with jax.named_scope("gdn.project"):
            qkv = jnp.concatenate(
                [proj("Wq"), proj("Wk"), proj("Wv")], axis=-1).astype(cd)
            z = proj("Wz").astype(cd).reshape(n, t, h, dv)
            rate = jnp.exp(params["A_log"].astype(jnp.float32))
            log_alpha = -rate * jax.nn.softplus(
                proj("Wa") + params["dt_bias"].astype(jnp.float32))
            beta = (2.0 if self.allow_neg_eigval else 1.0) \
                * jax.nn.sigmoid(proj("Wb"))
            if valid is not None:
                qkv = jnp.where(valid[..., None], qkv, 0)
                log_alpha = jnp.where(valid[..., None], log_alpha, 0.0)
                beta = jnp.where(valid[..., None], beta, 0.0)
        with jax.named_scope("gdn.conv"):
            tail = state.get("gdn_conv") if stream else None
            if tail is None:
                tail = jnp.zeros((n, self.conv_kernel - 1,
                                  self.conv_channels), cd)
            y, tail = _la.causal_conv(qkv, params["conv"], tail, pad)
            y = jax.nn.silu(y).astype(cd)
            q, k, v = jnp.split(y, [h * dk, 2 * h * dk], axis=-1)
            q = _la.l2_normalize(q.reshape(n, t, h, dk), self.l2_eps)
            q = (q.astype(jnp.float32) * dk ** -0.5).astype(cd)
            k = _la.l2_normalize(k.reshape(n, t, h, dk), self.l2_eps)
            v = v.reshape(n, t, h, dv)
        s0 = state.get("gdn_s") if stream else None
        if s0 is None:
            s0 = jnp.zeros((n, h, dk, dv), jnp.float32)
        if t == 1:
            with jax.named_scope("gdn.update"):
                o, s1 = _la.gdn_step(q[:, 0], k[:, 0], v[:, 0],
                                     log_alpha[:, 0], beta[:, 0], s0)
            o = o[:, None]                                 # [N, 1, H, dv]
            counts = (0, 0, n)
        else:
            with jax.named_scope("gdn.scan"):
                o, s1 = _la.gdn_chunked(
                    *(jnp.moveaxis(a, 1, 2) for a in
                      (q, k, v, log_alpha, beta)), s0)
            o = jnp.moveaxis(o, 2, 1)                      # [N, T, H, dv]
            fed = n * t if valid is None else jnp.sum(valid)
            counts = (n * (t + -t % _la.CHUNK), fed, 0)
        with jax.named_scope("gdn.gate"):
            y = _la.gated_rms_norm(o, z, params["norm"], self.eps)
            y = jnp.dot(y.astype(cd).reshape(n, t, h * dv), params["Wo"],
                        preferred_element_type=jnp.float32).astype(cd)
        if order is not None:
            y = jnp.take_along_axis(
                y, jnp.argsort(order, axis=1)[..., None], axis=1)
        if stream:
            prev = state.get("gdn_stats")
            if prev is None:
                prev = jnp.zeros((3,), jnp.int32)
            state = {**state, "gdn_s": s1, "gdn_conv": tail,
                     "gdn_stats": prev + jnp.stack(
                         [jnp.asarray(c, jnp.int32) for c in counts])}
        return _act.get(self.activation)(jnp.moveaxis(y, 1, 2)), state
