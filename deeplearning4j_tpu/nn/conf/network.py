"""Network-level configuration: builder DSL + JSON round-trip.

TPU-native equivalent of:
- NeuralNetConfiguration.Builder (deeplearning4j-nn/.../conf/
  NeuralNetConfiguration.java:570-1138): global defaults (seed, updater,
  weight init, activation, l1/l2) cascading into per-layer configs.
- MultiLayerConfiguration (MultiLayerConfiguration.java: backprop/pretrain
  flags, tbptt lengths default 20 :62, input preprocessors, toJson/fromJson).
- ComputationGraphConfiguration.GraphBuilder (ComputationGraphConfiguration.java:
  addLayer/addVertex/addInputs/setOutputs + topology validation).

The reference's workspace/cacheMode knobs are intentionally absent: XLA buffer
assignment replaces manual memory arenas on TPU (SURVEY §3.2 note).
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    LayerConf,
    BaseLayerConf,
    FeedForwardLayerConf,
    layer_from_dict,
    layer_to_dict,
)
from deeplearning4j_tpu.nn.conf.preprocessors import (
    CnnToFeedForwardPreProcessor,
    FeedForwardToCnnPreProcessor,
    Preprocessor,
    RnnToFeedForwardPreProcessor,
    preprocessor_from_dict,
    preprocessor_to_dict,
)
from deeplearning4j_tpu.nn.updater import Sgd, Updater, updater_from_dict, updater_to_dict

# layer kinds each layer family expects as input
_EXPECTS = {
    "ff": {"DenseLayer", "OutputLayer", "EmbeddingLayer", "AutoEncoder",
           "CenterLossOutputLayer", "BatchNormalization", "VariationalAutoencoder"},
    "cnn": {"ConvolutionLayer", "SubsamplingLayer", "Upsampling2DLayer",
            "ZeroPaddingLayer", "LocalResponseNormalization", "Deconvolution2DLayer",
            "Yolo2OutputLayer", "SpaceToDepthLayer"},
    "rnn": {"LSTM", "GravesLSTM", "GravesBidirectionalLSTM", "SimpleRnn",
            "RnnOutputLayer", "Convolution1DLayer", "Subsampling1DLayer",
            "LastTimeStepLayer", "ZeroPadding1DLayer", "Upsampling1DLayer",
            "TokenProjectionLayer"},
}


def _expected_kind(layer: LayerConf) -> Optional[str]:
    name = type(layer).__name__
    if name == "FrozenLayer":
        return _expected_kind(layer.layer)
    for kind, names in _EXPECTS.items():
        if name in names:
            return kind
    return None  # agnostic (Activation, Dropout, GlobalPooling handle any)


def infer_preprocessor(it: InputType, layer: LayerConf) -> Optional[Preprocessor]:
    """Auto-insert shape adapters (ref: InputTypeUtil / MultiLayerConfiguration
    setInputType → getPreProcessorForInputType)."""
    want = _expected_kind(layer)
    if want is None:
        return None
    have = "ff" if it.kind == "cnn_flat" else it.kind
    # BatchNormalization accepts both ff and cnn input natively
    if type(layer).__name__ == "BatchNormalization" and have in ("ff", "cnn"):
        return None
    if have == want:
        return None
    if it.kind == "cnn_flat" and want == "cnn":
        return FeedForwardToCnnPreProcessor(it.height, it.width, it.channels)
    if have == "cnn" and want == "ff":
        return CnnToFeedForwardPreProcessor(it.height, it.width, it.channels)
    if have == "ff" and want == "cnn":
        raise ValueError(
            "Cannot infer FeedForwardToCnn preprocessor shape automatically; "
            "add it explicitly")
    if have == "rnn" and want == "ff":
        return RnnToFeedForwardPreProcessor()
    raise ValueError(f"No automatic preprocessor from {it} to {type(layer).__name__}")


_GLOBAL_DEFAULT_FIELDS = ("activation", "weight_init", "dist", "bias_init",
                          "l1", "l2", "l1_bias", "l2_bias", "dropout")


def apply_global_defaults(layer: LayerConf, defaults: Dict[str, Any]) -> None:
    """Cascade builder-level defaults into a layer conf, DL4J-style: a global
    value applies unless the layer explicitly set the field (detected as the
    field differing from its dataclass default)."""
    cls_defaults = {}
    for f in dataclasses.fields(layer):
        if f.default is not dataclasses.MISSING:
            cls_defaults[f.name] = f.default
        elif f.default_factory is not dataclasses.MISSING:  # type: ignore
            cls_defaults[f.name] = f.default_factory()  # type: ignore
    for k, v in defaults.items():
        if v is None:
            continue
        if not hasattr(layer, k):
            continue
        if k == "activation" and not isinstance(layer, BaseLayerConf):
            continue
        if getattr(layer, k) == cls_defaults.get(k):
            setattr(layer, k, v)


def _set_cnn_data_format_fields(layers, preprocessors, fmt: str) -> None:
    """Set `data_format` on every layer/preprocessor that declares one."""
    for obj in list(layers) + list(preprocessors):
        if obj is not None and hasattr(obj, "data_format"):
            obj.data_format = fmt


@dataclass
class MultiLayerConfiguration:
    """Sequential net config (ref: MultiLayerConfiguration.java)."""

    layers: List[LayerConf] = field(default_factory=list)
    preprocessors: Dict[int, Preprocessor] = field(default_factory=dict)
    input_type: Optional[InputType] = None
    seed: int = 12345
    updater: Updater = field(default_factory=lambda: Sgd(0.1))
    backprop: bool = True
    pretrain: bool = False
    tbptt_fwd_length: int = 20  # ref default :62
    tbptt_back_length: int = 20
    tbptt: bool = False
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: float = 1.0
    dtype: str = "float32"

    # ---- shape inference ----
    def layer_input_types(self) -> List[InputType]:
        """Input type seen by each layer (after its preprocessor)."""
        if self.input_type is None:
            raise ValueError("input_type not set; call set_input_type or provide n_in")
        it = self.input_type
        out = []
        for i, layer in enumerate(self.layers):
            pre = self.preprocessors.get(i)
            if pre is not None:
                it = pre.output_type(it)
            out.append(it)
            it = layer.output_type(it)
        return out

    def output_type(self) -> InputType:
        it = self.input_type
        for i, layer in enumerate(self.layers):
            pre = self.preprocessors.get(i)
            if pre is not None:
                it = pre.output_type(it)
            it = layer.output_type(it)
        return it

    def use_cnn_data_format(self, fmt: str = "NHWC") -> "MultiLayerConfiguration":
        """Switch the INTERNAL activation layout of the CNN stack
        (performance mode; "NHWC" keeps channel work lane-aligned on TPU —
        ~10% faster ResNet-class training). The public API stays NCHW:
        inputs are [N,C,H,W], weights [O,I,kH,kW], flat feature order and
        serialized checkpoints are unchanged. Intermediate CNN activations
        (feed_forward per-layer dumps) are in `fmt` when enabled."""
        _set_cnn_data_format_fields(self.layers, self.preprocessors.values(),
                                    fmt)
        if fmt == "NHWC" and self.input_type is not None and \
                self.input_type.kind == "cnn":
            entry = self.preprocessors.get(0)
            if entry is None:
                it = self.input_type
                self.preprocessors[0] = FeedForwardToCnnPreProcessor(
                    height=it.height, width=it.width, channels=it.channels,
                    data_format=fmt)
            elif isinstance(entry, CnnToFeedForwardPreProcessor):
                # entry flatten consumes the PUBLIC NCHW input directly —
                # it must not un-transpose an NHWC tensor it never sees
                entry.data_format = "NCHW"
        return self

    # ---- serde ----
    def to_dict(self) -> dict:
        return {
            "layers": [layer_to_dict(l) for l in self.layers],
            "preprocessors": {str(k): preprocessor_to_dict(v)
                              for k, v in self.preprocessors.items()},
            "input_type": self.input_type.to_dict() if self.input_type else None,
            "seed": self.seed,
            "updater": updater_to_dict(self.updater),
            "backprop": self.backprop,
            "pretrain": self.pretrain,
            "tbptt": self.tbptt,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
            "gradient_normalization": self.gradient_normalization,
            "gradient_normalization_threshold": self.gradient_normalization_threshold,
            "dtype": self.dtype,
        }

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2)

    @staticmethod
    def from_dict(d: dict) -> "MultiLayerConfiguration":
        conf = MultiLayerConfiguration(
            layers=[layer_from_dict(l) for l in d["layers"]],
            preprocessors={int(k): preprocessor_from_dict(v)
                           for k, v in d.get("preprocessors", {}).items()},
            input_type=InputType.from_dict(d["input_type"]) if d.get("input_type") else None,
            seed=d.get("seed", 12345),
            updater=updater_from_dict(d["updater"]) if d.get("updater") else Sgd(0.1),
            backprop=d.get("backprop", True),
            pretrain=d.get("pretrain", False),
            tbptt=d.get("tbptt", False),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20),
            gradient_normalization=d.get("gradient_normalization"),
            gradient_normalization_threshold=d.get("gradient_normalization_threshold", 1.0),
            dtype=d.get("dtype", "float32"),
        )
        return conf

    @staticmethod
    def from_json(s: str) -> "MultiLayerConfiguration":
        return MultiLayerConfiguration.from_dict(json.loads(s))


class ListBuilder:
    """Sequential-net builder (ref: NeuralNetConfiguration.ListBuilder)."""

    def __init__(self, parent: "NeuralNetConfiguration.Builder"):
        self._parent = parent
        self._layers: List[LayerConf] = []
        self._preprocessors: Dict[int, Preprocessor] = {}
        self._input_type: Optional[InputType] = None
        self._backprop = True
        self._pretrain = False
        self._tbptt = False
        self._tbptt_fwd = 20
        self._tbptt_back = 20

    def layer(self, *args):
        """layer(conf) or layer(index, conf)."""
        conf = args[-1]
        self._layers.append(conf)
        return self

    def input_preprocessor(self, index: int, pre: Preprocessor):
        self._preprocessors[int(index)] = pre
        return self

    def set_input_type(self, it: InputType):
        self._input_type = it
        return self

    def backprop(self, b: bool):
        self._backprop = b
        return self

    def pretrain(self, p: bool):
        self._pretrain = p
        return self

    def tbptt(self, fwd: int = 20, back: Optional[int] = None):
        self._tbptt = True
        self._tbptt_fwd = fwd
        self._tbptt_back = back if back is not None else fwd
        return self

    def build(self) -> MultiLayerConfiguration:
        g = self._parent
        for layer in self._layers:
            apply_global_defaults(layer, g._defaults)
        conf = MultiLayerConfiguration(
            layers=self._layers,
            preprocessors=dict(self._preprocessors),
            input_type=self._input_type,
            seed=g._seed,
            updater=g._updater,
            backprop=self._backprop,
            pretrain=self._pretrain,
            tbptt=self._tbptt,
            tbptt_fwd_length=self._tbptt_fwd,
            tbptt_back_length=self._tbptt_back,
            gradient_normalization=g._grad_norm,
            gradient_normalization_threshold=g._grad_norm_threshold,
        )
        if conf.input_type is not None:
            _infer_shapes_and_preprocessors(conf)
        return conf


def _infer_shapes_and_preprocessors(conf: MultiLayerConfiguration) -> None:
    """Walk the net once: auto-insert preprocessors and fill n_in fields
    (ref: MultiLayerConfiguration setInputType path)."""
    it = conf.input_type
    for i, layer in enumerate(conf.layers):
        if i not in conf.preprocessors:
            pre = infer_preprocessor(it, layer)
            if pre is not None:
                conf.preprocessors[i] = pre
        if i in conf.preprocessors:
            it = conf.preprocessors[i].output_type(it)
        tgt = layer.layer if type(layer).__name__ == "FrozenLayer" else layer
        if isinstance(tgt, FeedForwardLayerConf) and tgt.n_in is None:
            if it.kind == "cnn":
                tgt.n_in = it.channels
            else:
                tgt.n_in = it.flat_size()
        it = layer.output_type(it)


class NeuralNetConfiguration:
    """Namespace matching the reference's entry point
    (ref: NeuralNetConfiguration.Builder)."""

    class Builder:
        def __init__(self):
            self._seed = 12345
            self._updater: Updater = Sgd(0.1)
            self._defaults: Dict[str, Any] = {}
            self._grad_norm: Optional[str] = None
            self._grad_norm_threshold = 1.0

        def seed(self, s: int):
            self._seed = int(s)
            return self

        def updater(self, u: Updater):
            self._updater = u
            return self

        def learning_rate(self, lr: float):
            self._updater.learning_rate = float(lr)
            return self

        def weight_init(self, w: str):
            self._defaults["weight_init"] = w
            return self

        def dist(self, d: dict):
            self._defaults["dist"] = d
            return self

        def activation(self, a: str):
            self._defaults["activation"] = a
            return self

        def l1(self, v: float):
            self._defaults["l1"] = v
            return self

        def l2(self, v: float):
            self._defaults["l2"] = v
            return self

        def bias_init(self, v: float):
            self._defaults["bias_init"] = v
            return self

        def dropout(self, retain: float):
            self._defaults["dropout"] = retain
            return self

        def gradient_normalization(self, method: str, threshold: float = 1.0):
            self._grad_norm = method
            self._grad_norm_threshold = threshold
            return self

        def list(self) -> ListBuilder:
            return ListBuilder(self)

        def graph_builder(self):
            from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
            return GraphBuilder(self)


@dataclass
class ComputationGraphConfiguration:
    """DAG net config (ref: ComputationGraphConfiguration.java). Constructed
    via NeuralNetConfiguration.Builder().graph_builder(); see graph_conf.py."""

    vertices: Dict[str, Any] = field(default_factory=dict)  # name -> GraphVertexConf
    vertex_inputs: Dict[str, List[str]] = field(default_factory=dict)
    network_inputs: List[str] = field(default_factory=list)
    network_outputs: List[str] = field(default_factory=list)
    input_types: Dict[str, InputType] = field(default_factory=dict)
    seed: int = 12345
    updater: Updater = field(default_factory=lambda: Sgd(0.1))
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: float = 1.0
    dtype: str = "float32"

    def topological_order(self) -> List[str]:
        """Kahn topo sort (ref: ComputationGraph.topologicalSortOrder :1190)."""
        indeg = {name: 0 for name in self.vertices}
        for name, ins in self.vertex_inputs.items():
            indeg[name] = sum(1 for i in ins if i in self.vertices)
        ready = sorted([n for n, d in indeg.items() if d == 0])
        order: List[str] = []
        children: Dict[str, List[str]] = {n: [] for n in self.vertices}
        for name, ins in self.vertex_inputs.items():
            for i in ins:
                if i in children:
                    children[i].append(name)
        while ready:
            n = ready.pop(0)
            order.append(n)
            for c in children[n]:
                indeg[c] -= 1
                if indeg[c] == 0:
                    ready.append(c)
        if len(order) != len(self.vertices):
            raise ValueError("Graph has a cycle or disconnected vertex inputs")
        return order

    def use_cnn_data_format(self, fmt: str = "NHWC") -> "ComputationGraphConfiguration":
        """Switch the INTERNAL activation layout of the CNN stack (see
        MultiLayerConfiguration.use_cnn_data_format). Entry vertices fed by
        a CNN network input get a FeedForwardToCnn preprocessor that
        performs the one NCHW->NHWC transpose at the graph boundary."""
        from deeplearning4j_tpu.nn.conf.graph_conf import LayerVertex
        layers, pres = [], []
        for v in self.vertices.values():
            if isinstance(v, LayerVertex):
                layers.append(v.layer)
                pres.append(v.preprocessor)
            elif hasattr(v, "data_format"):
                layers.append(v)
        _set_cnn_data_format_fields(layers, pres, fmt)
        if fmt != "NHWC":
            return self
        cnn_inputs = {n for n in self.network_inputs
                      if n in self.input_types and
                      self.input_types[n].kind == "cnn"}
        for name, ins in self.vertex_inputs.items():
            hit = [i for i in ins if i in cnn_inputs]
            if not hit:
                continue
            v = self.vertices[name]
            if not isinstance(v, LayerVertex):
                raise ValueError(
                    f"use_cnn_data_format: vertex {name!r} consumes CNN "
                    f"network input {hit[0]!r} directly; only layer "
                    "vertices can host the entry transpose")
            if v.preprocessor is None:
                it = self.input_types[hit[0]]
                v.preprocessor = FeedForwardToCnnPreProcessor(
                    height=it.height, width=it.width, channels=it.channels,
                    data_format=fmt)
            elif isinstance(v.preprocessor, CnnToFeedForwardPreProcessor):
                # entry flatten consumes the PUBLIC NCHW input directly
                v.preprocessor.data_format = "NCHW"
        return self

    def to_dict(self) -> dict:
        from deeplearning4j_tpu.nn.conf.graph_conf import vertex_to_dict
        return {
            "vertices": {k: vertex_to_dict(v) for k, v in self.vertices.items()},
            "vertex_inputs": self.vertex_inputs,
            "network_inputs": self.network_inputs,
            "network_outputs": self.network_outputs,
            "input_types": {k: v.to_dict() for k, v in self.input_types.items()},
            "seed": self.seed,
            "updater": updater_to_dict(self.updater),
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
            "gradient_normalization": self.gradient_normalization,
            "gradient_normalization_threshold": self.gradient_normalization_threshold,
            "dtype": self.dtype,
        }

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2)

    @staticmethod
    def from_dict(d: dict) -> "ComputationGraphConfiguration":
        from deeplearning4j_tpu.nn.conf.graph_conf import vertex_from_dict
        return ComputationGraphConfiguration(
            vertices={k: vertex_from_dict(v) for k, v in d["vertices"].items()},
            vertex_inputs={k: list(v) for k, v in d["vertex_inputs"].items()},
            network_inputs=list(d["network_inputs"]),
            network_outputs=list(d["network_outputs"]),
            input_types={k: InputType.from_dict(v)
                         for k, v in d.get("input_types", {}).items()},
            seed=d.get("seed", 12345),
            updater=updater_from_dict(d["updater"]) if d.get("updater") else Sgd(0.1),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20),
            gradient_normalization=d.get("gradient_normalization"),
            gradient_normalization_threshold=d.get("gradient_normalization_threshold", 1.0),
            dtype=d.get("dtype", "float32"),
        )

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        return ComputationGraphConfiguration.from_dict(json.loads(s))


def _graph_builder_attr():
    from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
    return GraphBuilder


# Reference spelling: ComputationGraphConfiguration.GraphBuilder()
# (ComputationGraphConfiguration.java inner class). Assigned after the class
# body to avoid a circular import with graph_conf.
class _LazyGraphBuilder:
    def __get__(self, obj, objtype=None):
        return _graph_builder_attr()


ComputationGraphConfiguration.GraphBuilder = _LazyGraphBuilder()
