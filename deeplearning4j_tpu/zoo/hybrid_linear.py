"""HybridLinearTransformer: a decoder whose layers are of two kinds with
two kinds of cache, from the keys of a published ``config.json`` of that
family: ``layer_types`` names each layer ``linear_attention`` (a
``GatedDeltaNetLayer``: a state a stream, whatever its length) or
``full_attention`` (a ``SelfAttentionLayer``: keys and values a token).

Blocks over token ids ``[N, T]`` normalise each branch's OUTPUT before
adding it (``x + RMSNorm(f(x))``, no norm on the way in):
``SequenceEmbeddingLayer`` -> per layer the mixer -> ``RMSNorm`` -> add ->
``GatedFeedForward`` -> ``RMSNorm`` -> add; a final ``RMSNorm`` and an
untied ``LastStepOutputLayer`` head. No biases. The full-attention layers
pass queries and keys through an RMSNorm over their whole projected
width and rotate nothing where the config's ``rope_parameters.rope_theta``
is null: the linear layers carry order.
"""

from __future__ import annotations

from deeplearning4j_tpu.nn.conf.graph_conf import ElementWiseVertex
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    GatedDeltaNetLayer, GatedFeedForward, LastStepOutputLayer, RMSNorm,
    SelfAttentionLayer, SequenceEmbeddingLayer)
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.updater import Adam
from deeplearning4j_tpu.zoo.base import ZooModel, register_model


#: queries a prime's full-attention layers attend at once: with as many
#: query heads as key-value heads of 128 the float32 scores of a 2,048
#: bucket against 3,072 slots are 0.75 GB a tensor; a block's are a quarter
PRIME_QUERY_BLOCK = 512


@register_model
class HybridLinearTransformer(ZooModel):
    def __init__(self, config: dict, max_length: int = 1024,
                 seed: int = 12345, **kw):
        """``config``: the family's ``config.json`` keys (hidden_size,
        intermediate_size, num_hidden_layers, layer_types — its first
        ``num_hidden_layers`` entries are built —, num_attention_heads,
        num_key_value_heads, attention_bias, rms_norm_eps, vocab_size,
        linear_num_key_heads = linear_num_value_heads,
        linear_key_head_dim, linear_value_head_dim,
        linear_conv_kernel_dim, linear_allow_neg_eigval,
        rope_parameters.rope_theta, and ``torch_dtype`` where the net
        computes in another dtype than float32)."""
        super().__init__(config["vocab_size"], seed, **kw)
        self.config = dict(config)
        self.max_length = int(max_length)
        kinds = list(config["layer_types"])[:config["num_hidden_layers"]]
        unknown = set(kinds) - {"linear_attention", "full_attention"}
        if len(kinds) < config["num_hidden_layers"] or unknown:
            raise ValueError(
                f"layer_types must name each of the "
                f"{config['num_hidden_layers']} layers linear_attention "
                f"or full_attention; got {len(kinds)} entries"
                + (f", unknown kinds {sorted(unknown)}" if unknown else ""))
        if config["linear_num_key_heads"] != config["linear_num_value_heads"]:
            raise ValueError("GatedDeltaNetLayer gives every value head a "
                             "key head of its own")
        self.layer_kinds = kinds

    def _mixer(self, kind: str):
        c = self.config
        if kind == "linear_attention":
            return GatedDeltaNetLayer(
                n_out=c["hidden_size"], n_heads=c["linear_num_value_heads"],
                key_dim=c["linear_key_head_dim"],
                value_dim=c["linear_value_head_dim"],
                conv_kernel=c["linear_conv_kernel_dim"],
                allow_neg_eigval=c["linear_allow_neg_eigval"],
                eps=c["rms_norm_eps"], activation="identity")
        theta = (c.get("rope_parameters") or {}).get("rope_theta")
        return SelfAttentionLayer(
            n_out=c["hidden_size"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], causal=True,
            rope=theta is not None, rope_base=float(theta or 10000.0),
            has_bias=bool(c["attention_bias"]), qk_norm=True,
            qk_norm_eps=c["rms_norm_eps"], cache_length=self.max_length,
            stream_query_block=PRIME_QUERY_BLOCK, activation="identity")

    def conf(self):
        c = self.config
        eps = c["rms_norm_eps"]
        g = (NeuralNetConfiguration.Builder()
             .seed(self.seed)
             .updater(self.kwargs.get("updater", Adam(3e-4)))
             .weight_init("xavier")
             .graph_builder()
             .add_inputs("in")
             .set_input_types(InputType.recurrent(c["vocab_size"],
                                                  self.max_length)))
        dtype = c.get("torch_dtype", "float32")
        g.add_layer("embed", SequenceEmbeddingLayer(
            n_out=c["hidden_size"], out_dtype=dtype), "in")
        prev = "embed"
        for n, kind in enumerate(self.layer_kinds):
            mixer = ("gdn" if kind == "linear_attention" else "attn") + str(n)
            g.add_layer(mixer, self._mixer(kind), prev)
            g.add_layer(f"norm{n}a", RMSNorm(eps=eps), mixer)
            g.add_vertex(f"res{n}a", ElementWiseVertex(op="add"), prev,
                         f"norm{n}a")
            g.add_layer(f"ffn{n}", GatedFeedForward(
                hidden=c["intermediate_size"]), f"res{n}a")
            g.add_layer(f"norm{n}b", RMSNorm(eps=eps), f"ffn{n}")
            g.add_vertex(f"res{n}b", ElementWiseVertex(op="add"),
                         f"res{n}a", f"norm{n}b")
            prev = f"res{n}b"
        g.add_layer("norm_f", RMSNorm(eps=eps), prev)
        g.add_layer("out", LastStepOutputLayer(
            n_out=c["vocab_size"], has_bias=False, loss="mcxent",
            activation="softmax"), "norm_f")
        conf = g.set_outputs("out").build()
        conf.dtype = dtype
        return conf
