"""SparseGQAMoETransformer: a decoder of grouped-query attention with
learned sparse selection and softmax-routed experts, from the keys of a
published ``config.json`` of that family (the mixture-of-experts keys
``num_experts``, ``num_experts_per_tok``, ``moe_intermediate_size``,
``norm_topk_prob``, ``decoder_sparse_step``, ``mlp_only_layers`` and a
``head_dim`` of its own, plus ``sa_config`` for the indexer).

Pre-norm residual blocks over token ids ``[N, T]``:
``SequenceEmbeddingLayer`` -> per layer ``RMSNorm`` ->
``SelfAttentionLayer`` (no biases, queries and keys normed per head,
rope over the whole head, the indexer of ``sa_config`` inside it) -> add
-> ``RMSNorm`` -> ``RoutedExpertsLayer`` (softmax over all experts, the
``num_experts_per_tok`` largest, no shared expert), or a
``GatedFeedForward`` ``intermediate_size`` wide in a layer that
``mlp_only_layers`` names or ``decoder_sparse_step`` skips -> add; a final
``RMSNorm`` and an untied ``LastStepOutputLayer`` head.

``held_experts`` = (first, count) is the range of the router's experts
this device holds (the whole of them by default): the layer then gives its
own experts' part of the result, the cut a deployment over several chips
makes of each layer (``RoutedExpertsLayer``).
"""

from __future__ import annotations

from deeplearning4j_tpu.nn.conf.graph_conf import ElementWiseVertex
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    GatedFeedForward, LastStepOutputLayer, RMSNorm, RoutedExpertsLayer,
    SelfAttentionLayer, SequenceEmbeddingLayer)
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers.sparse_latent import QUERY_BLOCK
from deeplearning4j_tpu.nn.updater import Adam
from deeplearning4j_tpu.zoo.base import ZooModel, register_model


@register_model
class SparseGQAMoETransformer(ZooModel):
    def __init__(self, config: dict, max_length: int = 1024,
                 held_experts=None, router_experts=None, seed: int = 12345,
                 **kw):
        """``config``: the family's ``config.json`` keys (hidden_size,
        num_attention_heads, num_key_value_heads, head_dim, num_experts,
        num_experts_per_tok, moe_intermediate_size, norm_topk_prob,
        decoder_sparse_step, mlp_only_layers, intermediate_size,
        sa_config.{indexer_num_heads, indexer_head_dim, topk},
        num_hidden_layers, rope_theta, rms_norm_eps, vocab_size, and
        ``torch_dtype`` where the net computes in another dtype than
        float32). ``router_experts`` is the router's width where
        ``num_experts`` counts the experts held here. Without
        ``sa_config`` the attention selects nothing."""
        super().__init__(config["vocab_size"], seed, **kw)
        self.config = dict(config)
        self.max_length = int(max_length)
        self.router_experts = int(router_experts or config["num_experts"])
        self.held_experts = tuple(held_experts
                                  or (0, config["num_experts"]))

    def routes(self, n: int) -> bool:
        """Whether layer ``n`` is a layer of routed experts."""
        c = self.config
        return (n not in c.get("mlp_only_layers", ())
                and c["num_experts"] > 0
                and (n + 1) % c.get("decoder_sparse_step", 1) == 0)

    def _attention(self):
        c = self.config
        sa = c.get("sa_config") or {}
        if sa and sa.get("indexer_num_kv_heads", 1) != 1:
            raise ValueError("the indexer keeps one index key a token "
                             "(indexer_num_kv_heads 1)")
        return SelfAttentionLayer(
            n_out=c["hidden_size"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            causal=True, rope=True, rope_base=float(c["rope_theta"]),
            has_bias=bool(c.get("attention_bias", False)),
            qk_norm="head", qk_norm_eps=c["rms_norm_eps"],
            index_n_heads=sa.get("indexer_num_heads", 0),
            index_head_dim=sa.get("indexer_head_dim", 0),
            index_topk=sa.get("topk", 0),
            cache_length=self.max_length, stream_query_block=QUERY_BLOCK,
            activation="identity")

    def conf(self):
        c = self.config
        e, eps = c["hidden_size"], c["rms_norm_eps"]
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
            n_out=e, out_dtype=dtype), "in")
        prev = "embed"
        for n in range(c["num_hidden_layers"]):
            g.add_layer(f"norm{n}a", RMSNorm(eps=eps), prev)
            g.add_layer(f"attn{n}", self._attention(), f"norm{n}a")
            g.add_vertex(f"res{n}a", ElementWiseVertex(op="add"), prev,
                         f"attn{n}")
            g.add_layer(f"norm{n}b", RMSNorm(eps=eps), f"res{n}a")
            if self.routes(n):
                ffn = f"moe{n}"
                g.add_layer(ffn, RoutedExpertsLayer(
                    hidden=c["moe_intermediate_size"],
                    router_experts=self.router_experts,
                    held=self.held_experts, top_k=c["num_experts_per_tok"],
                    scoring="softmax",
                    norm_topk=bool(c.get("norm_topk_prob", True)),
                    shared=0), f"norm{n}b")
            else:
                ffn = f"ffn{n}"
                g.add_layer(ffn, GatedFeedForward(
                    hidden=c["intermediate_size"]), f"norm{n}b")
            g.add_vertex(f"res{n}b", ElementWiseVertex(op="add"),
                         f"res{n}a", ffn)
            prev = f"res{n}b"
        g.add_layer("norm_f", RMSNorm(eps=eps), prev)
        g.add_layer("out", LastStepOutputLayer(
            n_out=c["vocab_size"], has_bias=False, loss="mcxent",
            activation="softmax"), "norm_f")
        conf = g.set_outputs("out").build()
        conf.dtype = dtype
        return conf
