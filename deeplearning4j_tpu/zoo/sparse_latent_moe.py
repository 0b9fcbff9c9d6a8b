"""SparseLatentMoETransformer: a decoder of the latent-attention,
sparse-selection, routed-expert kind, from the keys of a published
``config.json`` of that family.

Pre-norm residual blocks over token ids ``[N, T]``:
``SequenceEmbeddingLayer`` -> per layer ``RMSNorm`` ->
``LatentAttentionLayer`` (the indexer inside it) -> add -> ``RMSNorm`` ->
``GatedFeedForward`` in the ``first_k_dense_replace`` leading layers,
``RoutedExpertsLayer`` after them -> add; a final ``RMSNorm`` and an
untied ``LastStepOutputLayer`` head. No biases.

``held_experts`` = (first, count) is the range of the router's experts
this device holds (the whole of them by default): the layer then gives its
own experts' part of the result, the cut a deployment over several chips
makes of each layer (``RoutedExpertsLayer``).
"""

from __future__ import annotations

from deeplearning4j_tpu.nn.conf.graph_conf import ElementWiseVertex
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    GatedFeedForward, LastStepOutputLayer, LatentAttentionLayer, RMSNorm,
    RoutedExpertsLayer, SequenceEmbeddingLayer)
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.updater import Adam
from deeplearning4j_tpu.zoo.base import ZooModel, register_model


@register_model
class SparseLatentMoETransformer(ZooModel):
    def __init__(self, config: dict, max_length: int = 1024,
                 held_experts=None, router_experts=None, seed: int = 12345,
                 **kw):
        """``config``: the family's ``config.json`` keys (hidden_size,
        num_attention_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
        qk_rope_head_dim, v_head_dim, index_n_heads, index_head_dim,
        index_topk, intermediate_size, moe_intermediate_size,
        n_routed_experts, n_shared_experts, num_experts_per_tok, n_group,
        topk_group, routed_scaling_factor, first_k_dense_replace,
        num_hidden_layers, rms_norm_eps, rope_theta, rope_scaling,
        vocab_size, and ``torch_dtype`` where the net computes in another
        dtype than float32). ``router_experts`` is the router's width
        where ``n_routed_experts`` counts the experts held here."""
        super().__init__(config["vocab_size"], seed, **kw)
        self.config = dict(config)
        self.max_length = int(max_length)
        self.router_experts = int(router_experts
                                  or config["n_routed_experts"])
        self.held_experts = tuple(held_experts
                                  or (0, config["n_routed_experts"]))

    def _attention(self):
        c = self.config
        rs = c.get("rope_scaling") or {}
        return LatentAttentionLayer(
            n_out=c["hidden_size"], n_heads=c["num_attention_heads"],
            q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"], index_n_heads=c["index_n_heads"],
            index_head_dim=c["index_head_dim"],
            index_topk=c["index_topk"], eps=c["rms_norm_eps"],
            rope_theta=c["rope_theta"],
            rope_factor=float(rs.get("factor", 1.0)),
            rope_original_max=int(rs.get(
                "original_max_position_embeddings", self.max_length)),
            rope_beta_fast=float(rs.get("beta_fast", 32)),
            rope_beta_slow=float(rs.get("beta_slow", 1)),
            rope_mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)),
            cache_length=self.max_length, activation="identity")

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
            if n < c["first_k_dense_replace"]:
                ffn = f"ffn{n}"
                g.add_layer(ffn, GatedFeedForward(
                    hidden=c["intermediate_size"]), f"norm{n}b")
            else:
                ffn = f"moe{n}"
                g.add_layer(ffn, RoutedExpertsLayer(
                    hidden=c["moe_intermediate_size"],
                    router_experts=self.router_experts,
                    held=self.held_experts, top_k=c["num_experts_per_tok"],
                    groups=c["n_group"], top_groups=c["topk_group"],
                    scale=c["routed_scaling_factor"],
                    shared=c["n_shared_experts"]), f"norm{n}b")
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
