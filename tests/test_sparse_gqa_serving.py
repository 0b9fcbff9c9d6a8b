"""The decoder of selecting grouped-query attention and softmax-routed
experts (``zoo/sparse_gqa_moe.py``) at a small size on the CPU behind
``GenerationEngine.submit``: a prime through the dense cache, one scatter
that seats three leaves a layer, the masked decode through pages, against
the plain reference of the ``keye-vl-2.0-30b-a3b`` configuration and the
one-shot ``sample_stream``; what the engine answers for this net's int8,
kernel and speculation requests."""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.reference import keye_vl2 as ref
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.serving import (GenerationEngine, PagedKVConfig,
                                        SpeculationConfig)
from deeplearning4j_tpu.util import decoding
from deeplearning4j_tpu.zoo import SparseGQAMoETransformer

#: hidden 64, 4 query heads on 2 key-value heads of 24 (not 64 / 4), 8
#: experts of which a token takes 2, an indexer that keeps 16 positions
CFG = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=24, intermediate_size=96, moe_intermediate_size=32,
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[], num_hidden_layers=3,
    rms_norm_eps=1e-6, rope_theta=10000000, vocab_size=96,
    attention_bias=False,
    sa_config=dict(indexer_head_dim=16, indexer_num_heads=2,
                   indexer_num_kv_heads=1, topk=16, q_chunk_size=512,
                   kv_chunk_size=512))
CAP = 128


def build(cfg=CFG, seed=7, cap=CAP):
    """The zoo's graph with the reference's seeded leaves installed."""
    net = ComputationGraph(
        SparseGQAMoETransformer(cfg, max_length=cap).conf()).init()
    w = weights.make_weights(ref.param_specs(cfg), seed, jnp.float32)
    weights.check_tree_matches(w, net.params)
    for vertex, leaves in weights.as_tree(w).items():
        net.params[vertex] = leaves
    return net, w


@pytest.fixture(scope="module")
def model():
    return build()


def ids_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG["vocab_size"], n)


def ref_logits(w, ids, positions, cfg=CFG):
    padded = list(ids) + [0] * (-len(ids) % CAP)
    return np.asarray(ref.logits_at(cfg, w, padded, positions))


def test_the_model_is_built_from_the_familys_keys(model):
    net, _ = model
    layers = {n: v.layer for n, v in net.conf.vertices.items()
              if getattr(v, "layer", None) is not None}
    attn = layers["attn1"]
    assert (attn.n_heads, attn.n_kv_heads, attn.head_width, attn.qk_norm,
            attn.has_bias, attn.rope, attn.rope_base) == (
        4, 2, 24, "head", False, True, 1e7)
    assert (attn.index_n_heads, attn.index_head_dim, attn.index_topk) == \
        (2, 16, 16)
    moe = layers["moe2"]
    assert (moe.scoring, moe.norm_topk, moe.shared, moe.held,
            moe.router_experts, moe.top_k) == ("softmax", True, 0, (0, 8),
                                               8, 2)
    assert "br" not in net.params["moe2"]
    # a layer the config names dense takes the dense width
    dense = SparseGQAMoETransformer(dict(CFG, mlp_only_layers=[1]),
                                    max_length=CAP)
    assert [dense.routes(n) for n in range(3)] == [True, False, True]
    v = dense.conf().vertices
    assert v["ffn1"].layer.hidden == 96 and "moe1" not in v
    every_other = SparseGQAMoETransformer(dict(CFG, decoder_sparse_step=2),
                                          max_length=CAP)
    assert [every_other.routes(n) for n in range(3)] == [False, True, False]
    share = SparseGQAMoETransformer(CFG, max_length=CAP,
                                    held_experts=(4, 2), router_experts=8)
    assert share.conf().vertices["moe0"].layer.held == (4, 2)


def test_the_full_forward_gives_the_references_distribution(model):
    net, w = model
    ids = ids_of(50, seed=1)
    pos = np.arange(5, 50)
    probs = np.asarray(net.output(ids[None].astype(np.int32)))[0]
    got = np.log(probs[:, pos].T)
    want = ref_logits(w, ids, pos)
    assert np.abs((got - got.mean(1, keepdims=True))
                  - (want - want.mean(1, keepdims=True))).max() < 2e-4


@pytest.fixture(scope="module")
def served(model):
    """Five requests over three slots behind ``decode_impl="auto"``: rows
    are admitted and retired mid-stream (slots are reused), the fifth
    shares four pages of the first's prompt (a prefix-cache hit)."""
    net, w = model
    engine = GenerationEngine(
        net, CFG["vocab_size"], slots=3, queue_limit=8,
        paging=PagedKVConfig(page_size=8, total_pages=60,
                             decode_impl="auto", prefix_cache=True))
    prompts = [list(ids_of(n, seed=n)) for n in (40, 55, 33, 70)]
    prompts.append(prompts[0][:32] + list(ids_of(20, seed=99)))
    handles = [engine.submit(p, 6 + i, top_k=1,
                             rng=np.random.default_rng(0))
               for i, p in enumerate(prompts)]
    engine.run_until_idle()
    health = engine.health()
    outputs = [[int(t) for t in h.result(timeout=0)] for h in handles]
    engine.shutdown()
    return prompts, outputs, health


def test_every_served_token_is_the_references_next_token(model, served):
    _, w = model
    prompts, outputs, _ = served
    for i, (prompt, ids) in enumerate(zip(prompts, outputs)):
        assert len(ids) == len(prompt) + 6 + i
        logits = ref_logits(w, ids, np.arange(len(prompt) - 1,
                                              len(ids) - 1))
        assert ids[len(prompt):] == logits.argmax(axis=1).tolist()


@pytest.mark.parametrize("which", [1, 4])
def test_the_engine_serves_what_the_one_shot_stream_serves(model, served,
                                                           which):
    """Request 1 alone through ``sample_stream`` (dense cache, the masked
    form every step), and request 4, whose prime was a prefix hit."""
    net, _ = model
    prompts, outputs, _ = served
    n = len(outputs[which]) - len(prompts[which])
    assert decoding.sample_stream(
        net, prompts[which], n, CFG["vocab_size"], top_k=1,
        prime_padded=True) == outputs[which]


def test_the_decode_path_and_the_counters(served):
    prompts, outputs, health = served
    # the kernel walks whole pages and cannot skip tokens: xla, though
    # ``auto`` was asked
    assert health["kv_traffic"]["decode_path"] == "direct-xla"
    # a table of CAP = 8 x topk slots is read whole, under the mask
    assert health["kv_traffic"]["selected_read"] == "masked"
    assert health["prefix_cache"]["hits"] == 1
    assert health["prefix_cache"]["reused_tokens"] == 32
    fed = sum(len(p) for p in prompts) - 32
    assert health["prefill"]["fed_tokens"] == fed
    assert health["prefill"]["bucket_tokens"] == 3 * 64 + 128 + 32
    cycles = health["decode_dispatch"]["count"]
    rows = health["decode_dispatch"]["rows"]
    experts = health["experts"]
    assert experts["tokens"] == 3 * (fed + 3 * cycles)
    # every expert is held: every token meets its two
    assert experts["held_pairs"] == 2 * experts["tokens"]
    assert experts["held_pairs"] <= experts["rows_computed"]
    assert experts["decode_calls"] == 3 * cycles
    # three rows a call take two experts each: 2 to 6 of the 8 get a token
    assert 2 * experts["decode_calls"] <= experts["decode_experts_touched"] \
        <= 6 * experts["decode_calls"]
    sparse = health["sparse_attn"]
    assert sparse["query_positions"] == 3 * (fed + rows)
    assert sparse["selected_positions"] < sparse["context_positions"]
    # four fresh primes in buckets of 64, 64, 64 and 128 (one block of
    # queries each: its own slots), the prefix hit's 32 rows against the
    # whole cache, and every slot of the table for each of 3 rows a cycle
    assert sparse["attended_positions"] == 3 * (
        3 * 64 * 64 + 128 * 128 + 32 * CAP + CAP * 3 * cycles)


def test_what_the_engine_answers_for_int8_the_kernel_and_speculation(model):
    """The index key is a third leaf: no int8 sidecar scales it and no
    kernel reads it (both refused by name), the slot arena has no page
    table to select through, and a last-position head cannot verify a
    widened chunk."""
    net, _ = model
    with pytest.raises(ValueError, match="page table"):
        GenerationEngine(net, CFG["vocab_size"], slots=2)
    for kw in (dict(kv_dtype="int8"), dict(decode_impl="pallas")):
        with pytest.raises(ValueError, match="kv_i"):
            GenerationEngine(net, CFG["vocab_size"], slots=2,
                             paging=PagedKVConfig(page_size=8, **kw))
    with pytest.raises(ValueError, match="last"):
        GenerationEngine(
            net, CFG["vocab_size"], slots=2,
            paging=PagedKVConfig(page_size=8, decode_impl="xla"),
            speculation=SpeculationConfig(lambda ctx, g: [0] * g, 2))
