"""The latent-attention / sparse-selection / routed-expert decoder at a
small size on the CPU, against the plain reference of the ``deepseek-v3.2``
configuration (``benchmark/reference/deepseek_v32.py``, which imports
nothing of the program): every new layer, the whole net, prefill then
decode through the paged latent cache behind ``GenerationEngine.submit``,
per-head against absorbed attention, the selected sets, and the share test
of a cut expert layer."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.reference import deepseek_v32 as ref
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    GatedFeedForward, LastStepOutputLayer, LatentAttentionLayer, PagedLeaf,
    RMSNorm, RoutedExpertsLayer, SelfAttentionLayer, SequenceEmbeddingLayer,
    layer_from_dict, paged_leaves)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import routed_experts, sparse_latent
from deeplearning4j_tpu.serving import GenerationEngine, PagedKVConfig
from deeplearning4j_tpu.serving.paging import gather_pages, scatter_pages
from deeplearning4j_tpu.util import decoding
from deeplearning4j_tpu.zoo import SparseLatentMoETransformer

#: hidden 64, 4 heads, 8 routed experts of which 2 held, index_topk 16
CFG = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    index_n_heads=2, index_head_dim=16, index_topk=16,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=2,
    n_shared_experts=1, num_experts_per_tok=2, n_group=2, topk_group=1,
    routed_scaling_factor=2.5, first_k_dense_replace=1,
    num_hidden_layers=3, rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=40, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=16,
                      type="yarn"),
    vocab_size=96, published=dict(n_routed_experts=8))
CAP = 128


def build(cfg=CFG, dtype="float32", seed=7, cap=CAP):
    """The zoo's graph with the reference's seeded leaves installed."""
    held = cfg["n_routed_experts"]
    net = ComputationGraph(SparseLatentMoETransformer(
        dict(cfg, torch_dtype=dtype), max_length=cap,
        held_experts=(0, held),
        router_experts=cfg.get("published", {}).get("n_routed_experts",
                                                    held)).conf()).init()
    w = weights.make_weights(
        ref.param_specs(cfg), seed,
        jnp.bfloat16 if dtype == "bfloat16" else jnp.float32)
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


# ------------------------------------------------------------- the pieces
def test_top_k_mask_is_the_stable_sorts_first_k_ties_to_the_lower_index():
    rng = np.random.default_rng(1)
    scores = rng.normal(size=(5, 40)).astype(np.float32)
    scores[0, 3] = scores[0, 30] = scores[0].max() + 1     # a tie on top
    scores[1, :] = 0.25                                     # all equal
    scores[2, 5] = -0.0
    scores[2, 6] = 0.0
    valid = rng.random((5, 40)) < 0.8
    valid[3, 6:] = False                                    # fewer than k
    got = np.asarray(sparse_latent.top_k_mask(
        jnp.asarray(scores), jnp.asarray(valid), 7))
    for row in range(5):
        live = np.flatnonzero(valid[row])
        order = live[np.argsort(-scores[row, live], kind="stable")][:7]
        assert set(np.flatnonzero(got[row])) == set(order), row
    assert got[1].sum() == 7 and got[3].sum() == valid[3].sum()
    assert np.array_equal(
        np.asarray(sparse_latent.top_k_mask(jnp.asarray(scores),
                                            jnp.asarray(valid), 40)), valid)


def test_yarn_frequencies_are_the_references():
    layer = LatentAttentionLayer(
        qk_rope_head_dim=8, rope_factor=40.0, rope_original_max=16,
        rope_mscale_all_dim=1.0)
    assert np.allclose(layer._inv_freq(), ref.yarn_inv_freq(CFG))
    assert layer.softmax_scale == pytest.approx(ref.softmax_scale(dict(
        CFG, qk_nope_head_dim=16)))
    plain = sparse_latent.yarn_inv_freq(8, 10000.0, 1.0, 16, 32, 1)
    assert np.allclose(plain, 10000.0 ** (-np.arange(0, 8, 2) / 8))


def test_the_grouped_product_is_the_dense_sum_and_counts_its_rows():
    rng = np.random.default_rng(2)
    t, e, i, g = 50, 24, 12, 4
    x = jnp.asarray(rng.normal(size=(t, e)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(g, e, i)) * 0.2, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(g, i, e)) * 0.2, jnp.float32)
    gates = rng.random((t, g)).astype(np.float32)
    gates[rng.random((t, g)) < 0.6] = 0.0
    gates[:, 2] = 0.0                       # an expert no token chose
    gates[7] = 0.0                          # a token that chose none here
    y, stats = routed_experts.grouped_experts(
        x, jnp.asarray(gates), wg, wu, wd, tile=8, max_per_token=g)
    want = routed_experts.dense_experts(x, jnp.asarray(gates), wg, wu, wd)
    assert np.allclose(y, want, atol=1e-5)
    sizes = (gates > 0).sum(axis=0)
    assert list(np.asarray(stats)) == [
        sizes.sum(), sum(-(-s // 8) * 8 for s in sizes), sizes.max()]


@pytest.mark.parametrize("layer", [
    RMSNorm(eps=1e-6), GatedFeedForward(hidden=48, n_out=20),
    SequenceEmbeddingLayer(n_out=12), LastStepOutputLayer(
        n_out=9, has_bias=False),
    LatentAttentionLayer(n_heads=2, index_topk=5, rope_factor=40.0,
                         cache_length=32),
    RoutedExpertsLayer(hidden=8, router_experts=8, held=(2, 4), top_k=3,
                       groups=4, top_groups=2, scale=2.5, shared=1)],
    ids=lambda l: type(l).__name__)
def test_a_new_layer_round_trips_through_json(layer):
    again = layer_from_dict(json.loads(json.dumps(layer.to_dict())))
    assert type(again) is type(layer) and again == layer


def test_the_small_layers_are_the_references_functions():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 24, 6)), jnp.float32)   # [N,F,T]
    it = InputType.recurrent(24, 6)
    norm = RMSNorm(eps=1e-6)
    p, _ = norm.init(jax.random.PRNGKey(0), it)
    p["gamma"] = jnp.asarray(1 + 0.1 * rng.normal(size=24), jnp.float32)
    y, _ = norm.apply(p, x, {})
    want = ref._rms_norm(jnp.moveaxis(x, 1, 2), p["gamma"], 1e-6)
    assert np.allclose(jnp.moveaxis(y, 1, 2), want, atol=1e-6)
    ffn = GatedFeedForward(hidden=40)
    p, _ = ffn.init(jax.random.PRNGKey(1), it)
    y, _ = ffn.apply(p, x, {})
    want = ref.gated(jnp.moveaxis(x, 1, 2), p["Wg"], p["Wu"], p["Wd"],
                     low=False)
    assert np.allclose(jnp.moveaxis(y, 1, 2), want, atol=1e-5)
    embed = SequenceEmbeddingLayer(n_out=10)
    p, _ = embed.init(jax.random.PRNGKey(2), InputType.recurrent(30, 6))
    ids = jnp.asarray(rng.integers(0, 30, (2, 6)), jnp.int32)
    y, _ = embed.apply(p, ids, {})
    assert y.shape == (2, 10, 6)
    assert np.array_equal(y[1, :, 4], p["W"][ids[1, 4]])
    with pytest.raises(ValueError, match="takes ids"):
        embed.apply(p, jnp.zeros((2, 30, 6)), {})
    head = LastStepOutputLayer(n_out=9, has_bias=False)
    p, _ = head.init(jax.random.PRNGKey(3), it)
    full, _ = head.apply(p, x, {})
    last, _ = head.apply(p, x, {}, stream=True)
    assert full.shape == (2, 9, 6) and last.shape == (2, 9)
    assert np.allclose(last, full[:, :, -1], atol=1e-6)


# ----------------------------------------------------------- the whole net
def test_the_full_forward_gives_the_references_distribution(model):
    net, w = model
    ids = ids_of(CAP)
    out = np.asarray(net.output(ids[None].astype(np.int32)))[0]    # [V, T]
    want = jax.nn.softmax(ref_logits(w, ids, np.arange(CAP)), axis=-1)
    assert np.abs(out.T - np.asarray(want)).max() < 2e-6


def test_an_attention_layer_alone_is_the_references(model):
    net, w = model
    layer = net.conf.vertices["attn1"].layer
    h = jnp.asarray(np.random.default_rng(4).normal(size=(CAP, 64)),
                    jnp.float32)
    y, _ = jax.jit(lambda p, x: layer.apply(p, x, {}))(
        net.params["attn1"], h.T[None])
    want, sel = ref.attention(
        h, ref._attn_params(w, 1), jnp.asarray(ref.yarn_inv_freq(CFG)),
        z=ref._sizes(CFG), eps=1e-6,
        scale=ref.softmax_scale(CFG), low=False)
    assert np.allclose(y[0].T, want, atol=2e-5)
    _, mine = jax.jit(layer.index_selection)(net.params["attn1"],
                                             h.T[None])
    assert np.array_equal(np.asarray(mine[0]), np.asarray(sel))
    assert sel.sum(axis=1).tolist() == [min(16, t + 1) for t in range(CAP)]


def test_streaming_per_head_and_paged_absorbed_attention_agree(model):
    """One function, two forms: a prime through the dense cache, then one
    more token (a) per-head against the dense cache and (b) absorbed,
    through a page table, against the same cache scattered into pages."""
    net, _ = model
    layer, p = net.conf.vertices["attn0"].layer, net.params["attn0"]
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 64, 41)), jnp.float32)
    stream = jax.jit(lambda p, x, state: layer.apply(p, x, state,
                                                     stream=True))
    _, state = stream(p, x[:, :, :40], {})
    dense, after = stream(p, x[:, :, 40:], state)
    # what each form scored: the fresh chunk its own 40 slots a query, the
    # later chunk every cache slot (the paged form, below, the selected 16)
    assert int(state["attn_stats"]) == 2 * 40 * 40
    assert int(after["attn_stats"]) == 2 * 40 * 40 + 2 * CAP
    leaves = layer.paged_leaves()
    assert [l.key for l in leaves] == ["kv_c", "kv_r", "kv_i"]
    table = np.array([[3, 9, 1, 7, 5, 11, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0],
                      [2, 4, 6, 8, 10, 12, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0]],
                     np.int32)
    axes = tuple(l.token_axis + 1 for l in leaves)
    pools = scatter_pages(
        [jnp.zeros(l.shape(14, 8), jnp.float32) for l in leaves],
        [state[l.key] for l in leaves], table, axes=axes)
    paged_state = {"kv_pos": jnp.full((2,), 40, jnp.int32),
                   "kv_page_table": jnp.asarray(table)}
    paged_state.update({l.page_key: pool
                        for l, pool in zip(leaves, pools)})
    paged, out = stream(p, x[:, :, 40:], paged_state)
    assert np.allclose(paged, dense, atol=2e-5)
    assert out["kv_pos"].tolist() == [41, 41]
    assert int(out["attn_stats"]) == 2 * 16
    # the appended token landed where the dense cache holds it
    back = gather_pages([out[l.page_key] for l in leaves], table,
                        length=CAP, axes=axes)
    for l, b in zip(leaves, back):
        assert np.allclose(b[:, :41], after[l.key][:, :41], atol=1e-6)


def test_what_a_layer_keeps_per_token_is_what_it_declares():
    attn = SelfAttentionLayer(n_out=64, n_heads=4, n_kv_heads=2,
                              cache_length=32)
    assert paged_leaves(attn) == (PagedLeaf("kv_k", (2, 16), 1),
                                  PagedLeaf("kv_v", (2, 16), 1))
    assert paged_leaves(attn)[0].shape(9, 8) == (9, 2, 8, 16)
    assert paged_leaves(attn)[0].page_key == "kv_page_k"
    latent = LatentAttentionLayer(kv_lora_rank=16, qk_rope_head_dim=8,
                                  index_head_dim=16, cache_length=32)
    assert [(l.key, l.shape(9, 8)) for l in paged_leaves(latent)] == [
        ("kv_c", (9, 8, 16)), ("kv_r", (9, 8, 8)), ("kv_i", (9, 8, 16))]
    assert paged_leaves(RMSNorm()) == ()
    # a fresh prime of 8,192 rows in blocks of 128: four groups of 16
    # blocks against 2,048, 4,096, 6,144 and 8,192 slots, 5/8 of the square
    wide = LatentAttentionLayer(index_topk=2048, cache_length=8192)
    assert wide._query_groups(8192, 8192, True) == (
        128, 0, [(16, 2048), (16, 4096), (16, 6144), (16, 8192)])
    assert wide._query_groups(40, 128, False) == (40, 0, [(1, 128)])
    # 300 queries against a cache: three blocks, the last filled with 84
    assert wide._query_groups(300, 8192, False) == (128, 84, [(3, 8192)])


# ------------------------------------------------ many blocks of queries
@pytest.fixture
def blocks_of_16(monkeypatch):
    """Query blocks of 16 and not 128: a chunk of 128 then goes in 8
    blocks and 4 causal groups, the shape of a prime of 4,096 or 8,192 in
    blocks of 128 (32 or 64 blocks in 4 groups), which no chunk of this
    size takes otherwise."""
    monkeypatch.setattr(sparse_latent, "QUERY_BLOCK", 16)


def test_a_prime_in_many_query_blocks_is_the_references(model,
                                                        blocks_of_16):
    """The per-head streaming form over several blocks, against the
    reference's attention over the same tokens (its explicit selected-set
    mask: at 16 of up to 128 positions a set that differed would move the
    output by a sixteenth of itself, four orders over the tolerance): a
    fresh chunk (slot for query, causal groups), the same behind left
    pads, and a later chunk against the cache, its last block filled."""
    net, w = model
    layer, p = net.conf.vertices["attn1"].layer, net.params["attn1"]
    assert layer._query_groups(CAP, CAP, True) == (
        16, 0, [(2, 32), (2, 64), (2, 96), (2, 128)])
    rng = np.random.default_rng(12)
    h = jnp.asarray(rng.normal(size=(CAP, 64)), jnp.float32)

    def want(n):
        return ref.attention(
            h[:n], ref._attn_params(w, 1),
            jnp.asarray(ref.yarn_inv_freq(CFG)), z=ref._sizes(CFG),
            eps=1e-6, scale=ref.softmax_scale(CFG), low=False)

    def stream(x, state, pad_left=None):
        return jax.jit(lambda p, x, state: layer.apply(
            p, x, state, stream=True, pad_left=pad_left))(p, x.T[None],
                                                          state)

    y, state = stream(h, {})
    full, selected = want(CAP)
    assert selected.sum(axis=1).tolist() == [min(16, t + 1)
                                             for t in range(CAP)]
    assert np.allclose(y[0].T, full, atol=2e-5)
    assert int(state["attn_stats"]) == 2 * 16 * (32 + 64 + 96 + 128)
    # 100 tokens behind 28 pads of noise: positions count from the first
    # real token, the pads leave nothing in the cache
    pads = jnp.asarray(rng.normal(size=(28, 64)), jnp.float32)
    y, state = stream(jnp.concatenate([pads, h[:100]]), {}, pad_left=28)
    assert np.allclose(y[0].T[28:], want(100)[0], atol=2e-5)
    assert int(state["kv_pos"]) == 100
    assert int(state["attn_stats"]) == 2 * 16 * (32 + 64 + 96 + 128)
    # 20 more against the cache: blocks of 16 and 4 (+ 12), every slot
    y, state = stream(h[100:120], state)
    assert layer._query_groups(20, CAP, False) == (16, 12, [(2, CAP)])
    assert np.allclose(y[0].T, want(120)[0][100:], atol=2e-5)
    assert int(state["attn_stats"]) == 2 * 16 * (32 + 64 + 96 + 128) \
        + 32 * CAP


def test_served_in_many_query_blocks_the_tokens_are_the_references(
        blocks_of_16):
    """The whole net behind ``submit`` with blocks of 16: primes of 4 and
    8 blocks, and after a prefix hit of 32 a suffix of 2 blocks against
    the whole cache."""
    net, w = build()
    engine = GenerationEngine(
        net, CFG["vocab_size"], slots=2, queue_limit=4,
        paging=PagedKVConfig(page_size=8, total_pages=40,
                             decode_impl="xla"))
    prompts = [list(ids_of(n, seed=20 + n)) for n in (61, 100)]
    prompts.append(prompts[0][:32] + list(ids_of(21, seed=98)))
    handles = [engine.submit(p, 4, top_k=1, rng=np.random.default_rng(0))
               for p in prompts]
    engine.run_until_idle()
    health = engine.health()
    engine.shutdown()
    for prompt, handle in zip(prompts, handles):
        ids = [int(t) for t in handle.result(timeout=0)]
        logits = ref_logits(w, ids, np.arange(len(prompt) - 1,
                                              len(ids) - 1))
        assert ids[len(prompt):] == logits.argmax(axis=1).tolist()
    assert health["prefix_cache"]["hits"] == 1
    assert health["prefill"]["bucket_tokens"] == 64 + 128 + 32
    cycles = health["decode_dispatch"]["count"]
    assert health["sparse_attn"]["attended_positions"] == 3 * (
        16 * (16 + 32 + 48 + 64) + 2 * 16 * (32 + 64 + 96 + 128)
        + 32 * CAP + 16 * 2 * cycles)


# ------------------------------------------------- through the engine
def test_prefill_then_paged_decode_through_submit_follows_the_reference(
        model):
    """Five requests over three slots: rows are admitted and retired
    mid-stream, the fifth shares four pages of the first's prompt (a
    prefix-cache hit). Every served token is the reference's own next
    token over the full sequence, and plain ``sample_stream`` (dense
    cache, per-head decode) serves the same."""
    net, w = model
    engine = GenerationEngine(
        net, CFG["vocab_size"], slots=3, queue_limit=8,
        paging=PagedKVConfig(page_size=8, total_pages=60,
                             decode_impl="xla"))
    prompts = [list(ids_of(n, seed=n)) for n in (40, 55, 33, 70)]
    prompts.append(prompts[0][:32] + list(ids_of(20, seed=99)))
    handles = [engine.submit(p, 6 + i, top_k=1,
                             rng=np.random.default_rng(0))
               for i, p in enumerate(prompts)]
    engine.run_until_idle()
    health = engine.health()
    for prompt, handle in zip(prompts, handles):
        ids = [int(t) for t in handle.result(timeout=0)]
        served = ids[len(prompt):]
        logits = ref_logits(w, ids, np.arange(len(prompt) - 1,
                                              len(ids) - 1))
        assert served == logits.argmax(axis=1).tolist()
    assert health["prefix_cache"]["hits"] == 1
    assert health["prefix_cache"]["reused_tokens"] == 32
    assert health["kv_traffic"]["decode_path"] == "direct-xla"
    # the latent layer's paged decode gathers what it keeps
    assert health["kv_traffic"]["selected_read"] == "gathered"
    # ids in: 4 bytes a token up, the last position's row back
    fed = sum(len(p) for p in prompts) - 32
    assert health["prefill"]["fed_tokens"] == fed
    assert health["host_io"]["prefill"] == {
        "h2d_bytes": 4 * health["prefill"]["bucket_tokens"],
        "d2h_bytes": 4 * CFG["vocab_size"] * len(prompts),
        "results": len(prompts), "result_positions": len(prompts)}
    cycles = health["decode_dispatch"]["count"]
    assert health["host_io"]["decode"] == {"h2d_bytes": 4 * 3 * cycles,
                                           "d2h_bytes": 4 * 3 * cycles}
    # the counters: two expert layers, each routes every token it is fed
    experts = health["experts"]
    assert experts["tokens"] == 2 * (fed + 3 * cycles)
    assert 0 < experts["held_pairs"] <= experts["rows_computed"]
    assert experts["rows_computed"] % 16 == 0
    assert 1 <= experts["max_expert_load"] <= 70
    assert engine.health()["experts"] == experts       # drained once
    sparse = health["sparse_attn"]
    decoded = health["decode_dispatch"]["rows"]
    assert sparse["query_positions"] == 3 * (fed + decoded)
    assert sparse["selected_positions"] < sparse["context_positions"]
    # four fresh primes in buckets of 64, 64, 64 and 128 (one block of
    # queries each: its own slots), the prefix hit's 32 rows against the
    # whole cache, and the gathered 16 of each of 3 rows a cycle
    assert health["prefill"]["bucket_tokens"] == 3 * 64 + 128 + 32
    assert sparse["attended_positions"] == 3 * (
        3 * 64 * 64 + 128 * 128 + 32 * CAP + 16 * 3 * cycles)
    engine.shutdown()
    assert decoding.sample_stream(
        net, prompts[1], 7, CFG["vocab_size"], top_k=1,
        prime_padded=True)[len(prompts[1]):] == \
        [int(t) for t in handles[1].result(timeout=0)][len(prompts[1]):]


def test_the_engine_refuses_what_these_leaves_cannot_do(model):
    net, _ = model
    with pytest.raises(ValueError, match="page table"):
        GenerationEngine(net, CFG["vocab_size"], slots=2)
    for kw in (dict(kv_dtype="int8"), dict(decode_impl="pallas")):
        with pytest.raises(ValueError, match="keys and values only"):
            GenerationEngine(net, CFG["vocab_size"], slots=2,
                             paging=PagedKVConfig(page_size=8, **kw))


# ------------------------------------------------------------- selection
def test_selected_sets_are_the_references_where_margins_pass_rounding(
        model):
    """bfloat16 index scores against the reference's float32: where the
    k-th and (k+1)-th score of a query lie further apart than twice what
    the rounding moved any score of that query, the two select the same
    set. How many queries that is, the test says: over a fifth of them at
    this size, and over eight in ten select the same set whatever the
    margin (31 and 99 of 112 when this was written)."""
    net, w = model
    ids = ids_of(CAP, seed=6)
    want = np.asarray(ref.selected_at(CFG, w, list(ids), np.arange(CAP)))[0]
    layer = net.conf.vertices["attn0"].layer
    x = jnp.take(net.params["embed"]["W"], jnp.asarray(ids), axis=0)
    h, _ = net.conf.vertices["norm0a"].layer.apply(
        net.params["norm0a"], x.T[None], {})
    select = jax.jit(layer.index_selection)
    exact, same_precision = select(net.params["attn0"], h)
    assert np.array_equal(np.asarray(same_precision[0]), want)
    low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                 (net.params["attn0"], h))
    rounded, mine = select(*low)
    exact = np.asarray(exact[0], np.float64)
    moved = np.abs(np.asarray(rounded[0], np.float64) - exact)
    mine = np.asarray(mine[0])
    clear = same = 0
    for t in range(16, CAP):
        ranked = np.sort(exact[t, :t + 1])[::-1]
        if ranked[15] - ranked[16] > 2 * moved[t, :t + 1].max():
            clear += 1
            assert np.array_equal(mine[t], want[t]), t
        same += np.array_equal(mine[t], want[t])
    print("clear", clear, "same", same, "of", CAP - 16)
    assert clear >= (CAP - 16) // 5, clear
    assert same >= 0.8 * (CAP - 16), same
    assert np.array_equal(mine[:16], want[:16])   # everything is selected


# ------------------------------------------------------------- the shares
def test_the_shares_of_a_cut_expert_layer_add_up_to_the_whole_layer():
    """Guide section 4: four chips hold two of eight experts each. The
    parts the four shares give, the shared expert (which every chip
    computes alike) counted once, add up to what the uncut reference
    gives for the whole layer."""
    whole = dict(CFG, n_routed_experts=8)
    whole.pop("published")
    w = weights.make_weights(ref.param_specs(whole), 11, jnp.float32)
    h = jnp.asarray(np.random.default_rng(8).normal(size=(60, 64)),
                    jnp.float32)
    want = ref.experts(whole, w, 1, h, False)
    shared = ref.gated(h, w["moe1/Ws_g"], w["moe1/Ws_u"], w["moe1/Ws_d"],
                       low=False)
    total = shared
    for chip in range(4):
        first = 2 * chip
        layer = RoutedExpertsLayer(
            hidden=32, router_experts=8, held=(first, 2), top_k=2,
            groups=2, top_groups=1, scale=2.5, shared=1)
        p = {k: w[f"moe1/{k}"] for k in ("Wr", "br", "Ws_g", "Ws_u",
                                         "Ws_d")}
        p.update({k: w[f"moe1/{k}"][first:first + 2]
                  for k in ("Wg", "Wu", "Wd")})
        part, state = layer.apply(p, h.T[None], {}, stream=True)
        dense, _ = layer.apply(p, h.T[None], {})
        assert np.allclose(part, dense, atol=1e-5)
        # the reference, given the same share, gives the same part
        cut = dict(CFG)
        held = dict(w)
        held.update({f"moe1/{k}": p[k] for k in ("Wg", "Wu", "Wd")})
        assert np.allclose(part[0].T, ref.experts(cut, held, 1, h, False,
                                                  held=(first, 2)),
                           atol=1e-5)
        total = total + (part[0].T - shared)
        assert int(state["moe_stats"][0]) == 60
    assert np.allclose(total, want, atol=2e-5)
    # every token met its two experts somewhere
    assert float(jnp.abs(want - shared).min(axis=1).max()) > 0
