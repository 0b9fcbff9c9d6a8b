"""``SelfAttentionLayer`` with an indexer (learned sparse selection on the
grouped-query path), a head width of its own and a per-head q/k norm, at a
small size on the CPU: the training forward, dense streaming, the prime
followed by the paged decode, and the plain reference of the
``keye-vl-2.0-30b-a3b`` configuration (``benchmark/reference/keye_vl2.py``,
which imports nothing of the program) are one function; with every new
field at its default the layer is the layer it was."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import keye_vl2 as ref
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    PagedLeaf, SelfAttentionLayer, layer_from_dict, paged_leaves,
    stream_counters)
from deeplearning4j_tpu.serving.paging import gather_pages, scatter_pages

E, H, G, D, HI, DI, TOPK, CAP = 48, 4, 2, 16, 2, 8, 12, 96
THETA = 10000.0
Z = ref.Sizes(e=E, v=0, h=H, g=G, d=D, hi=HI, di=DI, topk=TOPK, dense=0,
              moe=0, experts=0, per_tok=0, layers=1)


def make(topk=TOPK, block=16, cap=CAP, **kw):
    return SelfAttentionLayer(
        n_out=E, n_heads=H, n_kv_heads=G, head_dim=D, rope=True,
        rope_base=THETA, has_bias=False, qk_norm="head",
        index_n_heads=HI, index_head_dim=DI, index_topk=topk,
        cache_length=cap, stream_query_block=block, **kw)


def params_of(layer, seed=3, ties=False):
    p, _ = layer.init(jax.random.PRNGKey(seed), InputType.recurrent(E, CAP))
    rng = np.random.default_rng(seed)
    p = dict(p)
    for k in ("q_norm", "k_norm", "ik_gamma"):
        p[k] = jnp.asarray(1 + 0.1 * rng.normal(size=p[k].shape),
                           jnp.float32)
    p["ik_beta"] = jnp.asarray(0.1 * rng.normal(size=(DI,)), jnp.float32)
    if ties:
        # no index weight: every score is 0, every choice a tie
        p["Wiw"] = jnp.zeros_like(p["Wiw"])
    return p


def x_of(t, n=1, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(n, E, t)),
                       jnp.float32)


def reference(p, x, topk=TOPK):
    """The reference's attention of one sequence x [E, T]."""
    z = Z._replace(topk=topk)
    y, sel = ref.attention(x.T, p, z=z, eps=1e-6, theta=THETA, low=False)
    return np.asarray(y).T, np.asarray(sel)


STREAM = {}


def stream(layer):
    """One jitted streaming apply a layer object."""
    if id(layer) not in STREAM:
        STREAM[id(layer)] = (layer, jax.jit(
            lambda p, x, state, pad_left=None: layer.apply(
                p, x, state, stream=True, pad_left=pad_left)))
    return STREAM[id(layer)][1]


# ------------------------------------------------------ what the layer is
def test_the_leaves_are_the_declared_ones():
    layer = make()
    p = params_of(layer)
    assert {k: v.shape for k, v in p.items()} == {
        "Wq": (E, H * D), "Wk": (E, G * D), "Wv": (E, G * D),
        "Wo": (H * D, E), "q_norm": (D,), "k_norm": (D,),
        "Wiq": (E, HI * DI), "Wik": (E, DI), "Wiw": (E, HI),
        "ik_gamma": (DI,), "ik_beta": (DI,)}
    assert layer.head_width == D != E // H
    # the index key's leaf is kept a lane tile wide (zeros past Di)
    assert paged_leaves(layer) == (
        PagedLeaf("kv_k", (G, D), 1), PagedLeaf("kv_v", (G, D), 1),
        PagedLeaf("kv_i", (1, 128), 1))
    decl = stream_counters(layer)
    assert (decl.key, decl.kind, decl.fields) == (
        "attn_stats", "sparse_attn", ("attended_positions",))
    assert decl.host([5, 20, 12]) == {
        "query_positions": 3, "context_positions": 37,
        "selected_positions": 5 + 12 + 12}
    # a table of 8 x topk is read whole, under the selection's mask
    assert layer.selected_read == "masked"
    assert layer.paged_read_tokens() == {"kv_k": CAP, "kv_v": CAP,
                                         "kv_i": CAP}
    again = layer_from_dict(json.loads(json.dumps(layer.to_dict())))
    assert again == layer and again.qk_norm == "head"


def test_with_every_new_field_at_its_default_the_layer_is_the_old_one():
    """No indexer: the leaves, the declarations and the outputs of the
    layer StarCoder2 and OLMo run; an explicit head_dim of n_out / n_heads
    is the same layer bit for bit."""
    old = SelfAttentionLayer(n_out=E, n_heads=H, n_kv_heads=G, rope=True,
                             qk_norm=True, cache_length=CAP)
    assert (old.head_dim, old.index_topk, old.index_n_heads,
            old.index_head_dim) == (None, 0, 0, 0)
    p, _ = old.init(jax.random.PRNGKey(1), InputType.recurrent(E, CAP))
    d = E // H
    assert {k: v.shape for k, v in p.items()} == {
        "Wq": (E, E), "Wk": (E, G * d), "Wv": (E, G * d), "Wo": (E, E),
        "bq": (E,), "bk": (G * d,), "bv": (G * d,), "bo": (E,),
        "q_norm": (E,), "k_norm": (G * d,)}
    assert paged_leaves(old) == (PagedLeaf("kv_k", (G, d), 1),
                                 PagedLeaf("kv_v", (G, d), 1))
    assert stream_counters(old) is None and old.paged_read_tokens() == {}
    assert old.selected_read is None
    same = SelfAttentionLayer(n_out=E, n_heads=H, n_kv_heads=G, rope=True,
                              qk_norm=True, cache_length=CAP, head_dim=d)
    p2, _ = same.init(jax.random.PRNGKey(1), InputType.recurrent(E, CAP))
    assert all(np.array_equal(p[k], p2[k]) for k in p) and set(p) == set(p2)
    x = x_of(24, n=2)
    assert np.array_equal(old.apply(p, x, {})[0], same.apply(p2, x, {})[0])
    a, sa = old.apply(p, x[:, :, :20], {}, stream=True)
    b, sb = same.apply(p2, x[:, :, :20], {}, stream=True)
    assert np.array_equal(a, b) and set(sa) == set(sb) == {
        "kv_k", "kv_v", "kv_pos"}
    # no gqa.* scope reaches the program of a layer that selects nothing
    hlo = jax.jit(lambda p, x: old.apply(p, x, {})).lower(p, x).as_text(
        debug_info=True)
    assert "gqa." not in hlo
    hlo = jax.jit(lambda p, x: make().apply(p, x, {})).lower(
        params_of(make()), x).as_text(debug_info=True)
    assert all(s in hlo for s in ("gqa.project", "gqa.index", "gqa.select",
                                  "gqa.attend"))


def test_a_head_width_of_its_own_and_the_per_head_norm_without_an_indexer():
    """head_dim != n_out / n_heads and qk_norm="head" on the plain path
    (blockwise attention, the dense cache): a layer that keeps every
    position (topk past the length) is the same function."""
    plain = SelfAttentionLayer(
        n_out=E, n_heads=H, n_kv_heads=G, head_dim=D, rope=True,
        rope_base=THETA, has_bias=False, qk_norm="head", cache_length=CAP)
    sel = make(topk=4 * CAP)
    p = params_of(sel)
    mine = {k: p[k] for k in ("Wq", "Wk", "Wv", "Wo", "q_norm", "k_norm")}
    own, _ = plain.init(jax.random.PRNGKey(0), InputType.recurrent(E, CAP))
    assert {k: v.shape for k, v in own.items()} == \
        {k: v.shape for k, v in mine.items()}
    x = x_of(40)
    want, _ = reference(p, x[0], topk=4 * CAP)
    full, _ = plain.apply(mine, x, {})
    assert np.allclose(full[0], want, atol=2e-5)
    a, state = plain.apply(mine, x[:, :, :30], {}, stream=True)
    b, state = plain.apply(mine, x[:, :, 30:], state, stream=True)
    assert np.allclose(np.concatenate([a, b], 2)[0], want, atol=2e-5)
    assert state["kv_k"].shape == (1, G, CAP, D)
    with pytest.raises(ValueError, match="qk_norm"):
        SelfAttentionLayer(n_out=E, n_heads=H, qk_norm="heads").init(
            jax.random.PRNGKey(0), InputType.recurrent(E, 8))


# ----------------------------------------------- one function, every form
@pytest.mark.parametrize("t,ties", [(8, False), (64, False), (64, True)],
                         ids=["under_topk", "over_topk", "ties"])
def test_the_training_forward_is_the_references(t, ties):
    layer = make()
    p = params_of(layer, ties=ties)
    x = x_of(t, seed=t)
    want, sel = reference(p, x[0])
    got, _ = jax.jit(lambda p, x: layer.apply(p, x, {}))(p, x)
    assert np.allclose(got[0], want, atol=2e-5)
    assert sel.sum(axis=1).tolist() == [min(TOPK, i + 1) for i in range(t)]
    if ties:
        # every score equal: the lowest indices are kept
        assert all(sel[i, :min(TOPK, i + 1)].all() for i in range(t))


@pytest.mark.parametrize("ties", [False, True], ids=["scores", "ties"])
def test_dense_streaming_is_the_training_forward(ties):
    """A first chunk of 48 (three blocks of 16 queries, its own keys slot
    for query), then a chunk of 16 against the whole cache."""
    layer = make()
    p = params_of(layer, ties=ties)
    x = x_of(64, n=2, seed=5)
    want = np.stack([reference(p, x[i])[0] for i in range(2)])
    a, state = stream(layer)(p, x[:, :, :48], {})
    assert int(state["attn_stats"]) == 2 * 3 * 16 * 48
    b, state = stream(layer)(p, x[:, :, 48:], state)
    assert int(state["attn_stats"]) == 2 * (3 * 16 * 48 + 16 * CAP)
    assert np.allclose(np.concatenate([a, b], 2), want, atol=2e-5)
    assert state["kv_i"].shape == (2, 1, CAP, 128)
    assert not np.asarray(state["kv_i"][..., DI:]).any()
    assert int(state["kv_pos"]) == 64


def test_key_slots_in_several_spans_are_the_same_function(monkeypatch):
    """Spans of 16 slots and not 4,096: the fresh prime's groups and the
    later chunk's cache go in two to six pieces joined by the running
    maximum, the shape of a bucket of 8,192 or 12,544."""
    monkeypatch.setattr(L, "_KEY_SPAN", 16)
    layer = make()
    p = params_of(layer)
    x = x_of(64, n=2, seed=5)
    want = np.stack([reference(p, x[i])[0] for i in range(2)])
    a, state = layer.apply(p, x[:, :, :48], {}, stream=True)
    b, state = layer.apply(p, x[:, :, 48:], state, stream=True)
    assert np.allclose(np.concatenate([a, b], 2), want, atol=2e-5)
    full, _ = layer.apply(p, x, {})
    assert np.allclose(full, want, atol=2e-5)


def test_a_left_padded_prime_is_the_unpadded_prompt():
    layer = make()
    p = params_of(layer)
    x = x_of(51, seed=6)
    want, _ = reference(p, x[0])
    padded = jnp.concatenate([jnp.zeros((1, E, 13)), x], axis=2)
    got, state = stream(layer)(p, padded, {}, jnp.asarray(13, jnp.int32))
    assert np.allclose(got[0, :, 13:], want, atol=2e-5)
    assert int(state["kv_pos"]) == 51
    plain, unpadded = stream(layer)(p, x, {})
    for k in ("kv_k", "kv_v", "kv_i"):
        assert np.allclose(state[k], unpadded[k], atol=1e-6)
    # one more token after either prime
    step = x_of(1, seed=7)
    a, _ = stream(layer)(p, step, state)
    b, _ = stream(layer)(p, step, unpadded)
    assert np.allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("ties", [False, True], ids=["scores", "ties"])
def test_a_prime_then_the_paged_decode_is_the_dense_stream(ties):
    """A prime through the dense cache, then one more token (a) against
    the dense cache and (b) through a page table, against the same cache
    scattered into pages: contexts of 40 (over topk) in both rows."""
    layer = make()
    p = params_of(layer, ties=ties)
    x = x_of(41, n=2, seed=8)
    _, state = stream(layer)(p, x[:, :, :40], {})
    dense, after = stream(layer)(p, x[:, :, 40:], state)
    leaves = layer.paged_leaves()
    table = np.array([[3, 9, 1, 7, 5, 11] + [0] * 6,
                      [2, 4, 6, 8, 10, 12] + [0] * 6], np.int32)
    axes = tuple(l.token_axis + 1 for l in leaves)
    pools = scatter_pages(
        [jnp.zeros(l.shape(14, 8), jnp.float32) for l in leaves],
        [state[l.key] for l in leaves], table, axes=axes)
    paged_state = {"kv_pos": jnp.full((2,), 40, jnp.int32),
                   "kv_page_table": jnp.asarray(table)}
    paged_state.update({l.page_key: pool
                        for l, pool in zip(leaves, pools)})
    paged, out = stream(layer)(p, x[:, :, 40:], paged_state)
    assert np.allclose(paged, dense, atol=2e-5)
    want = np.stack([reference(p, x[i])[0] for i in range(2)])
    assert np.allclose(paged[:, :, 0], want[:, :, 40], atol=2e-5)
    assert out["kv_pos"].tolist() == [41, 41]
    # the masked form (a table of 8 x topk) computes the scores of every
    # slot of the table
    assert int(out["attn_stats"]) == 2 * CAP
    back = gather_pages([out[l.page_key] for l in leaves], table,
                        length=CAP, axes=axes)
    for l, b in zip(leaves, back):
        assert np.allclose(b[:, :, :41], after[l.key][:, :, :41], atol=1e-6)


def test_a_context_under_topk_decodes_through_pages_too():
    layer = make()
    p = params_of(layer)
    x = x_of(9, seed=9)
    _, state = stream(layer)(p, x[:, :, :8], {})
    dense, _ = stream(layer)(p, x[:, :, 8:], state)
    leaves = layer.paged_leaves()
    table = np.array([[5, 2] + [0] * 10], np.int32)
    axes = tuple(l.token_axis + 1 for l in leaves)
    pools = scatter_pages(
        [jnp.zeros(l.shape(7, 8), jnp.float32) for l in leaves],
        [state[l.key] for l in leaves], table, axes=axes)
    paged_state = {"kv_pos": jnp.full((1,), 8, jnp.int32),
                   "kv_page_table": jnp.asarray(table)}
    paged_state.update({l.page_key: pool
                        for l, pool in zip(leaves, pools)})
    paged, _ = stream(layer)(p, x[:, :, 8:], paged_state)
    assert np.allclose(paged, dense, atol=2e-5)


def test_a_table_past_the_ratio_decodes_gathered_through_pages(monkeypatch):
    """The prime-then-decode of above with the rule's constant under this
    table's 8 x topk: the gathered form, the same token, and only the
    kept positions scored."""
    monkeypatch.setattr(L, "_MASKED_READ_RATIO", 4)
    layer = make()
    assert layer.selected_read == "gathered"
    assert layer.paged_read_tokens() == {"kv_k": TOPK, "kv_v": TOPK,
                                         "kv_i": CAP}
    p = params_of(layer)
    x = x_of(41, n=2, seed=8)
    _, state = stream(layer)(p, x[:, :, :40], {})
    dense, _ = stream(layer)(p, x[:, :, 40:], state)
    leaves = layer.paged_leaves()
    table = np.array([[3, 9, 1, 7, 5, 11] + [0] * 6,
                      [2, 4, 6, 8, 10, 12] + [0] * 6], np.int32)
    pools = scatter_pages(
        [jnp.zeros(l.shape(14, 8), jnp.float32) for l in leaves],
        [state[l.key] for l in leaves], table,
        axes=tuple(l.token_axis + 1 for l in leaves))
    paged_state = {"kv_pos": jnp.full((2,), 40, jnp.int32),
                   "kv_page_table": jnp.asarray(table)}
    paged_state.update({l.page_key: pool
                        for l, pool in zip(leaves, pools)})
    paged, out = stream(layer)(p, x[:, :, 40:], paged_state)
    assert np.allclose(paged, dense, atol=2e-5)
    assert int(out["attn_stats"]) == 2 * TOPK


def paged_inputs(cap, t, seed, n=3, ps=8):
    """Pools of random float32 keys, values and index keys behind a table
    of distinct pages a row, one query chunk of ``t`` a row at positions
    from 0 to the table's last slot."""
    rng = np.random.default_rng(seed)
    n_blk = -(-cap // ps)
    pages = 1 + n * n_blk

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    table = jnp.asarray(1 + rng.permutation(n * n_blk).reshape(n, n_blk),
                        jnp.int32)
    last = np.array([cap - t, min(5, cap - t), rng.integers(0, cap - t)])
    q_pos = jnp.asarray(last[:, None] + np.arange(t), jnp.int32)
    return (normal(n, H, t, D), normal(pages, G, ps, D),
            normal(pages, G, ps, D), normal(pages, 1, ps, 128), table,
            (normal(n, t, HI, DI), normal(n, t, HI)), q_pos)


@pytest.mark.parametrize("cap,topk,t", [
    (32, 48, 1), (98, 16, 1), (98, 16, 3),
    (4 * (L._MASKED_READ_RATIO + 1), 4, 1)],
    ids=["under_topk", "cells_ratio", "cells_ratio_chunk", "gathered_side"])
def test_both_paged_forms_are_one_function(cap, topk, t):
    """The masked and the gathered paged forms on the same pools, table,
    index keys and positions: a table narrower than topk, one at the
    longdocs cell's 6.1 x topk (a table of 104 slots for 98 positions), a
    chunk of three queries there, and one wide enough for the rule to
    take the gathered form. The same outputs to 1e-5; the masked form
    scores every slot of the table, the gathered the kept ones."""
    layer = make(topk, cap=cap)
    args = paged_inputs(cap, t, seed=cap + topk + t)
    masked, m_scored = layer._attend_paged_masked(*args)
    gathered, g_scored = layer._attend_gathered(*args)
    assert masked.shape == gathered.shape == (3, H, t, D)
    assert np.abs(np.asarray(masked) - np.asarray(gathered)).max() < 1e-5
    assert (m_scored, g_scored) == (3 * t * cap, 3 * t * min(topk, cap))


def test_the_rule_picks_each_form_by_the_tables_width():
    """``cache_length`` up to ``_MASKED_READ_RATIO`` x topk reads masked,
    one slot more gathers; the longdocs cell's table (12,544 slots, topk
    2,048) reads masked."""
    ratio = L._MASKED_READ_RATIO
    for topk in (16, 2048):
        assert make(topk, cap=ratio * topk).selected_read == \
            "masked"
        assert make(topk, cap=ratio * topk + 1).selected_read == \
            "gathered"
    assert make(2048, cap=12544).selected_read == "masked"


def test_what_the_selecting_layer_does_not_stream():
    layer = make()
    p = params_of(layer)
    x = x_of(8, n=2)
    with pytest.raises(ValueError, match="maskless"):
        layer.apply(p, x, {}, stream=True, mask=jnp.ones((2, 8)))
    _, state = layer.apply(p, x, {}, stream=True)
    rows = {**state, "kv_pos": jnp.asarray([8, 6], jnp.int32)}
    with pytest.raises(ValueError, match="page table"):
        layer.apply(p, x[:, :, :1], rows, stream=True)
    paged = {"kv_pos": jnp.zeros((2,), jnp.int32),
             "kv_page_table": jnp.zeros((2, 12), jnp.int32),
             "kv_page_k": jnp.zeros((3, G, 8, D), jnp.int8),
             "kv_page_v": jnp.zeros((3, G, 8, D), jnp.int8),
             "kv_page_scale_k": jnp.ones((3, G)),
             "kv_page_scale_v": jnp.ones((3, G))}
    with pytest.raises(ValueError, match="kv_i"):
        layer.apply(p, x[:, :, :1], paged, stream=True)
    with pytest.raises(ValueError, match="causal"):
        make(window=8).init(jax.random.PRNGKey(0),
                            InputType.recurrent(E, CAP))
    with pytest.raises(ValueError, match="index_n_heads"):
        SelfAttentionLayer(n_out=E, n_heads=H, index_topk=4).init(
            jax.random.PRNGKey(0), InputType.recurrent(E, CAP))


def test_the_gather_reads_the_pool_as_rows():
    """``_paged_gather`` is ``pool[page, :, off]``."""
    pool = jnp.asarray(np.random.default_rng(0).normal(size=(7, 3, 4, 5)),
                       jnp.float32)
    page = jnp.asarray([[1, 6, 0], [3, 3, 2]])
    off = jnp.asarray([[0, 3, 1], [2, 0, 3]])
    assert np.array_equal(L._paged_gather(pool, page, off),
                          pool[page, :, off])
