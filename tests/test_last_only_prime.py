"""A prime answers for its last position (ISSUE 37): the caller of a
streaming forward says that it will read the last position only
(``rnn_time_step(last_only=True)``; ``util.decoding.prime_prompt`` asks),
and a per-position head then computes, and the host then fetches,
``[N, V]`` and nothing wider.

Pinned here, on tiny nets compiled once a module: the asked answer is the
last column of the all-positions answer and the state a prime leaves is
the same bit for bit (padded, chunked, behind a prefix; a zoo transformer
as ``ComputationGraph`` and a ``MultiLayerNetwork`` headed by
``RnnOutputLayer``); the prime program's result is ``[1, V]`` and neither
its product nor its softmax is ``V x P`` wide; who does not ask
(``verify_tokens``, a decode step, in-engine speculation) gets what it
got; a ``LastStepOutputLayer`` net compiles the program it compiled; the
engine's ``health()["host_io"]["prefill"]`` counts one position a prime.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.layers import (
    LastStepOutputLayer, RnnOutputLayer, SelfAttentionLayer)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater import Adam
from deeplearning4j_tpu.serving import (
    GenerationEngine, PagedKVConfig, SpeculationConfig)
from deeplearning4j_tpu.serving.engine import _HostIO
from deeplearning4j_tpu.util import decoding
from deeplearning4j_tpu.zoo import TextGenerationTransformer

V = 13                     # no other axis of these nets is 13 wide
PREFIX = [4, 9, 1, 12]
IDS = [1, 2, 3, 4, 5, 11]  # bucket 8: two left pads; chunks 4 + 2


@pytest.fixture(scope="module")
def zoo():
    return TextGenerationTransformer(vocab_size=V, embed_dim=16, n_heads=2,
                                     n_layers=1, max_length=32,
                                     positional="rope")


@pytest.fixture(scope="module")
def graph_net(zoo):
    return zoo.init()


@pytest.fixture(scope="module")
def mln_net():
    conf = (NeuralNetConfiguration.Builder()
            .seed(3).updater(Adam(1e-3)).weight_init("xavier").list()
            .layer(SelfAttentionLayer(n_out=16, n_heads=4, causal=True,
                                      activation="identity",
                                      cache_length=32))
            .layer(RnnOutputLayer(n_out=V, loss="mcxent",
                                  activation="softmax"))
            .set_input_type(InputType.recurrent(V, 32))
            .build())
    return MultiLayerNetwork(conf).init()


@pytest.fixture(scope="module")
def last_step_net(zoo):
    """The zoo transformer with the head the three newer zoo models
    have: ``LastStepOutputLayer`` on the same leaves."""
    conf = zoo.conf()
    conf.vertices["out"].layer = LastStepOutputLayer(
        n_out=V, loss="mcxent", activation="softmax")
    return ComputationGraph(conf).init()


@pytest.fixture(scope="module")
def nets(graph_net, mln_net):
    return {"graph": graph_net, "mln": mln_net}


def _state(net):
    return jax.tree_util.tree_map(np.asarray, net.state)


def _fresh(net, prefix):
    net.rnn_clear_previous_state()
    if prefix:
        decoding._prime(net, prefix, V)


def _stream_fn(net, padded: bool, last_only: bool):
    """The jitted streaming forward `net` keeps for these statics."""
    if isinstance(net, MultiLayerNetwork):
        return net._get_output_fn(False, True, stream=True, padded=padded,
                                  last_only=last_only)
    return net._jit_cache[("rnn_step", padded, False, last_only,
                           net.conf.dtype, L._STREAM_CACHE_SHARDING,
                           net._paged_reads())]


def _lower_padded(net, last_only: bool, P: int = 8, pad: int = 2):
    """Lower the padded prime program at bucket `P` from a fresh state
    (traced by a call first, so that the graph's function exists)."""
    net.rnn_clear_previous_state()
    x = decoding._encode(net, np.zeros((1, P), np.int64), V)
    net.rnn_time_step(x, pad_left=pad, last_only=last_only)
    net.rnn_clear_previous_state()
    ins = jnp.asarray(x) if isinstance(net, MultiLayerNetwork) \
        else net._as_input_dict([jnp.asarray(x)])
    return _stream_fn(net, True, last_only).lower(
        net.params, net.state, ins, jax.random.PRNGKey(0),
        jnp.asarray(pad, jnp.int32))


MODES = {"padded": (True, None), "chunked": (False, None),
         "padded_behind_a_prefix": (True, PREFIX),
         "chunked_behind_a_prefix": (False, PREFIX)}


@pytest.mark.parametrize("mode", sorted(MODES))
@pytest.mark.parametrize("kind", ["graph", "mln"])
def test_prime_prompt_is_the_last_column_and_leaves_the_same_state(
        nets, kind, mode):
    net = nets[kind]
    padded, prefix = MODES[mode]
    _fresh(net, prefix)
    out = (decoding._prime_padded if padded else decoding._prime)(
        net, IDS, V)
    full = decoding._probs(out)
    assert full.shape == (1, V, 8 if padded else 2)
    want_state = _state(net)
    _fresh(net, prefix)
    p = decoding.prime_prompt(net, IDS, V, padded=padded)
    assert p.shape == (V,) and p.dtype == np.float32
    np.testing.assert_allclose(p, full[0, :, -1], rtol=1e-6, atol=1e-7)
    got_state = _state(net)
    assert jax.tree_util.tree_structure(got_state) == \
        jax.tree_util.tree_structure(want_state)
    for a, b in zip(jax.tree_util.tree_leaves(got_state),
                    jax.tree_util.tree_leaves(want_state)):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def _wide_results(text: str, shape: str):
    """Products and exponentials of a lowered program whose RESULT type
    holds `shape` (operands may: a net fed ids builds its one-hot
    ``[N, V, P]`` inside the program)."""
    out = []
    for line in text.splitlines():
        if any(op in line for op in ("stablehlo.dot_general",
                                     "stablehlo.convolution",
                                     "stablehlo.exponential")):
            if shape in line.split("->")[-1].split(" : ")[-1]:
                out.append(line.strip())
    return out


@pytest.mark.parametrize("kind", ["graph", "mln"])
def test_the_prime_program_answers_1_by_V_and_holds_no_V_by_P_result(
        nets, kind):
    net = nets[kind]
    asked = _lower_padded(net, True)
    whole = _lower_padded(net, False)
    out_asked = jax.tree_util.tree_leaves(asked.out_info[0])[0]
    out_whole = jax.tree_util.tree_leaves(whole.out_info[0])[0]
    assert out_asked.shape == (1, V) and out_whole.shape == (1, V, 8)
    assert out_asked.dtype == out_whole.dtype == jnp.float32
    # the all-positions program computes the head and its softmax over
    # [1, V, P]; the asked one over [1, V, 1], and nothing V x P wide
    assert _wide_results(whole.as_text(), f"1x{V}x8x")
    assert not _wide_results(asked.as_text(), f"x{V}x8x")
    assert _wide_results(asked.as_text(), f"1x{V}x1x")


@pytest.mark.parametrize("kind", ["graph", "mln"])
def test_who_does_not_ask_gets_every_position(nets, kind):
    """``verify_tokens`` reads 1 + gamma positions and a decode step's
    output keeps its time axis: neither asks."""
    net = nets[kind]
    net.rnn_clear_previous_state()
    probs = decoding.verify_tokens(net, [[1, 2, 3], [4, 5, 6]], V)
    assert probs.shape == (2, V, 3)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-5)
    out = net.rnn_time_step(decoding._encode(net, np.array([[7], [8]]), V))
    assert decoding._probs(out).shape == (2, V, 1)
    assert decoding.step_tokens(net, [1, 2], V).shape == (2, V)


def test_a_graph_narrows_only_a_per_position_head_that_feeds_nothing(
        graph_net, last_step_net):
    assert graph_net._last_only_heads() == {"out"}
    # a head that answers [N, V] already narrows itself
    assert last_step_net._last_only_heads() == set()
    assert L.narrows_to_last(RnnOutputLayer(n_out=3))
    assert not L.narrows_to_last(LastStepOutputLayer(n_out=3))
    assert not L.narrows_to_last(SelfAttentionLayer(n_out=4, n_heads=2))
    y = jnp.arange(24.0).reshape(2, 3, 4)
    assert np.array_equal(L.last_position(y), y[:, :, -1])
    assert L.last_position(y[:, :, 0]).shape == (2, 3)


def test_a_last_step_head_compiles_the_program_it_compiled(last_step_net):
    """Asking a head that already answers ``[N, V]`` is a no-op: the
    same lowered text, the same result."""
    net = last_step_net
    asked, plain = _lower_padded(net, True), _lower_padded(net, False)
    assert asked.as_text() == plain.as_text()
    net.rnn_clear_previous_state()
    want = decoding._probs(decoding._prime_padded(net, IDS, V))
    assert want.shape == (1, V)
    want_state = _state(net)
    net.rnn_clear_previous_state()
    got = decoding.prime_prompt(net, IDS, V, padded=True)
    assert np.array_equal(got, want[0])
    for a, b in zip(jax.tree_util.tree_leaves(_state(net)),
                    jax.tree_util.tree_leaves(want_state)):
        assert np.array_equal(a, b)


def test_sample_stream_is_what_all_positions_priming_gives(zoo, graph_net):
    """The one-shot decoder primes through ``prime_prompt``: greedy ids
    equal those from the all-positions prime and plain decode steps."""
    net = graph_net
    got = zoo.sample_stream(net, IDS, steps=5, top_k=1)
    net.rnn_clear_previous_state()
    p = decoding._probs(decoding._prime(net, IDS, V))[0, :, -1]
    ids = list(IDS)
    for _ in range(5):
        ids.append(int(np.argmax(p)))
        p = decoding.step_tokens(net, [ids[-1]], V)[0]
    assert got == ids


def test_in_engine_speculation_over_a_zoo_transformer_commits_the_same(
        zoo, graph_net):
    """The verify chunk does not ask, so ``[S, V, 1 + gamma]`` comes
    back and a speculative engine over an ``RnnOutputLayer`` head builds
    and commits the one-shot decoder's greedy tokens."""
    prompts = [[1, 2, 3] * 3, [6, 7] * 4, [5, 5, 9] * 2]
    ref = [zoo.sample_stream(graph_net, p, steps=8, top_k=1)
           for p in prompts]
    eng = GenerationEngine(
        graph_net, V, slots=2, paging=PagedKVConfig(page_size=4),
        speculation=SpeculationConfig(
            draft=decoding.prompt_lookup_proposer(2), gamma=2))
    hs = [eng.submit(p, steps=8, top_k=1, rng=np.random.default_rng(i))
          for i, p in enumerate(prompts)]
    eng.run_until_idle()
    assert [h.result(timeout=0) for h in hs] == ref
    assert eng.health()["speculation"] == {"gamma": 2}


@pytest.mark.parametrize("prime_padded", [True, False])
def test_health_counts_one_fetched_position_a_prime(graph_net,
                                                    prime_padded):
    eng = GenerationEngine(graph_net, V, slots=2,
                           prime_padded=prime_padded)
    prompts = [IDS, [3, 1, 2], list(range(1, 10))]
    hs = [eng.submit(p, steps=3, top_k=1) for p in prompts]
    eng.run_until_idle()
    for h in hs:
        h.result(timeout=0)
    pre = eng.health()["host_io"]["prefill"]
    assert pre["results"] == pre["result_positions"] == len(prompts)
    assert pre["d2h_bytes"] == len(prompts) * V * 4
    # the decode kind counts bytes alone
    assert set(eng.health()["host_io"]["decode"]) == {"h2d_bytes",
                                                      "d2h_bytes"}


def test_host_io_counts_the_positions_of_a_result_that_came_back_whole():
    """What the counter would read of a prime that did not engage the
    mechanism: P positions a result, and the bytes with them."""
    io = _HostIO("prefill", widths=True)
    io.d2h(np.zeros((1, V, 8), np.float32))
    io.d2h(np.zeros((1, V), np.float32))
    assert io.as_dict() == {"h2d_bytes": 0, "d2h_bytes": (8 + 1) * V * 4,
                            "results": 2, "result_positions": 8 + 1}
    assert _HostIO("decode").as_dict() == {"h2d_bytes": 0, "d2h_bytes": 0}
