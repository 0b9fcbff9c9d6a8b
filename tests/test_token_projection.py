"""``TokenProjectionLayer`` (nn/conf/layers.py): the zoo transformer's
token embedding takes ids. Its leaves are a kernel-1
``Convolution1DLayer``'s, integer ``[N, T]`` in gives the columns of ``W``
at those ids plus ``b``, a float ``[N, V, T]`` the convolution — every
test against the ONE-HOT TWIN: the same net with ``"embed"`` swapped back
to ``Convolution1DLayer`` over the same leaves, fed the host-built
one-hot. Float32 and bfloat16 leaves (the benchmark's), on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    Convolution1DLayer, TokenProjectionLayer, layer_from_dict,
    layer_to_dict)
from deeplearning4j_tpu.nn.conf.network import (
    ComputationGraphConfiguration)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.updater import Sgd
from deeplearning4j_tpu.serving import GenerationEngine, PagedKVConfig
from deeplearning4j_tpu.util import decoding
from deeplearning4j_tpu.util.decoding import (
    prime_prompt, step_tokens, takes_ids)
from deeplearning4j_tpu.zoo import TextGenerationTransformer

V, E, L = 24, 16, 32
DTYPES = ["float32", "bfloat16"]
PROMPT = [5, 17, 3, 3, 22, 9, 1, 14, 8, 2, 19]       # 11: bucket 16, 5 pads


def _zoo(**kw):
    return TextGenerationTransformer(
        vocab_size=V, embed_dim=E, n_heads=2, n_layers=2, max_length=L,
        positional="rope", n_kv_heads=1, **kw)


def _net(conf, dtype, params=None):
    """A graph over `conf` computing in `dtype`, its leaves stored in it
    (the benchmark installs bfloat16 leaves under a bfloat16 conf)."""
    if dtype == "bfloat16":
        conf.dtype = dtype
    net = ComputationGraph(conf).init()
    # a copy of its own: ``fit`` donates the leaves it steps from
    net.params = jax.tree_util.tree_map(
        lambda a: jnp.array(a, dtype), net.params if params is None
        else params)
    return net


def _twin_conf(zoo):
    conf = zoo.conf()
    conf.vertices["embed"].layer = Convolution1DLayer(
        n_out=E, kernel=1, convolution_mode="same", activation="identity")
    return conf


@pytest.fixture(scope="module", params=DTYPES)
def pair(request):
    """(the zoo net, its one-hot twin over the same leaves, the dtype)."""
    zoo = _zoo()
    net = _net(zoo.conf(), request.param)
    twin = _net(_twin_conf(zoo), request.param, params=net.params)
    assert not takes_ids(twin)
    return net, twin, request.param


def _fresh(*nets):
    for n in nets:
        n.rnn_clear_previous_state()


def test_embed_keeps_the_convolutions_leaves_and_the_net_takes_ids(pair):
    net, twin, dtype = pair
    assert isinstance(net.conf.vertices["embed"].layer,
                      TokenProjectionLayer)
    assert {k: (v.shape, str(v.dtype))
            for k, v in net.params["embed"].items()} == \
        {"W": ((E, V, 1), dtype), "b": ((E,), dtype)}
    assert takes_ids(net)
    # every zoo transformer, learned positions too, with no argument
    assert takes_ids(ComputationGraph(TextGenerationTransformer(
        vocab_size=V, embed_dim=E, n_heads=2, n_layers=1,
        max_length=L).conf()))
    # the same initialiser: the twin's own init draws the same leaves
    own = ComputationGraph(_twin_conf(_zoo())).init()
    zoo_own = _zoo().init()
    for k in ("W", "b"):
        np.testing.assert_array_equal(
            np.asarray(own.params["embed"][k]),
            np.asarray(zoo_own.params["embed"][k]))


@pytest.mark.parametrize("dtype", DTYPES)
def test_apply_on_ids_equals_apply_on_their_one_hot(dtype):
    layer = TokenProjectionLayer(n_out=E, activation="identity",
                                 weight_init="xavier")
    it = InputType.recurrent(V, L)
    params, state = layer.init(jax.random.PRNGKey(3), it)
    params = {"W": params["W"].astype(dtype),
              "b": jnp.linspace(-1, 1, E).astype(dtype)}
    assert layer.output_type(it) == Convolution1DLayer(
        n_out=E, kernel=1, convolution_mode="same").output_type(it)
    ids = np.random.default_rng(0).integers(0, V, (3, 7))
    hot = jnp.asarray(decoding._one_hot(ids, V)).astype(dtype)
    for x in (ids.astype(np.int32), ids):        # int32 and int64 ids
        y, _ = layer.apply(params, jnp.asarray(x), state)
        y_hot, _ = layer.apply(params, hot, state)
        assert y.shape == (3, E, 7) and y.dtype == y_hot.dtype == dtype
        np.testing.assert_array_equal(np.asarray(y, np.float32),
                                      np.asarray(y_hot, np.float32))
    # and is the columns of W plus b
    want = (params["W"][:, :, 0].T[ids] + params["b"]).transpose(0, 2, 1)
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(want, np.float32))
    # a float [N, V, T] that is no one-hot stays the convolution
    soft = jax.nn.softmax(jnp.asarray(
        np.random.default_rng(1).normal(size=(2, V, 5)), dtype), axis=1)
    y_soft, _ = layer.apply(params, soft, state)
    conv = Convolution1DLayer(n_out=E, kernel=1, convolution_mode="same",
                              activation="identity")
    y_conv, _ = conv.apply(params, soft, state)
    np.testing.assert_array_equal(np.asarray(y_soft, np.float32),
                                  np.asarray(y_conv, np.float32))
    with pytest.raises(ValueError, match="integer ids"):
        layer.apply(params, jnp.zeros((2, 5), jnp.float32), state)


def test_left_padded_chunk_real_positions_equal_the_twins(pair):
    """Pads carry id 0 where the one-hot carried a zero column: masked
    out of attention, never in the pool; no real position moves."""
    net, twin, _ = pair
    pad = 16 - len(PROMPT)
    ids = np.asarray([0] * pad + PROMPT, np.int32)[None, :]
    hot = decoding._one_hot(ids, V)
    hot[:, :, :pad] = 0.0
    _fresh(net, twin)
    got = np.asarray(net.rnn_time_step(ids, pad_left=pad))
    want = np.asarray(twin.rnn_time_step(hot, pad_left=pad))
    np.testing.assert_array_equal(got[:, :, pad:], want[:, :, pad:])


@pytest.mark.parametrize("padded", [True, False],
                         ids=["padded", "chunked"])
def test_prime_prompt_and_step_tokens_equal_the_twins(pair, padded):
    net, twin, _ = pair
    _fresh(net, twin)
    kw = dict(padded=padded, chunk_max=None if padded else 4)
    seen = []

    class Watch(decoding.RoundTrip):
        def h2d(self, x):
            seen.append((x.dtype, x.shape))

    p = prime_prompt(net, PROMPT, V, io=Watch(), **kw)
    q = prime_prompt(twin, PROMPT, V, **kw)
    np.testing.assert_array_equal(p, q)
    # what went up: int32 [1, width], 4 bytes a position
    assert all(dt == np.int32 and len(shape) == 2 for dt, shape in seen)
    assert sum(s[1] for _, s in seen) == (16 if padded else len(PROMPT))
    tok = int(p.argmax())
    for _ in range(4):
        p = step_tokens(net, [tok], V)
        q = step_tokens(twin, [tok], V)
        np.testing.assert_array_equal(p, q)
        tok = int(p[0].argmax())


@pytest.mark.parametrize("dtype", DTYPES)
def test_fit_on_one_hot_data_gives_the_twins_loss_and_gradients(dtype):
    """Training sends float [N, V, T]: the convolution, as before. One
    SGD step (new = old - lr * gradient) from the same leaves."""
    zoo = _zoo(updater=Sgd(0.5))
    net = _net(zoo.conf(), dtype)
    twin = _net(_twin_conf(zoo), dtype, params=net.params)
    rng = np.random.default_rng(4)
    ids = rng.integers(0, V, (4, 12))
    ds = DataSet(decoding._one_hot(ids[:, :-1], V),
                 decoding._one_hot(ids[:, 1:], V))
    before = jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float32), net.params)
    assert net.score(ds) == twin.score(ds)
    net.fit(ds)
    twin.fit(ds)
    moved = 0
    for vertex, leaves in net.params.items():
        for k, a in leaves.items():
            np.testing.assert_array_equal(
                np.asarray(a, np.float32),
                np.asarray(twin.params[vertex][k], np.float32))
            moved += bool((np.asarray(a, np.float32) !=
                           before[vertex][k]).any())
    assert moved >= 10 and (
        np.asarray(net.params["embed"]["W"], np.float32) !=
        before["embed"]["W"]).any()
    assert net.score(ds) == twin.score(ds)


def test_configuration_round_trip():
    layer = TokenProjectionLayer(n_out=E, activation="identity")
    d = layer_to_dict(layer)
    assert d["@class"] == "TokenProjectionLayer" and d["kernel"] == 1
    assert layer_from_dict(d) == layer
    conf = _zoo().conf()
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    assert isinstance(again.vertices["embed"].layer, TokenProjectionLayer)
    assert takes_ids(ComputationGraph(again))
    # a saved configuration that names Convolution1DLayer loads as the
    # one-hot net it was
    old = ComputationGraphConfiguration.from_json(
        _twin_conf(_zoo()).to_json())
    assert type(old.vertices["embed"].layer) is Convolution1DLayer
    assert not takes_ids(ComputationGraph(old))


def test_engine_with_paged_kv_sends_ids(pair):
    net, twin, _ = pair
    eng = GenerationEngine(net, V, slots=2,
                           paging=PagedKVConfig(page_size=4))
    prompts, steps = [PROMPT, [4, 4, 7, 20, 11]], [6, 4]
    handles = [eng.submit(p, steps=s, top_k=1,
                          rng=np.random.default_rng(0))
               for p, s in zip(prompts, steps)]
    eng.run_until_idle()
    h = eng.health()
    io = h["host_io"]
    assert io["input_form"] == "ids"
    # a prime's upload is 4 bytes a bucket position ...
    assert h["prefill"]["bucket_tokens"] == 16 + 8
    assert io["prefill"]["h2d_bytes"] == 4 * (16 + 8)
    # ... and a decode cycle's the [S, 1] int32 token vector: no S x V x 4
    cycles = h["decode_dispatch"]["count"]
    assert cycles > 0
    assert io["decode"]["h2d_bytes"] == cycles * 2 * 4 < 2 * V * 4
    zoo = _zoo()
    for handle, p, s in zip(handles, prompts, steps):
        _fresh(twin)
        want = zoo.sample_stream(twin, p, s, top_k=1, prime_padded=True)
        assert handle.result(timeout=0) == want
    eng.shutdown()
    # the twin behind the same engine says so
    other = GenerationEngine(twin, V, slots=2,
                             paging=PagedKVConfig(page_size=4))
    assert other.health()["host_io"]["input_form"] == "one-hot"
    other.shutdown()
