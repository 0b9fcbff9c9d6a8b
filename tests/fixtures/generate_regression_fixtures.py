"""Generate the serialized-format regression fixtures committed under
tests/fixtures/ (run from the repo root: python
tests/fixtures/generate_regression_fixtures.py).

Mirrors the reference's regressiontest suites
(deeplearning4j-core/src/test/java/org/deeplearning4j/regressiontest/
RegressionTest080.java et al.): models serialized by an OLD build are
committed, and every later build must keep loading them bit-exactly.
Regenerating the fixtures is an explicit format break — don't do it
casually.
"""

import os
import sys

import numpy as np

# fixtures are generated on CPU whatever JAX_PLATFORMS says (jax.config
# wins over the env var, as in tests/conftest.py)
import jax

jax.config.update("jax_platforms", "cpu")

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "..", ".."))

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.graph_conf import (  # noqa: E402
    ElementWiseVertex, MergeVertex,
)
from deeplearning4j_tpu.nn.conf.inputs import InputType  # noqa: E402
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration  # noqa: E402
from deeplearning4j_tpu.nn.graph import ComputationGraph  # noqa: E402
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.nn.updater import Adam, Nesterovs  # noqa: E402
from deeplearning4j_tpu.util.model_serializer import write_model  # noqa: E402


def mln():
    conf = (NeuralNetConfiguration.Builder()
            .seed(101)
            .updater(Adam(0.001))
            .list()
            .layer(L.ConvolutionLayer(n_out=4, kernel=(3, 3),
                                      convolution_mode="same",
                                      activation="relu"))
            .layer(L.BatchNormalization())
            .layer(L.SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
            .layer(L.DenseLayer(n_out=8, activation="tanh"))
            .layer(L.OutputLayer(n_out=3, activation="softmax",
                                 loss="negativeloglikelihood"))
            .set_input_type(InputType.convolutional(8, 8, 2))
            .build())
    net = MultiLayerNetwork(conf).init()
    # a couple of train steps so updater state + BN running stats are
    # non-trivial in the fixture
    rng = np.random.default_rng(7)
    x = rng.standard_normal((4, 2, 8, 8)).astype(np.float32)
    y = np.zeros((4, 3), np.float32)
    y[np.arange(4), rng.integers(0, 3, 4)] = 1.0
    net.fit(x, y, epochs=2, batch_size=4)
    return net, x


def cg():
    conf = (NeuralNetConfiguration.Builder()
            .seed(202)
            .updater(Nesterovs(0.01, momentum=0.9))
            .graph_builder()
            .add_inputs("in")
            .set_input_types(InputType.recurrent(5, 7))
            .add_layer("lstm", L.GravesLSTM(n_out=6, activation="tanh"), "in")
            .add_layer("lstm2", L.LSTM(n_out=6, activation="tanh"), "in")
            .add_vertex("add", ElementWiseVertex(op="add"), "lstm", "lstm2")
            .add_vertex("mrg", MergeVertex(), "add", "lstm")
            .add_layer("out", L.RnnOutputLayer(n_out=4, loss="mcxent",
                                               activation="softmax"), "mrg")
            .set_outputs("out")
            .build())
    net = ComputationGraph(conf).init()
    rng = np.random.default_rng(9)
    x = rng.standard_normal((3, 5, 7)).astype(np.float32)
    return net, x


def tfm():
    """Transformer stack fixture (v1, added later than mln/cg): pins the
    SelfAttentionLayer / LayerNormalization / PositionalEmbeddingLayer
    serde + checkpoint formats."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.zoo import TextGenerationTransformer

    model = TextGenerationTransformer(vocab_size=12, seed=303, embed_dim=16,
                                      n_heads=2, n_layers=2, max_length=10,
                                      updater=Adam(0.001))
    net = model.init()
    rng = np.random.default_rng(11)
    ids = rng.integers(0, 12, (2, 10))
    x = np.zeros((2, 12, 10), np.float32)
    x[np.arange(2)[:, None], ids, np.arange(10)[None, :]] = 1.0
    y = np.roll(x, -1, axis=2)
    net.fit(DataSet(x, y))   # non-trivial updater state in the fixture
    return net, x


def params_sha256(params) -> str:
    """Deterministic digest over the param pytree (sorted path order,
    float32 little-endian bytes) — pins the decode path bit-exactly."""
    import hashlib
    h = hashlib.sha256()

    def walk(tree, path):
        if isinstance(tree, dict):
            for k in sorted(tree):
                walk(tree[k], path + "/" + str(k))
        elif tree is not None and hasattr(tree, "shape"):
            h.update(path.encode())
            h.update(np.ascontiguousarray(
                np.asarray(tree, np.float32)).tobytes())

    walk(params, "")
    return h.hexdigest()


def main(which=("mln", "cg", "tfm")):
    import json

    try:
        with open(os.path.join(ROOT, "regression_checksums.json")) as f:
            sums = json.load(f)
    except FileNotFoundError:
        sums = {}

    unknown = set(which) - {"mln", "cg", "tfm"}
    if unknown:
        sys.exit(f"unknown fixture name(s): {sorted(unknown)} "
                 "(choose from mln, cg, tfm)")

    if "mln" in which:
        net, x = mln()
        write_model(net, os.path.join(ROOT, "regression_mln_v1.zip"))
        np.save(os.path.join(ROOT, "regression_mln_v1_input.npy"), x)
        np.save(os.path.join(ROOT, "regression_mln_v1_output.npy"),
                np.asarray(net.output(x)))
        with open(os.path.join(ROOT, "regression_mln_v1.json"), "w") as f:
            f.write(net.conf.to_json())
        sums["mln_v1_params"] = params_sha256(net.params)

    def write_graph_fixture(name, builder):
        g, xg = builder()
        write_model(g, os.path.join(ROOT, f"regression_{name}_v1.zip"))
        np.save(os.path.join(ROOT, f"regression_{name}_v1_input.npy"), xg)
        out = g.output(xg)
        np.save(os.path.join(ROOT, f"regression_{name}_v1_output.npy"),
                np.asarray(out[0] if isinstance(out, (list, tuple))
                           else out))
        with open(os.path.join(ROOT, f"regression_{name}_v1.json"),
                  "w") as f:
            f.write(g.conf.to_json())
        sums[f"{name}_v1_params"] = params_sha256(g.params)

    for name, builder in (("cg", cg), ("tfm", tfm)):
        if name in which:
            write_graph_fixture(name, builder)

    with open(os.path.join(ROOT, "regression_checksums.json"), "w") as f:
        json.dump(sums, f, indent=2)
    print("fixtures written to", ROOT, "(", ", ".join(which), ")")


if __name__ == "__main__":
    main(tuple(sys.argv[1:]) or ("mln", "cg", "tfm"))
