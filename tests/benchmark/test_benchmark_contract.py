"""BENCHMARK.json against the builder's contract, the loader's data-driven
promise, the last line's keys and the refusal of a non-TPU backend."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark import compare, harness

ROOT = harness.ROOT
BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_are_exactly_the_contracts():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = len(BENCH["workloads"])
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= \
        max(1, cells // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536


ENTRY_KEYS = [
    ("configs", {"name", "source", "file", "reduced", "why"}, set()),
    ("workloads", {"name", "config", "traffic", "chips", "why"}, set()),
    ("end_to_end", {"name", "unit", "better", "bound", "source"},
     {"workloads"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"},
     {"workloads"}),
]


@pytest.mark.parametrize("section,keys,optional", ENTRY_KEYS)
def test_entries_have_just_the_keys_shown(section, keys, optional):
    names = [e["name"] for e in BENCH[section]]
    assert len(names) == len(set(names))
    for e in BENCH[section]:
        assert keys <= set(e) <= keys | optional, e["name"]
        assert NAME.match(e["name"]), e["name"]
        for k in ("why", "source", "layer"):
            if k in e and section in ("configs", "workloads", "per_layer") \
                    and k != "source" or (k == "source"
                                          and section == "configs"):
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                    and "\t" not in e[k], (e["name"], k)


def test_metrics_units_sources_and_bounds():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1, m
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e, m
        if m["name"].endswith("roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_files_of_its_own(name):
    cell = harness.Cell(BENCH, name, root=ROOT)
    # the runner, the model and its plain reference are found by name
    assert callable(cell.runner().run)
    assert cell.model() is cell.model()
    assert callable(cell.reference().param_specs)
    assert set(cell.limits)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]))
        # what it moves is reported in this cell
        assert m["moves"] in reported, (name, m["name"])
    for m in BENCH["per_layer"]:
        for w in m["workloads"]:
            assert w in CELLS


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_files(cfg):
    """A configuration cut to one chip's share says so: what ``reduced``
    names is a key of the file, ``published`` holds the source's value of
    just those keys, each other than the value held, and ``deployment``
    says in one line over how many chips each layer is divided, and how.
    A whole model has no ``published``. (The floors of a cut, a period and
    four layers, 8 experts, an eighth of the vocabulary, are the issue's
    and the reviewer's to hold: this cannot know a model's key names.)"""
    assert any(cfg["file"].startswith(p + "/") for p in BENCH["paths"])
    data = harness._load_json(os.path.join(ROOT, cfg["file"]))
    assert data["source"] == cfg["source"]
    assert data["reduced"] == cfg["reduced"]
    if cfg["reduced"]:
        assert len(set(cfg["reduced"])) == len(cfg["reduced"]) <= 16
        assert set(cfg["reduced"]) <= set(data), "reduced names no key"
        assert set(data.get("published", {})) == set(cfg["reduced"])
        for key in cfg["reduced"]:
            assert data["published"][key] != data[key], key
        line = data.get("deployment")
        assert isinstance(line, str) and 1 <= len(line) <= 200 \
            and "\n" not in line and "\t" not in line
    else:
        assert "published" not in data
    assert data["assumed"]
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))


def test_command_names_no_file_outside_paths():
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
        assert not p.startswith("/") and ".." not in p


#: a model the benchmark has never seen, as a later PR would bring it: the
#: program's side, from the DSL ...
DUMMY_MODEL = '''
import numpy as np


def build(cfg):
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.updater import Nesterovs
    g = (NeuralNetConfiguration.Builder().seed(1)
         .updater(Nesterovs(cfg["learning_rate"], momentum=cfg["momentum"]))
         .graph_builder().add_inputs("input")
         .set_input_types(InputType.feed_forward(cfg["n_in"])))
    g.add_layer("hidden", DenseLayer(n_out=cfg["hidden"],
                                     activation="tanh"), "input")
    g.add_layer("output", OutputLayer(n_out=cfg["classes"], loss="mcxent",
                                      activation="softmax"), "hidden")
    g.set_outputs("output")
    return ComputationGraph(g.build()).init()


def batches(cfg, rows, seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, cfg["n_in"]), dtype=np.float32)
    y = np.zeros((rows, cfg["classes"]), np.float32)
    y[np.arange(rows), rng.integers(0, cfg["classes"], rows)] = 1.0
    return x, y


def first_gradient(cfg, updater_state):
    import jax
    return jax.tree_util.tree_map(lambda v: -v / cfg["learning_rate"],
                                  updater_state["v"])
'''

#: ... and its plain reference, which imports nothing of the program
DUMMY_REFERENCE = '''
import jax
import jax.numpy as jnp


def param_specs(cfg):
    return [("hidden/W", (cfg["n_in"], cfg["hidden"]), 0.0, 0.3),
            ("hidden/b", (cfg["hidden"],), 0.0, 0.1),
            ("output/W", (cfg["hidden"], cfg["classes"]), 0.0, 0.3),
            ("output/b", (cfg["classes"],), 0.0, 0.1)]


def train_flops(cfg):
    return 6 * (cfg["n_in"] * cfg["hidden"] + cfg["hidden"] * cfg["classes"])


def _loss(p, x, y, rows):
    if rows is not None:
        x, y = x[:rows], y[:rows]
    h = jnp.tanh(x @ p["hidden/W"] + p["hidden/b"])
    logp = jax.nn.log_softmax(h @ p["output/W"] + p["output/b"])
    return jnp.mean(-jnp.sum(y * logp, axis=-1))


def train_readings(cfg, params0, batches, low=False, rows=None):
    lr, mu = cfg["learning_rate"], cfg["momentum"]
    norm = lambda t: {k: float(jnp.linalg.norm(v)) for k, v in t.items()}
    p = dict(params0)
    vel = {k: jnp.zeros_like(v) for k, v in p.items()}
    losses, grad1 = [], None
    for x, y in batches:
        loss, g = jax.value_and_grad(_loss)(p, x, y, rows)
        if low:                      # a control that is plainly not correct
            g = {k: 0.5 * v for k, v in g.items()}
        vel = {k: mu * vel[k] - lr * g[k] for k in p}
        p = {k: p[k] + mu * vel[k] - lr * g[k] for k in p}
        losses.append(float(loss))
        grad1 = grad1 or norm(g)
    return {"losses": losses, "grad1_norms": grad1,
            "change_norms": norm({k: p[k] - params0[k] for k in p})}
'''

#: a kind of traffic the benchmark has never seen: one general generator
DUMMY_RUNNER = '''
import time
from benchmark import compare


def run(cell, args, devices, clock0, tracer=None, control=False):
    t0 = time.perf_counter()
    pings = cell.traffic["pings"]
    return {"setup_s": t0 - clock0, "window_s": time.perf_counter() - t0,
            "attempted": pings, "failed": 0, "memory_peak_bytes": 0,
            "end_to_end": {"train_samples_per_s": float(pings)},
            "checks": [compare.Check("pings_lost", 0, cell.limits["lost"])]}
'''

#: a decoder the serving runner has never seen, cut to a chip's share: one
#: parallel block (a single norm feeds attention and the FFN side by side),
#: one key/value head, no bias on any position-wise product, ReLU. Not the
#: zoo transformer's shape; it borrows only the weightless init
DUMMY_DECODER = '''
def build_shell(cfg, max_length):
    from deeplearning4j_tpu.nn.conf.graph_conf import ElementWiseVertex
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (
        Convolution1DLayer, LayerNormalization, RnnOutputLayer,
        SelfAttentionLayer)
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from benchmark.models.starcoder2 import _shell_init

    e = cfg["hidden_size"]

    def linear(n_out, act="identity"):
        return Convolution1DLayer(n_out=n_out, kernel=1, has_bias=False,
                                  convolution_mode="same", activation=act)

    g = (NeuralNetConfiguration.Builder().seed(1).graph_builder()
         .add_inputs("in").set_input_types(
             InputType.recurrent(cfg["vocab_size"], max_length)))
    g.add_layer("embed", linear(e), "in")
    prev = "embed"
    for n in range(cfg["num_hidden_layers"]):
        g.add_layer(f"ln{n}", LayerNormalization(), prev)
        g.add_layer(f"attn{n}", SelfAttentionLayer(
            n_out=e, n_heads=cfg["num_attention_heads"], n_kv_heads=1,
            causal=True, rope=True, rope_base=cfg["rope_theta"],
            cache_length=max_length, activation="identity"), f"ln{n}")
        g.add_layer(f"up{n}", linear(cfg["intermediate_size"], "relu"),
                    f"ln{n}")
        g.add_layer(f"down{n}", linear(e), f"up{n}")
        g.add_vertex(f"res{n}", ElementWiseVertex(op="add"), prev,
                     f"attn{n}", f"down{n}")
        prev = f"res{n}"
    g.add_layer("ln_f", LayerNormalization(), prev)
    g.add_layer("out", RnnOutputLayer(n_out=cfg["vocab_size"],
                                      loss="mcxent",
                                      activation="softmax"), "ln_f")
    conf = g.set_outputs("out").build()
    conf.dtype = cfg["torch_dtype"]
    net = ComputationGraph(conf)
    return net, _shell_init(net)
'''

#: ... and its plain reference: x + attn(ln(x)) + ffn(ln(x)), float32
DUMMY_DECODER_REFERENCE = '''
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.quant import stored

HI = lax.Precision.HIGHEST


def param_specs(cfg):
    e, v, i = (cfg["hidden_size"], cfg["vocab_size"],
               cfg["intermediate_size"])
    d = e // cfg["num_attention_heads"]
    specs = [("embed/W", (e, v, 1), 0.0, 0.5)]
    for n in range(cfg["num_hidden_layers"]):
        specs += [(f"ln{n}/gamma", (e,), 1.0, 0.02),
                  (f"ln{n}/beta", (e,), 0.0, 0.02)]
        for p, shape in (("q", (e, e)), ("k", (e, d)), ("v", (e, d)),
                         ("o", (e, e))):
            specs += [(f"attn{n}/W{p}", shape, 0.0, 1 / math.sqrt(e)),
                      (f"attn{n}/b{p}", shape[1:], 0.0, 0.02)]
        specs += [(f"up{n}/W", (i, e, 1), 0.0, 1 / math.sqrt(e)),
                  (f"down{n}/W", (e, i, 1), 0.0, 1 / math.sqrt(i))]
    return specs + [("ln_f/gamma", (e,), 1.0, 0.02),
                    ("ln_f/beta", (e,), 0.0, 0.02),
                    ("out/W", (e, v), 0.0, 1 / math.sqrt(e)),
                    ("out/b", (v,), 0.0, 0.02)]


def _norm(x, p, name):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + 1e-5) * p[name + "/gamma"] \\
        + p[name + "/beta"]


def _rope(x, base):
    t, half = x.shape[1], x.shape[2] // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def logits_at(cfg, params, ids, positions, low=False):
    return _forward(params, jnp.asarray(ids), jnp.asarray(positions),
                    heads=cfg["num_attention_heads"],
                    base=cfg["rope_theta"],
                    layers=cfg["num_hidden_layers"], low=low)


@functools.partial(jax.jit,
                   static_argnames=("heads", "base", "layers", "low"))
def _forward(params, ids, positions, *, heads, base, layers, low):
    p = {k: v.astype(jnp.float32) for k, v in params.items()}

    def mm(x, w):
        return stored(jnp.matmul(stored(x, low), stored(w, low),
                                 precision=HI), low)

    x = stored(p["embed/W"][:, ids, 0].T, low)                   # [T, E]
    t, e = x.shape
    d = e // heads
    causal = jnp.tril(jnp.ones((t, t), bool))
    for n in range(layers):
        h = _norm(x, p, f"ln{n}")
        a = f"attn{n}/"
        q = (mm(h, p[a + "Wq"]) + p[a + "bq"]).reshape(t, heads, d)
        q = _rope(q.transpose(1, 0, 2), base)                   # [H, T, D]
        k = _rope((mm(h, p[a + "Wk"]) + p[a + "bk"])[None], base)[0]
        v = mm(h, p[a + "Wv"]) + p[a + "bv"]            # one key/value head
        s = jnp.einsum("htd,sd->hts", stored(q, low), stored(k, low),
                       precision=HI) / math.sqrt(d)
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,sd->htd", stored(w, low), stored(v, low),
                       precision=HI).transpose(1, 0, 2).reshape(t, e)
        attn = mm(o, p[a + "Wo"]) + p[a + "bo"]
        up = jax.nn.relu(mm(h, p[f"up{n}/W"][:, :, 0].T))
        x = stored(x + attn + mm(up, p[f"down{n}/W"][:, :, 0].T), low)
    h = _norm(x[positions], p, "ln_f")
    return jnp.matmul(stored(h, low), stored(p["out/W"], low),
                      precision=HI) + p["out/b"]
'''

#: its configuration: two keys hold the chip's share, and the file says so
DUMMY_DECODER_CONFIG = {
    "kind": "serve", "model": "tinydecoder", "source": "nowhere",
    "reduced": ["num_hidden_layers", "vocab_size"],
    "published": {"num_hidden_layers": 12, "vocab_size": 4096},
    "deployment": "each layer on 8 chips, the vocabulary split over them; "
                  "this chip holds 1 of 12 layers and 512 of 4,096 rows",
    "assumed": {"all": "of it"},
    "hidden_size": 32, "intermediate_size": 64, "num_hidden_layers": 1,
    "num_attention_heads": 2, "vocab_size": 512, "rope_theta": 10000.0,
    "torch_dtype": "bfloat16",
    "departures": {"served_max_context": 64},
    "engine": {"slots": 3, "page_size": 8, "total_pages": 32,
               "kv_dtype": "bf16", "decode_impl": "xla",
               "prefix_cache": True, "queue_limit": 8}}

#: its mix, as ``runners/serve_closed_replay.py`` states a mix of that
#: kind: the parameters, and the table ``traffic/draw_table.py`` draws
#: from them (three clients, prompts in three prefill buckets; long enough
#: for a CPU that serves a request in a few milliseconds)
DUMMY_DECODER_TRAFFIC = {
    "kind": "serve_closed_replay", "why": "test", "loop": "closed",
    "think_time_s": 0.0, "decoding": "greedy (top_k=1), no stop tokens",
    "generator_seed": 28,
    "prompt_tokens": {"dist": "uniform", "min": 5, "max": 30},
    "output_tokens": {"dist": "uniform", "min": 4, "max": 8},
    "latency_sample": "finished", "checked_requests": 40,
    "table": {"clients": 3, "requests_per_client": 600}}


def _draw_table(path):
    """What a later PR does with a mix it has written the parameters of:
    ``python3 benchmark/traffic/draw_table.py <path>``."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "draw_table", os.path.join(ROOT, "benchmark", "traffic",
                                   "draw_table.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.main(str(path))


def _copy_with_dummies(tmp_path):
    """A temporary copy of the benchmark beside a directory of a later
    PR's own, which adds a model, a kind of traffic, two cells and a
    metric by files and entries alone."""
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "tests" / "benchmark").mkdir(parents=True)
    extra = root / "extra_bench"
    for d in ("configs", "traffic", "metrics", "limits", "models",
              "reference", "runners"):
        (extra / d).mkdir(parents=True)
    bench = json.loads(json.dumps(BENCH))
    bench["paths"].append("extra_bench")
    (extra / "configs" / "dummy.json").write_text(json.dumps(
        {"kind": "train", "model": "tinymlp", "source": "nowhere",
         "reduced": [], "assumed": {"all": "of it"}, "n_in": 12,
         "hidden": 16, "classes": 5, "learning_rate": 0.05,
         "momentum": 0.9}))
    (extra / "models" / "tinymlp.py").write_text(DUMMY_MODEL)
    (extra / "reference" / "tinymlp.py").write_text(DUMMY_REFERENCE)
    (extra / "runners" / "ping.py").write_text(DUMMY_RUNNER)
    (extra / "traffic" / "fit_dummy.json").write_text(json.dumps(
        {"kind": "train_fit", "global_batch": 8, "distinct_batches": 3,
         "checked_steps": 3}))
    (extra / "traffic" / "ping_dummy.json").write_text(json.dumps(
        {"kind": "ping", "pings": 7}))
    (extra / "limits" / "dummy.fit_dummy.json").write_text(json.dumps(
        {"limits": {"loss_rel_gap": 1e-4,
                    "grad1_norm_worst_leaf_gap": 1e-3,
                    "change_norm_worst_leaf_gap": 1e-3,
                    "grad1_norm_median_leaf_gap": 1e-3,
                    "change_norm_median_leaf_gap": 1e-3}}))
    (extra / "limits" / "dummy.ping_dummy.json").write_text(json.dumps(
        {"limits": {"lost": 0}}))
    (extra / "metrics" / "dummy.answer.py").write_text(
        "def read(ctx):\n    return ctx.get('answer')\n")
    bench["configs"].append({"name": "dummy", "source": "nowhere",
                             "file": "extra_bench/configs/dummy.json",
                             "reduced": [], "why": "test"})
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for traffic in ("fit_dummy", "ping_dummy"):
        bench["workloads"].append({
            "name": "dummy." + traffic, "config": "dummy",
            "traffic": traffic, "chips": 1, "why": "test"})
        e2e["train_samples_per_s"]["workloads"].append("dummy." + traffic)
    bench["per_layer"].append({
        "name": "dummy.answer", "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "fit loop",
        "moves": "train_samples_per_s",
        "workloads": ["dummy.fit_dummy", "dummy.ping_dummy"]})
    # the cut decoder: a configuration, a model, a reference, a mix of the
    # serving kind, limits, and a per-layer metric of its cell
    (extra / "configs" / "dummydec.json").write_text(
        json.dumps(DUMMY_DECODER_CONFIG))
    (extra / "models" / "tinydecoder.py").write_text(DUMMY_DECODER)
    (extra / "reference" / "tinydecoder.py").write_text(
        DUMMY_DECODER_REFERENCE)
    (extra / "traffic" / "serve_dummy.json").write_text(json.dumps(
        DUMMY_DECODER_TRAFFIC))
    _draw_table(extra / "traffic" / "serve_dummy.json")
    # over the ~250 served positions of 40 requests, on the CPU, 16 seeds:
    # the program reads 0.0026-0.0239, the 8-bit control 0.18-0.45
    (extra / "limits" / "dummydec.serve_dummy.json").write_text(json.dumps(
        {"limits": {"served_token_gap_max": 0.07}}))
    (extra / "metrics" / "dummydec.rows_per_dispatch.py").write_text(
        "from benchmark.metrics._spans import health_delta\n\n\n"
        "def read(ctx):\n"
        "    rows = health_delta(ctx, 'decode_dispatch', 'rows')\n"
        "    n = health_delta(ctx, 'decode_dispatch', 'count')\n"
        "    return rows / n if rows is not None and n else None\n")
    bench["configs"].append({
        "name": "dummydec", "source": "nowhere",
        "file": "extra_bench/configs/dummydec.json",
        "reduced": DUMMY_DECODER_CONFIG["reduced"], "why": "test"})
    bench["workloads"].append({
        "name": "dummydec.serve_dummy", "config": "dummydec",
        "traffic": "serve_dummy", "chips": 1, "why": "test"})
    e2e["serve_out_tokens_per_s"]["workloads"].append(
        "dummydec.serve_dummy")
    bench["per_layer"].append({
        "name": "dummydec.rows_per_dispatch", "unit": "rows",
        "better": "higher", "source": "program_counter",
        "layer": "serving engine", "moves": "serve_out_tokens_per_s",
        "workloads": ["dummydec.serve_dummy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root), bench


def test_a_cell_a_configuration_and_a_metric_are_added_by_files_alone(
        tmp_path):
    """A later PR adds entries and files and edits none: a dummy of each,
    in a temporary copy, loads through the same harness."""
    root, bench = _copy_with_dummies(tmp_path)
    cell = harness.Cell(bench, "dummy.fit_dummy", root=root)
    assert cell.config["model"] == "tinymlp"
    assert cell.traffic["global_batch"] == 8
    assert cell.limits["loss_rel_gap"] == 1e-4
    assert [m["name"] for m in cell.per_layer] == ["dummy.answer"]
    got = harness.per_layer_metrics(cell, {"answer": 42})
    assert got == {"dummy.answer": {"value": 42.0, "unit": "ms"}}
    # a reader that finds nothing to read leaves its metric out
    assert harness.per_layer_metrics(cell, {}) == {}
    # and the old cells still load from the same tree
    assert harness.Cell(bench, CELLS[0], root=root).per_layer


def _cpu_run(cell, seed=3, control=False, seconds=0.3):
    import argparse
    import time
    import jax
    args = argparse.Namespace(seed=seed, seconds=seconds)
    return cell.runner().run(cell, args, jax.devices()[:1],
                             time.perf_counter(), None, control=control)


def test_a_model_of_another_architecture_runs_by_files_alone(tmp_path):
    """Not only the loader: the general training runner drives a model it
    has never seen (its builder and its plain reference found by the
    configuration's ``model``) through ``fit`` on the CPU, compares it
    with that reference, and the harness makes the result from it."""
    root, bench = _copy_with_dummies(tmp_path)
    cell = harness.Cell(bench, "dummy.fit_dummy", root=root)
    assert cell.runner().__file__.startswith(root)    # the copy's runner
    assert cell.model().__file__.endswith("extra_bench/models/tinymlp.py")
    record = _cpu_run(cell, control=True)
    assert record["attempted"] > 0 and record["failed"] == 0
    assert [c.line() for c in record["checks"] if not c.ok] == []
    assert len(record["checks"]) == 3 + 4 + 2
    # float32 on both sides: the same function, to rounding
    assert max(c.value for c in record["checks"]) < 1e-4
    # the model's own control and a planted fault fail its limits
    for case in ("control_fp8", "fault_half_batch"):
        assert max(record["readings"][case].values()) > 1e-3, case
    out = harness.result(cell, record, [_Dev()])
    assert out["correct"] is True
    assert set(out["metrics"]) == {"setup_s", "train_samples_per_s"}
    # the whole step's share of the peak finds the new model's count
    ctx = {"cell": cell, "config": cell.config, "record": record,
           "chips": 1, "peaks": {"bf16_flops_per_s": 197e12}}
    rate = record["end_to_end"]["train_samples_per_s"]
    assert cell.reader("train_step.mfu")(ctx) == pytest.approx(
        100 * 6 * (12 * 16 + 16 * 5) * rate / 197e12)


@pytest.fixture(scope="module")
def dummies(tmp_path_factory):
    """One temporary copy for the tests that only read it (or add files of
    their own to it): ``(root, bench)``."""
    return _copy_with_dummies(tmp_path_factory.mktemp("dummies"))


@pytest.fixture(scope="module")
def cut_decoder(dummies):
    """The dummy cut decoder's cell, and one run of it through the serving
    runner on the CPU (``control`` read beside it)."""
    root, bench = dummies
    cell = harness.Cell(bench, "dummydec.serve_dummy", root=root)
    return root, bench, cell, _cpu_run(cell, control=True, seconds=1.0)


def test_a_cut_decoder_runs_through_the_serving_runner_by_files_alone(
        cut_decoder):
    """What ``tinymlp`` proves of the training runner: the general serving
    runner takes a decoder it has never seen (another graph than the zoo
    transformer's, a cache the Mosaic kernel's path is not asked for)
    through ``GenerationEngine`` by the contract its docstring states, and
    compares what was served with that decoder's own plain reference."""
    root, bench, cell, record = cut_decoder
    assert cell.runner().__file__.startswith(root)
    assert cell.model().__file__.endswith(
        "extra_bench/models/tinydecoder.py")
    assert cell.config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert [c.line() for c in record["checks"] if not c.ok] == []
    assert [c.name for c in record["checks"]] == [
        "served_token_gap_max", "requests_failed",
        "output_length_mismatches", "compiles_in_window",
        "decode_path_not_direct_pallas", "max_context_positions"]
    health = record["serve"]["health1"]
    assert health["kv_traffic"]["decode_path"] == "direct-xla"
    assert health["slots"] == 3
    assert record["attempted"] > 10 and record["failed"] == 0
    assert record["checked"]["requests"] >= 10
    # the served tokens are the reference's, not one token for ever
    assert record["readings"]["program"]["distinct_served_tokens"] >= 5
    # ... and the same reference in 8 bits, at the same positions, is not
    # correct: its widest gap lies well over the limit the program is under
    control = compare.Check(
        "served_token_gap_max",
        record["readings"]["control_fp8"]["served_token_gap_max"],
        cell.limits["served_token_gap_max"])
    assert not control.ok
    assert control.value > 1.5 * control.limit
    # ids are drawn from the slice of the vocabulary held here
    assert max(t for r in record["serve"]["finished"]
               for t in r.prompt) < cell.config["vocab_size"] == 512
    out = harness.result(cell, record, [_Dev()])
    assert out["correct"] is True
    assert set(out["metrics"]) == {"setup_s", "serve_out_tokens_per_s"}
    # its own per-layer metric, against a hand count on its counters
    ctx = {"record": {"serve": {
        "health0": {"decode_dispatch": {"rows": 10, "count": 5}},
        "health1": {"decode_dispatch": {"rows": 40, "count": 17}}}}}
    assert harness.per_layer_metrics(cell, ctx) == {
        "dummydec.rows_per_dispatch": {"value": 2.5, "unit": "rows"}}
    got = harness.per_layer_metrics(cell, {"record": record})
    assert 1.0 <= got["dummydec.rows_per_dispatch"]["value"] <= 3.0


def test_a_run_on_another_decode_path_than_its_decode_impl_is_not_correct(
        cut_decoder, monkeypatch):
    """``engine.decode_impl`` says which path the window has to have run
    on: ``xla`` is held to ``direct-xla`` (the run above), ``auto`` and
    ``pallas`` to ``direct-pallas``, and no key lets a configuration say
    otherwise. An engine that fell back to another path, here to the
    round trip through the host, fails that one check and no other."""
    from deeplearning4j_tpu.serving import GenerationEngine
    root, bench = cut_decoder[:2]
    cell = harness.Cell(bench, "dummydec.serve_dummy", root=root)
    health = GenerationEngine.health

    def fell_back(self):
        out = health(self)
        out["kv_traffic"]["decode_path"] = "roundtrip"
        return out

    monkeypatch.setattr(GenerationEngine, "health", fell_back)
    record = _cpu_run(cell)
    assert [c.name for c in record["checks"] if not c.ok] == [
        "decode_path_not_direct_pallas"]
    assert harness.result(cell, record, [_Dev()])["correct"] is False


def _write_config(root, bench, name, tag, changes):
    """The entry ``name`` again, for a changed copy of its file (``None``
    drops a key)."""
    entry = next(c for c in bench["configs"] if c["name"] == name)
    data = harness._load_json(os.path.join(root, entry["file"]))
    for k, v in changes.items():
        if v is None:
            data.pop(k, None)
        else:
            data[k] = v
    path = entry["file"].replace(".json", f".{tag}.json")
    with open(os.path.join(root, path), "w") as f:
        json.dump(data, f)
    return dict(entry, file=path)


CUT_CASES = [
    ("a whole model", "dummy", {}, True),
    ("a cut model that says what it cut", "dummydec", {}, True),
    ("published lacks a reduced key", "dummydec",
     {"published": {"num_hidden_layers": 12}}, False),
    ("published holds a key that is not reduced", "dummydec",
     {"published": {"num_hidden_layers": 12, "vocab_size": 4096,
                    "hidden_size": 64}}, False),
    ("a published value equals the one held", "dummydec",
     {"published": {"num_hidden_layers": 12, "vocab_size": 512}}, False),
    ("no published", "dummydec", {"published": None}, False),
    ("no deployment", "dummydec", {"deployment": None}, False),
    ("a deployment of two lines", "dummydec",
     {"deployment": "8 chips\na layer"}, False),
    ("a deployment of 201 characters", "dummydec",
     {"deployment": "x" * 201}, False),
    ("reduced names no key of the file", "dummydec",
     {"vocab_size": None}, False),
    ("the file's list is not the entry's", "dummydec",
     {"reduced": ["num_hidden_layers"]}, False),
    ("a whole model with a published", "dummy",
     {"published": {"hidden": 32}}, False),
]


@pytest.mark.parametrize("case,config,changes,ok", CUT_CASES,
                         ids=[c[0].replace(" ", "_") for c in CUT_CASES])
def test_a_cut_configuration_says_what_it_cut(dummies, monkeypatch, case,
                                              config, changes, ok):
    root, bench = dummies
    entry = _write_config(root, bench, config, case.replace(" ", "_"),
                          changes)
    monkeypatch.setattr(sys.modules[__name__], "ROOT", root)
    monkeypatch.setattr(sys.modules[__name__], "BENCH", bench)
    if ok:
        test_configuration_files(entry)
    else:
        with pytest.raises(AssertionError):
            test_configuration_files(entry)


def test_a_later_prs_entries_pass_every_rule_of_this_directory(
        dummies, monkeypatch):
    """A later PR's configurations, cells and per-layer entries (a cut
    decoder's among them), each with files of its own, against every test
    of this directory that goes over the entries of ``BENCHMARK.json``
    (the others name the accepted cells they are about): the rules of this
    file, of a ``serve_closed_replay`` mix (``test_benchmark_replay.py``)
    and of the two reader tables. None needs an edit to a file that is
    there."""
    import test_benchmark_replay
    import test_benchmark_spans
    import test_benchmark_xplane
    root, bench = dummies
    assert len(bench["per_layer"]) == len(BENCH["per_layer"]) + 2
    assert len(bench["workloads"]) == len(BENCH["workloads"]) + 3
    me = sys.modules[__name__]
    for module in (me, test_benchmark_replay):
        monkeypatch.setattr(module, "ROOT", root)
        monkeypatch.setattr(module, "BENCH", bench)
    monkeypatch.setattr(me, "CELLS", [w["name"] for w in bench["workloads"]])
    monkeypatch.setattr(harness, "load_benchmark",
                        lambda root=root: harness._load_json(
                            os.path.join(root, "BENCHMARK.json")))
    test_top_level_keys_are_exactly_the_contracts()
    for params in ENTRY_KEYS:
        test_entries_have_just_the_keys_shown(*params)
    test_metrics_units_sources_and_bounds()
    for name in CELLS:
        test_every_cell_resolves_to_files_of_its_own(name)
    for cfg in bench["configs"]:
        test_configuration_files(cfg)
    test_command_names_no_file_outside_paths()
    serving = test_benchmark_replay.serving_cells(bench, root)
    assert serving == test_benchmark_replay.SERVING + [
        "dummydec.serve_dummy"]
    for name in serving:
        test_benchmark_replay.\
            test_the_schedule_is_data_and_stays_under_the_served_context(
                name)
        test_benchmark_replay.\
            test_the_committed_table_is_what_its_recorded_parameters_draw(
                name)
    test_benchmark_xplane.\
        test_every_serving_metric_of_the_benchmark_has_that_test()
    test_benchmark_spans.\
        test_every_reader_file_without_an_entry_is_in_that_table()


def test_a_kind_of_traffic_is_added_by_a_runner_file_alone(tmp_path):
    root, bench = _copy_with_dummies(tmp_path)
    cell = harness.Cell(bench, "dummy.ping_dummy", root=root)
    assert cell.runner().__file__.endswith("extra_bench/runners/ping.py")
    out = harness.result(cell, _cpu_run(cell), [_Dev()])
    assert out["correct"] is True and out["attempted"] == 7
    assert out["metrics"]["train_samples_per_s"]["value"] == 7.0
    assert out["checks"]["pings_lost"] == {"value": 0.0, "limit": 0.0,
                                           "ok": True}


def test_a_name_with_no_file_is_an_error_that_names_the_file():
    cell = harness.Cell(BENCH, CELLS[0])
    cell.traffic = dict(cell.traffic, kind="no_such_kind")
    with pytest.raises(FileNotFoundError, match="runners/no_such_kind.py"):
        cell.runner()
    cell.config = dict(cell.config, model="no_such_model")
    with pytest.raises(FileNotFoundError, match="models/no_such_model.py"):
        cell.model()
    with pytest.raises(FileNotFoundError,
                       match="reference/no_such_model.py"):
        cell.reference()


class _Dev:
    platform, device_kind = "tpu", "TPU v5 lite"


def _trace():
    """Two executions of the train step, 40 ms busy in each 50 ms."""
    from benchmark.xplane import Trace
    ops = [("fusion.1", 0.000, 0.030), ("convolution.2", 0.030, 0.040),
           ("fusion.1", 0.050, 0.080), ("convolution.2", 0.080, 0.090)]
    modules = [("jit_step(123)", 0.000, 0.040),
               ("jit_step(123)", 0.050, 0.090)]
    host = [("PjitFunction(step)", 0.041, 0.049)]
    return Trace({0: ops}, {0: modules}, host)


@pytest.mark.parametrize("traced", [False, True])
def test_last_line_has_exactly_the_contracts_keys(traced):
    cell = harness.Cell(BENCH, "resnet50.fit_b256")
    record = {"setup_s": 30.0, "window_s": 50.0, "attempted": 500,
              "failed": 0, "memory_peak_bytes": 9 * 2 ** 30,
              "end_to_end": {"train_samples_per_s": 2600.0},
              "checks": [compare.Check("loss_step1_rel_gap", 1e-3, 1e-2)]}
    from benchmark.peaks import peaks_for
    out = harness.result(cell, record, [_Dev()],
                         _trace() if traced else None,
                         peaks_for("TPU v5 lite"))
    line = json.loads(json.dumps(out))
    want = ["correct", "attempted", "failed", "metrics", "device"]
    if traced:
        want.append("breakdown")
    assert list(line) == want + ["checks"]          # checks comes last
    assert line["correct"] is True and line["attempted"] == 500
    dev = {"platform", "kind", "count", "memory_peak_bytes"}
    if traced:
        dev |= {"busy_s", "window_s"}
        # per-layer metrics only; the trace-less reader returns None-free
        assert "train_step.mfu" in line["metrics"]
        assert "setup_s" not in line["metrics"]
        assert line["metrics"]["train_step.mfu"]["value"] < 100
        assert line["metrics"]["train_step.device_ms"]["value"] == \
            pytest.approx(40.0)
        assert line["metrics"]["fit.host_gap_ms_per_step"]["value"] == \
            pytest.approx(5.0)
        assert line["metrics"]["train.device_idle_share"]["value"] == \
            pytest.approx(100 * 10 / 90)
        assert line["device"]["busy_s"] == pytest.approx(0.08)
        assert line["breakdown"]["idle_gaps"] == [
            ["PjitFunction_step", pytest.approx(0.010)]]
    else:
        assert set(line["metrics"]) == {"setup_s", "train_samples_per_s"}
    assert set(line["device"]) == dev
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert line["checks"]["loss_step1_rel_gap"] == {
        "value": 1e-3, "limit": 1e-2, "ok": True}
    # a run's notes (how many requests a tail was taken over) ride in the
    # line too, before the checks, which stay last
    record["notes"] = {"requests_finished_in_window": 44}
    noted = harness.result(cell, record, [_Dev()],
                           _trace() if traced else None,
                           peaks_for("TPU v5 lite"))
    assert list(noted)[-2:] == ["notes", "checks"]
    assert list(noted)[:-2] == want


def test_an_unknown_device_has_no_peaks():
    from benchmark.peaks import peaks_for
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")


def test_a_backend_that_is_not_a_tpu_is_refused_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "resnet50.fit_b256", "--seed", "1", "--seconds",
         "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "no result" in p.stderr
