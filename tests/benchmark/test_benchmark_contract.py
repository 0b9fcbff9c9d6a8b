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


@pytest.mark.parametrize("section,keys,optional", [
    ("configs", {"name", "source", "file", "reduced", "why"}, set()),
    ("workloads", {"name", "config", "traffic", "chips", "why"}, set()),
    ("end_to_end", {"name", "unit", "better", "bound", "source"},
     {"workloads"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"},
     {"workloads"}),
])
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
    cell = harness.Cell(BENCH, name)
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
    assert any(cfg["file"].startswith(p + "/") for p in BENCH["paths"])
    data = harness._load_json(os.path.join(ROOT, cfg["file"]))
    assert data["source"] == cfg["source"]
    assert data["reduced"] == cfg["reduced"] == []
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


def _copy_with_dummies(tmp_path):
    """A temporary copy of the benchmark beside a directory of a later
    PR's own, which adds a model, a kind of traffic, two cells and a
    metric by files and entries alone."""
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
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
    for traffic in ("fit_dummy", "ping_dummy"):
        bench["workloads"].append({
            "name": "dummy." + traffic, "config": "dummy",
            "traffic": traffic, "chips": 1, "why": "test"})
        bench["end_to_end"][0]["workloads"].append("dummy." + traffic)
    bench["per_layer"].append({
        "name": "dummy.answer", "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "fit loop",
        "moves": "train_samples_per_s", "workloads": ["dummy.fit_dummy"]})
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


def _cpu_run(cell, seed=3, control=False):
    import argparse
    import time
    import jax
    args = argparse.Namespace(seed=seed, seconds=0.3)
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
