"""The program against the plain references at sizes a test run can hold,
the 8-bit controls, and a run driven past the look for a chip with the
timed path broken underneath: ``correct`` has to come out false."""

import argparse
import time

import jax
import numpy as np
import pytest

from benchmark import compare, harness, weights

BENCH = harness.load_benchmark()


def _args(seed, seconds=1.0):
    return argparse.Namespace(seed=seed, seconds=seconds)


# ------------------------------------------------------------------ training
@pytest.fixture(scope="module")
def resnet():
    """The train cell at 32x32 images, batch 8, float32 compute (bfloat16
    at eight rows a BatchNorm is rounding noise, not a comparison): one
    program, its compiled step reused by every case."""
    cell = harness.Cell(BENCH, "resnet50.fit_b256", dry_run=True)
    cell.config["compute_dtype"] = "float32"
    train = cell.runner()
    prog = train.Program(cell, 5)
    ref = train.reference_readings(cell, 5, prog.feed)
    return cell, prog, ref, train


def _rerun(prog):
    """The program's first steps again from the seed's weights, through
    the step it has already compiled."""
    import jax.numpy as jnp
    flat = weights.make_weights(prog.specs, prog.seed, jnp.float32)
    for vertex, leaves in weights.as_tree(flat).items():
        prog.net.params[vertex] = leaves
    prog.net.updater_state = prog.net.conf.updater.init_state(
        prog.net.params)
    prog.feed.pos = 0
    return prog.checked_steps()


TINY_LIMITS = {"loss_rel_gap": 0.02, "grad1_norm_worst_leaf_gap": 0.1,
               "change_norm_worst_leaf_gap": 0.2,
               "grad1_norm_median_leaf_gap": 0.02,
               "change_norm_median_leaf_gap": 0.04}


def test_the_train_step_follows_the_reference(resnet):
    cell, prog, ref, _ = resnet
    got = _rerun(prog)
    checks = compare.training_checks(got, ref, TINY_LIMITS)
    assert all(c.ok for c in checks), [c.line() for c in checks]
    # step 1 is the same function on both sides
    assert checks[0].value < 1e-3
    assert set(got["grad1_norms"]) == set(ref["grad1_norms"])
    assert len(got["losses"]) == cell.traffic["checked_steps"] == 3


def test_the_8bit_control_is_not_correct(resnet):
    cell, prog, ref, train = resnet
    low = train.reference_readings(cell, 5, prog.feed, low=True)
    checks = compare.training_checks(low, ref, TINY_LIMITS)
    assert not all(c.ok for c in checks), [c.line() for c in checks]


class _Faulty:
    """Wraps the net's compiled step: what the window drives, broken."""

    def __init__(self, net, fault):
        self.net, self.fault = net, fault
        self.real = net._get_train_step

    def __enter__(self):
        fault, real = self.fault, self.real

        def get(carry_rnn, policy="off"):
            step = real(carry_rnn, policy)

            def broken(params, state, upd, inputs, labels, *rest):
                if fault == "half_batch":
                    # the second half never enters: the mean is over the
                    # first half, seen twice
                    def half(a):
                        n = a.shape[0] // 2
                        return np.concatenate([a[:n], a[:n]])
                    inputs = {k: half(np.asarray(v))
                              for k, v in inputs.items()}
                    labels = {k: half(np.asarray(v))
                              for k, v in labels.items()}
                if fault == "state_unchanged":
                    # the step donates its state: keep what went in
                    kept = jax.tree_util.tree_map(lambda a: a.copy(),
                                                  (params, upd))
                out = step(params, state, upd, inputs, labels, *rest)
                if fault == "state_unchanged":
                    return (kept[0], out[1], kept[1]) + tuple(out[3:])
                return out
            return broken
        self.net._get_train_step = get
        return self

    def __exit__(self, *exc):
        del self.net._get_train_step


@pytest.mark.parametrize("fault,failing", [
    ("state_unchanged", "change_norm_worst_leaf_gap"),
    ("half_batch", "grad1_norm_worst_leaf_gap"),
])
def test_a_broken_train_step_comes_out_not_correct(resnet, fault, failing):
    cell, prog, ref, _ = resnet
    with _Faulty(prog.net, fault):
        got = _rerun(prog)
    checks = {c.name: c for c in
              compare.training_checks(got, ref, TINY_LIMITS)}
    assert not checks[failing].ok, [c.line() for c in checks.values()]
    if fault == "state_unchanged":
        assert checks[failing].value == pytest.approx(1.0)


# ------------------------------------------------------------------- serving
@pytest.fixture(scope="module")
def chat_cell():
    return harness.Cell(BENCH, "starcoder2-3b.chat_closed32", dry_run=True)


def _serve_run(cell, seed, control=False):
    return cell.runner().run(cell, _args(seed, 1.5), jax.devices()[:1],
                             time.perf_counter(), None, control=control)


def test_served_tokens_follow_the_reference(chat_cell):
    rec = _serve_run(chat_cell, 9, control=True)
    checks = {c.name: c for c in rec["checks"]}
    assert all(c.ok for c in checks.values()), \
        [c.line() for c in checks.values()]
    assert rec["checked"]["requests"] >= 3
    assert rec["checked"]["positions"] >= 10
    assert rec["attempted"] > 0 and rec["failed"] == 0
    assert checks["compiles_in_window"].value == 0
    # ``decode_impl: auto`` is held to the Mosaic kernel's path
    assert chat_cell.config["engine"]["decode_impl"] == "auto"
    assert checks["decode_path_not_direct_pallas"].value == 0
    assert rec["serve"]["health1"]["kv_traffic"]["decode_path"] == \
        "direct-pallas"
    served = rec["readings"]["program"]["served_token_gap_max"]
    assert served <= chat_cell.limits["served_token_gap_max"]
    assert "control_fp8" in rec["readings"]


def test_the_8bit_control_of_the_served_model_is_not_correct(chat_cell):
    """The reference in float8, put in the program's place: over a few
    hundred positions the token it puts first lies further below the
    float32 reference's best than the limit allows."""
    from benchmark.reference import starcoder2
    cfg = chat_cell.config
    params = weights.make_weights(starcoder2.param_specs(cfg), 9,
                                  "bfloat16")
    rng = np.random.default_rng(9)
    sample = [(rng.integers(0, cfg["vocab_size"], 24).tolist(),
               rng.integers(0, cfg["vocab_size"], 40).tolist())
              for _ in range(6)]
    served, control, n = chat_cell.runner().reference_gaps(
        chat_cell, params, sample, low=True, pad=64)
    assert n == 6 * 40
    assert max(control) > chat_cell.limits["served_token_gap_max"]
    # random "served" tokens are as wrong as an answer can be
    assert min(served) > max(control)


def test_a_token_altered_where_it_is_produced_comes_out_not_correct(
        chat_cell, monkeypatch):
    from deeplearning4j_tpu.serving import engine as engine_mod
    real = engine_mod.draw
    calls = {"n": 0}

    def altered(probs, *a, **kw):
        tok = real(probs, *a, **kw)
        calls["n"] += 1
        return (tok + 1) % len(probs) if calls["n"] % 7 == 0 else tok

    monkeypatch.setattr(engine_mod, "draw", altered)
    rec = _serve_run(chat_cell, 9)
    checks = {c.name: c for c in rec["checks"]}
    assert not checks["served_token_gap_max"].ok
    assert not all(c.ok for c in rec["checks"])


def test_the_references_list_the_models_the_programs_build(chat_cell):
    """Shape for shape: the leaves the benchmark draws are the leaves of
    the program's own parameter tree."""
    from benchmark.reference import starcoder2
    full = harness.Cell(BENCH, "starcoder2-3b.chat_closed32").config
    specs = starcoder2.param_specs(full)
    n = sum(int(np.prod(s)) for _, s, _, _ in specs)
    assert 3.1e9 < n < 3.3e9            # untied head: 3.18 B
    assert len(specs) == 2 + 30 * 16 + 2 + 2
    with pytest.raises(RuntimeError, match="not the reference's model"):
        weights.check_tree_matches({"a/W": np.zeros((2, 3))},
                                   {"a": {"W": np.zeros((3, 2))}})


def test_a_graph_whose_internals_moved_fails_the_weightless_init_loudly(
        chat_cell):
    """``models/starcoder2.py`` initialises the graph without drawing its
    float32 weights through names the program does not publish; a graph
    without them is an error that says where, not a half-made net."""
    model = chat_cell.model()

    class Renamed:
        _initialized = False

    with pytest.raises(RuntimeError, match="no longer has .*_topo"):
        model._shell_init(Renamed())
    net, shapes = model.build_shell(chat_cell.config, 128)
    assert set(shapes) == set(net.params) and net._initialized
    with pytest.raises(RuntimeError, match="a fresh state"):
        model._shell_init(net)


def test_weights_are_a_function_of_the_seed():
    specs = [("a/W", (4, 3), 0.0, 1.0), ("a/b", (3,), 1.0, 0.1)]
    w1 = weights.make_weights(specs, 2 ** 31 + 7, np.float32)
    w2 = weights.make_weights(specs, 2 ** 31 + 7, np.float32)
    w3 = weights.make_weights(specs, 2 ** 31 + 8, np.float32)
    np.testing.assert_array_equal(w1["a/W"], w2["a/W"])
    assert not np.array_equal(w1["a/W"], w3["a/W"])
    assert abs(float(np.mean(w1["a/b"])) - 1.0) < 0.3
    assert weights.as_tree(w1).keys() == {"a"}
