"""The ``olmo-hybrid-7b.reason_closed32`` cell: every key of its
configuration against the source's row, literally; the cut's arithmetic;
its table; its per-layer readers against hand counts (and on a program
that has none of their counters); its operation counts; the reference
against the program's layers; and its dry run through the serving runner
with the 8-bit control beside it."""

import json
import math
import os

import numpy as np
import pytest

from benchmark import compare, harness

CELL = "olmo-hybrid-7b.reason_closed32"
BENCH = harness.load_benchmark()

#: huggingface.co/allenai/Olmo-Hybrid-7B config.json, every key of the
#: catalog row's ``config``, nulls and groups included
SOURCE = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": ["linear_attention", "linear_attention",
                    "linear_attention", "full_attention"] * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(BENCH, CELL)


def test_every_source_key_is_there_verbatim_but_the_one_it_cut(cell):
    cfg = cell.config
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 32}
    for key, value in SOURCE.items():
        assert key in cfg, key
        want = 16 if key == "num_hidden_layers" else value
        assert cfg[key] == want and type(cfg[key]) is type(want), key
    assert len(cfg["layer_types"]) == 32
    assert cfg["rope_parameters"] == {"rope_theta": None}
    entry = next(c for c in BENCH["configs"] if c["name"] == "olmo-hybrid-7b")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] == \
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    # the floors of a cut: whole periods, at least one and four layers
    built = cfg["layer_types"][:cfg["num_hidden_layers"]]
    assert built == SOURCE["layer_types"][:4] * 4
    assert len(cfg["deployment"]) <= 200 and "16 layers" in cfg["deployment"]
    assert set(cfg["departures"]) >= {
        "served_max_context", "max_position_embeddings", "layer_types",
        "pipeline_ends", "state_dtype", "prefix_cache"}
    assert set(cfg["assumed"]) >= {"norm_placement", "qk_norm", "rotation",
                                   "init"}
    e = cfg["engine"]
    assert (e["slots"], e["page_size"], e["kv_dtype"], e["prefix_cache"],
            e["queue_limit"]) == (32, 16, "bf16", False, 64)
    assert e["decode_impl"] in ("auto", "xla")
    assert cfg["departures"]["served_max_context"] == 3072
    assert cfg["dry_run"]["hidden_size"] < 128


def test_the_cut_is_the_issues_arithmetic(cell):
    """215.6 M a linear layer, 185.8 M a full one, 4.10 B in all, 8.20 GB
    in bfloat16; 61,440 B a cached token; 27.4 MB a slot."""
    cfg, ref = cell.config, cell.reference()
    by_vertex = {}
    for name, shape, _, _ in ref.param_specs(cfg):
        v = name.split("/")[0]
        by_vertex[v] = by_vertex.get(v, 0) + math.prod(shape)
    assert round(by_vertex["gdn0"] / 1e6, 2) == 88.75
    assert round(by_vertex["attn3"] / 1e6, 2) == 58.99      # + two norms
    assert round(by_vertex["ffn0"] / 1e6, 2) == 126.81
    linear = by_vertex["gdn0"] + by_vertex["ffn0"] + 2 * 3840
    full = by_vertex["attn3"] + by_vertex["ffn3"] + 2 * 3840
    assert (round(linear / 1e6, 1), round(full / 1e6, 1)) == (215.6, 185.8)
    assert round((3 * linear + full) / 4e6, 1) == 208.1
    total = sum(by_vertex.values())
    assert total == 12 * linear + 4 * full + 2 * 100352 * 3840 + 3840
    assert round(total / 1e9, 2) == 4.10
    assert round(2 * total / 1e9, 2) == 8.20
    token = 4 * 2 * cfg["num_key_value_heads"] * 128 * 2
    assert token == 61440
    slot = ref.state_bytes_per_slot(cfg)
    assert slot == 12 * (30 * 96 * 192 * 4 + 11520 * 3 * 2)
    assert round(slot / 1e6, 1) == 27.4
    # the slots' state weighs what 445 tokens of this model's pages do
    assert slot // token == 445


def test_the_table_is_the_mix_the_issue_names(cell):
    t = cell.traffic
    assert len(t["clients"]) == t["table"]["clients"] == 32
    assert t["table"]["requests_per_client"] == 40
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 640,
                                  "sigma": 0.6, "min": 256, "max": 2048}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 320,
                                  "sigma": 0.5, "min": 128, "max": 768}
    assert t["latency_sample"] == "sent" and t["checked_requests"] == 3
    others = {harness.Cell(BENCH, w["name"]).traffic.get("generator_seed")
              for w in BENCH["workloads"] if w["name"] != CELL}
    assert t["generator_seed"] not in others
    assert 560 <= t["drawn"]["prompt_median"] <= 720
    assert t["drawn"]["max_context"] <= \
        cell.config["departures"]["served_max_context"]
    from benchmark.runners.serve_closed_replay import bucket
    assert {bucket(p, 3072) for c in t["clients"] for p, _ in c} == \
        {256, 512, 1024, 2048}
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["chips"], entry["traffic"], entry["config"]) == \
        (1, "reason_closed32", "olmo-hybrid-7b")


#: the cell's own per-layer entries: a later PR may add to them
READERS = ["reason.device_idle_share", "reason_step.mfu",
           "reason.decode_step_device_ms",
           "reason.prefill_device_ms_per_ktok", "reason.prefill_time_share",
           "reason.prefill_padding_share", "reason.idle_in_seat_ms",
           "gdn.scan_padding_share", "gdn.state_floor_share",
           "reason.paged_attn_roofline"]


def test_the_cells_entries_list_it():
    mine = {m["name"]: m for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [])}
    assert set(READERS) <= set(mine)
    for name in READERS:
        assert mine[name]["workloads"] == [CELL]
    assert mine["gdn.scan_padding_share"]["layer"] == \
        mine["gdn.state_floor_share"]["layer"] == "linear attention"
    reported = {m["name"] for m in BENCH["end_to_end"]
                if CELL in m.get("workloads", [CELL])}
    assert reported >= {"serve_out_tokens_per_s", "tpot_p90_s",
                        "ttft_p90_s", "setup_s"}


# ------------------------------------------------------ operation counts
def test_the_operation_counts_are_a_hand_count(cell):
    cfg, ref = cell.config, cell.reference()
    e, i, h = 3840, 11008, 30
    linear = (2 * e * (2880 + 2880 + 5760 + 5760 + 30 + 30) + 2 * 5760 * e
              + 2 * 4 * 11520 + 6 * h * 96 * 192)
    full = 2 * 4 * e * e
    token = 12 * linear + 4 * full + 16 * 2 * 3 * e * i
    head = 2 * e * 100352
    assert ref.decode_flops(cfg, 900) == token + head + 4 * 4 * e * 900
    n = 1500
    assert ref.prefill_flops(cfg, n) == n * token + head \
        + 4 * 4 * e * (n * (n + 1) // 2)
    # ~6.7 GFLOP a token: twice the 3.33 B parameters of the 16 layers
    assert 6.6e9 < token < 6.8e9


# ------------------------------------------------------------ the readers
HOST = 1.7e9


class _Handle:
    def __init__(self, sent, popped, start, end, fed):
        self._events = [
            {"event": "submit", "t": HOST + sent},
            {"event": "queue_pop", "t": HOST + popped},
            {"event": "prefill_start", "t": HOST + start, "width": fed},
            {"event": "prefill_end", "t": HOST + end}]
        self._b = {"queue_wait_s": popped - sent, "prefill_s": end - start}

    def trace(self):
        return self

    def events(self):
        return [dict(e) for e in self._events]

    def breakdown(self):
        return self._b


class _Req:
    def __init__(self, prompt_len, token_t, handle):
        self.prompt, self.token_t = [0] * prompt_len, token_t
        self.handle = handle


class _Replay:
    t0, t1 = 0.0, 10.0

    def __init__(self, requests):
        self.requests = requests

    def in_window(self, t):
        return t is not None and self.t0 <= t <= self.t1


def _health(scanned, fed, p_fed, p_bucket):
    return {"slots": 32,
            "linear_attn": {"layers": 12, "scanned_positions": scanned,
                            "fed_positions": fed, "state_updates": 0},
            "prefill": {"fed_tokens": p_fed, "bucket_tokens": p_bucket}}


def _ctx(cell, counters=True, kernel=True):
    """A 10 s window traced from 2 s to 8 s. Five decode dispatches of 40
    ms, each with four kernel ops of 1 ms (one a full layer); two whole
    primes (B: 600 tokens, 120 ms on the device; C: 1,400 tokens, 230 ms)
    and one the trace cuts (A); seats of 6 and 4 ms after the whole ones,
    the device idle in them."""
    from benchmark.peaks import peaks_for
    from benchmark.xplane import Trace
    decodes = [(2.60, 2.64), (3.00, 3.04), (5.00, 5.04), (5.50, 5.54),
               (7.00, 7.04)]
    primes = [("jit_fwd(3)", 2.00, 2.30), ("jit_fwd(2)", 3.50, 3.62),
              ("jit_fwd(4)", 6.02, 6.25)]
    ops = [("fusion.1", a, b) for a, b in decodes] \
        + [("fusion.9", a, b) for _, a, b in primes]
    if kernel:
        ops = [("fusion.1", a, a + 0.036) for a, _ in decodes] \
            + [(f"fwd.{j}_x_custom-call", a + 0.036 + 0.001 * j,
                a + 0.037 + 0.001 * j) for a, _ in decodes
               for j in range(4)] + ops[5:]
    ops = sorted(ops, key=lambda e: e[1])
    mods = sorted([("jit_fwd(1)", a, b) for a, b in decodes] + primes,
                  key=lambda e: e[1])
    host = [("prefill.fetch", 2.20, 2.40),
            ("engine.admit", 3.44, 3.45),
            ("prefill.input", 3.45, 3.50), ("prefill.forward", 3.50, 3.52),
            ("prefill.fetch", 3.52, 3.63), ("engine.seat", 3.63, 3.636),
            ("engine.admit", 5.99, 6.00),
            ("prefill.input", 6.00, 6.02), ("prefill.forward", 6.02, 6.03),
            ("prefill.fetch", 6.03, 6.26), ("engine.seat", 6.26, 6.264)]
    reqs = [
        _Req(2000, [2.4, 2.64, 3.04], _Handle(1.0, 1.2, 1.6, 2.4, 2000)),
        _Req(600, [3.64, 5.04, 5.54],
             _Handle(2.0, 3.1, 3.4495, 3.6305, 600)),
        _Req(1400, [6.27, 7.04], _Handle(5.0, 5.9, 5.9995, 6.2605, 1400))]
    h0, h1 = (_health(1000, 600, 500, 512),
              _health(1000 + 12 * 5120, 600 + 12 * 4000, 4500, 5632)) \
        if counters else ({}, {})
    record = {"window_s": 10.0,
              "serve": {"replay": _Replay(reqs), "sent": reqs,
                        "health0": h0, "health1": h1}}
    return {"cell": cell, "config": cell.config, "traffic": cell.traffic,
            "record": record, "trace": Trace({0: ops}, {0: mods}, host),
            "peaks": peaks_for("TPU v5 lite"), "chips": 1,
            "trace_interval": (2.0, 8.0)}


def _by_hand(cell):
    cfg, ref = cell.config, cell.reference()
    window = (sum(ref.prefill_flops(cfg, n) for n in (2000, 600, 1400))
              + sum(ref.decode_flops(cfg, c)
                    for c in (2001, 2002, 601, 602, 1401)))
    # decode tokens stamped after 2.25 s: contexts 2001, 2002, 601, 602,
    # 1401; a full layer reads 2 x 30 x 128 x 2 B a key and value pair
    # and moves q and o; four full layers; 20 ms of kernel in the trace
    moved = sum(c * 2 * 30 * 128 * 2 + 2 * 30 * 128 * 2
                for c in (2001, 2002, 601, 602, 1401))
    state = 2 * 32 * 12 * (30 * 96 * 192 * 4 + 11520 * 3 * 2) * 5
    return {
        # the trace's own span: its first op starts at 2.00, its last
        # ends at 7.04
        "reason.device_idle_share": 100 * (1 - (0.2 + 0.3 + 0.12 + 0.23)
                                           / 5.04),
        "reason_step.mfu": 100 * window / (10.0 * 197e12),
        "reason.decode_step_device_ms": 40.0,
        "reason.prefill_device_ms_per_ktok": 350.0 / 2.0,
        "reason.prefill_time_share":
            100 * (0.8 + 0.181 + 0.261) / 10.0,
        "reason.prefill_padding_share": 100 * (1 - 4000 / 5120),
        "reason.idle_in_seat_ms": (10 + 6 + 10 + 4) / 2,
        "gdn.scan_padding_share": 100 * (1 - 4000 / 5120),
        "gdn.state_floor_share": 100 * state / 819e9 / 0.2,
        "reason.paged_attn_roofline": 100 * 4 * moved / 819e9 / 0.020,
    }


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_reads_the_number_a_hand_count_gives(cell, metric):
    got = cell.reader(metric)(_ctx(cell))
    assert got == pytest.approx(_by_hand(cell)[metric], rel=1e-9)
    if "mfu" in metric or "roofline" in metric or "floor" in metric:
        assert 0 < got < 100


@pytest.mark.parametrize("metric", [
    "gdn.scan_padding_share", "reason.prefill_padding_share"])
def test_on_a_program_without_the_counters_it_reads_nothing(cell, metric):
    assert cell.reader(metric)(_ctx(cell, counters=False)) is None


def test_where_no_kernel_ran_the_roofline_reads_nothing(cell):
    assert cell.reader("reason.paged_attn_roofline")(
        _ctx(cell, kernel=False)) is None


def test_the_state_floor_wants_the_references_count(cell):
    """On a configuration whose reference counts no state (the parent's
    models), nothing is read and nothing raised."""
    ctx = _ctx(cell)
    other = harness.Cell(BENCH, "starcoder2-3b.chat_closed32")
    ctx["cell"], ctx["config"] = other, other.config
    assert cell.reader("gdn.state_floor_share")(ctx) is None


def test_the_result_line_of_a_traced_run_holds_every_one(cell):
    got = harness.per_layer_metrics(cell, _ctx(cell))
    assert set(READERS) <= set(got)
    assert {got[m]["unit"] for m in READERS} == {"%", "ms"}


# ----------------------------------- the reference against the program
def test_the_reference_is_the_programs_model_on_the_cpu():
    """Seeded float32 weights at the dry run's widths: the zoo model's
    full forward and the reference's token-by-token pass give the same
    logits; the float8 control does not."""
    import jax
    import jax.numpy as jnp
    from benchmark import weights
    dry = harness.Cell(BENCH, CELL, dry_run=True)
    cfg, ref = dry.config, dry.reference()
    net, shapes = dry.model().build_shell(cfg, 64)
    params = weights.make_weights(ref.param_specs(cfg), 11, jnp.float32)
    weights.check_tree_matches(params, shapes)
    for vertex, leaves in weights.as_tree(params).items():
        net.params[vertex] = leaves
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], 41)
    pos = np.arange(5, 41)
    want = np.asarray(ref.logits_at(cfg, params, list(ids), pos))
    probs = np.asarray(net.output(ids[None].astype(np.int32)))[0]  # [V, T]
    got = np.log(probs[:, pos].T)
    got = got - got.mean(1, keepdims=True)
    want_c = want - want.mean(1, keepdims=True)
    assert np.abs(got - want_c).max() < 2e-4
    low = np.asarray(ref.logits_at(cfg, params, list(ids), pos, low=True))
    assert compare.widest_token_gap(want, low.argmax(1)) > 0.2
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a: a.dtype == jnp.float32, params))


# ------------------------------------------------- the dry run, on the CPU
@pytest.fixture(scope="module")
def dry_run():
    import argparse
    import time
    import jax
    dry = harness.Cell(BENCH, CELL, dry_run=True)
    args = argparse.Namespace(seed=2 ** 31 + 34, seconds=0.5)
    return dry, dry.runner().run(dry, args, jax.devices()[:1],
                                 time.perf_counter(), None, control=True)


def test_the_dry_run_ends_with_every_check_ok(dry_run):
    dry, record = dry_run
    assert [c.line() for c in record["checks"] if not c.ok] == []
    assert dry.config["hidden_size"] == 64
    health = record["serve"]["health1"]
    assert health["kv_traffic"]["decode_path"] == "direct-pallas"
    assert record["attempted"] > 5 and record["failed"] == 0
    assert record["compiles_in_window"] == 0
    assert max(len(r.prompt) for r in record["serve"]["finished"]) >= 150
    assert record["readings"]["program"]["distinct_served_tokens"] >= 3
    # the counters the program_counter reader reads are there, and the
    # buckets' padding shows in them
    la = health["linear_attn"]
    assert la["layers"] == 3 and 0 < la["fed_positions"] \
        < la["scanned_positions"]
    assert la["seated_state_bytes"] >= \
        record["attempted"] * la["state_bytes_per_slot"]
    assert "prefix_cache" not in health


def test_computing_in_float8_fails_the_tolerance(dry_run):
    dry, record = dry_run
    control = compare.Check(
        "served_token_gap_max",
        record["readings"]["control_fp8"]["served_token_gap_max"],
        dry.limits["served_token_gap_max"])
    assert not control.ok and control.value > 1.5 * control.limit


def test_the_limit_lies_between_its_two_readings():
    path = os.path.join(harness.ROOT, "benchmark", "limits", CELL + ".json")
    with open(path) as f:
        data = json.load(f)
    limit = data["limits"]["served_token_gap_max"]
    low = data["set_from"]["lower"]["reading"]
    high = data["set_from"]["upper"]["reading"]
    assert low < limit < high
