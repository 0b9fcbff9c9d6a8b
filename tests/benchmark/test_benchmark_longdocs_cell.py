"""The ``keye-vl-2.0-30b-a3b.longdocs_closed16`` cell: every key of its
configuration against the source's row, literally; the cut's arithmetic;
its table; its per-layer readers against hand counts (and on a program
that has none of their counters); its operation counts; the reference
against the program's model; and its dry run through the serving runner
with the 8-bit control beside it."""

import json
import math
import os

import numpy as np
import pytest

from benchmark import compare, harness

CELL = "keye-vl-2.0-30b-a3b.longdocs_closed16"
BENCH = harness.load_benchmark()

#: huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B config.json, every key of
#: the catalog row's ``config``, empty lists, groups and its null included
SOURCE = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "num_local_experts": 128,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}

#: source keys the file may not hold, each named under ``departures``:
#: none. The one null, ``sliding_window``, is in the file as the source
#: gives it (a null is no window to ``test_benchmark_replay.py``)
NULL_KEYS = []


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(BENCH, CELL)


def test_every_source_key_is_there_verbatim_but_the_one_it_cut(cell):
    cfg = cell.config
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert len(SOURCE) + len(NULL_KEYS) == 26
    for key, value in SOURCE.items():
        assert key in cfg, key
        want = 6 if key == "num_hidden_layers" else value
        assert cfg[key] == want and type(cfg[key]) is type(want), key
    assert cfg["mlp_only_layers"] == []
    for key in NULL_KEYS:
        assert key not in cfg and "null" in cfg["departures"][key]
    assert "sliding_window" not in cfg["departures"]
    assert cfg["use_sliding_window"] is False
    assert cfg["sa_config"] == SOURCE["sa_config"]
    assert cfg["rope_scaling"] == SOURCE["rope_scaling"]
    assert cfg["max_window_layers"] == 48
    assert cfg["torch_dtype"] == "bfloat16"
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "keye-vl-2.0-30b-a3b")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
        "config.json")
    # the floors of a cut: a period is one layer, at least four layers,
    # no expert cut, the whole vocabulary
    assert cfg["num_hidden_layers"] >= 4
    assert cfg["num_experts"] == cfg["num_local_experts"] == 128
    assert len(cfg["deployment"]) <= 200 and "6 layers" in cfg["deployment"]
    assert set(cfg["departures"]) >= {
        "served_max_context", "max_position_embeddings", "vision_tower",
        "mrope", "pipeline_ends", "index_cache_dtype", "router_precision"}
    assert set(cfg["assumed"]) >= {"qk_norm", "rope_pairing", "indexer",
                                   "chunk_sizes", "router", "init"}
    assert "NOT as a granularity of selection" in \
        cfg["assumed"]["chunk_sizes"]
    e = cfg["engine"]
    assert (e["slots"], e["page_size"], e["kv_dtype"], e["decode_impl"],
            e["prefix_cache"], e["queue_limit"]) == (16, 16, "bf16", "xla",
                                                     True, 32)
    assert cfg["departures"]["served_max_context"] == 12544 == \
        e["total_pages"]
    dry = cfg["dry_run"]
    assert dry["head_dim"] != dry["hidden_size"] // \
        dry["num_attention_heads"]
    assert dry["num_experts"] >= 8 and dry["sa_config"]["topk"] < 33


def test_the_cut_is_the_issues_arithmetic(cell):
    """18.87 M of attention, 2.26 M of indexer, 0.26 M of router and 604.0
    M of experts a layer: 625.4 M; 622.3 M of embedding and head; 4.37 B
    in all, 8.75 GB in bfloat16; 2,304 B a cached token a layer as kept
    (2,176 B as counted: the 64-wide index key lies in a 128-lane row)."""
    cfg, ref = cell.config, cell.reference()
    by_leaf = {name: math.prod(shape)
               for name, shape, _, _ in ref.param_specs(cfg)}

    def layer0(*leaves):
        return sum(by_leaf[f"{leaf}"] for leaf in leaves)

    attention = layer0("attn0/Wq", "attn0/Wk", "attn0/Wv", "attn0/Wo")
    indexer = layer0("attn0/Wiq", "attn0/Wik", "attn0/Wiw")
    experts = layer0("moe0/Wg", "moe0/Wu", "moe0/Wd")
    assert attention == 8388608 + 2 * 1048576 + 8388608
    assert indexer == 2097152 + 131072 + 32768
    assert by_leaf["moe0/Wr"] == 262144
    assert experts == 128 * 3 * 2048 * 768 == 128 * 4718592
    assert ref.expert_bytes(cfg) == 9437184
    layer = sum(n for leaf, n in by_leaf.items()
                if leaf.split("/")[0] in ("norm0a", "attn0", "norm0b",
                                          "moe0"))
    assert round(layer / 1e6, 1) == 625.4
    ends = by_leaf["embed/W"] + by_leaf["out/W"]
    assert ends == 2 * 151936 * 2048 and round(ends / 1e6, 1) == 622.3
    total = sum(by_leaf.values())
    assert total == 6 * layer + ends + 2048
    assert round(total / 1e9, 2) == 4.37
    assert round(2 * total / 1e9, 2) == 8.75
    assert round((48 * layer + ends) / 1e9, 1) == 30.6
    # 16 rows of a stage's batch hand each expert one token on average
    assert 16 * cfg["num_experts_per_tok"] / cfg["num_experts"] == 1.0
    counted = 2 * 4 * 128 * 2 + 64 * 2
    kept = 2 * 4 * 128 * 2 + 128 * 2
    assert (counted, kept) == (2176, 2304)
    pool = (cfg["engine"]["total_pages"] + 1) * 16 * 6 * kept
    assert round(pool / 1e9, 2) == 2.77


def test_the_table_is_the_mix_the_issue_names(cell):
    t = cell.traffic
    assert len(t["clients"]) == t["table"]["clients"] == 16
    assert t["table"]["requests_per_client"] == 40
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 5120,
                                  "sigma": 0.5, "min": 2560, "max": 12000}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 224,
                                  "sigma": 0.5, "min": 96, "max": 512}
    assert t["loop"] == "closed" and t["think_time_s"] == 0.0
    assert t["latency_sample"] == "sent" and t["checked_requests"] == 3
    others = {harness.Cell(BENCH, w["name"]).traffic.get("generator_seed")
              for w in BENCH["workloads"] if w["name"] != CELL}
    assert t["generator_seed"] not in others
    from benchmark.traffic.draw_table import draw_clients
    assert draw_clients(t) == t["clients"]
    assert 4600 <= t["drawn"]["prompt_median"] <= 5600
    cap = cell.config["departures"]["served_max_context"]
    lengths = [(p, o) for c in t["clients"] for p, o in c]
    assert max(p + o for p, o in lengths) == t["drawn"]["max_context"] \
        == 12512 <= cap
    # every context of the cell is past topk: every prime and every
    # decode step selects
    assert min(p for p, _ in lengths) >= 2560 > \
        cell.config["sa_config"]["topk"]
    # the replay test's rule, for this cell: the pool holds the 16
    # longest contexts of the table at once
    e = cell.config["engine"]
    need = sum(sorted((-(-(p + o) // e["page_size"]) for p, o in lengths),
                      reverse=True)[:len(t["clients"])])
    assert need == 12315 <= e["total_pages"]
    from benchmark.runners.serve_closed_replay import bucket
    assert {bucket(p, cap) for p, _ in lengths} == {4096, 8192, 12544}
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["chips"], entry["traffic"], entry["config"]) == \
        (1, "longdocs_closed16", "keye-vl-2.0-30b-a3b")


#: the cell's own per-layer entries: a later PR may add to them
READERS = ["longdocs.device_idle_share", "longdocs_step.mfu",
           "longdocs.decode_step_device_ms",
           "longdocs.prefill_device_ms_per_ktok",
           "longdocs.prefill_time_share", "longdocs.prefill_padding_share",
           "longdocs.batch_occupancy", "gqa_dsa.attend_waste_share",
           "moe128.expert_padding_share", "moe128.touched_share",
           "moe128.weight_floor_share"]


def test_the_cells_entries_list_it():
    """The cell's own entries, exactly; the set-up layer's readers,
    which every cell may list, are ``test_benchmark_setup_readers.py``'s."""
    mine = {m["name"]: m for m in BENCH["per_layer"]
            if CELL in m.get("workloads", []) and m["layer"] != "set-up"}
    assert set(READERS) == set(mine) and len(READERS) == 11
    for name in READERS:
        assert mine[name]["workloads"] == [CELL]
    assert {mine[n]["layer"] for n in READERS if n.startswith("moe128")} \
        == {"routed experts"}
    assert mine["gqa_dsa.attend_waste_share"]["layer"] == "sparse attention"
    assert not any("roofline" in n for n in mine)
    reported = {m["name"] for m in BENCH["end_to_end"]
                if CELL in m.get("workloads", [CELL])}
    assert reported == {"serve_out_tokens_per_s", "tpot_p90_s",
                        "ttft_p90_s", "setup_s"}


# ------------------------------------------------------ operation counts
def test_the_operation_counts_are_a_hand_count(cell):
    cfg, ref = cell.config, cell.reference()
    e = 2048
    attn = 2 * e * 32 * 128 + 2 * e * 4 * 128
    index = e * 16 * 64 + e * 64 + e * 16
    moe = e * 128 + 8 * 3 * e * 768
    token = 2 * 6 * (attn + index + moe)
    head = 2 * e * 151936
    # a decode token: index scores over its whole context, attention
    # (score and value products, 32 heads of 128) over the 2,048 kept
    assert ref.decode_flops(cfg, 9000) == token + head + 2 * 6 * (
        9000 * 16 * 64 + 2048 * 32 * 2 * 128)
    assert ref.decode_flops(cfg, 900) == token + head + 2 * 6 * (
        900 * 16 * 64 + 900 * 32 * 2 * 128)
    n = 5000
    scored = n * (n + 1) // 2
    attended = 2048 * 2049 // 2 + (n - 2048) * 2048
    assert ref.prefill_flops(cfg, n) == n * token + head + 2 * 6 * (
        scored * 16 * 64 + attended * 32 * 2 * 128)
    # 0.71 GFLOP a token in products: twice the 59 M parameters a token
    # meets in each of 6 layers (attention, indexer, router, 8 experts)
    assert 0.70e9 < token < 0.72e9


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


def _health(selected, attended, pairs, rows, calls, touched, p_fed,
            p_bucket, d_rows, d_count):
    return {"slots": 16,
            "sparse_attn": {"query_positions": 1, "context_positions": 2,
                            "selected_positions": selected,
                            "attended_positions": attended},
            "experts": {"tokens": 1, "held_pairs": pairs,
                        "rows_computed": rows, "max_expert_load": 9,
                        "decode_calls": calls,
                        "decode_experts_touched": touched},
            "prefill": {"fed_tokens": p_fed, "bucket_tokens": p_bucket},
            "decode_dispatch": {"rows": d_rows, "count": d_count}}


def _ctx(cell, counters=True):
    """A 10 s window traced from 2 s to 8 s. Five decode dispatches of 20
    ms; two whole primes (B: 3,000 tokens, 240 ms on the device; C: 7,000
    tokens, 560 ms) and one the trace cuts (A)."""
    from benchmark.peaks import peaks_for
    from benchmark.xplane import Trace
    decodes = [(2.60, 2.62), (3.00, 3.02), (5.00, 5.02), (5.50, 5.52),
               (7.00, 7.02)]
    primes = [("jit_fwd(3)", 2.00, 2.30), ("jit_fwd(2)", 3.50, 3.74),
              ("jit_fwd(4)", 6.02, 6.58)]
    ops = sorted([("fusion.1", a, b) for a, b in decodes]
                 + [("while.9", a, b) for _, a, b in primes],
                 key=lambda e: e[1])
    mods = sorted([("jit_fwd(1)", a, b) for a, b in decodes] + primes,
                  key=lambda e: e[1])
    host = [("prefill.fetch", 2.20, 2.40),
            ("engine.admit", 3.44, 3.45),
            ("prefill.input", 3.45, 3.50), ("prefill.forward", 3.50, 3.52),
            ("prefill.fetch", 3.52, 3.75), ("engine.seat", 3.75, 3.756),
            ("engine.admit", 5.99, 6.00),
            ("prefill.input", 6.00, 6.02), ("prefill.forward", 6.02, 6.03),
            ("prefill.fetch", 6.03, 6.59), ("engine.seat", 6.59, 6.594)]
    reqs = [
        _Req(9000, [2.4, 2.62, 3.02], _Handle(1.0, 1.2, 1.6, 2.4, 9000)),
        _Req(3000, [3.76, 5.02, 5.52],
             _Handle(2.0, 3.1, 3.4495, 3.7505, 3000)),
        _Req(7000, [6.60, 7.02], _Handle(5.0, 5.9, 5.9995, 6.5905, 7000))]
    # over the window: 600 decode calls (6 layers x 100 dispatches) that
    # touched 48,600 experts (81 a call); 95 of 100 x 16 rows live
    h0, h1 = (_health(100, 400, 1000, 1024, 60, 4800, 500, 512, 160, 10),
              _health(100 + 30000, 400 + 120000, 1000 + 9000, 1024 + 12000,
                      60 + 600, 4800 + 48600, 500 + 19000, 512 + 24832,
                      160 + 1520, 10 + 100)) if counters else ({}, {})
    record = {"window_s": 10.0,
              "serve": {"replay": _Replay(reqs), "sent": reqs,
                        "health0": h0, "health1": h1}}
    return {"cell": cell, "config": cell.config, "traffic": cell.traffic,
            "record": record, "trace": Trace({0: ops}, {0: mods}, host),
            "peaks": peaks_for("TPU v5 lite"), "chips": 1,
            "trace_interval": (2.0, 8.0)}


def _by_hand(cell):
    cfg, ref = cell.config, cell.reference()
    window = (sum(ref.prefill_flops(cfg, n) for n in (9000, 3000, 7000))
              + sum(ref.decode_flops(cfg, c)
                    for c in (9001, 9002, 3001, 3002, 7001)))
    return {
        # the trace's own span: its first op starts at 2.00, its last
        # ends at 7.02
        "longdocs.device_idle_share": 100 * (1 - (0.1 + 0.3 + 0.24 + 0.56)
                                             / 5.02),
        "longdocs_step.mfu": 100 * window / (10.0 * 197e12),
        "longdocs.decode_step_device_ms": 20.0,
        "longdocs.prefill_device_ms_per_ktok": 800.0 / 10.0,
        "longdocs.prefill_time_share":
            100 * (0.8 + 0.301 + 0.591) / 10.0,
        "longdocs.prefill_padding_share": 100 * (1 - 19000 / 24832),
        "longdocs.batch_occupancy": 100 * 1520 / (100 * 16),
        "gqa_dsa.attend_waste_share": 100 * (1 - 30000 / 120000),
        "moe128.expert_padding_share": 100 * (1 - 9000 / 12000),
        "moe128.touched_share": 100 * 48600 / (600 * 128),
        # 81 experts a call x 6 layers x 9,437,184 B = 4.59 GB: 5.6 ms of
        # a 20 ms step
        "moe128.weight_floor_share":
            100 * (81 * 6 * 9437184 / 819e9) / 0.020,
    }


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_reads_the_number_a_hand_count_gives(cell, metric):
    got = cell.reader(metric)(_ctx(cell))
    assert got == pytest.approx(_by_hand(cell)[metric], rel=1e-9)
    assert 0 < got < 100 or metric.endswith("_ms") \
        or metric.endswith("_per_ktok")


@pytest.mark.parametrize("metric", [
    "gqa_dsa.attend_waste_share", "moe128.expert_padding_share",
    "moe128.touched_share", "moe128.weight_floor_share",
    "longdocs.prefill_padding_share", "longdocs.batch_occupancy"])
def test_on_a_program_without_the_counters_it_reads_nothing(cell, metric):
    assert cell.reader(metric)(_ctx(cell, counters=False)) is None


def test_on_the_parents_counters_the_new_shares_read_nothing(cell):
    """A program whose experts count four things and not six (the parent
    of the PR that brought these entries) gives the two new shares nothing
    to read; the padding share reads as before."""
    ctx = _ctx(cell)
    for end in ("health0", "health1"):
        for key in ("decode_calls", "decode_experts_touched"):
            del ctx["record"]["serve"][end]["experts"][key]
    assert cell.reader("moe128.touched_share")(ctx) is None
    assert cell.reader("moe128.weight_floor_share")(ctx) is None
    assert cell.reader("moe128.expert_padding_share")(ctx) == \
        pytest.approx(25.0)


def test_the_weight_floor_wants_the_references_count(cell):
    """On a configuration whose reference counts no expert's bytes,
    nothing is read and nothing raised."""
    ctx = _ctx(cell)
    other = harness.Cell(BENCH, "deepseek-v3.2.docs_closed16")
    ctx["cell"], ctx["config"] = other, other.config
    assert cell.reader("moe128.weight_floor_share")(ctx) is None


def test_the_result_line_of_a_traced_run_holds_every_one(cell):
    got = harness.per_layer_metrics(cell, _ctx(cell))
    assert set(READERS) == set(got)
    assert {got[m]["unit"] for m in READERS} == {"%", "ms"}


# ----------------------------------- the reference against the program
def test_the_reference_is_the_programs_model_on_the_cpu():
    """Seeded float32 weights at the dry run's widths: the zoo model's
    full forward and the reference's pass give the same logits (contexts
    past the dry ``topk``: selection acts); the float8 control does not."""
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
    assert cfg["sa_config"]["topk"] == 24 < 41
    want = np.asarray(ref.logits_at(cfg, params, list(ids), pos))
    probs = np.asarray(net.output(ids[None].astype(np.int32)))[0]  # [V, T]
    got = np.log(probs[:, pos].T)
    got = got - got.mean(1, keepdims=True)
    want_c = want - want.mean(1, keepdims=True)
    assert np.abs(got - want_c).max() < 2e-4
    selected = np.asarray(ref.selected_at(cfg, params, list(ids), pos))
    assert selected.shape == (3, 36, 41)
    assert selected.sum(axis=2).tolist() == \
        [[min(24, p + 1) for p in pos]] * 3
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
    args = argparse.Namespace(seed=2 ** 31 + 36, seconds=0.5)
    return dry, dry.runner().run(dry, args, jax.devices()[:1],
                                 time.perf_counter(), None, control=True)


def test_the_dry_run_ends_with_every_check_ok(dry_run):
    dry, record = dry_run
    assert [c.line() for c in record["checks"] if not c.ok] == []
    assert dry.config["hidden_size"] == 64
    health = record["serve"]["health1"]
    assert health["kv_traffic"]["decode_path"] == "direct-xla"
    assert record["attempted"] > 5 and record["failed"] == 0
    assert record["compiles_in_window"] == 0
    assert max(len(r.prompt) for r in record["serve"]["finished"]) >= 150
    assert record["readings"]["program"]["distinct_served_tokens"] >= 3
    # the counters the program_counter readers read are there
    sparse, experts = health["sparse_attn"], health["experts"]
    assert 0 < sparse["selected_positions"] < sparse["attended_positions"]
    assert sparse["selected_positions"] < sparse["context_positions"]
    assert experts["held_pairs"] == 2 * experts["tokens"]
    assert experts["decode_calls"] == \
        3 * health["decode_dispatch"]["count"] > 0
    assert 2 * experts["decode_calls"] <= \
        experts["decode_experts_touched"] <= 6 * experts["decode_calls"]
    assert "prefix_cache" in health


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
    assert data["set_from"]["limit"] and data["set_from"]["lower"]["from"]
