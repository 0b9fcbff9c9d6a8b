"""The ``deepseek-v3.2.docs_closed16`` cell: its configuration against the
source's numbers and the floors of a cut, its table, its per-layer
readers against hand counts (and on a program that has none of their
counters), its operation counts, and its dry run through the serving
runner with the 8-bit control beside it."""

import json
import math
import os

import pytest

from benchmark import compare, harness

CELL = "deepseek-v3.2.docs_closed16"
BENCH = harness.load_benchmark()

#: huggingface.co/deepseek-ai/DeepSeek-V3.2 config.json, the numbers
SOURCE = dict(
    first_k_dense_replace=3, hidden_size=7168, index_head_dim=128,
    index_n_heads=64, index_topk=2048, intermediate_size=18432,
    kv_lora_rank=512, max_position_embeddings=163840,
    moe_intermediate_size=2048, moe_layer_freq=1, n_group=8,
    n_routed_experts=256, n_shared_experts=1, num_attention_heads=128,
    num_experts_per_tok=8, num_hidden_layers=61, num_key_value_heads=128,
    num_nextn_predict_layers=1, q_lora_rank=1536, qk_nope_head_dim=128,
    qk_rope_head_dim=64, rms_norm_eps=1e-06, rope_theta=10000,
    routed_scaling_factor=2.5, topk_group=4, v_head_dim=128,
    vocab_size=129280, ep_size=1)


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(BENCH, CELL)


def test_the_configuration_is_the_sources_but_for_what_it_says_it_cut(cell):
    cfg = cell.config
    reduced = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
               "n_routed_experts": 16, "vocab_size": 16160,
               "num_nextn_predict_layers": 0}
    assert cfg["reduced"] == list(reduced)
    for key, value in SOURCE.items():
        assert cfg[key] == reduced.get(key, value), key
        if key in reduced:
            assert cfg["published"][key] == value
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    # the floors of a cut: the leading dense layer once and four expert
    # layers, at least 8 experts, at least an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["first_k_dense_replace"] >= 1
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= SOURCE["vocab_size"]
    assert "16 chips share each layer" in cfg["deployment"]
    e = cfg["engine"]
    assert (e["slots"], e["page_size"], e["decode_impl"],
            e["kv_dtype"]) == (16, 16, "xla", "bf16")
    assert e["total_pages"] >= 8704 and e["prefix_cache"] is True
    assert cfg["dry_run"]["index_topk"] < 33     # below the dry contexts


def test_the_share_is_the_issues_arithmetic(cell):
    """4.64 B parameters, 9.27 GB in bfloat16, 7,040 B a cached token."""
    specs = cell.reference().param_specs(cell.config)
    by_vertex = {}
    for name, shape, _, _ in specs:
        v = name.split("/")[0]
        by_vertex[v] = by_vertex.get(v, 0) + math.prod(shape)
    assert by_vertex["attn0"] == by_vertex["attn4"]
    assert round(by_vertex["attn0"] / 1e6, 1) == 201.1     # 187.1 + 14.0
    assert round(by_vertex["ffn0"] / 1e6, 1) == 396.4
    assert round(by_vertex["moe1"] / 1e6, 1) == 750.5  # 44.0+1.8+16x44.04
    total = sum(by_vertex.values())
    assert round(total / 1e9, 2) == 4.64
    assert round(2 * total / 1e9, 2) == 9.27
    cfg = cell.config
    token = 2 * cfg["num_hidden_layers"] * (
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
        + cfg["index_head_dim"])
    assert token == 7040


def test_the_table_is_the_mix_the_issue_names(cell):
    t = cell.traffic
    assert len(t["clients"]) == t["table"]["clients"] == 16
    assert t["generator_seed"] == 20261002
    assert t["prompt_tokens"] == {"dist": "lognormal", "median": 5120,
                                  "sigma": 0.35, "min": 3072, "max": 8000}
    assert t["output_tokens"] == {"dist": "uniform", "min": 64, "max": 192}
    assert 4800 <= t["drawn"]["prompt_median"] <= 5500
    assert t["latency_sample"] == "sent" and t["checked_requests"] == 3
    assert t["drawn"]["max_context"] <= \
        cell.config["departures"]["served_max_context"] == 8192
    # contexts are 1.5 to 4 times index_topk: the prefill buckets are two
    from benchmark.runners.serve_closed_replay import bucket
    assert {bucket(p, 8192) for c in t["clients"] for p, _ in c} == \
        {4096, 8192}
    assert min(p for c in t["clients"] for p, _ in c) >= 1.5 * 2048


#: the cell's own per-layer entries: a later PR may add to them
READERS = ["docs.device_idle_share", "docs_step.mfu",
           "docs.prefill_device_ms_per_ktok", "docs.decode_step_device_ms",
           "moe.expert_padding_share", "dsa.attend_waste_share",
           "docs.prefill_padding_share", "docs.prefill_time_share",
           "docs.batch_occupancy", "docs.queue_wait_p50_s"]


def test_the_cells_entries_list_it():
    """Membership only: a later cell appended to an end-to-end metric, or
    a later reader on this cell, is a file of its own and no edit here."""
    mine = {m["name"]: m for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [])}
    assert set(READERS) <= set(mine)
    for name in READERS:
        assert mine[name]["workloads"] == [CELL]
    reported = {m["name"] for m in BENCH["end_to_end"]
                if CELL in m.get("workloads", [CELL])}
    assert reported >= {"serve_out_tokens_per_s", "tpot_p90_s",
                        "ttft_p90_s", "setup_s"}


# ------------------------------------------------------ operation counts
def test_the_operation_counts_are_a_hand_count_at_this_share(cell):
    cfg, ref = cell.config, cell.reference()
    e, h = 7168, 128
    attn = (e * 1536 + 1536 * h * 192 + e * 576 + h * 128 * e
            + 512 * h * 256)
    index = 1536 * 64 * 128 + e * 128 + e * 64
    moe = e * 256 + 3 * e * 2048 * (1 + 8 * 16 / 256)
    token = 2 * (5 * (attn + index) + 3 * e * 18432 + 4 * moe)
    head = 2 * e * 16160
    # a query at context c scores c index keys, attends min(2048, c)
    assert ref.decode_flops(cfg, 5000) == int(token) + head + 2 * 5 * (
        5000 * 64 * 128 + 2048 * h * (512 + 512 + 64))
    assert ref.decode_flops(cfg, 100) == int(token) + head + 2 * 5 * (
        100 * 64 * 128 + 100 * h * (512 + 512 + 64))
    n = 3000
    pairs = n * (n + 1) // 2
    seen = 2048 * 2049 // 2 + (n - 2048) * 2048
    assert ref.prefill_flops(cfg, n) == n * int(token) + head + 2 * 5 * (
        pairs * 64 * 128 + seen * h * (128 + 64 + 128))
    # a prime of 8,192 is ~36 TFLOP, ~4.4 GFLOP a token, of which 3.35
    # are the token's own products
    assert 3.3e9 < token < 3.4e9
    assert 4.3e9 < ref.prefill_flops(cfg, 8192) / 8192 < 4.5e9


# ------------------------------------------------------------ the readers
#: the host's clock (a request's own records) over the trace's, seconds
HOST = 1.7e9


class _Handle:
    """A request's trace as the readers use it: its records, and the
    breakdown the engine's ``RequestTrace`` makes of them."""

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


def _health(pairs, rows, selected, attended, fed, bucket, count, active):
    return {"slots": 16,
            "experts": {"tokens": 2 * pairs, "held_pairs": pairs,
                        "rows_computed": rows, "max_expert_load": 9},
            "sparse_attn": {"query_positions": 1, "context_positions": 1,
                            "selected_positions": selected,
                            "attended_positions": attended},
            "prefill": {"fed_tokens": fed, "bucket_tokens": bucket},
            "decode_dispatch": {"count": count, "mean_ms": 20.0,
                                "rows": active}}


def _ctx(cell, counters=True):
    """A 10 s window traced from 2 s to 8 s; five decode dispatches of 50
    ms in the trace, and five requests' primes:

    - E, 4,200 tokens, primed from 0.5 s to 0.972 s, before the trace: as
      long as B's to the microsecond, so an offset that lays B's spans on
      E's records fits one prime, and the right one fits two;
    - A, 5,000 tokens: its prime began before the trace (no input span),
      400 ms of its program are in it;
    - B, 4,000 tokens, whole: 400 ms on the device;
    - C, 3,500 tokens of which a prefix hit served 500, whole: 300 ms;
    - D, 6,000 tokens: its prime outlasts the trace (no fetch span), 250
      ms of its program are in it."""
    from benchmark.peaks import peaks_for
    from benchmark.xplane import Trace
    decodes = [(2.60, 2.65), (3.00, 3.05), (5.00, 5.05), (5.50, 5.55),
               (7.00, 7.05)]
    primes = [("jit_fwd(3)", 2.00, 2.40), ("jit_fwd(2)", 3.50, 3.90),
              ("jit_fwd(2)", 6.02, 6.32), ("jit_fwd(3)", 7.75, 8.00)]
    ops = sorted([("fusion.1", a, b) for a, b in decodes]
                 + [("fusion.9", a, b) for _, a, b in primes],
                 key=lambda e: e[1])
    mods = sorted([("jit_fwd(1)", a, b) for a, b in decodes] + primes,
                  key=lambda e: e[1])
    host = [("prefill.fetch", 2.30, 2.50),
            ("prefill.input", 3.45, 3.50), ("prefill.forward", 3.50, 3.52),
            ("prefill.fetch", 3.52, 3.92),
            ("prefill.input", 6.00, 6.02), ("prefill.forward", 6.02, 6.03),
            ("prefill.fetch", 6.03, 6.33),
            ("prefill.input", 7.70, 7.75), ("prefill.forward", 7.75, 7.76)]
    reqs = [
        _Req(4200, [0.98], _Handle(0.0, 0.4, 0.4995, 0.9725, 4200)),
        _Req(5000, [2.5, 2.65, 3.05], _Handle(1.0, 1.2, 1.6, 2.5, 5000)),
        _Req(4000, [3.95, 5.05, 5.55],
             _Handle(2.0, 3.1, 3.4495, 3.9225, 4000)),
        _Req(3500, [6.35, 7.05], _Handle(5.0, 5.9, 5.9995, 6.3305, 3000)),
        _Req(6000, [8.45], _Handle(7.0, 7.6, 7.699, 8.44, 6000))]
    h0, h1 = (_health(100, 400, 5000, 9000, 1000, 2048, 10, 150),
              _health(150, 600, 8000, 21000, 23200, 34816, 30, 430)) \
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
    window = (sum(ref.prefill_flops(cfg, n)
                  for n in (4200, 5000, 4000, 3500, 6000))
              + sum(ref.decode_flops(cfg, c)
                    for c in (5001, 5002, 4001, 4002, 3501)))
    return {
        "docs.device_idle_share": 100 * (1 - 1.6 / 6.0),
        "docs_step.mfu": 100 * window / (10.0 * 197e12),
        # B and C alone: 700 ms of their programs over the 7,000 tokens
        # they were fed; the 650 ms of the two cut primes count nowhere
        "docs.prefill_device_ms_per_ktok": 700.0 / 7.0,
        "docs.decode_step_device_ms": 50.0,
        "moe.expert_padding_share": 100 * (1 - 50 / 200),
        "dsa.attend_waste_share": 100 * (1 - 3000 / 12000),
        "docs.prefill_padding_share": 100 * (1 - 22200 / 32768),
        "docs.prefill_time_share":
            100 * (0.473 + 0.9 + 0.473 + 0.331 + 0.741) / 10.0,
        "docs.batch_occupancy": 100 * 280 / (20 * 16),
        "docs.queue_wait_p50_s": 0.6,
    }


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_reads_the_number_a_hand_count_gives(cell, metric):
    got = cell.reader(metric)(_ctx(cell))
    assert got == pytest.approx(_by_hand(cell)[metric], rel=1e-9)
    if "mfu" in metric:
        assert 0 < got < 100


@pytest.mark.parametrize("metric", [
    "moe.expert_padding_share", "dsa.attend_waste_share",
    "docs.prefill_padding_share", "docs.batch_occupancy"])
def test_on_a_program_without_the_counters_it_reads_nothing(cell, metric):
    assert cell.reader(metric)(_ctx(cell, counters=False)) is None


def test_a_trace_that_holds_no_whole_prime_reads_no_prefill_time(cell):
    """Cut primes alone, or spans no request's records fit: nothing is
    read, nothing raised."""
    from benchmark.xplane import Trace
    ctx = _ctx(cell)
    reader = cell.reader("docs.prefill_device_ms_per_ktok")
    whole = ctx["trace"]
    cut = [e for e in whole.host if not 3.0 < e[1] < 7.0]
    ctx["trace"] = Trace(whole.ops, whole.modules, cut)
    assert reader(ctx) is None
    late = [(n, a + 0.1, b + 0.3) for n, a, b in whole.host]
    ctx["trace"] = Trace(whole.ops, whole.modules, late)
    assert reader(ctx) is None
    ctx["trace"] = Trace(whole.ops, whole.modules, [])
    assert reader(ctx) is None


def test_the_result_line_of_a_traced_run_holds_every_one(cell):
    got = harness.per_layer_metrics(cell, _ctx(cell))
    assert set(READERS) <= set(got)
    assert {got[m]["unit"] for m in READERS} == {"%", "ms", "s"}


# ------------------------------------------------- the dry run, on the CPU
@pytest.fixture(scope="module")
def dry_run():
    import argparse
    import time
    import jax
    dry = harness.Cell(BENCH, CELL, dry_run=True)
    args = argparse.Namespace(seed=2 ** 31 + 29, seconds=0.5)
    return dry, dry.runner().run(dry, args, jax.devices()[:1],
                                 time.perf_counter(), None, control=True)


def test_the_dry_run_ends_with_every_check_ok(dry_run):
    dry, record = dry_run
    assert [c.line() for c in record["checks"] if not c.ok] == []
    assert dry.config["hidden_size"] == 64 and dry.config["index_topk"] == 16
    health = record["serve"]["health1"]
    assert health["kv_traffic"]["decode_path"] == "direct-xla"
    assert record["attempted"] > 5 and record["failed"] == 0
    # a prime of two query blocks (sparse_latent.QUERY_BLOCK = 128) was
    # served in the window, and the longest is always checked
    assert max(len(r.prompt) for r in record["serve"]["finished"]) == 200
    assert record["readings"]["program"]["distinct_served_tokens"] >= 3
    # ids from the slice of the vocabulary held here
    assert max(t for r in record["serve"]["finished"]
               for t in r.prompt) < dry.config["vocab_size"] == 96
    # the counters the two program_counter readers read are there
    assert health["experts"]["held_pairs"] > 0
    assert 0 < health["sparse_attn"]["selected_positions"] \
        < health["sparse_attn"]["attended_positions"]


def test_computing_in_float8_fails_the_tolerance(dry_run):
    """The nearest precision below: the reference itself with float8
    wherever the program has bfloat16 (``reference/quant.py``), at the
    positions the run checked, lies well over the limit the program is
    under."""
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
