"""Serving cells (traffic kind ``serve_closed_replay``): a started
``GenerationEngine`` over the net the configuration's ``model`` names
(``models/<model>.py``; plain reference ``reference/<model>.py``), driven
by the closed-loop replay through ``GenerationEngine.submit``.

Set-up: weights on the device from the seed (bfloat16, one jitted call),
the engine as deployed (paged KV, slots and pool from the configuration),
a warm-up of exactly the prefill buckets the table uses and the decode
arena, then the lead-in that puts every client in flight. The plain
reference runs over a sample of the finished requests once the window has
closed, the peak has been read and the engine is gone.

What a served model brings (two files and a configuration, found by the
configuration's ``model``; nothing here knows a model's name):

- ``models/<model>.py``: ``build_shell(cfg, max_length) -> (net, shapes)``,
  a ``ComputationGraph`` that streams through ``rnn_time_step``, fed by the
  engine as the net asks (token ids ``[N, T]`` where a layer of it takes
  ids, as every zoo transformer's does; else one-hot ``[N, V, T]``),
  initialised without drawing a weight, and the shapes of its parameter
  tree (vertex -> leaf -> shape);
- ``reference/<model>.py``: ``param_specs(cfg)``, the leaves as ``(name,
  shape, mean, std)`` with ``name`` = ``<vertex>/<leaf>`` of that tree, and
  ``logits_at(cfg, params, ids, positions, low=False)``, the plain forward
  pass (``low``: the 8-bit control of ``reference/quant.py``). The
  ``*_step.mfu`` readers also want ``prefill_flops`` and ``decode_flops``;
- the configuration's keys ``vocab_size`` (the slice of the vocabulary
  held here: ``prompt_ids`` draws from it and the logits are over it),
  ``departures.served_max_context`` (no context of the table may pass
  it; see Windows, below) and ``engine.{slots, page_size, total_pages,
  kv_dtype, decode_impl, prefix_cache, queue_limit}``. ``decode_impl``
  also says which path ``health()["kv_traffic"]["decode_path"]`` has to
  read after the window: ``direct-xla`` for ``xla`` (a decoder whose cache
  the grouped-query Mosaic kernel cannot read says that),
  ``direct-pallas`` for ``auto`` and ``pallas``; a run that fell back to
  another path is not correct.

What a mix of this kind holds (``traffic/<traffic>.json``; the rules are
``tests/benchmark/test_benchmark_replay.py``'s, over every such cell):
``loop`` ``"closed"`` and ``think_time_s`` 0.0 (the one loop this runner
drives); the parameters the table was drawn from (``generator_seed``,
``prompt_tokens`` and ``output_tokens``, each a ``dist`` with ``min`` and
``max``, ``table.{clients, requests_per_client}``) and what
``traffic/draw_table.py`` made of them, once: ``clients`` (per client an
ordered list of ``[prompt tokens, output tokens]``, at least 40, one
client a slot at the most, no context over ``served_max_context``, never
more pages than the pool has) and ``drawn``; ``latency_sample`` (``sent``:
wait for the first token of every request sent in the window;
``finished``) and ``checked_requests`` (how many finished requests the
reference follows, beside the longest). ``dry_run`` may hold a smaller
table for a test run.

Windows. A configuration holds its source's ``sliding_window`` as the
source gives it (a null is no window). Where the program serves the
windowed layers as full causal attention, the file says so under
``departures.sliding_window``, and then ``served_max_context`` may not
pass the window: up to it the two are one function. A file without that
departure says the windowed layers run as windows; its contexts may pass
the window, its reference computes the windows, and the cell's
``correct`` holds the program to them: that is the model's to meet, not
this runner's.
"""

from __future__ import annotations

import time
from typing import List

import numpy as np

from benchmark import compare, harness, weights
from benchmark.replay import ClosedLoopReplay, percentile, prompt_ids


def bucket(n: int, cap: int) -> int:
    """The engine's padded-prefill bucket of a prompt: next power of two,
    capped at the streaming capacity."""
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


class Program:
    """The system under test: net + engine, weights from the seed."""

    def __init__(self, cell, seed: int, on_tpu: bool):
        import jax.numpy as jnp
        from deeplearning4j_tpu.serving import (GenerationEngine,
                                                PagedKVConfig)
        cfg = cell.config
        self.cfg = cfg
        self.cap = cfg["departures"]["served_max_context"]
        net, shapes = cell.model().build_shell(cfg, self.cap)
        specs = cell.reference().param_specs(cfg)
        self.weights = weights.make_weights(specs, seed, jnp.bfloat16)
        weights.check_tree_matches(self.weights, shapes)
        for vertex, leaves in weights.as_tree(self.weights).items():
            net.params[vertex] = leaves
        self.net = net
        e = cfg["engine"]
        # off the TPU ``auto`` would fall back to XLA: the kernel runs
        # there in interpret mode, so that a dry run takes the path the
        # chip takes; a configuration that says ``xla`` takes it everywhere
        impl = e["decode_impl"]
        if not on_tpu and impl != "xla":
            impl = "pallas"
        #: what ``health()`` has to report: ``direct-<the impl that ran>``
        self.decode_path = "direct-" + ("xla" if impl == "xla" else "pallas")
        paging = PagedKVConfig(
            page_size=e["page_size"], total_pages=e["total_pages"],
            prefix_cache=e["prefix_cache"], kv_dtype=e["kv_dtype"],
            decode_impl=impl, kernel_interpret=not on_tpu)
        self.engine = GenerationEngine(
            net, cfg["vocab_size"], slots=e["slots"],
            queue_limit=e["queue_limit"], paging=paging)

    def submit(self, prompt: List[int], steps: int):
        return self.engine.submit(
            prompt, steps, top_k=1, rng=np.random.default_rng(0))

    def warm_up(self, table, seed: int) -> dict:
        """One request per prefill bucket the table uses, with unequal
        lengths so that a slot retires while others decode: compiles each
        bucket's prefill, the page scatter, the arena join, the decode
        dispatch and the free-row reset, and no shape the cell never
        sends."""
        vocab = self.cfg["vocab_size"]
        by_bucket = {}
        for client in table:
            for p_len, _ in client:
                by_bucket.setdefault(bucket(p_len, self.cap), p_len)
        handles = []
        for i, (_, p_len) in enumerate(sorted(by_bucket.items())):
            handles.append(self.submit(
                prompt_ids(seed, 1 << 20, i, p_len, vocab), 3 + 2 * i))
        self.engine.run_until_idle()
        for h in handles:
            h.result(timeout=0)
        return {"buckets": sorted(by_bucket)}

    def release(self) -> None:
        self.engine.shutdown()
        self.net.state = None
        self.net.params = None
        self.engine = self.net = None


def sample_requests(finished, seed: int, n: int):
    """``n`` finished requests drawn from the seed, and the longest."""
    if not finished:
        return []
    rng = np.random.default_rng([int(seed), 77])
    order = rng.permutation(len(finished))
    picked = [finished[i] for i in order[:n]]
    longest = max(finished, key=lambda r: len(r.prompt) + len(r.token_t))
    if longest not in picked:
        picked.append(longest)
    return picked


#: the reference pads each sampled request to a multiple of this many
#: positions: a few compiled lengths, none much longer than the request
REFERENCE_PAD = 512


def reference_gaps(cell, params, sample, low: bool = False,
                   pad: int = REFERENCE_PAD):
    """For each sampled request, the reference's logits at every served
    position (one causal pass over prompt + served tokens). Returns, per
    request, the widest gap of the served tokens, and (for the control)
    of the tokens the ``low`` precision would have put first."""
    cfg, ref = cell.config, cell.reference()
    served_gaps, low_gaps, positions_n = [], [], 0
    for prompt, generated in sample:
        ids = list(prompt) + list(generated)
        pos = np.arange(len(prompt) - 1, len(ids) - 1)
        padded = ids + [0] * (-len(ids) % pad)
        logits = np.asarray(ref.logits_at(cfg, params, padded, pos))
        served_gaps.append(compare.widest_token_gap(logits, generated))
        positions_n += len(pos)
        if low:
            lo = np.asarray(ref.logits_at(cfg, params, padded, pos,
                                          low=True))
            low_gaps.append(compare.widest_token_gap(
                logits, lo.argmax(axis=1)))
    return served_gaps, low_gaps, positions_n


def run(cell, args, devices, clock0: float, tracer=None,
        control: bool = False) -> dict:
    """One run of a serving cell. ``control`` (benchmark/limits.py) also
    reads, at the same positions, the gap of the token the 8-bit control
    puts first."""
    from deeplearning4j_tpu import monitoring
    from deeplearning4j_tpu.monitoring import runtime

    cfg, traffic = cell.config, cell.traffic
    monitoring.ensure_started()
    compiles = monitoring.global_registry().get(runtime.COMPILE_COUNTER)
    on_tpu = devices[0].platform == "tpu"

    prog = Program(cell, args.seed, on_tpu)
    table = traffic["clients"]
    max_context = max(p + o for c in table for p, o in c)
    if max_context > prog.cap:
        raise ValueError(f"the table reaches {max_context} positions, "
                         f"over the served maximum {prog.cap}")
    warm = prog.warm_up(table, args.seed)
    prog.engine.start()

    marks = {}

    def on_open(t0: float) -> None:
        marks["compiles"] = compiles.total()
        marks["health"] = prog.engine.health()
        if tracer is not None:
            tracer.arm(t0)

    replay = ClosedLoopReplay(prog.submit, table, args.seed,
                              cfg["vocab_size"], args.seconds,
                              on_open=on_open).start()
    replay.wait_closed(timeout=args.seconds + 600)
    compiled_in_window = int(compiles.total() - marks["compiles"])
    health1 = prog.engine.health()
    if traffic["latency_sample"] == "sent":
        replay.drain_first_tokens(timeout=60.0)
    if tracer is not None:
        tracer.finish()
    window_s = replay.t1 - replay.t0

    sent = replay.sent_in_window()
    finished = replay.finished_in_window()
    failed = [r for r in replay.requests
              if r.error is not None and replay.in_window(r.done_t)]
    wrong_length = [r for r in finished if len(r.token_t) != r.steps]
    tokens = replay.tokens_in_window()
    ttfts, tpots = replay.ttfts(), replay.tpots()
    record = {
        "setup_s": replay.t0 - clock0,
        "window_s": window_s,
        "attempted": len(sent),
        "failed": len(failed),
        "compiles_in_window": compiled_in_window,
        "warm_up": warm,
        "end_to_end": {"serve_out_tokens_per_s": tokens / window_s},
        "serve": {
            "replay": replay, "health0": marks["health"],
            "health1": health1, "tokens": tokens, "ttfts": ttfts,
            "tpots": tpots, "sent": sent, "finished": finished,
            "max_context": max_context,
        },
    }
    if tpots:
        record["end_to_end"]["tpot_p90_s"] = percentile(tpots, 90)
    if ttfts:
        record["end_to_end"]["ttft_p90_s"] = percentile(ttfts, 90)
    # everything a per-layer reader wants from the program is read now:
    # the handles (and their traces) outlive the engine
    sample = [(r.prompt, r.generated[:len(r.token_t)])
              for r in sample_requests(
                  [r for r in finished if r.error is None],
                  args.seed, traffic["checked_requests"])]
    decode_path = health1["kv_traffic"]["decode_path"]
    record["memory_peak_bytes"] = harness.peak_memory(devices)
    params = prog.weights
    prog.release()

    gaps, low_gaps, n_pos = reference_gaps(cell, params, sample,
                                           low=control)
    limits = cell.limits
    record["checks"] = [
        compare.Check("served_token_gap_max",
                      max(gaps) if gaps else float("inf"),
                      limits["served_token_gap_max"]),
        compare.Check("requests_failed", len(failed), 0, exact=True),
        compare.Check("output_length_mismatches", len(wrong_length), 0,
                      exact=True),
        compare.Check("compiles_in_window", compiled_in_window, 0,
                      exact=True),
        compare.Check("decode_path_not_direct_pallas",
                      int(decode_path != prog.decode_path), 0, exact=True),
        compare.Check("max_context_positions", max_context, prog.cap),
    ]
    record["checked"] = {"requests": len(sample), "positions": n_pos}
    # beside the result, on standard error: the medians that stand beside
    # the tails as per-layer metrics, and what the comparison covered
    record["notes"] = {
        "requests_sent_in_window": len(sent),
        "requests_finished_in_window": len(finished),
        "tpot_samples": len(tpots), "ttft_samples": len(ttfts),
        "tpot_p50_s": percentile(tpots, 50) if tpots else None,
        "ttft_p50_s": percentile(ttfts, 50) if ttfts else None,
        "checked_requests": len(sample), "checked_positions": n_pos}
    served = [t for _, g in sample for t in g]
    record["readings"] = {"program": {
        "served_token_gap_max": max(gaps) if gaps else None,
        "per_request": gaps,
        "distinct_served_tokens": len(set(served)),
        "served_tokens": len(served)}}
    if control:
        record["readings"]["control_fp8"] = {
            "served_token_gap_max": max(low_gaps), "per_request": low_gaps}
    return record
