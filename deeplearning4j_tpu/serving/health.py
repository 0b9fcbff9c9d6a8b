"""Shared serving telemetry registration.

Every serving component publishes the same ``dl4jtpu_serving_*`` series
through ONE code path (this module) instead of per-component copies:
request/error/deadline/rejection counters with their handles resolved
once (the hot path must not re-enter the registry's get-or-create lock
per request), and scrape-time health gauges holding a WEAK reference —
a registry series must not pin a shut-down server (and its device
params) alive forever; a collected instance scrapes as down/empty.

``ParallelInference`` and ``GenerationEngine`` both register here; the
``model`` label value distinguishes their series (the engine prefixes
``engine:``).
"""

from __future__ import annotations

import weakref
from typing import Dict, Optional

from deeplearning4j_tpu.monitoring.metrics import (
    MetricsRegistry, global_registry)

SERVING_HEALTHY = "dl4jtpu_serving_healthy"
SERVING_READY = "dl4jtpu_serving_ready"
SERVING_QUEUE_DEPTH = "dl4jtpu_serving_queue_depth"
SERVING_REQUESTS = "dl4jtpu_serving_requests_total"
SERVING_ERRORS = "dl4jtpu_serving_errors_total"
SERVING_DEADLINE_EXCEEDED = "dl4jtpu_serving_deadline_exceeded_total"
SERVING_QUEUE_REJECTED = "dl4jtpu_serving_queue_rejected_total"

#: continuous-batching engine extras (engine.py registers these)
SERVING_ACTIVE_SLOTS = "dl4jtpu_serving_active_slots"
SERVING_TOKENS = "dl4jtpu_serving_tokens_total"
SERVING_TTFT = "dl4jtpu_serving_ttft_seconds"
SERVING_TPOT = "dl4jtpu_serving_tpot_seconds"
SERVING_QUEUE_WAIT = "dl4jtpu_serving_queue_wait_seconds"

#: block-paged KV arena + prefix cache + in-engine speculation (engine
#: registers these only in the matching mode)
SERVING_KV_PAGES_TOTAL = "dl4jtpu_serving_kv_pages_total"
SERVING_KV_PAGES_USED = "dl4jtpu_serving_kv_pages_used"
SERVING_PREFIX_HITS = "dl4jtpu_serving_prefix_cache_hits_total"
SERVING_PREFIX_MISSES = "dl4jtpu_serving_prefix_cache_misses_total"
SERVING_PREFIX_REUSED_TOKENS = \
    "dl4jtpu_serving_prefix_cache_reused_tokens_total"
SERVING_SPEC_ACCEPTANCE = "dl4jtpu_serving_spec_acceptance_ratio"

#: KV-traffic accounting for the paged decode paths (engine registers
#: these in paged mode): bytes the KV round trip MOVES per dispatch —
#: modeled host-side from the path in use (legacy round trip:
#: gather + scatter of the full dense view; direct-xla: one in-dispatch
#: gather + the one-token append; direct-pallas: live pages read + the
#: one-token append) — plus the per-step decode dispatch latency. The
#: round-trip elimination is a number here, not a claim.
SERVING_KV_BYTES_MOVED = "dl4jtpu_serving_kv_bytes_moved_total"
SERVING_DISPATCH_LATENCY = "dl4jtpu_serving_decode_dispatch_seconds"

#: counts at the engine cycle's phase boundaries, read at scrape time
#: from the totals engine.health() keeps under ``decode_dispatch.rows``,
#: ``prefill`` and ``host_io``: active rows summed over decode dispatches
#: (occupancy = rows / (dispatches x slots)); prompt tokens per prime by
#: ``kind`` (fed / bucket = the padded width dispatched; tokens the prefix
#: cache served instead: SERVING_PREFIX_REUSED_TOKENS); bytes of the numpy
#: arrays that cross the host boundary around ``rnn_time_step``, by
#: ``phase`` (decode / prefill) and ``direction`` (h2d / d2h);
#: ``health()["host_io"]["input_form"]`` says what the h2d bytes are:
#: ``"ids"`` (int32, 4 bytes a token) or ``"one-hot"`` (float32 [B, V, T]).
#: ``health()["host_io"]["prefill"]`` also counts ``results`` (primes
#: whose result was fetched) and ``result_positions`` (the positions those
#: results held): a prime reads its last position only and says so to the
#: streaming call (``rnn_time_step(last_only=True)``), so ``[1, V]`` comes
#: back and the two are equal; a ``[1, V, P]`` result would count P, and
#: the d2h bytes with it (no registry series: read from the payload)
SERVING_DECODE_ROWS = "dl4jtpu_serving_decode_rows_total"
SERVING_PREFILL_TOKENS = "dl4jtpu_serving_prefill_tokens_total"
SERVING_HOST_IO_BYTES = "dl4jtpu_serving_host_io_bytes_total"
#: how plain decode cycles selected their tokens (engine.health() keeps
#: the totals under ``sample``): rows by ``kind`` — greedy (top_k == 1:
#: the id came from the on-device argmax) or drawn (sampled by
#: ``util.decoding.draw`` from its row of the block) — and the cycles
#: that fetched the ``[S, V]`` distributions to the host because some
#: row samples
SERVING_SAMPLE_ROWS = "dl4jtpu_serving_sample_rows_total"
SERVING_BLOCK_FETCHES = "dl4jtpu_serving_block_fetches_total"

#: ``engine.health()["setup"]`` (every engine; no registry series):
#: ``started_at``, the ``time.perf_counter()`` reading taken when
#: ``start()`` first started the loop thread (``None`` before that; a
#: later ``start()`` leaves it). It is the clock of the request traces and
#: of the compile ledger (monitoring/runtime.py), so what a deployment's
#: set-up did before it and the first requests it serves after it line up
#: on one axis. A plain attribute read: no lock, no device call.
#:
#: ``engine.health()`` keys that are no registry series (read where the
#: payload is read; a net without such layers has none of them). The
#: layers DECLARE them (``nn.conf.layers.StreamCounters``: the key of
#: their kind, the fields, which of them are maxima, and a ``host``
#: function where part is counted from a dispatch's rows); the engine
#: tests no attribute of a layer for them:
#:
#: ``experts`` — a net with ``RoutedExpertsLayer``s: the layers'
#: ``moe_stats`` summed over layers and over everything served so far.
#: ``tokens`` routed (a decode dispatch routes all S rows, idle slots
#: too; a prime's left pads route nowhere), ``held_pairs`` (token, held
#: expert) pairs among them (tokens x top_k x held / router_experts on
#: average), ``rows_computed`` by the grouped product (whole tiles: 1 -
#: held_pairs / rows_computed is its padding), ``max_expert_load`` the
#: most pairs one expert took in one dispatch, and over the layers' calls
#: of one position a row (decode steps) ``decode_calls``, how many there
#: were, and ``decode_experts_touched``, the held experts that got at
#: least one token in them, summed (the expert weights a decode step
#: cannot do without, counted from the gates whatever computes the
#: product). Counted inside the device
#: programs and joined on the device behind every dispatch, outside the
#: donated state; ``health()`` fetches the sum (from any thread, with no
#: step lock: complete up to the last dispatch that finished) and the
#: cycle never does.
#:
#: ``sparse_attn`` — a net with sparse-selection attention
#: (``LatentAttentionLayer``, or ``SelfAttentionLayer`` with an indexer),
#: summed over those layers. Host counts from
#: each dispatch's rows: ``query_positions`` real queries (prompt tokens
#: fed, live decode rows), ``context_positions`` the positions they could
#: see (their own included), ``selected_positions`` what the selection
#: keeps of those (min(index_topk, context) a query). Counted by the
#: layers inside the device programs (``attn_stats``, joined and fetched
#: like the experts' counters): ``attended_positions``, the cache
#: positions whose attention scores the programs computed, by the form
#: each dispatch took: in the per-head prime of a fresh stream the
#: chunk's own slots in causal groups (5/8 of width x width for a bucket
#: of four groups; pads too), in a later chunk (after a prefix hit, or
#: chunked priming) every cache slot of every row of its query blocks,
#: in paged decode, by ``kv_traffic.selected_read``, every slot of the
#: table (``masked``) or the gathered index_topk (``gathered``) of all S
#: rows. 1 - selected / attended is attention work the selection had
#: already ruled out.
#:
#: ``linear_attn`` — a net with linear-attention layers
#: (``GatedDeltaNetLayer``), summed over those layers. From what they
#: declare they keep a stream (``slot_leaves()``): ``layers``,
#: ``state_bytes_per_slot`` (the float32 state and the convolution's
#: tail of every such layer, one slot's row), ``seated_state_bytes``
#: (that, times the admissions seated: what the seats' scatter wrote).
#: Counted by the layers inside the device programs (``gdn_stats``,
#: joined and fetched like the experts' counters): ``scanned_positions``
#: every position a prime's chunked scan went over (left pads and the
#: fill to whole chunks included), ``fed_positions`` the real ones among
#: them (1 - fed / scanned is scan work a bucket's padding cost),
#: ``state_updates`` one-step updates (every row of every decode
#: dispatch, idle slots too).

#: fleet layer (serving/fleet/router.py registers these): multi-replica
#: routing, prefix-affinity placement, ledger migration, autoscaling.
#: ``fleet`` labels distinguish routers; ``replica`` / ``cause`` /
#: ``direction`` label the per-series dimensions.
FLEET_REPLICAS = "dl4jtpu_fleet_replicas"
FLEET_GENERATION = "dl4jtpu_fleet_generation"
FLEET_ROUTED = "dl4jtpu_fleet_routed_total"
FLEET_AFFINITY_HITS = "dl4jtpu_fleet_affinity_hits_total"
FLEET_AFFINITY_MISSES = "dl4jtpu_fleet_affinity_misses_total"
FLEET_MIGRATIONS = "dl4jtpu_fleet_migrations_total"
FLEET_MIGRATED_REQUESTS = "dl4jtpu_fleet_migrated_requests_total"
FLEET_DEAD_REPLICAS = "dl4jtpu_fleet_dead_replicas_total"
FLEET_SCALE_EVENTS = "dl4jtpu_fleet_scale_events_total"

#: cross-process fleet transport (serving/fleet/transport.py +
#: agent.py register these): shared-fs mailbox command traffic at the
#: agent (``kind`` labels admit/revoke/shutdown), at-least-once
#: duplicates dropped by request-id dedupe, torn command files moved
#: to quarantine instead of crashing the poll loop, and the journal
#: token events the router relayed into local stream handles.
FLEET_TRANSPORT_COMMANDS = "dl4jtpu_fleet_transport_commands_total"
FLEET_TRANSPORT_DUPLICATES = \
    "dl4jtpu_fleet_transport_duplicates_total"
FLEET_TRANSPORT_QUARANTINED = \
    "dl4jtpu_fleet_transport_quarantined_total"
FLEET_RELAYED_TOKENS = "dl4jtpu_fleet_relayed_tokens_total"
FLEET_REPLACED_REQUESTS = "dl4jtpu_fleet_replaced_requests_total"
#: journal lines that were complete (newline-terminated) yet
#: undecodable — real transport corruption, distinct from the torn
#: tail a crashed writer leaves (which is silently retried). The
#: router promotes ``JournalReader.corrupt`` through this counter so
#: /metrics and flight-recorder bundles see it, not just ``health()``.
FLEET_TRANSPORT_CORRUPT_LINES = \
    "dl4jtpu_fleet_transport_corrupt_lines_total"

#: disaggregated prefill/decode (serving/fleet/pages.py, prefill.py;
#: the agent and router register these): the content-addressed KV page
#: store on the fleet root. ``published``/``ship_bytes`` count store
#: writes, ``imported`` counts pages a decode replica mapped into its
#: pool instead of re-priming, hits/misses count store probes at
#: admission, ``quarantined`` counts torn/mismatched entries moved
#: aside, ``prefills`` counts CMD_PREFILL admissions a prefill replica
#: served.
FLEET_PAGES_PUBLISHED = "dl4jtpu_fleet_pages_published_total"
FLEET_PAGES_IMPORTED = "dl4jtpu_fleet_pages_imported_total"
FLEET_PAGE_STORE_HITS = "dl4jtpu_fleet_page_store_hits_total"
FLEET_PAGE_STORE_MISSES = "dl4jtpu_fleet_page_store_misses_total"
FLEET_PAGES_QUARANTINED = "dl4jtpu_fleet_pages_quarantined_total"
FLEET_PAGE_SHIP_BYTES = "dl4jtpu_fleet_page_ship_bytes_total"
FLEET_PREFILLS = "dl4jtpu_fleet_prefills_total"

#: survivability layer (supervisor.py / overload.py register these)
SERVING_ENGINE_REBUILDS = "dl4jtpu_serving_engine_rebuilds_total"
SERVING_ENGINE_ESCALATIONS = \
    "dl4jtpu_serving_engine_escalations_total"
SERVING_RECOVERED_REQUESTS = \
    "dl4jtpu_serving_recovered_requests_total"
SERVING_SHED = "dl4jtpu_serving_shed_total"
SERVING_EARLY_REJECTED = "dl4jtpu_serving_early_rejected_total"
SERVING_BROWNOUT_LEVEL = "dl4jtpu_serving_brownout_level"
SERVING_DRAINING = "dl4jtpu_serving_draining"

_COUNTERS = (
    (SERVING_REQUESTS, "Serving requests received"),
    (SERVING_ERRORS, "Serving requests failed by model errors"),
    (SERVING_DEADLINE_EXCEEDED, "Requests that outlived their deadline"),
    (SERVING_QUEUE_REJECTED, "Requests rejected by fail_fast admission"),
)


def scrape_probe(component, fn, default: float = 0.0):
    """Scrape-time gauge callback over a WEAK reference to `component`:
    reads ``fn(component)`` at collection time, `default` once the
    component is collected. The one probe shape every serving gauge
    uses — fix it here, every component's gauges follow."""
    ref = weakref.ref(component)

    def read():
        inst = ref()
        return default if inst is None else float(fn(inst))
    return read


def register_serving_metrics(component, model: str,
                             registry: Optional[MetricsRegistry] = None
                             ) -> Dict[str, object]:
    """Register the shared serving series for `component` and return its
    resolved counter handles ``{metric name: handle}``.

    `component` must expose ``is_healthy()`` / ``is_ready()`` /
    ``queue_depth()``; the healthy/ready/queue-depth gauges are
    scrape-time callbacks over a weakref to it, so a crashed worker
    flips them on the next scrape with no event having fired. One
    serving stack per `model` label value per registry; a newer
    instance takes over the series.
    """
    r = registry or global_registry()
    handles = {
        metric: r.counter(metric, help, ("model",)).labels(model=model)
        for metric, help in _COUNTERS}
    r.gauge(SERVING_HEALTHY, "Serving loop alive (1) or down (0)",
            ("model",)).set_function(
        scrape_probe(component,
                     lambda s: 1.0 if s.is_healthy() else 0.0),
        model=model)
    r.gauge(SERVING_READY, "Serving admitting requests (1) or not (0)",
            ("model",)).set_function(
        scrape_probe(component,
                     lambda s: 1.0 if s.is_ready() else 0.0),
        model=model)
    r.gauge(SERVING_QUEUE_DEPTH,
            "Requests waiting in the admission queue",
            ("model",)).set_function(
        scrape_probe(component, lambda s: s.queue_depth()), model=model)
    return handles
