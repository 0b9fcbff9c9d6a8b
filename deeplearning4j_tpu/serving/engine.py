"""Continuous-batching generation engine over a slot-based KV arena.

The one-shot batch decoders (``util/decoding.sample_stream_batch``)
stall a serving batch on its slowest request and re-dispatch from
scratch per call. This engine decomposes the serving batch into
independently admitted/retired micro-units (the μ-batching lever,
arXiv:1804.04806) while keeping the dispatch loop free of per-request
shape work (the framework-overhead lesson of arXiv:2001.04206):

- **Slot arena**: the net's carried streaming state (attention KV
  caches; recurrent state: LSTM h/c, linear-attention state) lives at a
  fixed batch of S slots — ONE canonical
  ``[S, V, 1]`` decode dispatch advances every active request per step,
  so after warmup the steady state never retraces regardless of request
  mix. Per-slot positions ride the per-row ``kv_pos`` vector the
  batched-speculation machinery introduced; free slots idle harmlessly
  (their writes drop, their outputs are discarded).
- **Admission mid-flight**: a request prefills at batch 1 through the
  shared ``_prime_padded`` width buckets (one left-padded dispatch, one
  jit shape per power-of-two bucket) into a detached state that ONE
  jitted scatter joins to the arena at its slot — running requests
  never wait for a newcomer's prompt.
- **Retirement per request**: stop-token / length / capacity /
  deadline / cancellation free the slot immediately (host bookkeeping
  only — no device op); the next queued request takes it on the same
  step.
- **Streaming**: tokens stream to a per-request ``GenerationStream``
  handle as each dispatch retires — TTFT is queue-wait + one prefill,
  not a batch drain.

Per-request outputs are bit-identical to one-shot ``sample_stream``
with the same rng (test-pinned): the arena feeds each request exactly
the token sequence a dedicated stream would, row independence makes the
math per-slot, a sampling request draws from its OWN rng in generation
order, and a greedy one (top_k=1) follows ``util.decoding``'s greedy
rule — the lowest-index maximum of the distribution the program
returned, no random numbers consumed — which a plain cycle answers for
all S rows with one on-device argmax (``util.decoding.greedy_ids``).

Exactness conditions are ``sample_stream_batch``'s: recurrent state
(LSTM h/c, linear-attention state) or attention with rope / no positions. Models with LEARNED
positional tables are rejected at construction (``pos_offset`` is a
scalar shared across the batch — it cannot track per-slot positions).

Chaos/resilience seams (tests/test_serving_engine.py drives these with
``resilience/chaos.py`` injectors): ``prefill_chaos`` fires before each
admission's prefill — a raise fails THAT request only, the arena is
restored untouched; ``decode_chaos`` fires before each decode dispatch
INSIDE the optional ``decode_retry`` RetryPolicy — a transient
mid-stream preemption is retried with numerics identical to a
fault-free run (the fault fires before any state mutates).

Serving engine v2 extras, each orthogonal and composable:

- ``paging=PagedKVConfig(...)`` rebuilds the arena's KV storage as
  **block-paged** (``serving/paging.py``): capacity becomes a token
  budget — admission checks the request's worst-case pages against the
  free pool (head-of-line blocking when short; requests that can NEVER
  fit are rejected at submit), retirement frees pages immediately, and
  decode runs DIRECTLY on the page pool: the attention step reads K/V
  through the per-slot page table (the folded XLA gather, or the
  ``serving/paged_kernel.py`` Pallas paged-attention kernel:
  ``paged_kernel.choose_paged_read`` answers which, once, at
  construction) and the new token appends with an O(one-token)
  in-dispatch write. Outputs stay bit-identical to the slot arena (and
  to one-shot ``sample_stream``) on either read. With
  ``prefix_cache=True`` (default) shared full-block prompt prefixes
  prime once (``serving/prefix_cache.py``):
  later requests map the cached pages and prefill only their suffix.
  ``dl4jtpu_serving_kv_bytes_moved_total`` prices the KV path in use;
  see ARCHITECTURE.md "Paged decode fast path".
- ``speculation=SpeculationConfig(draft, gamma)`` folds the
  ``speculative_sample`` machinery into the decode loop: per step the
  host `draft` proposes up to gamma tokens per active slot and ONE
  widened ``[S, V, 1+gamma]`` verify dispatch scores them all; each
  row's accept/reject walk (``util.decoding.accept_proposals``) commits
  accepted+1 tokens and a per-row ``rewind_stream_state`` drops the
  rejected positions — greedy outputs stay bit-identical to plain
  ``sample_stream`` (every committed token is the argmax chain), and
  the target's sampling distribution is exactly preserved.

Survivability (PR 9, ARCHITECTURE.md "Serving survivability"):

- ``supervisor=EngineSupervisor(...)`` replaces the terminal
  fail-all with request-preserving recovery: a step-cycle fault
  quarantines the arena and rebuilds it from the host-side ledger,
  re-admitting every in-flight request bit-identically; a windowed
  ``RestartBudget`` bounds the rebuild rate and escalates to the
  original ``_break`` when exhausted.
- ``overload=OverloadConfig(...)`` adds SLO-aware admission control:
  sustained-breach shedding of low-priority queued work
  (``ServingOverloaded``), deadline-based early rejection at submit,
  and the page-pressure brownout ladder (reduced gamma → speculation
  off → prefix-cache inserts off, auto-restoring).
- ``drain(timeout)`` stops admission and finishes the actives — the
  clean handoff point for planned restarts.
- the request-ledger seam (``export_ledger`` / ``admit_from_ledger`` /
  ``detach_ledger``): every in-flight request exports as a versioned
  ``RequestLedgerEntry`` and re-admits bit-identically on this or ANY
  other replica — the one rebuild path the supervisor's quarantine and
  ``serving/fleet``'s live migration both ride.
- ``seat_chaos`` fires in the pop-to-seat admission window (the
  handoff seam the supervisor also covers); ``prefill_chaos`` /
  ``seat_chaos`` receive the request as event context, so
  ``resilience.chaos.RequestFaultInjector`` can target named victims.

Phases. While a cycle has work, the stepping thread is in exactly one
``monitoring`` span at a time (``monitoring.phases``: flat, no parents;
static names), so a profiler trace names every device-idle gap by what
the host was doing. In cycle order: ``engine.reap`` (expiry,
cancellation, overload control), ``engine.admit`` (from a request
popped: page reservation, prefix lookup and install), ``prefill.input`` /
``prefill.forward`` / ``prefill.fetch`` (``util.decoding.prime_prompt``:
the padded prompt as the net takes it — int32 ids, or the host-built
one-hot of a net that takes none; upload and launch; the result coming
back, the last position's ``[1, V]`` alone), ``engine.seat`` (first
draw, arena join, page-table update),
``decode.input`` (token vector, position mirrors, paged-view install,
under speculation the host draft; the one-hot block only for a net that
takes no ids), ``decode.forward`` (the
dispatch, and the greedy argmax queued behind it), ``decode.fetch`` (the
host waits for the device and copies: the [S] ids of a plain cycle —
its one required sync — and the [S, V] block only when a seated request
samples; under speculation the distributions; pool extract),
``engine.sample`` (selection — a greedy row reads its id, a sampling
row draws from its row of the block, speculation walks acceptance —
push, rollup, stop test, retire). A request with ``top_k == 1`` is
greedy whatever its temperature and top_p: nothing it emits depends on
its Generator, whose state (``rng_state_payload`` in the ledger) stays
what it was at submit; before PR 27 such a row went through the filter
and consumed one ``rng.choice`` per token. One of each per cycle; the
admission phases once per admitted request (chunked priming alternates
input and forward per chunk). The names are ``PHASES``; the sequence opens when a poll finds work (a seated
row, or a request popped), so an idle poll records nothing. ``health()``
counts at the same boundaries: ``decode_dispatch.rows``, ``sample``
(``greedy_rows`` that took the device's id, ``drawn_rows`` that sampled
from their row, ``block_fetches`` = plain cycles that fetched [S, V]),
``prefill`` (tokens fed, padded widths dispatched; tokens the prefix
cache served instead are ``prefix_cache.reused_tokens``) and ``host_io``
(bytes of the numpy arrays that cross around ``rnn_time_step``; under
``prefill`` also ``results`` and ``result_positions``, the primes whose
result was fetched and the positions those results held — equal, since
a prime asks its streaming calls for the last position only and ``[1,
V]`` comes back whatever the head, where a ``[1, V, P]`` block would
count P; and ``input_form``, which says what goes up: ``"ids"``, int32,
for a net that takes ids — every zoo transformer — and ``"one-hot"``,
the float32
``[B, V, T]`` block, otherwise; the engine asks the net,
``util.decoding.takes_ids``). Layers declare what they count
(``stream_counters()`` beside ``paged_leaves()``) and ``health()`` shows
it under the key of their kind: routed experts ``experts``,
sparse-selection attention ``sparse_attn`` (with the host counts its
declaration makes from each dispatch's rows), linear attention
``linear_attn`` (with ``layers``, ``state_bytes_per_slot`` and
``seated_state_bytes`` from what the layers declare they keep a stream,
``slot_leaves()``); what the layers count is joined on the device
behind every dispatch and fetched when ``health()`` is called, never in
the cycle. ``serving/health.py`` documents them.

Two kinds of cache in one manager. A net whose layers declare pages
(``paged_leaves()``) AND a state a stream (``slot_leaves()``: recurrent
state, whose size does not grow with the context) runs with
``PagedKVConfig(prefix_cache=False)``: the pages of a request go to the
pool through its table, the state rows stay in the slot arena beside it,
and the ONE jitted scatter of an admission seats both the row's
positions and its whole state on the device. A reused slot's row is
overwritten by the seat; a free row's updates touch its own row only.
``prefix_cache=True``, ``kv_dtype="int8"`` and ``speculation=`` raise
for such a net (a prefix hit would need a state snapshot at the block
boundary; the int8 prime runs through the pool; a rejected token cannot
be taken back out of a recurrent state). The supervisor's rebuild and
``admit_from_ledger`` carry such a state by recomputing it: the re-prime
feeds prompt + committed tokens, so the row holds the same state to
rounding (the chunked scan where the lost row had taken single steps).
Between cycles, in no span: the serving loop's ``HANDOFF_WAIT_S`` park
after a cycle that freed a slot.
"""

from __future__ import annotations

import functools
import logging
import os
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.monitoring import flightrecorder
from deeplearning4j_tpu.monitoring.events import emit as emit_event
from deeplearning4j_tpu.monitoring.metrics import (
    MetricsRegistry, global_registry)
from deeplearning4j_tpu.monitoring.tracing import next_phase, phases
from deeplearning4j_tpu.nn.conf.layers import (
    BATCHED_STREAM_KEYS, PositionalEmbeddingLayer, check_rewindable,
    paged_leaves, rewind_stream_state, slot_leaves, stream_capacity,
    stream_counters)
from deeplearning4j_tpu.resilience.chaos import fire as _fire_chaos
from deeplearning4j_tpu.resilience.retry import RetryPolicy, retry_call
from deeplearning4j_tpu.serving.errors import (
    EngineShutdown, InferenceTimeout, RequestCancelled,
    ServingOverloaded, ServingQueueFull)
from deeplearning4j_tpu.serving.health import (
    SERVING_ACTIVE_SLOTS, SERVING_BLOCK_FETCHES, SERVING_BROWNOUT_LEVEL,
    SERVING_DEADLINE_EXCEEDED, SERVING_DECODE_ROWS,
    SERVING_DISPATCH_LATENCY, SERVING_DRAINING, SERVING_EARLY_REJECTED,
    SERVING_ERRORS, SERVING_HOST_IO_BYTES, SERVING_KV_BYTES_MOVED,
    SERVING_KV_PAGES_TOTAL, SERVING_KV_PAGES_USED, SERVING_PREFILL_TOKENS,
    SERVING_PREFIX_HITS, SERVING_PREFIX_MISSES,
    SERVING_PREFIX_REUSED_TOKENS, SERVING_QUEUE_REJECTED,
    SERVING_QUEUE_WAIT, SERVING_REQUESTS, SERVING_SAMPLE_ROWS, SERVING_SHED,
    SERVING_SPEC_ACCEPTANCE, SERVING_TOKENS, SERVING_TPOT, SERVING_TTFT,
    register_serving_metrics, scrape_probe)
from deeplearning4j_tpu.serving.overload import (
    BROWNOUT_NO_PREFIX_INSERTS, BROWNOUT_NO_SPECULATION,
    BROWNOUT_REDUCED_GAMMA, OverloadConfig, OverloadController)
from deeplearning4j_tpu.serving.paged_kernel import (
    PLAIN_LEAVES, choose_paged_read, pages_per_step)
from deeplearning4j_tpu.serving.paging import (
    PagedKVConfig, PagePool, allocate_pools, gather_pages, pages_needed,
    scatter_pages, set_page)
from deeplearning4j_tpu.serving.prefix_cache import (
    ROOT_DIGEST, PrefixCache, chain_digests)
from deeplearning4j_tpu.serving.request import (
    GenerationRequest, GenerationStream, RequestLedgerEntry,
    rng_state_payload)
from deeplearning4j_tpu.serving.scheduler import AdmissionQueue
from deeplearning4j_tpu.util.decoding import (
    ArgmaxRow, RoundTrip, _check_seed, _stream_layers, _width_bucket,
    accept_proposals, draw, filter_probs, prime_prompt, selects_one,
    step_greedy, stop_reason, takes_ids, verify_tokens)

log = logging.getLogger(__name__)

#: stream-state keys the admission scatter writes into the arena row
#: (kv_mask is deliberately absent: engine prefill is packed/maskless,
#: so per-slot validity is carried by kv_pos alone)
_SCATTER_KEYS = frozenset(BATCHED_STREAM_KEYS | {"kv_pos", "kv_abs"}) \
    - {"kv_mask"}


@dataclass
class SpeculationConfig:
    """In-engine speculative decoding knobs.

    `draft` is a HOST proposer callable ``(ids, gamma) -> proposals``
    (e.g. ``util.decoding.prompt_lookup_proposer()``): zero extra
    device dispatches, applied per active slot each step. `gamma` caps
    proposals per slot per step; the verify dispatch is the fixed
    ``[S, V, 1+gamma]`` widened shape regardless of how many proposals
    each row actually made (short rows pad with dummies that causality
    hides and the per-row rewind drops). Model-based drafting (a second
    net with its own arena) stays on the one-shot
    ``speculative_sample`` path."""

    draft: Callable
    gamma: int = 4

    def __post_init__(self):
        if self.gamma < 1:
            raise ValueError(f"gamma must be >= 1, got {self.gamma}")
        if hasattr(self.draft, "rnn_time_step") or \
                not callable(self.draft):
            raise TypeError(
                "in-engine speculation takes a host proposer callable "
                "(ids, gamma) -> proposals, e.g. "
                "util.decoding.prompt_lookup_proposer(); model-based "
                "drafting stays on the one-shot speculative_sample path")


#: the cycle's phases in order (module docstring, "Phases"): the one
#: table of span names. ``util/decoding`` knows a round trip's three
#: steps only; ``_HostIO`` gives them these names.
PHASES = ("engine.reap", "engine.admit",
          "prefill.input", "prefill.forward", "prefill.fetch",
          "engine.seat",
          "decode.input", "decode.forward", "decode.fetch",
          "engine.sample")


#: how long the serving loop parks, after a cycle that freed a slot and
#: with nothing queued, for the next request to arrive before it commits
#: to a whole decode cycle without it. A caller with one request
#: outstanding (a completion plug-in, a chat turn, a batch pipeline) sends
#: its next the moment the last one ends — a millisecond or two after the
#: cycle that retired it — and the admission check of the following cycle
#: comes sooner than that now that selection no longer takes tens of
#: milliseconds; a request that misses it waits a decode cycle, and may
#: queue behind another caller's prime. Event-driven (it ends when a
#: submit lands), and only ever paid with an empty queue.
HANDOFF_WAIT_S = 0.005


class _HostIO(RoundTrip):
    """The engine's view of one kind of host round trip (``"decode"`` or
    ``"prefill"``), handed to ``util/decoding`` as `io`: each step
    switches the cycle to its phase (``decode.input`` …), and the numpy
    arrays handed to ``rnn_time_step`` and fetched from it are counted
    where they cross. ``health()["host_io"][kind]`` reads the bytes.
    Where the kind asks for it (`widths`: a prime) the time axes are
    counted too: ``width`` sums what was handed over — a prime's padded
    bucket — and ``results`` / ``result_positions`` the results fetched
    and the positions they held: one a result since a prime asks for its
    last position only (``[1, V]``), its whole bucket where a result
    came back ``[1, V, P]``."""

    __slots__ = ("h2d_bytes", "d2h_bytes", "width", "results",
                 "result_positions", "_widths", "_phase")

    def __init__(self, kind: str, widths: bool = False):
        self.h2d_bytes = self.d2h_bytes = self.width = 0
        self.results = self.result_positions = 0
        self._widths = widths
        self._phase = {name.split(".")[1]: name for name in PHASES
                       if name.startswith(kind + ".")}

    def step(self, name: str) -> None:
        next_phase(self._phase[name])

    def h2d(self, x: np.ndarray) -> None:
        self.h2d_bytes += x.nbytes
        if self._widths:
            self.width += x.shape[-1]

    def d2h(self, p: np.ndarray) -> None:
        self.d2h_bytes += p.nbytes
        if self._widths:
            self.results += 1
            self.result_positions += p.shape[2] if p.ndim == 3 else 1

    def as_dict(self) -> dict:
        out = {"h2d_bytes": self.h2d_bytes, "d2h_bytes": self.d2h_bytes}
        if self._widths:
            out.update(results=self.results,
                       result_positions=self.result_positions)
        return out


def _seat_rows(arena, primed, slot):
    """Join one primed request's stream state into the arena at `slot`:
    batch-leading leaves take the primed row 0, per-row counters
    (kv_pos [S] <- scalar, kv_abs [S, L] <- [L]) take the primed value.
    One trace per net structure — `slot` rides as a traced scalar."""
    out = []
    for a, p in zip(arena, primed):
        out.append(a.at[slot].set(p[0] if p.ndim == a.ndim else p))
    return out


#: the seat's one program. Off the CPU the arena is DONATED: its leaves
#: are written in place, a row each — a net that keeps a state a stream
#: (a float32 state a head a layer) would otherwise copy the whole arena,
#: and hold two of them, at every admission. The caller treats the arena
#: it passed as consumed (``_merge``).
_scatter_rows_donated = jax.jit(_seat_rows, donate_argnums=(0,))
_scatter_rows = jax.jit(_seat_rows)


def _page_key(key: str) -> str:
    """The pool leaf's state key of a dense cache leaf: kv_k ->
    kv_page_k (``nn.conf.layers.PagedLeaf.page_key``)."""
    return "kv_page_" + key[len("kv_"):]


def _scale_key(key: str) -> str:
    return "kv_page_scale_" + key[len("kv_"):]


#: ``_join_stats`` keeps every counter as (high, low) uint32 with the low
#: word under 2^30: a prime of 8,192 scores 2^25 positions a sparse layer,
#: so five layers would pass one int32 in ten primes. One dispatch's own
#: count (an int32 out of the layer's state) stays under 2^31.
_LOW_BITS = 30


@functools.partial(jax.jit, static_argnames=("maxima",))
def _join_stats(acc, stats, maxima):
    """One dispatch's layer counters joined to the accumulators: per kind
    ``acc`` [layers, fields, 2] (high, low) and ``stats`` a list of one
    vector a layer. A field adds, carrying from the low word into the
    high, or — where ``maxima`` (kind -> a flag a field) says so — takes
    the maximum. One program whatever kinds the net has."""
    out = {}
    for kind, total in acc.items():
        new = jnp.stack([a.reshape(-1) for a in stats[kind]]
                        ).astype(jnp.uint32)
        high, low = total[..., 0], total[..., 1]
        added = low + new
        is_max = np.array(dict(maxima)[kind])       # a constant a field
        out[kind] = jnp.stack(
            [jnp.where(is_max, high, high + (added >> _LOW_BITS)),
             jnp.where(is_max, jnp.maximum(low, new),
                       added & ((1 << _LOW_BITS) - 1))], axis=-1)
    return out


class GenerationEngine:
    """Continuous-batching generation over a fixed S-slot arena.

    Drive it manually (``submit()`` then ``step()`` /
    ``run_until_idle()`` — deterministic single-threaded serving, the
    test/bench shape) or start the background loop (``start()`` /
    ``shutdown()``) and consume ``GenerationStream`` handles from any
    thread.
    """

    def __init__(self, net, vocab_size: int, slots: int = 8,
                 queue_limit: int = 64, queue_policy: str = "block",
                 prime_padded: bool = True,
                 registry: Optional[MetricsRegistry] = None,
                 name: Optional[str] = None,
                 prefill_chaos=None, decode_chaos=None, seat_chaos=None,
                 decode_retry: Optional[RetryPolicy] = None,
                 paging: Optional[PagedKVConfig] = None,
                 speculation: Optional[SpeculationConfig] = None,
                 supervisor=None,
                 overload=None):
        if not hasattr(net, "rnn_time_step"):
            raise TypeError("GenerationEngine needs a streaming net "
                            "(rnn_time_step / rnn_clear_previous_state)")
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if vocab_size < 1:
            raise ValueError(f"vocab_size must be >= 1, got {vocab_size}")
        if getattr(net, "_initialized", True) is False:
            net.init()
        layers = list(_stream_layers(net))
        for l in layers:
            if isinstance(l, PositionalEmbeddingLayer):
                raise ValueError(
                    "continuous batching needs per-slot positions: "
                    "learned positional tables carry a shared pos_offset "
                    "(use a rope, position-free, or recurrent model)")
        net_inputs = getattr(getattr(net, "conf", None),
                             "network_inputs", None)
        if net_inputs is not None and len(net_inputs) != 1:
            raise ValueError("GenerationEngine serves single-input "
                             "decoder graphs only")
        self.net = net
        self.V = int(vocab_size)
        self.slots = int(slots)
        self._cap = stream_capacity(layers)
        self._prime_padded = bool(prime_padded)
        self._label = name or f"engine:{type(net).__name__}"
        self._graph_vertices = tuple(
            n for n, v in (getattr(net.conf, "vertices", None) or {}).items()
            if getattr(getattr(v, "layer", None), "supports_streaming",
                       False)) if hasattr(net, "conf") else ()
        #: PUBLIC replica identity, set by a fleet router at join time
        #: (replicas built by one factory share the default model
        #: label, so traces/timeline need the rid to tell them apart);
        #: None outside a fleet
        self.replica_tag: Optional[int] = None
        self._pending = AdmissionQueue(queue_limit, queue_policy)
        self._slots: List[Optional[GenerationRequest]] = [None] * slots
        self._row_pos = np.zeros(slots, np.int64)
        self._arena_ready = False
        self._merge_keys = None
        # -- block-paged KV arena (serving/paging.py) ------------------
        self._paging = paging
        if paging is None and any(
                leaf.key not in PLAIN_LEAVES
                for l in layers for leaf in paged_leaves(l)):
            raise ValueError(
                "this net's attention keeps more than keys and values "
                "per token and decodes per-row positions only through "
                "a page table: construct with paging=PagedKVConfig()")
        self._pool: Optional[PagePool] = None
        self._prefix: Optional[PrefixCache] = None
        self._page_store = None            # device pools, per paged leaf
        self._paged_keys = None            # [(layer name, dense leaf key)]
        #: what the net's streaming layers declare they keep per token
        #: (``paged_leaves``), by state name in sorted order, and each
        #: pool's token axis: the pool's leaves are built from this
        self._paged_decl = []
        self._paged_axes = None
        self._page_tables: List[List[int]] = [[] for _ in range(slots)]
        #: fleet page-shipping hook (serving/fleet/agent.py sets it):
        #: called as ``page_publisher(prompt, table)`` right after a
        #: prefix-cache insert, under the engine lock — typically a
        #: closure over :meth:`export_prefix_chain`. Failures are
        #: swallowed: publishing is best-effort, admission is not.
        self.page_publisher: Optional[Callable] = None
        #: which code reads the page pool in a decode step ("xla" |
        #: "pallas"; None without paging): what
        #: ``paged_kernel.choose_paged_read`` answered at construction,
        #: recorded on this net's attention layers too. See
        #: ARCHITECTURE.md "Paged decode fast path"
        self._decode_impl: Optional[str] = None
        #: the pool's authoritative storage precision ("bf16" = the
        #: net's native leaf dtype, "int8" = serving/quant.py) and the
        #: int8 plumbing: per-leaf [P, Hkv] scale sidecars + the
        #: (name, Hkv, head_dim) layer map the eager store builds from
        self._kv_dtype = "bf16"
        self._scale_store = None
        self._quant_dims = None
        self._scale_row_bytes = 0          # per-dispatch scale read unit
        #: cached [S, n_max] page table — np + device copies, rebuilt
        #: only after a table MUTATION (admit/retire/rebuild), not per
        #: step (the host used to rebuild and re-upload it every step
        #: even when nothing changed)
        self._tables_cache: Optional[np.ndarray] = None
        self._tables_layer_cache = None    # per-layer copies (donation)
        #: modeled KV bytes moved by the pool<->dispatch paths (see
        #: serving/health.SERVING_KV_BYTES_MOVED)
        self._kv_bytes_total = 0
        self._tok_bytes = 0                # per-position bytes, all leaves
        #: whether paged dispatches actually donate state buffers
        #: (rnn_time_step resolves donation off on CPU — there the
        #: pre-dispatch table/pool references stay valid)
        self._state_donated = jax.default_backend() != "cpu"
        #: host mirror of the dispatch-latency histogram (health())
        self._dispatch_s_total = 0.0
        #: a retirement freed a slot whose DEVICE kv_pos keeps coasting
        #: (+1 per dispatch): the next install zeroes free rows'
        #: positions so an idle slot that once held a long context
        #: doesn't defeat the kernel's dead-block skip forever
        self._kv_pos_dirty = False
        if paging is not None:
            kv_layers = [l for l in layers
                         if getattr(l, "supports_streaming", False)
                         and getattr(l, "cache_length", 0)]
            if not kv_layers:
                raise ValueError(
                    "block-paged KV needs attention KV streaming state "
                    "(a layer with cache_length > 0) — a pure-recurrent "
                    "net has no per-token pages to manage")
            if any(getattr(l, "window", None) for l in kv_layers):
                raise ValueError(
                    "rolling (windowed) caches are not pageable: their "
                    "modular slot reuse has no stable token->page map "
                    "(use the slot arena, or a non-windowed model)")
            lens = {int(l.cache_length) for l in kv_layers}
            if len(lens) != 1:
                raise ValueError(
                    f"block-paged KV needs one shared cache_length "
                    f"across attention layers, got {sorted(lens)}")
            self._L = lens.pop()
            self._ps = paging.page_size
            self._n_max = -(-self._L // self._ps)
            self._paged_decl = self._declared_leaves()
            if not self._paged_decl:
                raise ValueError(
                    "block-paged KV needs layers that declare what they "
                    "keep per token (paged_leaves())")
            #: keys and values [Hkv, D] a token: the one layout the
            #: grouped-query kernel and the int8 sidecar know
            leaf_keys = [leaf.key for _, leaf in self._paged_decl]
            plain = PLAIN_LEAVES.issuperset(leaf_keys)
            native_dtype = getattr(net.conf, "dtype", None) or "float32"
            if paging.kv_dtype == "int8" and any(
                    getattr(l, "carries_recurrent_state", False)
                    for l in layers):
                raise ValueError(
                    "kv_dtype='int8' quantizes position-indexed KV "
                    "pages only; recurrent state (LSTM h/c, "
                    "linear-attention state) is a function of the whole "
                    "prefix and cannot re-prime through the paged path "
                    "(use kv_dtype='bf16', or a pure-attention model)")
            self._kv_dtype = paging.kv_dtype
            if paging.total_bytes is not None and not plain:
                usable = paging.resolve_pages_bytes(
                    self._ps * jnp.dtype(native_dtype).itemsize
                    * sum(leaf.token_elements
                          for _, leaf in self._paged_decl))
            elif paging.total_bytes is not None:
                from deeplearning4j_tpu.serving.quant import (
                    kv_page_bytes)
                dims = self._paged_layer_dims()
                usable = paging.resolve_pages_bytes(kv_page_bytes(
                    [(h, d) for _, h, d in dims], self._ps,
                    self._kv_dtype, native_dtype))
            else:
                usable = paging.resolve_pages(slots, self._n_max)
            self._pool = PagePool(usable + 1, self._ps)  # +1: null page
            # the read belongs to the net this engine serves: its
            # attention layers hold it and its streaming jit keys key on
            # it, so another engine's net keeps its own
            read = choose_paged_read(
                leaf_keys,
                [(self._pool.total_pages,
                  getattr(l, "n_kv_heads", None) or l.n_heads,
                  self._ps, l.head_width)
                 for l in kv_layers] if plain else (),
                kv_dtype=self._kv_dtype, decode_impl=paging.decode_impl,
                kernel_interpret=paging.kernel_interpret,
                backend=jax.default_backend())
            self._decode_impl = read[0]
            for l in kv_layers:
                if hasattr(l, "paged_read"):
                    l.paged_read = read
            if paging.prefix_cache:
                if any(getattr(l, "carries_recurrent_state", False)
                       for l in layers):
                    raise ValueError(
                        "the prefix cache reuses position-indexed KV "
                        "pages only; recurrent state (LSTM h/c, "
                        "linear-attention state) is a function of the "
                        "whole prefix and lives outside the pages — "
                        "construct with "
                        "PagedKVConfig(prefix_cache=False)")
                self._prefix = PrefixCache(self._pool)
            if self._kv_dtype == "int8":
                # EAGER store build (bf16 builds lazily from the first
                # primed state): int8 prefill itself writes through
                # the paged path — quantize-once means the pools must
                # exist BEFORE the first prime, so they are sized from
                # the layer configs instead of a primed pytree
                self._quant_dims = self._paged_layer_dims()
                self._init_quant_store()
        # -- in-engine speculation (SpeculationConfig) -----------------
        self._speculation = speculation
        if speculation is not None:
            # rewind up to the full uniform chunk (gamma + 1 — a free
            # row keeps nothing); fails fast for recurrent state (LSTM
            # h/c, linear-attention state) / tight windows
            check_rewindable(net, speculation.gamma + 1)
        if speculation is not None and any(
                getattr(l, "last_step_only", False) for l in layers):
            raise ValueError(
                "in-engine speculation verifies every position of a "
                "widened chunk; this net's head answers for the last "
                "position only (LastStepOutputLayer)")
        self._admissions = 0
        self._dispatches = 0
        #: active rows summed over dispatches; tokens the primes fed
        #: (the padded widths they dispatched: ``_io["prefill"].width``)
        self._dispatch_rows = 0
        self._prefill_fed = 0
        #: how plain decode cycles selected (health()["sample"]): rows
        #: that took the device's argmax, rows that sampled from their row,
        #: cycles that fetched the [S, V] block for the latter
        self._greedy_rows = self._drawn_rows = self._block_fetches = 0
        #: what the layers declare they count (``stream_counters()``), by
        #: the ``health()`` key of their kind: the declaration and the
        #: state names of the kind's layers. Every dispatch's counts are
        #: taken out of the state it returns and joined to ``_stats_acc``
        #: ON THE DEVICE (one tiny program queued behind the dispatch;
        #: nothing is fetched in the cycle). The accumulators are no part
        #: of the donated state, so ``health()`` reads them from any
        #: thread without the step lock. ``_host_counts`` holds what a
        #: kind's ``host`` function counts from each dispatch's rows.
        #: ``_slot_state``: what the layers declare they keep a stream
        #: (``slot_leaves()``), under the same key: layers, bytes a slot,
        #: and the bytes admissions have seated into arena rows.
        self._counted = {}
        self._host_counts = {}
        self._slot_state = {}
        native = getattr(getattr(net, "conf", None), "dtype", None) \
            or "float32"
        for n, l in self._named_layers():
            decl = stream_counters(l)
            if decl is None:
                continue
            kept = self._counted.setdefault(decl.kind, (decl, [], []))
            if kept[0][:4] != decl[:4]:
                raise ValueError(
                    f"layers of kind {decl.kind!r} declare different "
                    f"counters: {kept[0][:4]} and {decl[:4]}")
            kept[1].append(n)
            if decl.host is not None:
                kept[2].append(decl.host)
                self._host_counts.setdefault(decl.kind, {})
            leaves = slot_leaves(l)
            if leaves:
                row = self._slot_state.setdefault(
                    decl.kind, {"layers": 0, "state_bytes_per_slot": 0,
                                "seated_state_bytes": 0})
                row["layers"] += 1
                row["state_bytes_per_slot"] += sum(
                    leaf.row_bytes(native) for leaf in leaves)
        self._stats_acc = {
            kind: jnp.zeros((len(names), len(decl.fields), 2), jnp.uint32)
            for kind, (decl, names, _) in self._counted.items()}
        self._stats_maxima = tuple(sorted(
            (kind, tuple(f in decl.maxima for f in decl.fields))
            for kind, (decl, _, _) in self._counted.items()))
        self._io = {"decode": _HostIO("decode"),
                    "prefill": _HostIO("prefill", widths=True)}
        self._prefill_chaos = prefill_chaos
        self._decode_chaos = decode_chaos
        self._seat_chaos = seat_chaos
        self._decode_retry = decode_retry
        #: donate state into paged dispatches ONLY without a retry
        #: policy: a retried attempt would re-run against donated,
        #: already-consumed buffers. With decode_retry set, a paged
        #: engine pays a pool copy per step (on TPU/GPU) for
        #: retryability — the retry-exactness contract (the fault fires
        #: before any state mutates) then holds as on the slot arena.
        self._donate = self._pool is not None and decode_retry is None
        # -- survivability (serving/supervisor.py, serving/overload.py)
        self._supervisor = supervisor
        if isinstance(overload, OverloadConfig):
            overload = OverloadController(overload)
        self._overload: Optional[OverloadController] = overload
        if overload is not None:
            overload._bind(self)
        self._brownout = 0
        self._draining = False
        #: the pop-to-seat handoff window: a request popped from the
        #: admission queue but not yet seated in a slot lives here so a
        #: fault in that window can fail (or recover) it instead of
        #: stranding its handle with no terminal event
        self._seating: Optional[GenerationRequest] = None
        #: slots freed so far: the serving loop parks after a cycle that
        #: moved it (``HANDOFF_WAIT_S``)
        self._retirements = 0
        #: traces of recently retired requests — the flight recorder's
        #: "last N requests" context when the engine breaks (in-flight
        #: requests' traces are read live off the slots)
        self._recent_traces = deque(maxlen=16)
        #: this engine's own recent lifecycle events (mirrored from the
        #: global ring at emit time): health() reads THIS, not a full
        #: ring scan — health() sits on polled paths (the autoscaler
        #: reads every replica's health per tick)
        self._own_events = deque(maxlen=10)
        self._stop = threading.Event()
        self._worker: Optional[threading.Thread] = None
        #: ``time.perf_counter()`` when start() first started the loop
        #: thread: the end of set-up, on the clock of the request traces
        #: and of the compile ledger (health()["setup"])
        self._started_at: Optional[float] = None
        self._broken: Optional[BaseException] = None
        # ONE lock serializes every arena/net touch: step() may run from
        # the background loop while warmup/manual drivers call in
        self._lock = threading.RLock()
        net.rnn_clear_previous_state()     # the engine owns the stream
        self._register_metrics(registry)

    # ------------------------------------------------------------------
    # telemetry
    # ------------------------------------------------------------------
    def _register_metrics(self, registry) -> None:
        r = registry or global_registry()
        self._handles = register_serving_metrics(self, self._label,
                                                 registry)
        lab = dict(model=self._label)
        self._tokens = r.counter(
            SERVING_TOKENS, "Tokens generated by the serving engine",
            ("model",)).labels(**lab)
        self._ttft_hist = r.histogram(
            SERVING_TTFT, "Seconds from submit to first token",
            ("model",)).labels(**lab)
        self._tpot_hist = r.histogram(
            SERVING_TPOT, "Seconds between consecutive tokens of one "
            "request", ("model",)).labels(**lab)
        self._queue_wait_hist = r.histogram(
            SERVING_QUEUE_WAIT, "Seconds a request waited for admission",
            ("model",)).labels(**lab)
        self._dispatch_hist = r.histogram(
            SERVING_DISPATCH_LATENCY, "Wall seconds per decode/verify "
            "dispatch cycle (paged modes include the KV path around it)",
            ("model",)).labels(**lab)
        # the cycle's counts (health() reads the same ints): one store,
        # collected at scrape time, so the hot path takes no registry lock
        r.counter(
            SERVING_DECODE_ROWS, "Active rows summed over decode/verify "
            "dispatches", ("model",)).set_function(
            scrape_probe(self, lambda e: e._dispatch_rows), **lab)
        picked = r.counter(
            SERVING_SAMPLE_ROWS, "Rows of plain decode dispatches by how "
            "their token was selected: greedy (the device's argmax) or "
            "drawn (sampled from its row of the fetched block)",
            ("model", "kind"))
        picked.set_function(
            scrape_probe(self, lambda e: e._greedy_rows),
            kind="greedy", **lab)
        picked.set_function(
            scrape_probe(self, lambda e: e._drawn_rows),
            kind="drawn", **lab)
        r.counter(
            SERVING_BLOCK_FETCHES, "Plain decode cycles that fetched the "
            "[S, V] distributions because a row samples",
            ("model",)).set_function(
            scrape_probe(self, lambda e: e._block_fetches), **lab)
        tokens = r.counter(
            SERVING_PREFILL_TOKENS, "Prompt tokens per prime: fed, and "
            "the padded bucket dispatched", ("model", "kind"))
        tokens.set_function(
            scrape_probe(self, lambda e: e._prefill_fed),
            kind="fed", **lab)
        tokens.set_function(
            scrape_probe(self, lambda e: e._io["prefill"].width),
            kind="bucket", **lab)
        io = r.counter(
            SERVING_HOST_IO_BYTES, "Bytes of the numpy arrays handed to "
            "and fetched from rnn_time_step", ("model", "phase",
                                               "direction"))
        for kind in self._io:
            io.set_function(
                scrape_probe(self, lambda e, k=kind: e._io[k].h2d_bytes),
                phase=kind, direction="h2d", **lab)
            io.set_function(
                scrape_probe(self, lambda e, k=kind: e._io[k].d2h_bytes),
                phase=kind, direction="d2h", **lab)
        if self._pool is not None:
            self._kv_bytes = r.counter(
                SERVING_KV_BYTES_MOVED, "Modeled bytes the KV path "
                "moves between the page pool and the dispatch "
                "(in-dispatch read + one-token append)",
                ("model",)).labels(**lab)
        r.gauge(SERVING_ACTIVE_SLOTS, "Arena slots holding an active "
                "request", ("model",)).set_function(
            scrape_probe(self, lambda s: s.active_slots()),
            model=self._label)
        if self._pool is not None:
            r.gauge(SERVING_KV_PAGES_TOTAL, "Allocatable KV pages in "
                    "the paged arena's pool", ("model",)).set_function(
                scrape_probe(self, lambda s: s._pool.usable),
                model=self._label)
            r.gauge(SERVING_KV_PAGES_USED, "KV pages currently held by "
                    "slots or the prefix cache", ("model",)).set_function(
                scrape_probe(self, lambda s: s._pool.used_count()),
                model=self._label)
        if self._prefix is not None:
            self._prefix_hits = r.counter(
                SERVING_PREFIX_HITS, "Admissions that reused >= 1 "
                "cached prefix block", ("model",)).labels(**lab)
            self._prefix_misses = r.counter(
                SERVING_PREFIX_MISSES, "Admissions that reused no "
                "cached prefix block", ("model",)).labels(**lab)
            self._prefix_reused = r.counter(
                SERVING_PREFIX_REUSED_TOKENS, "Prompt tokens whose "
                "prefill was skipped via cached pages",
                ("model",)).labels(**lab)
        if self._speculation is not None:
            self._spec_accept_hist = r.histogram(
                SERVING_SPEC_ACCEPTANCE, "Per-slot fraction of draft "
                "proposals accepted by a verify dispatch",
                ("model",)).labels(**lab)
        r.gauge(SERVING_DRAINING, "Engine draining: admission stopped, "
                "actives finishing (1) or serving normally (0)",
                ("model",)).set_function(
            scrape_probe(self, lambda s: 1.0 if s._draining else 0.0),
            model=self._label)
        if self._supervisor is not None:
            self._supervisor._bind(self, registry)
        if self._overload is not None:
            self._shed_counter = r.counter(
                SERVING_SHED, "Queued requests shed under a sustained "
                "SLO breach", ("model",)).labels(**lab)
            self._early_rejected = r.counter(
                SERVING_EARLY_REJECTED, "Submits refused because their "
                "deadline provably cannot be met",
                ("model",)).labels(**lab)
            r.gauge(SERVING_BROWNOUT_LEVEL, "Brownout ladder rung: 0 "
                    "off, 1 reduced gamma, 2 speculation off, 3 prefix "
                    "inserts off", ("model",)).set_function(
                scrape_probe(self, lambda s: float(s._brownout)),
                model=self._label)

    # ------------------------------------------------------------------
    # health / readiness (the ParallelInference probe contract)
    # ------------------------------------------------------------------
    @property
    def label(self) -> str:
        """The model label this engine's telemetry/events carry — the
        public identity the fleet layer (and the event timeline) keys
        on."""
        return self._label

    @property
    def trace_identity(self) -> str:
        """The identity request traces record per lifecycle event: the
        model label, rid-suffixed when a fleet router stamped
        ``replica_tag`` (factory-built replicas share the label, and a
        migrated trace must name BOTH sides of its hop)."""
        if self.replica_tag is None:
            return self._label
        return f"{self._label}#r{self.replica_tag}"

    def _emit_serving_event(self, name: str, **attrs) -> None:
        """Publish one serving-lifecycle event under this engine's
        trace identity (rid-suffixed in a fleet — label-sharing
        replicas must not blend their timelines) and mirror it into
        the bounded per-engine tail ``health()`` serves. The
        supervisor emits its rebuild/escalate events through this too,
        so one engine's recovery history lives in one place."""
        ev = emit_event("serving", name, engine=self.trace_identity,
                        **attrs)
        if ev is not None:
            self._own_events.append({"name": ev.name, "wall": ev.wall,
                                     "attrs": dict(ev.attrs)})

    def is_healthy(self) -> bool:
        if self._broken is not None or self._stop.is_set():
            return False
        if self._worker is not None and not self._worker.is_alive():
            return False
        return True

    def is_ready(self) -> bool:
        return self.is_healthy() and not self._draining \
            and not self._pending.full()

    def queue_depth(self) -> int:
        return self._pending.depth()

    def active_slots(self) -> int:
        return sum(r is not None for r in self._slots)

    def health(self) -> dict:
        out = {"healthy": self.is_healthy(), "ready": self.is_ready(),
               # identity for multi-engine / multi-PROCESS probes: a
               # /health dump or an agent status file must say which
               # replica (and whose pid) this payload describes
               "label": self.trace_identity,
               "pid": os.getpid(),
               "queue_depth": self.queue_depth(),
               "active_slots": self.active_slots(),
               "slots": self.slots,
               "setup": {"started_at": self._started_at},
               "decode_dispatch": {
                   "count": self._dispatches,
                   "mean_ms": round(
                       self._dispatch_s_total * 1e3
                       / max(1, self._dispatches), 3),
                   "rows": self._dispatch_rows},
               "sample": {
                   "greedy_rows": self._greedy_rows,
                   "drawn_rows": self._drawn_rows,
                   "block_fetches": self._block_fetches},
               "prefill": {
                   "fed_tokens": self._prefill_fed,
                   "bucket_tokens": self._io["prefill"].width},
               "host_io": dict(
                   {k: io.as_dict() for k, io in self._io.items()},
                   input_form="ids" if takes_ids(self.net) else "one-hot")}
        for kind in self._counted:
            out[kind] = {**self._slot_state.get(kind, {}),
                         **self._host_counts.get(kind, {}),
                         **self._layer_counters(kind)}
        if self._pool is not None:
            out["kv_pages"] = {"total": self._pool.usable,
                               "used": self._pool.used_count(),
                               "free": self._pool.free_count(),
                               "page_size": self._pool.page_size}
            out["kv_traffic"] = {
                "decode_path": f"direct-{self._decode_impl}",
                # table entries one grid step of the kernel's walk
                # copies and scores (0 off the kernel path)
                "kernel_pages_per_step": self._kernel_pages_per_step(),
                "kv_dtype": self._kv_dtype,
                "bytes_moved_total": self._kv_bytes_total,
                "dispatches": self._dispatches,
            }
        if self._prefix is not None:
            out["prefix_cache"] = {"entries": len(self._prefix),
                                   "hits": self._prefix.hits,
                                   "misses": self._prefix.misses,
                                   "reused_tokens":
                                       self._prefix.reused_tokens}
        if self._speculation is not None:
            out["speculation"] = {"gamma": self._speculation.gamma}
        if self._draining:
            out["draining"] = True
        if self._supervisor is not None:
            out["supervisor"] = self._supervisor.health()
        if self._overload is not None:
            out["overload"] = {
                "brownout_level": self._brownout,
                "shed_total": self._overload.shed_total,
                "early_rejected_total":
                    self._overload.early_rejected_total,
            }
        # recent lifecycle events (bounded, non-mutating, O(1)): the
        # per-engine mirror, not a global-ring scan — health() runs on
        # polled paths (every autoscaler tick reads every replica)
        out["last_events"] = list(self._own_events)
        return out

    def _count_rows(self, contexts) -> None:
        """One dispatch's share of the host counts of ``health()``, from
        its rows alone: `contexts` = positions each REAL query of the
        dispatch may see (itself included), handed to every layer that
        declared a ``host`` function beside its counters (sparse
        selection: what it keeps of them. What the programs scored the
        layers count themselves)."""
        for kind, total in self._host_counts.items():
            for host in self._counted[kind][2]:
                for field, n in host(contexts).items():
                    total[field] = total.get(field, 0) + n

    def _take_stats(self, state: dict) -> dict:
        """Take one dispatch's layer counters out of the stream state it
        returned and join them to the accumulators on the device. The
        state handed on carries none: the next dispatch counts from
        nought, and the accumulators are never donated."""
        if not self._stats_acc:
            return state
        state = dict(state)
        stats = {}
        for kind, (decl, names, _) in self._counted.items():
            for n in names:
                state[n] = d = dict(state[n])
                stats.setdefault(kind, []).append(d.pop(decl.key))
        self._stats_acc = _join_stats(self._stats_acc, stats,
                                      maxima=self._stats_maxima)
        return state

    def _layer_counters(self, kind: str) -> dict:
        """What the layers of `kind` have counted, summed over them (a
        maximum field: the largest): the device accumulator fetched HERE
        (from any thread, no step lock: it is complete up to the last
        dispatch that finished), as Python integers."""
        decl = self._counted[kind][0]
        now = np.asarray(self._stats_acc[kind]).astype(np.uint64)
        values = (now[..., 0] << _LOW_BITS) + now[..., 1]   # [layers, fields]
        return {f: int(values[:, i].max() if f in decl.maxima
                       else values[:, i].sum())
                for i, f in enumerate(decl.fields)}

    @property
    def page_pool(self) -> Optional[PagePool]:
        """The paged arena's pool (None in slot-arena mode) — the
        chaos seam resilience.chaos.PageExhaustionInjector drives."""
        return self._pool

    @property
    def prefix_cache(self) -> Optional[PrefixCache]:
        return self._prefix

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------
    def submit(self, prompt, steps: int, *, temperature: float = 1.0,
               top_k: Optional[int] = None, top_p: Optional[float] = None,
               stop_tokens=(), rng=None, timeout: Optional[float] = None,
               priority: int = 0,
               max_length: Optional[int] = None) -> GenerationStream:
        """Queue one prompt for up to `steps` generated tokens; returns
        its streaming handle immediately (admission happens on a later
        ``step()``). Arguments mirror ``sample_stream`` — same rng, same
        stop semantics, `max_length` defaulting to the net's streaming
        capacity — plus serving controls: `timeout` (end-to-end deadline
        in seconds; expiry anywhere — queued or mid-generation — fails
        the handle with InferenceTimeout and frees the slot) and
        `priority` (higher admitted first)."""
        if self._broken is not None:
            raise EngineShutdown("GenerationEngine is broken: "
                                 f"{self._broken!r}")
        if self._stop.is_set():
            raise EngineShutdown("GenerationEngine shut down")
        if self._draining:
            raise EngineShutdown("GenerationEngine draining — submit "
                                 "to the replacement instance")
        prompt = [int(t) for t in prompt]
        if max_length is None:
            max_length = self._cap
        _check_seed(prompt, steps, max_length)
        if self._cap is not None and len(prompt) > self._cap:
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds the net's "
                f"streaming capacity ({self._cap})")
        want = len(prompt) + int(steps)
        if max_length is not None:
            want = min(want, int(max_length))
        if self._speculation is not None and self._cap is not None \
                and want > self._cap - self._speculation.gamma + 1:
            raise ValueError(
                f"prompt + steps ({want} ids) needs speculative "
                f"headroom: every verify dispatch transiently consumes "
                f"1 + gamma positions, so in-engine speculation serves "
                f"at most capacity - gamma + 1 = "
                f"{self._cap - self._speculation.gamma + 1} ids")
        if self._pool is not None:
            # admission-time capacity check: a request whose worst case
            # can NEVER fit the page budget is rejected here, not
            # admitted and retired mid-stream on capacity
            store = self._store_positions(want)
            if pages_needed(store, self._ps) > self._pool.usable:
                raise ValueError(
                    f"prompt + steps would hold {store} KV positions "
                    f"({pages_needed(store, self._ps)} pages of "
                    f"{self._ps} tokens) but the pool has only "
                    f"{self._pool.usable} pages total — the request "
                    f"can never be admitted")
        self._handles[SERVING_REQUESTS].inc()
        deadline = None if timeout is None else \
            time.monotonic() + float(timeout)
        req = GenerationRequest(
            prompt, steps, temperature=temperature, top_k=top_k,
            top_p=top_p, stop_tokens=stop_tokens, rng=rng,
            max_length=max_length, deadline=deadline, priority=priority)
        if self._overload is not None:
            reason = self._overload.reject_at_submit(
                self, req, time.monotonic())
            if reason is not None:
                self._early_rejected.inc()
                req.trace.record("early_reject", reason=reason)
                self._emit_serving_event("early_reject")
                raise ServingOverloaded(reason)
        try:
            self._pending.submit(req)
        except ServingQueueFull:
            self._handles[SERVING_QUEUE_REJECTED].inc()
            raise
        except InferenceTimeout:
            self._handles[SERVING_DEADLINE_EXCEEDED].inc()
            raise
        return req.handle

    # ------------------------------------------------------------------
    # the dispatch cycle
    # ------------------------------------------------------------------
    def step(self) -> bool:
        """One engine cycle: expire/cancel, shed under overload, admit
        into free slots, one decode (or widened speculative verify)
        dispatch over the arena, sample + stream + retire. Returns
        whether any progress was made (False = idle).

        The WHOLE cycle past reaping is one failure domain: a fault
        anywhere — the pop-to-seat admission window included, not just
        the dispatch — lands in one place where the supervisor (if any)
        can quarantine + rebuild the arena from the request ledger;
        without one (or with the restart budget exhausted) the engine
        falls to the terminal ``_break`` fail-all."""
        with self._lock:
            if self._stop.is_set() or self._broken is not None:
                return False
            # the cycle is a flat sequence of monitoring spans (module
            # docstring, "Phases"), opened where it finds work
            with phases():
                return self._cycle()

    def _cycle(self) -> bool:
        now = time.monotonic()
        if self.active_slots():
            next_phase("engine.reap")   # a decode cycle follows
        progress = self._reap(now) > 0
        try:
            if self._overload is not None:
                progress = self._apply_overload(now) or progress
            if not self._draining:
                # admission staging (prefill buffers, first-admission
                # pool build, prefix-page mapping) is per-REQUEST
                # slot-lifecycle work, not the per-token decode
                # steady state this rule protects — between
                # admissions steps re-upload nothing (cached tables)
                # tpulint: disable=device-transfer-in-hot-loop
                progress = self._admit_ready(now) > 0 or progress
            active = [s for s, r in enumerate(self._slots)
                      if r is not None]
            if not active:
                return progress
            next_phase("decode.input")
            if self._speculation is not None:
                self._step_speculative(active)
            else:
                self._step_plain(active)
        except Exception as e:  # noqa: BLE001 — fail waiters, not hang
            self._handles[SERVING_ERRORS].inc()
            if self._recover(e):
                return True
            self._break(e)
            return False
        return True

    def _recover(self, exc: BaseException) -> bool:
        """Hand a step-cycle fault to the supervisor (if any): True =
        the arena was rebuilt and every in-flight request re-admitted
        bit-identically, keep serving."""
        if self._supervisor is None:
            return False
        cause = ("admission_fault" if self._seating is not None
                 else "decode_fault")
        return self._supervisor.on_dispatch_fault(self, exc, cause)

    def _apply_overload(self, now: float) -> bool:
        """One overload-control tick: shed queued work under a
        sustained SLO breach, refresh the brownout rung from page
        pressure. Host-only; runs before admission so a shed victim is
        never admitted on the same step."""
        ov = self._overload
        victims = ov.shed(self)
        for req in victims:
            self._shed_counter.inc()
            req.trace.record("shed", engine=self.trace_identity)
            req.handle._fail(ServingOverloaded(
                "shed from the admission queue under a sustained "
                "latency-SLO breach (lowest-priority first)"))
        if victims:
            self._emit_serving_event("shed", victims=len(victims))
        prev = self._brownout
        self._brownout = ov.brownout_level(self)
        if self._brownout != prev:
            self._emit_serving_event("brownout", level=self._brownout,
                                     prev=prev)
        return bool(victims)

    def _step_plain(self, active) -> None:
        """One canonical [S, V, 1] decode dispatch, then one ``draw`` per
        row: from the device's id where the request is greedy
        (``selects_one``), from its row of the fetched block where it
        samples."""
        ids, probs = self._dispatch_step()
        next_phase("engine.sample")
        now = time.monotonic()
        for s in active:
            req = self._slots[s]
            if req is None:        # retired by the capacity guard
                continue
            row = (ArgmaxRow(ids[s], self.V) if selects_one(req.top_k)
                   else probs[s])
            tok = draw(row, req.temperature, req.rng,
                       top_k=req.top_k, top_p=req.top_p)
            if req.last_token_t is not None:
                self._tpot_hist.observe(now - req.last_token_t)
            req.last_token_t = now
            req.handle._push(tok)
            req.trace.rollup(1)
            self._tokens.inc()
            reason = stop_reason(tok, len(req.handle._ids), req.want,
                                 req.stop_tokens)
            if reason:
                self._retire(s, reason)
            else:
                req.pending_token = tok

    def _step_speculative(self, active) -> None:
        """One widened [S, V, 1+gamma] verify dispatch: the host draft
        proposes per slot, the target scores pending + proposals in ONE
        forward, each row commits its accepted prefix + one
        replacement/bonus token (the shared rejection rule), and a
        per-row rewind drops the rejected positions — accepted tokens
        advance multiple positions per engine step."""
        spec = self._speculation
        k = spec.gamma
        # brownout ladder: a reduced (or zero) gamma pads the SAME
        # widened [S, V, 1+gamma] dispatch with fewer real proposals —
        # feature degradation with zero shape changes, zero retraces
        g_cap = k
        if self._brownout >= BROWNOUT_NO_SPECULATION:
            g_cap = 0
        elif self._brownout >= BROWNOUT_REDUCED_GAMMA:
            g_cap = self._overload.brownout_gamma(k)
        if self._cap is not None:
            for s in active:
                if self._slots[s] is not None \
                        and self._row_pos[s] >= self._cap:
                    self._retire(s, "capacity")
        chunk = np.zeros((self.slots, 1 + k), np.int64)
        props: List[List[int]] = [[] for _ in range(self.slots)]
        q_dists = [None] * self.slots
        riders = []
        for s, req in enumerate(self._slots):
            if req is None:
                continue
            riders.append(s)
            g = min(g_cap, req.want - len(req.handle._ids))
            # g <= 0 (brownout rung 2+, or one token wanted): don't pay
            # the host draft — the rung exists to SHED host/device work
            p = ([int(t) for t in spec.draft(list(req.handle._ids), g)][:g]
                 if g > 0 else [])
            props[s] = p
            q_dists[s] = [None] * len(p)   # deterministic = one-hot draft
            chunk[s, 0] = req.pending_token
            chunk[s, 1:1 + len(p)] = p
        if not riders:
            return                 # everything retired at the guard
        self._sync_accounting()
        tp = self._run_dispatch(
            lambda: verify_tokens(self.net, chunk, self.V,
                                  donate_state=self._donate,
                                  io=self._io["decode"]),
            width=1 + k)
        next_phase("engine.sample")
        now = time.monotonic()
        amounts = np.full(self.slots, 1 + k, np.int32)  # free rows: all
        for s in riders:
            req = self._slots[s]
            g = len(props[s])
            p_dists = [filter_probs(tp[s, :, j], req.temperature,
                                    req.top_k, req.top_p)
                       for j in range(g)]
            p_bonus = filter_probs(tp[s, :, g], req.temperature,
                                   req.top_k, req.top_p)
            accepted, nxt = accept_proposals(props[s], p_dists,
                                             q_dists[s], p_bonus,
                                             req.rng)
            if g:
                self._spec_accept_hist.observe(accepted / g)
            committed = props[s][:accepted] + [nxt]
            req.trace.rollup(len(committed), accepted=accepted,
                             proposed=g)
            self._row_pos[s] += 1 + accepted
            amounts[s] = k - accepted
            reason = None
            for tok in committed:
                if req.last_token_t is not None:
                    self._tpot_hist.observe(now - req.last_token_t)
                req.last_token_t = now
                req.handle._push(tok)
                self._tokens.inc()
                reason = stop_reason(tok, len(req.handle._ids),
                                     req.want, req.stop_tokens)
                if reason:
                    break
            if reason:
                self._retire(s, reason)
            else:
                req.pending_token = committed[-1]
        rewind_stream_state(self.net, amounts)
        self._sync_accounting()

    def run_until_idle(self, max_steps: int = 1_000_000) -> int:
        """Manually drive ``step()`` until nothing is active or
        admissible (single-threaded serving: tests, warmup, offline
        drains). Returns the number of cycles taken."""
        n = 0
        while self.step():
            n += 1
            if n >= max_steps:
                raise RuntimeError(f"engine still busy after {n} steps")
        return n

    def _reap(self, now: float) -> int:
        """Retire expired/cancelled requests, ACTIVE (frees their slots
        — a slow consumer cannot squat the arena) and QUEUED (a full
        arena must not defer a queued request's deadline until a slot
        happens to free)."""
        n = 0
        for req in self._pending.reap(now):
            n += 1
            if req.handle.cancelled:
                req.handle._fail(RequestCancelled(
                    "request cancelled while queued"), reason="cancelled")
            else:
                self._handles[SERVING_DEADLINE_EXCEEDED].inc()
                req.handle._fail(InferenceTimeout(
                    "deadline expired in the admission queue"))
        for s, req in enumerate(self._slots):
            if req is None:
                continue
            if req.handle.cancelled:
                self._retire(s, "cancelled",
                             RequestCancelled("request cancelled"))
                n += 1
            elif req.deadline is not None and now >= req.deadline:
                self._handles[SERVING_DEADLINE_EXCEEDED].inc()
                self._retire(s, "error", InferenceTimeout(
                    "deadline expired mid-generation "
                    f"({len(req.handle._ids) - len(req.prompt)} tokens "
                    "streamed)"))
                n += 1
        return n

    def _store_positions(self, want: int) -> int:
        """KV positions a request of `want` total ids holds at worst:
        the final drawn token never re-enters the cache, and the
        stream-capacity guard retires a row before it can pass `cap`.
        The ONE formula behind submit()'s never-fits rejection, the
        head-of-line admission gate, and the page reservation — they
        must agree or a request could pass submit yet never admit."""
        return want - 1 if self._cap is None else min(want - 1,
                                                      self._cap)

    def _pages_admissible(self, req: GenerationRequest) -> bool:
        """Worst-case page check for the head-of-line request: admit
        only when its full reservation fits the free pool plus what the
        prefix cache could evict. Conservative — a prefix hit may need
        fewer fresh pages — so admission never over-commits; pages free
        as active requests retire, so a fitting-in-principle head
        always eventually admits."""
        store = self._store_positions(req.want)
        avail = self._pool.free_count() + (
            self._prefix.evictable_pages() if self._prefix is not None
            else 0)
        return pages_needed(store, self._ps) <= avail

    def _admit_ready(self, now: float) -> int:
        """Fill free slots from the admission queue in priority order
        (paged mode: while the head request's pages fit).

        Every popped request is pinned to ``self._seating`` until it is
        seated in a slot or its handle carries a terminal event: the
        pop-to-seat window is otherwise invisible to both the slot scan
        and the queue drain, and a fault inside it (arena join, the
        admission draw, a chaos hook) would strand the handle with no
        terminal event — callers blocked forever on a request the
        engine no longer knows about."""
        n = 0
        gate = self._pages_admissible if self._pool is not None else None
        while None in self._slots:
            req = self._pending.pop(admissible=gate)
            if req is None:
                break
            next_phase("engine.admit")
            self._seating = req
            n += 1
            if self._fail_if_dead(req, now, "in the admission queue"):
                self._seating = None
                continue
            _fire_chaos(self._seat_chaos, self._admissions, ctx=req)
            req.trace.record("queue_pop", engine=self.trace_identity)
            req.handle.queue_wait_s = now - req.submit_t
            self._queue_wait_hist.observe(req.handle.queue_wait_s)
            if self._overload is not None:
                self._overload.observe_queue_wait(req.handle.queue_wait_s)
            # a popped request that already streamed tokens is a ledger
            # survivor riding the queue (migration / requeue overflow):
            # re-prime it instead of fresh-admitting
            self._admit_one(req, self._slots.index(None),
                            readmit=req.streamed)
            self._seating = None
        return n

    def _fail_if_dead(self, req, now: float, where: str) -> bool:
        """Give `req` its terminal event if it was cancelled or its
        deadline has passed (or it already carries one); True means the
        caller must skip it. The ONE cancel/deadline gate shared by the
        admission pop and the rebuild's re-admissions, so the recovery
        path can never drift from the admission path's semantics."""
        if req.handle.done:
            return True
        if req.handle.cancelled:
            req.handle._fail(RequestCancelled(
                f"request cancelled {where}"), reason="cancelled")
            return True
        if req.deadline is not None and now >= req.deadline:
            self._handles[SERVING_DEADLINE_EXCEEDED].inc()
            req.handle._fail(InferenceTimeout(
                f"deadline expired {where}"))
            return True
        return False

    def _alloc_request_pages(self, req: GenerationRequest):
        """Reserve the request's worst-case pages: look up the longest
        cached full-block prefix (mapped shared, refcount++), evict
        unmapped cache entries if the fresh allocation falls short, and
        allocate the rest. Returns ``(table, hit_len)`` — the slot's
        block-ordered page table and how many prompt tokens the cached
        pages already cover."""
        hit_len, shared = 0, []
        if self._prefix is not None:
            if self._page_store is not None:
                hit_len, shared = self._prefix.lookup(req.prompt)
            else:
                self._prefix.misses += 1   # nothing cached before the
            (self._prefix_hits if shared  # first arena build
             else self._prefix_misses).inc()
            if hit_len:
                self._prefix_reused.inc(hit_len)
        store = self._store_positions(req.want)
        need_new = pages_needed(store, self._ps) - len(shared)
        # retain the shared pages BEFORE evicting: a deep shortfall must
        # not reclaim the very blocks this admission is about to map
        for p in shared:
            self._pool.retain(p)
        try:
            short = need_new - self._pool.free_count()
            if short > 0 and self._prefix is not None:
                self._prefix.evict(short)
            fresh = self._pool.alloc(need_new)  # PageExhausted only
        except Exception:                       # under a chaos seize
            for p in shared:
                self._pool.release(p)
            raise
        return shared + fresh, hit_len

    def _install_prefix(self, table, hit_len: int) -> None:
        """Seed the detached prefill state with the cached prefix: the
        mapped pages gather into a batch-1 dense view, kv_pos starts at
        the block boundary, and the host position mirrors follow — the
        suffix prime then continues the stream exactly as if the prefix
        had just been primed."""
        net = self.net
        row = np.zeros((1, self._n_max), np.int32)
        n_hit = hit_len // self._ps
        row[0, :n_hit] = table[:n_hit]
        dense = gather_pages(self._page_store, row, length=self._L,
                             axes=self._paged_axes)
        self._kv_traffic(self._L * self._tok_bytes)   # one-row gather
        pos = jnp.asarray(hit_len, jnp.int32)
        for (n, k), leaf in zip(self._paged_keys, dense):
            cur = net.state.get(n)
            cur = dict(cur) if isinstance(cur, dict) else {}
            cur[k] = leaf
            cur["kv_pos"] = pos
            net.state[n] = cur
        net._stream_pos = hit_len
        net._stream_pos_rows = None
        if self._graph_vertices:
            net._stream_pos_map = {n: hit_len
                                   for n in self._graph_vertices}

    @phases()
    def _admit_one(self, req: GenerationRequest, slot: int,
                   readmit: bool = False) -> None:
        """Prefill `req` at batch 1 and join it to the arena at `slot`.
        A prefill failure fails THAT request only: the arena state is
        restored untouched (and the request's pages released), so
        in-flight requests are unaffected.

        ``readmit=True`` is the supervisor's recovery path: the request
        already streamed tokens before the arena was quarantined, so
        the prime feeds ``ids[:-1]`` (prompt + committed tokens minus
        the pending one — exactly what the lost arena row had consumed)
        and NOTHING else happens: no draw (the rng must stay at its
        fault-time position), no token push, no TTFT/queue-wait
        observation, no prefill chaos (the request already cleared
        admission once). The next dispatch recomputes the identical
        next-token distribution, so the stream continues bit-identical
        to an unperturbed run."""
        next_phase("engine.admit")
        net = self.net
        saved_state = dict(net.state)
        saved_acct = self._save_accounting()
        prime_ids = req.handle._ids[:-1] if readmit else req.prompt
        table, hit_len = [], 0
        try:
            if self._pool is not None:
                table, hit_len = self._alloc_request_pages(req)
            if not readmit:
                _fire_chaos(self._prefill_chaos, self._admissions,
                            ctx=req)
            net.rnn_clear_previous_state()
            fed = len(prime_ids) - hit_len
            req.trace.record(
                "prefill_start", engine=self.trace_identity, width=fed,
                bucket=(_width_bucket(max(1, fed))
                        if self._prime_padded else None),
                prefix_hit=hit_len, readmit=readmit)
            if self._kv_dtype == "int8":
                # the int8 prime runs THROUGH the paged path (quantize-
                # once: the prompt's pool bytes must come from the same
                # quantized append the decode steps run) — a prefix hit
                # just starts kv_pos past the shared pages, no dense
                # gather and re-scatter
                self._install_prime_paged_state(table, hit_len)
            elif hit_len:
                self._install_prefix(table, hit_len)
            self._prefill_fed += fed
            p0 = prime_prompt(net, prime_ids[hit_len:], self.V,
                              padded=self._prime_padded,
                              io=self._io["prefill"])
            if self._host_counts:
                self._count_rows(np.arange(hit_len, len(prime_ids)) + 1)
            req.trace.record("prefill_end")
            next_phase("engine.seat")
            primed_pos = self._net_pos(net)
        except Exception as e:  # noqa: BLE001 — per-request failure domain
            net.state = saved_state
            self._restore_accounting(saved_acct)
            self._release_pages(table)
            if not readmit:
                self._admissions += 1
            self._handles[SERVING_ERRORS].inc()
            req.handle._fail(e)
            self._recent_traces.append(req.trace)
            return
        primed_state = dict(net.state)
        primed_state = self._take_stats(primed_state)
        if self._kv_dtype == "int8":
            # pools/scales come back out of the prime's state AFTER the
            # snapshot: every early-exit below (failure already returned;
            # one-token finish; dead-request skip) leaves the store
            # exactly as the prime left it — the prime wrote the
            # request's pages in place, and a one-token finish releases
            # those pages right here via _release_pages
            primed_state = self._extract_prime_paged_state(primed_state)
        if readmit:
            tok = req.handle._ids[-1]    # pending, drawn pre-fault
            req.trace.record("readmit", engine=self.trace_identity)
        else:
            self._admissions += 1
            tok = draw(p0, req.temperature, req.rng,
                       top_k=req.top_k, top_p=req.top_p)
            now = time.monotonic()
            req.handle.ttft_s = now - req.submit_t
            self._ttft_hist.observe(req.handle.ttft_s)
            if self._overload is not None:
                self._overload.observe_ttft(req.handle.ttft_s, now)
            req.last_token_t = now
            req.trace.record("first_token", engine=self.trace_identity)
            req.handle._push(tok)
            self._tokens.inc()
            reason = stop_reason(tok, len(req.handle._ids), req.want,
                                 req.stop_tokens)
            if reason is None and self._cap is not None \
                    and primed_pos >= self._cap:
                reason = "capacity"  # prompt filled the stream: no room
            if reason:
                # one-token request: never enters the arena at all
                net.state = saved_state
                self._restore_accounting(saved_acct)
                self._release_pages(table)
                req.handle._finish(reason)
                self._recent_traces.append(req.trace)
                return
        if not self._arena_ready:
            if self._pool is not None and self._page_store is None:
                self._init_page_store(primed_state)
            saved_state = self._build_arena(primed_state, saved_state)
            self._arena_ready = True
        net.state = self._merge(saved_state, primed_state, slot)
        if self._pool is not None:
            if self._kv_dtype == "int8":
                # the prime already wrote the pool in place (quantize-
                # once) — no dense→paged scatter; charge its pool
                # traffic: the folded-gather prime read the whole
                # context per chunk and appended `fed` tokens
                self._kv_traffic((self._L + fed) * self._tok_bytes)
            else:
                self._scatter_primed_pages(primed_state, table)
            self._page_tables[slot] = table
            self._invalidate_tables()
            if self._prefix is not None \
                    and self._brownout < BROWNOUT_NO_PREFIX_INSERTS:
                self._prefix.insert(req.prompt, table)
                if self.page_publisher is not None:
                    try:
                        self.page_publisher(req.prompt, list(table))
                    except Exception:   # noqa: BLE001 — best-effort
                        log.exception("fleet page publish failed; "
                                      "admission unaffected")
        self._slots[slot] = req
        self._row_pos[slot] = primed_pos
        for row in self._slot_state.values():
            row["seated_state_bytes"] += row["state_bytes_per_slot"]
        req.pending_token = tok
        req.trace.record("seat", engine=self.trace_identity, slot=slot)
        self._sync_accounting()

    def _release_pages(self, table) -> None:
        for p in table:
            self._pool.release(p)

    # ------------------------------------------------------------------
    # supervised recovery (serving/supervisor.py drives this)
    # ------------------------------------------------------------------
    def _quarantine_rebuild(self) -> int:
        """Drop the (possibly poisoned) device arena WHOLESALE and
        rebuild it from the host-side request ledger: fresh page pool +
        page tables + prefix cache (re-seeded by the re-primes), fresh
        arena skeleton on first re-admission, every surviving request
        re-primed from prompt + committed tokens with its pending token
        and untouched rng — each stream continues bit-identical to an
        unperturbed run. Returns the number of survivors re-admitted.
        Runs under the step lock (the supervisor is called from the
        step cycle's failure path).

        The rebuild reuses the warm prefill buckets and the compiled
        arena scatter/gather shapes, so after a full-envelope
        ``warmup()`` a recovery compiles nothing new (test-pinned).

        Survivors travel as :class:`RequestLedgerEntry` records through
        the same ``export_ledger`` capture fleet migration uses — ONE
        rebuild payload, not two hand-synced copies — including the
        pop-to-seat ``_seating`` request (at most S entries total: a
        seating request implies a free slot, so sequential free-slot
        assignment below always finds room)."""
        entries = self.export_ledger()      # actives + _seating
        self._seating = None
        self._slots = [None] * self.slots
        self._row_pos = np.zeros(self.slots, np.int64)
        self._arena_ready = False
        self._merge_keys = None
        if self._pool is not None:
            # fresh pool: the old one's refcounts may be mid-mutation
            # from the failed cycle (and chaos seizures die with it)
            self._pool = PagePool(self._pool.total_pages, self._ps)
            self._prefix = (PrefixCache(self._pool)
                            if self._prefix is not None else None)
            self._page_store = None
            self._scale_store = None
            self._paged_keys = None
            self._paged_axes = None
            self._page_tables = [[] for _ in range(self.slots)]
            self._invalidate_tables()
            self._kv_pos_dirty = False   # the rebuilt state is fresh
            if self._kv_dtype == "int8":
                # fresh zeroed pools + scales BEFORE the re-primes:
                # int8 prefill writes through the paged path, so the
                # store must exist (bf16 rebuilds it lazily from the
                # first re-primed state)
                self._init_quant_store()
        self.net.rnn_clear_previous_state()
        self._sync_accounting()
        if self._overload is not None:
            # the replacement pool starts fresh: recompute the rung so
            # the re-primes aren't gated by pre-fault page pressure
            # (rung 3 would silently skip re-seeding the prefix cache)
            self._brownout = self._overload.brownout_level(self)
        now = time.monotonic()
        n = 0
        try:
            for entry in entries:
                req = entry.request
                if self._fail_if_dead(req, now, "during recovery"):
                    continue
                # a streamed survivor re-primes (no draw, rng untouched);
                # a never-streamed one — the pop-to-seat window request —
                # admits fresh and may even finish clean (one-token)
                req.trace.record("rebuild", engine=self.trace_identity)
                slot = self._slots.index(None)
                self._admit_one(req, slot, readmit=req.streamed)
                if self._slots[slot] is req or (
                        req.handle.done and req.handle.error is None):
                    n += 1                   # seated, or finished clean
        except BaseException as e:
            # a fault raised mid-rebuild must strand nobody: the slots
            # and _seating were cleared up front, so the escalation
            # _break can no longer see survivors that didn't make it
            # back in — fail every unseated, unresolved handle HERE,
            # then let the supervisor escalate (seated survivors get
            # their terminal event from _break's slot scan)
            seated = {id(r) for r in self._slots if r is not None}
            for entry in entries:
                if id(entry.request) not in seated \
                        and not entry.request.handle.done:
                    entry.request.handle._fail(e)
            raise
        return n

    # ------------------------------------------------------------------
    # the request-ledger seam (serving/request.RequestLedgerEntry):
    # ONE export/re-admit path shared by supervisor recovery (above)
    # and fleet migration (serving/fleet/migration.py)
    # ------------------------------------------------------------------
    def export_ledger(self, include_queued: bool = False
                      ) -> List[RequestLedgerEntry]:
        """Snapshot every in-flight request as a versioned ledger
        entry: active slots (in slot order), the pop-to-seat
        ``_seating`` request if the export lands inside that window
        (the same visibility ``_break`` gained in PR 9 — without it a
        migration would strand the popped handle forever), and,
        with ``include_queued``, the admission queue in admission
        order. Non-mutating; safe on a stopped/broken engine (the
        dead-replica export path)."""
        with self._lock:
            entries = [RequestLedgerEntry.capture(r, "active")
                       for r in self._slots if r is not None]
            if self._seating is not None:
                entries.append(RequestLedgerEntry.capture(
                    self._seating, "seating"))
            if include_queued:
                entries.extend(
                    RequestLedgerEntry.capture(r, "queued")
                    for r in self._pending.peek_all())
            return entries

    def admit_from_ledger(self, entries, where: str = "during migration"
                          ) -> int:
        """Re-admit exported ledger entries on THIS engine: streamed
        survivors re-prime from ``ids[:-1]`` with their pending token
        and untouched rng (the supervisor-recovery semantics — the
        stream continues bit-identically), never-streamed entries admit
        fresh. Entries that no longer fit a free slot ride the
        admission queue (force-requeued past the limit: survivors were
        already admitted once). Returns how many requests this engine
        took over; dead entries (cancelled / expired, or already
        carrying a terminal event) are resolved and skipped."""
        with self._lock:
            if self._broken is not None:
                raise EngineShutdown("GenerationEngine is broken: "
                                     f"{self._broken!r}")
            if self._stop.is_set():
                raise EngineShutdown("GenerationEngine shut down")
            if self._draining:
                raise EngineShutdown("GenerationEngine draining — "
                                     "migrate to another replica")
            now = time.monotonic()
            n = 0
            for entry in entries:
                req = entry.request
                if self._fail_if_dead(req, now, where):
                    continue
                if self._pool is not None:
                    store = self._store_positions(req.want)
                    if pages_needed(store, self._ps) > self._pool.usable:
                        # heterogeneous-pool edge: this replica can
                        # NEVER hold the request — fail it the way
                        # submit() would have, don't head-of-line block
                        req.handle._fail(ValueError(
                            f"migrated request holds {store} KV "
                            f"positions but this replica's pool has "
                            f"only {self._pool.usable} pages"))
                        continue
                free = (self._slots.index(None)
                        if None in self._slots else None)
                if free is not None and (
                        self._pool is None
                        or self._pages_admissible(req)):
                    self._admit_one(req, free, readmit=req.streamed)
                    if self._slots[free] is req or (
                            req.handle.done
                            and req.handle.error is None):
                        n += 1
                else:
                    req.trace.record("requeue", engine=self.trace_identity)
                    self._pending.requeue(req)
                    n += 1
            return n

    def detach_ledger(self, lock_timeout: Optional[float] = None
                      ) -> List[RequestLedgerEntry]:
        """Export EVERYTHING in flight (actives + seating + queue) and
        release it from this engine WITHOUT terminal events: the
        requests live on wherever the entries are re-admitted. The
        planned-handoff half of live migration — scale-in drains
        through this instead of waiting out ``drain()``'s natural
        retirements — and equally the post-mortem export off a dead
        replica (works under ``_stop``; a broken engine already failed
        its handles, so its export is empty). The engine is left
        draining with an empty arena, a fresh-released page pool, and a
        closed queue: terminal for this replica.

        The queued entries come from ``close()``'s drain — the SAME
        atomic removal that refuses later submits — so a request that
        squeezes through the unlocked ``submit()`` draining check
        while the detach runs is either in the export or refused,
        never silently dropped.

        ``lock_timeout`` bounds the engine-lock wait: a replica whose
        step thread wedged INSIDE a dispatch still holds the lock, and
        a caller migrating it off lease-expiry must not deadlock on it
        (raises ``TimeoutError``; the wedged engine's streams cannot
        be exported from outside the lock)."""
        if lock_timeout is not None:
            if not self._lock.acquire(timeout=lock_timeout):
                raise TimeoutError(
                    f"engine lock not released within {lock_timeout:g}s "
                    f"— a wedged dispatch still holds it; its ledger "
                    f"cannot be exported")
        else:
            self._lock.acquire()
        try:
            self._draining = True
            entries = self.export_ledger()      # actives + seating
            self._seating = None
            for s, req in enumerate(self._slots):
                if req is None:
                    continue
                self._slots[s] = None
                self._row_pos[s] = 0
                if self._pool is not None:
                    for p in self._page_tables[s]:
                        self._pool.release(p)
                    self._page_tables[s] = []
            if self._pool is not None:
                self._invalidate_tables()
                self._kv_pos_dirty = True
            entries.extend(RequestLedgerEntry.capture(r, "queued")
                           for r in self._pending.close())
            self._sync_accounting()
            return entries
        finally:
            self._lock.release()

    def detach_queued(self, max_n: Optional[int] = None
                      ) -> List[RequestLedgerEntry]:
        """Export and remove queued (never-prefilled) requests, highest
        admission priority first, up to `max_n` (None = all) — the
        overload-rebalance payload: queued work moves for free (no warm
        KV to abandon, no re-prefill debt), actives stay where their
        cache is. The queue stays open; the engine keeps serving."""
        with self._lock:
            entries = []
            while max_n is None or len(entries) < max_n:
                req = self._pending.pop()
                if req is None:
                    break
                entries.append(RequestLedgerEntry.capture(req, "queued"))
            return entries

    def queue_snapshot(self):
        """Non-mutating admission-queue view (per-priority depths +
        oldest wait) — the router's placement-scoring accessor; see
        ``serving.scheduler.QueueSnapshot``."""
        return self._pending.snapshot()

    def load_stats(self) -> dict:
        """The narrow placement-scoring payload (what the fleet
        router's hot submit path reads per candidate): slots, occupied
        slots, queue depth, and the free-page fraction (1.0 unpaged) —
        without constructing the full ``health()`` observability dict."""
        free = 1.0
        if self._pool is not None and self._pool.usable:
            free = self._pool.free_count() / self._pool.usable
        return {"slots": self.slots,
                "active_slots": self.active_slots(),
                "queue_depth": self.queue_depth(),
                "free_page_frac": free}

    # ------------------------------------------------------------------
    # disaggregated prefill/decode (serving/fleet/pages.py rides these)
    # ------------------------------------------------------------------
    def prefix_held_blocks(self, prompt) -> int:
        """Leading full `prompt` blocks already in the prefix cache
        (pure probe — no stats, no LRU touch); 0 without a cache."""
        with self._lock:
            if self._prefix is None:
                return 0
            return self._prefix.held_blocks(prompt)

    def pages_importable(self) -> bool:
        """True once :meth:`import_prefix_chain` can actually map
        shipped pages: the device pools exist (the bf16 pools
        materialize lazily at the FIRST prime — warmup or real
        traffic — because their dtype is the net's, discoverable only
        from a primed state) and prefix inserts aren't browned out.
        Agents probe this before touching the store: a fresh un-warmed
        replica's first admission primes normally and materializes the
        pools; every admission after imports."""
        with self._lock:
            return (self._pool is not None
                    and self._prefix is not None
                    and self._page_store is not None
                    and self._brownout < BROWNOUT_NO_PREFIX_INSERTS)

    def prefix_digests(self, limit: Optional[int] = None) -> List[str]:
        """Chain digests of cached prefix blocks, LRU order (most
        recent last) — the page-locality advertisement an agent puts in
        its status file."""
        with self._lock:
            if self._prefix is None:
                return []
            return self._prefix.digests(limit)

    def export_prefix_chain(self, prompt, table, store) -> dict:
        """Publish every FULL block of a just-primed `prompt` to the
        fleet page store: per block, each paged leaf's page (plus its
        int8 scale row) is read back and shipped under the block's
        chain digest. Content addressing makes this idempotent —
        already-present digests are skipped without a device read.
        Returns ``{"digests", "published", "bytes"}``."""
        with self._lock:
            out = {"digests": [], "published": 0, "bytes": 0}
            if self._pool is None or self._page_store is None:
                return out
            ps = self._ps
            n_full = len(prompt) // ps
            if not n_full:
                return out
            digs = chain_digests(prompt, ps)
            for i in range(n_full):
                out["digests"].append(digs[i])
                if store.has(digs[i], self._kv_dtype):
                    continue
                page = table[i]
                arrays = []
                for j, (n, k) in enumerate(self._paged_keys):
                    arrays.append(
                        (n, k, "kv",
                         np.asarray(self._page_store[j][page])))
                    if self._scale_store is not None:
                        arrays.append(
                            (n, k, "scale",
                             np.asarray(self._scale_store[j][page])))
                if store.publish(
                        digs[i],
                        parent=ROOT_DIGEST if i == 0 else digs[i - 1],
                        tokens=prompt[i * ps:(i + 1) * ps],
                        kv_dtype=self._kv_dtype, page_size=ps,
                        arrays=arrays):
                    out["published"] += 1
                    out["bytes"] += sum(a.nbytes for *_, a in arrays)
            return out

    def import_prefix_chain(self, prompt, start_block: int,
                            blocks) -> dict:
        """Map verified store entries (``PageStore.load`` results for
        `prompt`'s chain digests, starting at block index
        `start_block` — the first block NOT already cached locally)
        into the local pool + prefix cache. Each entry gets a fresh
        page written through the jitted single-page scatter (warmup
        precompiles it), then one ``PrefixCache.insert`` registers the
        whole run — after which an admission of this prompt takes a
        plain prefix hit and primes only the suffix, exactly as if the
        blocks had been primed here. Any shape/dtype/token mismatch
        stops the import at the blocks already validated (the suffix
        simply primes fresh — exactness never depends on the import).
        Returns ``{"blocks", "tokens", "bytes"}`` actually mapped."""
        with self._lock:
            out = {"blocks": 0, "tokens": 0, "bytes": 0}
            if (self._pool is None or self._prefix is None
                    or self._page_store is None
                    or self._brownout >= BROWNOUT_NO_PREFIX_INSERTS):
                return out
            ps = self._ps
            new_pages: List[int] = []
            for bi, entry in enumerate(blocks):
                b = start_block + bi
                lo, hi = b * ps, (b + 1) * ps
                if (entry.get("page_size") != ps or hi > len(prompt)
                        or list(entry.get("tokens", ())) !=
                        [int(t) for t in prompt[lo:hi]]):
                    break
                if not self._pool.free_count():
                    self._prefix.evict(1)
                try:
                    page = self._pool.alloc(1)[0]
                except Exception:   # noqa: BLE001 — PageExhausted et al
                    break
                arrs = {(n, k, role): a
                        for n, k, role, a in entry["arrays"]}
                writes = []
                ok = True
                for j, (n, k) in enumerate(self._paged_keys):
                    a = arrs.get((n, k, "kv"))
                    pool_j = self._page_store[j]
                    if (a is None
                            or tuple(a.shape) != tuple(pool_j.shape[1:])
                            or a.dtype != pool_j.dtype):
                        ok = False
                        break
                    writes.append((j, a, False))
                    if self._scale_store is not None:
                        sa = arrs.get((n, k, "scale"))
                        sp = self._scale_store[j]
                        if (sa is None
                                or tuple(sa.shape) != tuple(sp.shape[1:])
                                or sa.dtype != sp.dtype):
                            ok = False
                            break
                        writes.append((j, sa, True))
                if not ok:
                    self._pool.release(page)
                    break
                idx = jnp.asarray(page, jnp.int32)
                # import-time (per shipped block) uploads, not the
                # decode loop
                # tpulint: disable=device-transfer-in-hot-loop
                for j, a, is_scale in writes:
                    tgt = (self._scale_store if is_scale
                           else self._page_store)
                    tgt[j] = set_page(tgt[j], idx, jnp.asarray(a))
                    out["bytes"] += a.nbytes
                new_pages.append(page)
            if new_pages:
                covered = (start_block + len(new_pages)) * ps
                # [0]-padding for the already-held leading blocks: the
                # insert only reads table[i] for MISSING entries, and
                # blocks < start_block are present by construction
                self._prefix.insert(
                    [int(t) for t in prompt[:covered]],
                    [0] * start_block + new_pages)
                for p in new_pages:
                    self._pool.release(p)   # insert retained: the
                out["blocks"] = len(new_pages)  # cache is sole owner
                out["tokens"] = len(new_pages) * ps
                self._kv_traffic(out["tokens"] * self._tok_bytes)
            return out

    def prefill_publish(self, req: GenerationRequest, store) -> dict:
        """The PrefillAgent admission (serving/fleet/prefill.py): prime
        `req` through the normal admission path — prefix hits, the
        first-token draw, TTFT observation, prefix-cache insert all
        included — publish its full-block pages to `store`, then
        DETACH the slot instead of decoding. The prefix cache keeps the
        pages warm (and advertised); the returned record carries what
        the router needs to hand the stream to a decode replica:
        the drawn first token, the post-draw rng (the decode re-prime
        must not re-draw), the chain digests, and whether the request
        already finished (one-token requests never leave this engine).
        Raises on admission failure (no slot / prefill fault) — the
        agent nacks, the router degrades to unified placement."""
        with self._lock:
            if self._broken is not None:
                raise EngineShutdown("GenerationEngine is broken: "
                                     f"{self._broken!r}")
            if self._stop.is_set():
                raise EngineShutdown("GenerationEngine shut down")
            if self._draining:
                raise EngineShutdown("GenerationEngine draining — "
                                     "prefill elsewhere")
            now = time.monotonic()
            if self._fail_if_dead(req, now, "at prefill admission"):
                err = req.handle.error
                return {"done": True,
                        "reason": req.handle.finish_reason,
                        "error": None if err is None else repr(err),
                        "token": None, "rng": None, "digests": [],
                        "published": 0, "bytes": 0}
            free = (self._slots.index(None)
                    if None in self._slots else None)
            if free is None or (self._pool is not None
                                and not self._pages_admissible(req)):
                raise ServingOverloaded(
                    "prefill replica has no free slot/pages")
            self._admit_one(req, free, readmit=False)
            if req.handle.error is not None:
                raise req.handle.error
            pub = {"digests": [], "published": 0, "bytes": 0}
            if self._slots[free] is req:
                pub = self.export_prefix_chain(
                    req.prompt, self._page_tables[free]
                    if self._pool is not None else [], store)
                self._detach_slot(free)
            req.trace.record("prefill_publish",
                             engine=self.trace_identity,
                             blocks=len(pub["digests"]),
                             published=pub["published"])
            return {"done": req.handle.done,
                    "reason": req.handle.finish_reason,
                    "error": None,
                    "token": int(req.handle._ids[-1]),
                    "rng": rng_state_payload(req.rng),
                    "digests": pub["digests"],
                    "published": pub["published"],
                    "bytes": pub["bytes"]}

    def _detach_slot(self, slot: int) -> None:
        """Release one seated request WITHOUT a terminal event (the
        per-slot slice of ``detach_ledger``): the prefill flow seats,
        publishes, and lets the stream live on at a decode replica."""
        self._slots[slot] = None
        self._row_pos[slot] = 0
        if self._pool is not None:
            for p in self._page_tables[slot]:
                self._pool.release(p)
            self._page_tables[slot] = []
            self._invalidate_tables()
            self._kv_pos_dirty = True
        self._sync_accounting()

    def _named_layers(self):
        """(state name, layer) of every layer of the net: the index of a
        MultiLayerNetwork's layer, the vertex name of a graph's."""
        named = [(str(i), l) for i, l in
                 enumerate(getattr(self.net, "layers", None) or [])]
        vertices = getattr(getattr(self.net, "conf", None),
                           "vertices", None) or {}
        return named + [(n, v.layer) for n, v in vertices.items()
                        if getattr(v, "layer", None) is not None]

    def _declared_leaves(self):
        """(state name, PagedLeaf) of everything the net's streaming
        layers keep per token, by state name and, within a layer, in the
        layer's own order: the order of every per-leaf list here
        (``_paged_keys``, ``_page_store``, ``_paged_axes``)."""
        out = []
        for n, l in sorted(self._named_layers(), key=lambda nl: nl[0]):
            if getattr(l, "supports_streaming", False) \
                    and getattr(l, "cache_length", 0):
                out += [(n, leaf) for leaf in paged_leaves(l)]
        return out

    def _init_page_store(self, primed_state) -> None:
        """First-admission pool build: one device page array per leaf
        the layers declare (``paged_leaves()``: an attention layer's
        kv_k / kv_v ``[total_pages, Hkv, page_size, D]``, a
        latent-attention layer's three ``[total_pages, page_size, W]``),
        in the dtype the primed leaf came in."""
        dtypes = []
        for n, leaf in self._paged_decl:
            v = (primed_state.get(n) or {}).get(leaf.key)
            if v is None or tuple(v.shape) != leaf.shape(1, self._L):
                raise RuntimeError(
                    f"layer {n} declares the paged leaf {leaf.key} as "
                    f"{leaf.shape(1, self._L)}; its primed state holds "
                    f"{None if v is None else tuple(v.shape)}")
            dtypes.append(v.dtype)
        leaves = [leaf for _, leaf in self._paged_decl]
        self._paged_keys = [(n, leaf.key) for n, leaf in self._paged_decl]
        self._paged_axes = tuple(leaf.token_axis + 1 for leaf in leaves)
        self._page_store = allocate_pools(
            self._pool.total_pages, self._ps, leaves, dtypes)
        # per-token KV bytes summed over leaves — the unit of the
        # modeled kv-bytes-moved accounting
        self._tok_bytes = sum(leaf.token_elements * dt.itemsize
                              for leaf, dt in zip(leaves, dtypes))
        # tokens of each leaf ONE row's direct-xla dispatch reads: the
        # whole mapped view, unless the layer says it reads fewer (a
        # sparse layer gathers its selection)
        layers = dict(self._named_layers())
        self._xla_read_bytes = sum(
            getattr(layers[n], "paged_read_tokens",
                    dict)().get(leaf.key, self._L)
            * leaf.token_elements * dt.itemsize
            for (n, leaf), dt in zip(self._paged_decl, dtypes))

    def _paged_layer_dims(self):
        """(state name, Hkv, head_dim) per paged attention layer,
        sorted by state name — the SAME (name, leaf) order
        _init_page_store derives from a primed state (sorted() over
        the state keys), so the eager int8 store and the lazy bf16
        store address identical leaves."""
        out = []
        for n, l in self._named_layers():
            if getattr(l, "supports_streaming", False) \
                    and getattr(l, "cache_length", 0):
                hkv = getattr(l, "n_kv_heads", None) or l.n_heads
                out.append((n, int(hkv), int(l.head_width)))
        return sorted(out)

    def _init_quant_store(self) -> None:
        """Eager int8 pool + scale-sidecar build (serving/quant.py):
        zeroed [P, Hkv, ps, D] int8 pools and [P, Hkv] f32 scales, two
        leaves (k, v) per attention layer. Runs at construction and
        again after a quarantine rebuild dropped the old store."""
        from deeplearning4j_tpu.serving.quant import pool_leaves
        self._paged_keys = [(n, k) for n, _, _ in self._quant_dims
                            for k in ("kv_k", "kv_v")]
        self._paged_axes = (2,) * len(self._paged_keys)
        self._page_store, self._scale_store = pool_leaves(
            self._pool.total_pages, self._ps,
            [(h, d) for _, h, d in self._quant_dims])
        self._tok_bytes = sum(2 * h * d                  # int8: 1 B/el
                              for _, h, d in self._quant_dims)
        self._xla_read_bytes = self._L * self._tok_bytes
        self._scale_row_bytes = sum(2 * h * 4
                                    for _, h, _ in self._quant_dims)

    def _install_prime_paged_state(self, table, hit_len: int) -> None:
        """Arm the detached batch-1 prefill to run THROUGH the paged
        path (the int8 prime: quantize-once forbids priming densely
        and converting — the prompt's pool bytes must come from the
        same quantized append the decode steps run, so a rebuild's
        re-prime reproduces them bit-identically). Installs the whole
        pools + scale sidecars, the request's one-row table, kv_pos at
        the prefix hit length, and the ``kv_page_prime`` marker that
        forces the folded-gather read and unlocks packed (pad_left)
        accounting in ``_stream_attend_paged``. On a prefix hit the
        suffix prime attends the shared pages in place — no dense
        gather, no page re-scatter (``_install_prefix``'s dense view
        has no int8 equivalent)."""
        net = self.net
        row = np.zeros((1, self._n_max), np.int32)
        row[0, :len(table)] = table
        # admission-time (per-prime) uploads, not the decode loop
        # tpulint: disable=device-transfer-in-hot-loop
        row_dev = jnp.asarray(row)
        pos = jnp.full((1,), hit_len, jnp.int32)
        marker = jnp.zeros((), jnp.int32)
        st = dict(net.state)
        for i, (n, k) in enumerate(self._paged_keys):
            cur = st.get(n)
            d = dict(cur) if isinstance(cur, dict) else {}
            d[_page_key(k)] = self._page_store[i]
            d[_scale_key(k)] = self._scale_store[i]
            d["kv_page_table"] = row_dev
            d["kv_page_prime"] = marker
            d["kv_pos"] = pos
            st[n] = d
        net.state = st
        net._stream_pos = hit_len
        net._stream_pos_rows = None
        if self._graph_vertices:
            net._stream_pos_map = {n: hit_len
                                   for n in self._graph_vertices}

    def _extract_prime_paged_state(self, primed_state):
        """Take the primed pools/scales back out of the prime's state
        snapshot (they are the authoritative store now — prime
        dispatches do not donate, so on failure the engine's pre-prime
        references were still valid and nothing was committed).
        Returns the cleaned state the arena build/merge sees: paged
        view keys stripped, the [1] kv_pos vector kept for the slot
        scatter."""
        out = {n: (dict(v) if isinstance(v, dict) else v)
               for n, v in primed_state.items()}
        store, scales = [], []
        for n, k in self._paged_keys:
            d = out[n]
            store.append(d.pop(_page_key(k)))
            scales.append(d.pop(_scale_key(k)))
            d.pop("kv_page_table", None)
            d.pop("kv_page_prime", None)
        self._page_store = store
        self._scale_store = scales
        return out

    def _scatter_primed_pages(self, primed_state, table) -> None:
        """Commit the primed batch-1 KV into the slot's pages (one
        jitted scatter; shared prefix pages are rewritten with the
        identical bytes they were gathered from)."""
        row = np.zeros((1, self._n_max), np.int32)
        row[0, :len(table)] = table
        dense = [primed_state[n][k] for n, k in self._paged_keys]
        self._page_store = scatter_pages(self._page_store, dense, row,
                                         axes=self._paged_axes)
        self._kv_traffic(self._L * self._tok_bytes)   # one-row commit

    def _dispatch_step(self):
        """ONE jitted decode dispatch advancing every active slot (free
        rows feed token 0; their outputs are discarded, their writes
        drop), with the greedy selection queued behind it on the device.
        Returns ``(ids, probs)``: the [S] ids every cycle, the [S, V]
        block only when a seated request samples (else None — it stays
        on the device). Slots at streaming capacity retire first — they
        cannot consume another position."""
        if self._cap is not None:
            for s, req in enumerate(self._slots):
                if req is not None and self._row_pos[s] >= self._cap:
                    self._retire(s, "capacity")
        toks = np.zeros(self.slots, np.int64)
        live = drawn = 0
        for s, req in enumerate(self._slots):
            if req is not None:
                toks[s] = req.pending_token
                live += 1
                drawn += not selects_one(req.top_k)
        if not live:
            return None, None   # everything retired at the capacity guard
        self._sync_accounting()
        picked = self._run_dispatch(
            lambda: step_greedy(self.net, toks, self.V,
                                donate_state=self._donate,
                                io=self._io["decode"], block=drawn > 0))
        self._greedy_rows += live - drawn
        self._drawn_rows += drawn
        self._block_fetches += drawn > 0
        if self._host_counts:
            seated = [s for s, r in enumerate(self._slots) if r is not None]
            self._count_rows(self._row_pos[seated] + 1)
        for s, req in enumerate(self._slots):
            if req is not None:
                self._row_pos[s] += 1
        self._sync_accounting()
        return picked

    def _run_dispatch(self, fn, width: int = 1):
        """The ONE paged/chaos/retry wrapper around a decode or verify
        dispatch (`width` = appended positions per row: 1 plain,
        1 + gamma speculative), with the chaos hook INSIDE the retried
        callable (the fault fires before any state mutates, so a
        retried dispatch is numerically identical to a fault-free one).

        A paged engine reads install → dispatch → extract: the pool +
        cached page tables are installed into ``net.state`` as
        references — the dispatch itself reads K/V through the table and
        appends the new tokens' K/V in place (O(one-token) write);
        afterwards the updated pool references are extracted back.
        Nothing is materialized densely, nothing is scattered back.

        Every cycle lands in the dispatch-latency histogram and the
        modeled KV traffic in the kv-bytes-moved counter."""
        paged = self._pool is not None
        if paged:
            self._install_paged_state()

        def once():
            _fire_chaos(self._decode_chaos, self._dispatches)
            return fn()

        t0 = time.perf_counter()
        out = (retry_call(once, policy=self._decode_retry,
                          op="serving_decode")
               if self._decode_retry is not None else once())
        if paged:
            self._extract_paged_state()
        self.net.state = self._take_stats(self.net.state)
        dt = time.perf_counter() - t0
        self._dispatch_s_total += dt
        self._dispatch_hist.observe(dt)
        if paged:
            self._kv_traffic(self._kv_dispatch_bytes(width))
        self._dispatches += 1
        self._dispatch_rows += self.active_slots()
        return out

    # ------------------------------------------------------------------
    # the paged pool <-> dispatch plumbing + cached page tables
    # ------------------------------------------------------------------
    def _kernel_pages_per_step(self) -> int:
        """The G the paged-attention kernel resolves for this engine's
        pool (``paged_kernel.pages_per_step``); 0 while dispatches run
        off the kernel path."""
        if self._decode_impl != "pallas":
            return 0
        _, hkv, d = self._paged_layer_dims()[0]
        native = getattr(self.net.conf, "dtype", None) or "float32"
        return pages_per_step(
            (self._pool.total_pages, hkv, self._ps, d), self._n_max,
            1 if self._kv_dtype == "int8"
            else jnp.dtype(native).itemsize)

    def _invalidate_tables(self) -> None:
        """Drop the cached [S, n_max] table snapshots — call after ANY
        page-table mutation (admit / retire / rebuild). Between
        mutations every dispatch reuses the same host array and device
        upload(s): steady-state decode re-uploads nothing."""
        self._tables_cache = None
        self._tables_layer_cache = None

    def _tables_np(self) -> np.ndarray:
        if self._tables_cache is None:
            t = np.zeros((self.slots, self._n_max), np.int32)
            for s, pages in enumerate(self._page_tables):
                t[s, :len(pages)] = pages
            self._tables_cache = t
        return self._tables_cache

    def _tables_dev_per_layer(self):
        """Device table copies, one DISTINCT buffer per paged layer:
        the dispatch donates the whole state pytree on TPU, and
        donation must never see the same buffer at two leaves."""
        if self._tables_layer_cache is None:
            tnp = self._tables_np()
            self._tables_layer_cache = {
                n: jnp.asarray(tnp)
                for n in dict.fromkeys(n for n, _ in self._paged_keys)}
        return self._tables_layer_cache

    def _install_paged_state(self) -> None:
        """Install the paged decode view for the coming dispatch: each
        paged layer's state dict gains the pool pair + its page table
        (the paged state protocol —
        ``SelfAttentionLayer._stream_attend_paged``). Pure reference
        plumbing: no bytes move here, and the table device upload
        happens only on the first dispatch after a mutation."""
        tables = self._tables_dev_per_layer()
        st = dict(self.net.state)
        for i, ((n, k), pool) in enumerate(zip(self._paged_keys,
                                               self._page_store)):
            d = dict(st[n])
            d[_page_key(k)] = pool
            if self._scale_store is not None:
                d[_scale_key(k)] = self._scale_store[i]
            d["kv_page_table"] = tables[n]
            st[n] = d
        if self._kv_pos_dirty:
            # a retirement left free rows' device kv_pos coasting:
            # without a reset a once-long idle slot keeps its stale
            # length forever (the kernel would scan its dead blocks
            # every step, and the modeled bytes would drift from the
            # real reads). One tiny [S] where per layer, only on the
            # first dispatch after a retirement — free rows' appends
            # already route to the null page, so zeroing their
            # positions changes nothing any live request reads.
            # one-shot, not per-step: guarded by _kv_pos_dirty, which
            # only a retirement sets — steady-state installs skip this
            # tpulint: disable=device-transfer-in-hot-loop
            free = jnp.asarray([r is None for r in self._slots])
            for n in dict.fromkeys(n for n, _ in self._paged_keys):
                d = st[n]
                d["kv_pos"] = jnp.where(free, 0, d["kv_pos"])
            self._kv_pos_dirty = False
        self.net.state = st

    def _extract_paged_state(self) -> None:
        """Pull the (appended-to) pools back out of ``net.state`` after
        a dispatch, and refresh the per-layer table cache from
        the returned leaves — under donation the pre-dispatch buffers
        are consumed, so the returned references are the only live
        copies."""
        st = dict(self.net.state)
        names = list(dict.fromkeys(n for n, _ in self._paged_keys))
        for n in names:
            st[n] = dict(st[n])
        store = [st[n].pop(_page_key(k)) for n, k in self._paged_keys]
        if self._scale_store is not None:
            # under donation the returned scale leaves are likewise the
            # only live copies (base-token appends rewrite scale rows)
            self._scale_store = [st[n].pop(_scale_key(k))
                                 for n, k in self._paged_keys]
        tables = {n: st[n].pop("kv_page_table") for n in names}
        self._page_store = store
        if self._state_donated and self._donate:
            # donation consumed the installed buffers: the returned
            # (pass-through) table leaves are the only live copies
            self._tables_layer_cache = tables
        self.net.state = st

    # -- modeled KV traffic (serving/health.SERVING_KV_BYTES_MOVED) ----
    def _kv_traffic(self, nbytes: int) -> None:
        if nbytes:
            self._kv_bytes_total += int(nbytes)
            self._kv_bytes.inc(int(nbytes))

    def _kv_dispatch_bytes(self, width: int) -> int:
        """Bytes the KV path moves around ONE dispatch, modeled from
        the path in use (summed over attention leaves; reads + writes):

        - direct-xla: the folded gather still materializes the mapped
          [S, L] view once inside the dispatch (S·L reads; of a leaf
          whose layer gathers a selection, ``paged_read_tokens()``
          positions a row), but the write is the one-token append
          (S·width).
        - direct-pallas: only LIVE pages are read (the kernel copies
          the pages that hold a row's keys and no others, however many
          table entries a grid step covers) — sum of each active row's
          page-rounded context — plus the append.

        int8 adds the scale-sidecar reads (one f32 row per page per
        leaf): the xla gather folds the whole ``scales[table]`` view
        (S·n_max rows), the kernel prefetches one row per live page.
        Tiny next to the halved pool bytes — but the model is exact,
        so the test pins both terms.
        """
        if self._tok_bytes == 0:
            return 0
        S, L, ps = self.slots, self._L, self._ps
        append = S * width * self._tok_bytes
        if self._decode_impl == "pallas":
            live = sum(
                min(-(-int(self._row_pos[s] + width) // ps) * ps, L)
                for s, r in enumerate(self._slots) if r is not None)
            return (live * self._tok_bytes + append
                    + (live // ps) * self._scale_row_bytes)
        return (S * self._xla_read_bytes + append
                + S * self._n_max * self._scale_row_bytes)

    def _retire(self, slot: int, reason: str,
                exc: Optional[BaseException] = None) -> None:
        """Free `slot` immediately — host bookkeeping only, no device
        op: the row's stale cache is invisible (its writes drop, its
        output is discarded) until the next admission overwrites it."""
        req = self._slots[slot]
        self._slots[slot] = None
        self._row_pos[slot] = 0
        self._retirements += 1
        if self._pool is not None:
            # pages return to the pool immediately; blocks the prefix
            # cache also references stay resident at the cache's own
            # refcount, warm for the next request sharing them
            for p in self._page_tables[slot]:
                self._pool.release(p)
            self._page_tables[slot] = []
            self._invalidate_tables()
            self._kv_pos_dirty = True
        if exc is not None:
            req.handle._fail(exc, reason)
        else:
            req.handle._finish(reason)
        self._recent_traces.append(req.trace)

    # ------------------------------------------------------------------
    # arena state plumbing
    # ------------------------------------------------------------------
    def _build_arena(self, primed_state, base_state):
        """First-admission skeleton: every stream key of the primed
        structure broadcast to S zeroed rows (kv_abs rows start -1 =
        empty, matching a fresh rolling cache), per-row kv_pos vector at
        0. Free rows are inert: nothing reads them until a scatter
        overwrites them.

        A paged engine drops the dense kv_k/kv_v leaves entirely: the
        pool is the only KV storage (no [S, Hkv, L, D] arena copy
        exists to allocate, gather into, or scatter from); the
        per-dispatch paged view rides in via _install_paged_state
        instead."""
        S = self.slots
        paged = set(self._paged_keys or ())
        arena = {}
        for name, s in primed_state.items():
            if not isinstance(s, dict):
                arena[name] = s
                continue
            if "kv_mask" in s:
                raise RuntimeError(
                    "engine prefill must be maskless (packed padded "
                    "priming) — a kv_mask in the primed state means the "
                    "stream was primed with an explicit mask")
            d = dict(base_state.get(name, {}) if isinstance(
                base_state.get(name), dict) else {})
            d.update({k: v for k, v in s.items()
                      if k not in _SCATTER_KEYS})
            for k, v in s.items():
                if k not in _SCATTER_KEYS:
                    continue
                if (name, k) in paged:
                    continue        # the page pool IS the KV storage
                # admission-time arena construction (slot lifecycle),
                # not the per-token decode steady state
                # tpulint: disable=device-transfer-in-hot-loop
                v = jnp.asarray(v)
                if k == "kv_pos":
                    d[k] = jnp.zeros((S,), v.dtype)
                elif k == "kv_abs":
                    d[k] = jnp.full((S,) + v.shape, -1, v.dtype)
                else:                      # batch-leading cache/state
                    d[k] = jnp.zeros((S,) + v.shape[1:], v.dtype)
            arena[name] = d
        return arena

    def _merge(self, arena_state, primed_state, slot: int):
        if self._merge_keys is None:
            # paged leaves join through the page scatter, not the dense
            # arena (the pool is their only storage)
            excl = set(self._paged_keys or ())
            self._merge_keys = [
                (n, k) for n in sorted(primed_state)
                if isinstance(primed_state[n], dict)
                for k in sorted(primed_state[n])
                if k in _SCATTER_KEYS and (n, k) not in excl]
        arena_leaves = [arena_state[n][k] for n, k in self._merge_keys]
        primed_leaves = [primed_state[n][k] for n, k in self._merge_keys]
        scatter = (_scatter_rows_donated if self._state_donated
                   else _scatter_rows)
        new_leaves = scatter(arena_leaves, primed_leaves, np.int32(slot))
        out = {n: (dict(v) if isinstance(v, dict) else v)
               for n, v in arena_state.items()}
        for (n, k), leaf in zip(self._merge_keys, new_leaves):
            out[n][k] = leaf
        return out

    @staticmethod
    def _net_pos(net) -> int:
        pm = getattr(net, "_stream_pos_map", None)
        if pm:
            return int(max(pm.values()))
        return int(getattr(net, "_stream_pos", 0) or 0)

    def _save_accounting(self):
        net = self.net
        pm = getattr(net, "_stream_pos_map", None)
        return (getattr(net, "_stream_pos", 0),
                getattr(net, "_stream_pos_rows", None),
                dict(pm) if pm is not None else None)

    def _restore_accounting(self, saved) -> None:
        pos, rows, pmap = saved
        net = self.net
        net._stream_pos = pos
        net._stream_pos_rows = rows
        if pmap is not None:
            net._stream_pos_map = pmap

    def _sync_accounting(self) -> None:
        """Engine-owned host position mirrors: active rows carry their
        true positions, free rows pin to 0 so an idle slot can never
        trip the stream-budget guard while its device-side counter
        coasts (those writes drop harmlessly)."""
        net = self.net
        mask = np.array([r is not None for r in self._slots])
        rows = np.where(mask, self._row_pos, 0).astype(np.int64)
        pos = int(rows.max()) if mask.any() else 0
        net._stream_pos = pos
        net._stream_pos_rows = rows
        if self._graph_vertices:
            net._stream_pos_map = {n: pos for n in self._graph_vertices}

    # ------------------------------------------------------------------
    # warmup
    # ------------------------------------------------------------------
    def warmup(self, max_prompt_len: Optional[int] = None,
               steps: int = 2) -> "GenerationEngine":
        """Compile every canonical serving shape before traffic: one
        synthetic greedy request per power-of-two prime bucket up to
        bucket(max_prompt_len) (default: the net's streaming capacity),
        driven to completion. Warms the per-bucket prefill, the
        scatter-join, and the [S, V, 1] decode dispatch, so staggered
        admissions of ANY prompt length <= max_prompt_len afterwards
        cause zero retraces (the PR 3 acceptance bar)."""
        if self._worker is not None and self._worker.is_alive():
            raise RuntimeError("warm up before start(): warmup drives "
                               "step() manually")
        cap = self._cap
        top = max_prompt_len
        if top is None:
            top = (cap - 1) if cap is not None else 64
        top = max(1, int(top))
        lens, n = [], 1
        while n <= top:
            lens.append(n)
            n *= 2
        if top not in lens:
            lens.append(top)      # a non-pow2 top primes at bucket(top)
        if cap is not None:
            lens = sorted({min(v, cap - 1) for v in lens})
        if self._speculation is not None and cap is not None:
            room = cap - self._speculation.gamma + 1 - steps
            lens = sorted({max(1, min(v, room)) for v in lens})
        tok = 1 if self.V > 1 else 0

        def drive(prompt):
            # drain per request: warmup must not depend on queue_limit
            # headroom (block policy would deadlock, fail_fast would
            # reject, with more buckets than queue slots)
            h = self.submit(prompt, steps=steps, top_k=1,
                            rng=np.random.default_rng(0))
            self.run_until_idle()
            h.result(timeout=0)

        # fresh pass: every prime bucket from an empty stream. The
        # prefix cache is bypassed so one bucket's blocks cannot short-
        # circuit a longer bucket's fresh-prime shape out of the warm set
        prefix, self._prefix = self._prefix, None
        try:
            for v in lens:
                drive([tok] * v)
        finally:
            self._prefix = prefix
        top = max(lens)        # post-clamp envelope (capacity, spec)
        if prefix is not None and top > self._ps:
            # prefix pass: warm the hit path — the [1, n_max] page
            # gather plus every WITH-PREFIX suffix-prime bucket a
            # cached-hit admission can reach. Seed one base block
            # (token 0 — disjoint from the fresh pass), then hit it
            # with suffixes covering each bucket; suffix leads cycle
            # the vocab so iterations don't chain-hit each other.
            ps = self._ps
            room = top - ps
            sfx, n = [], 1
            while n <= room:
                sfx.append(n)
                n *= 2
            if room not in sfx:
                sfx.append(room)
            drive([0] * (ps + 1))          # seed: caches the base block
            for j, b in enumerate(sorted(set(sfx))):
                lead = 1 + j % (self.V - 1) if self.V > 1 else 0
                drive([0] * ps + [lead] * b)
        if self._pool is not None and self._page_store is not None:
            # precompile the fleet page-ship seam for every pool leaf
            # by round-tripping the null page (zeros out, zeros back):
            # the export-side one-page gather and the import-side
            # jitted single-page scatter (paging.set_page) both land in
            # the compile cache here, so a later store import/publish
            # causes zero retraces — page-import admissions stay under
            # the same zero-retrace pin as everything else
            idx = jnp.asarray(0, jnp.int32)
            stores = [self._page_store]
            if self._scale_store is not None:
                stores.append(self._scale_store)
            for pools in stores:
                for j, pool in enumerate(pools):
                    z = np.zeros_like(np.asarray(pool[0]))
                    pools[j] = set_page(pool, idx, jnp.asarray(z))
        if self._overload is not None:
            # warmup TTFTs carry compile time — real traffic must not
            # inherit them as breach evidence or an admission rate
            self._overload.reset_observations()
        return self

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def start(self) -> "GenerationEngine":
        """Run the dispatch loop on a background thread (the serving
        deployment shape; manual ``step()`` still works for warmup)."""
        if self._stop.is_set():
            raise EngineShutdown("GenerationEngine shut down")
        if self._worker is not None and self._worker.is_alive():
            return self
        self._worker = threading.Thread(target=self._engine_loop,
                                        daemon=True)
        if self._started_at is None:
            self._started_at = time.perf_counter()
        self._worker.start()
        return self

    def _engine_loop(self):
        retired = self._retirements
        try:
            while not self._stop.is_set():
                if not self.step():
                    if self._draining:
                        # the queue is closed while draining: wait()
                        # would return immediately and busy-spin
                        time.sleep(0.02)
                    else:
                        self._pending.wait(0.02)
                elif self._retirements != retired:
                    retired = self._retirements
                    if not self._draining:
                        self._pending.wait(HANDOFF_WAIT_S)
        except Exception as e:  # noqa: BLE001 — strand no waiters
            log.exception("GenerationEngine loop died")
            self._break(e)

    def _flight_traces(self) -> list:
        """The flight recorder's request context: in-flight traces
        (slots + the pop-to-seat window) first, then recently retired
        ones — newest history the post-mortem most wants."""
        traces = [r.trace for r in self._slots if r is not None]
        if self._seating is not None:
            traces.append(self._seating.trace)
        traces.extend(reversed(self._recent_traces))
        return traces

    def _break(self, exc: BaseException) -> None:
        """Terminal failure: fail every in-flight and queued request
        with the original error and refuse new work. A broken arena is
        not resumable (the failed dispatch may or may not have consumed
        positions). With a supervisor this is the ESCALATION state —
        recovery already declined (budget exhausted / rebuild failed)."""
        with self._lock:
            self._broken = exc
            # stop the loop too: with the queue closed, wait() returns
            # immediately — a broken engine must park, not busy-spin
            self._stop.set()
            self._emit_serving_event("break", error=repr(exc))
            # post-mortem artifact BEFORE the handles are failed and
            # the queue drained — the bundle must show the state the
            # fault found, not the rubble _break leaves. Best-effort
            # and rate-limited inside maybe_dump.
            flightrecorder.maybe_dump(
                "engine_break", error=exc, health=self.health(),
                queue=self._pending.snapshot(),
                traces=self._flight_traces())
            if self._seating is not None:
                # popped but never seated: fail it here or nobody will
                req, self._seating = self._seating, None
                if not req.handle.done:
                    req.handle._fail(exc)
            for s, req in enumerate(self._slots):
                if req is not None:
                    self._retire(s, "error", exc)
            for req in self._pending.close():
                req.handle._fail(exc)

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Stop admission and finish the actives: the clean handoff
        point for a planned restart (config rollout, re-shard, binary
        upgrade). New submits are refused (``EngineShutdown``), queued
        never-prefilled requests fail immediately with the same (their
        callers resubmit to the replacement instance — cheaper than
        making them wait out a drain they cannot benefit from), and
        every ACTIVE request runs to its natural retirement: work
        already prefilled is work worth finishing.

        Works under the background loop (waits for it to finish the
        actives) or in manual mode (drives ``step()`` itself). Returns
        True when the arena emptied within `timeout` (None = wait
        forever); False on timeout or a broken/shut-down engine — the
        handoff then needs the supervisor's escalation story, not a
        clean restart."""
        self._draining = True
        self._emit_serving_event("drain")
        for req in self._pending.close():
            req.handle._fail(EngineShutdown(
                "GenerationEngine draining — resubmit to the "
                "replacement instance"))
        deadline = None if timeout is None else \
            time.monotonic() + float(timeout)
        threaded = self._worker is not None and self._worker.is_alive()
        while self.active_slots() > 0 and self._broken is None \
                and not self._stop.is_set():
            if deadline is not None and time.monotonic() >= deadline:
                break
            if threaded:
                time.sleep(0.005)
            elif not self.step():
                break
        return self.active_slots() == 0 and self._broken is None \
            and not self._stop.is_set()

    def shutdown(self) -> None:
        """Stop the loop and fail everything still in flight — nobody
        blocks forever on a dead server (the ParallelInference
        contract). Idempotent."""
        self._stop.set()
        for req in self._pending.close():
            req.handle._fail(EngineShutdown("GenerationEngine shut down"))
        if self._worker is not None and self._worker.is_alive():
            self._worker.join(timeout=5.0)
        with self._lock:
            if self._seating is not None:
                req, self._seating = self._seating, None
                if not req.handle.done:
                    req.handle._fail(EngineShutdown(
                        "GenerationEngine shut down"))
            for s, req in enumerate(self._slots):
                if req is not None:
                    self._retire(s, "error", EngineShutdown(
                        "GenerationEngine shut down"))
