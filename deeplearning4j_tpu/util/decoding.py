"""Decoding strategies over the streaming rnn_time_step machinery.

Model-agnostic: works for ANY network whose rnn_time_step carries
batch-leading streaming state — LSTM h/c (the reference's
rnnTimeStep-based generation, MultiLayerNetwork.java rnnTimeStep) and
attention KV caches alike. Beams ride the batch dimension; pruning
gathers the carried state with reorder_stream_state so surviving beams
continue from their parent's caches.

The greedy rule. A row whose filter leaves one token (``top_k == 1``;
temperature is monotone and ``top_p`` keeps the first of any sorted
distribution, so neither changes which entry that is) takes the
LOWEST-INDEX MAXIMUM of the distribution the program returned and
consumes no random numbers: ``selects_one`` is the test, ``draw``
answers it with ``np.argmax``, ``filter_probs`` with that one-hot, and
``step_greedy`` with ``jnp.argmax`` over the same float32 array on the
device (``greedy_ids``) — the same index on the same values, so the
one-shot decoders, the speculative acceptance walk and the serving
engine agree token for token, exact ties included. Nothing a greedy row
emits depends on its Generator, whose state stays what the caller
handed in.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.layers import (
    last_position, reorder_stream_state)


def _one_hot(rows: np.ndarray, vocab: int) -> np.ndarray:
    rows = np.asarray(rows)
    b, t = rows.shape
    x = np.zeros((b, vocab, t), np.float32)
    x[np.arange(b)[:, None], rows, np.arange(t)[None, :]] = 1.0
    return x


def takes_ids(net) -> bool:
    """Whether `net` is fed token ids ``[B, T]`` (a layer of it says
    ``takes_ids``: ``SequenceEmbeddingLayer``, ``TokenProjectionLayer`` —
    so every zoo transformer) and no one-hot ``[B, V, T]`` (a recurrent
    or imported net whose first layer reads a float sequence). Asked of
    the net, once; no caller passes an option."""
    known = getattr(net, "_takes_ids", None)
    if known is None:
        known = net._takes_ids = any(
            getattr(l, "takes_ids", False) for l in _stream_layers(net))
    return known


def _encode(net, rows, vocab: int) -> np.ndarray:
    """`rows` [B, T] of token ids as `net` wants them: int32 ids for a
    net that ``takes_ids`` (4 bytes a token up), else the float32 one-hot
    ``[B, V, T]``."""
    if takes_ids(net):
        return np.asarray(rows, np.int32)
    return _one_hot(rows, vocab)


def _last(p):
    """The last position's ``[B, V]`` of a streaming call's output: the
    ``[B, V]`` of a call that answered for the last position only (the
    caller asked, ``rnn_time_step(last_only=True)``, or the head is a
    ``LastStepOutputLayer``) as it is, and the last column of a full
    ``[B, V, T]`` (``layers.last_position``, which the nets apply
    inside the program where the caller asked)."""
    return last_position(p)


class RoundTrip:
    """What a caller may watch of one host round trip through
    ``rnn_time_step``: its three steps — ``"input"`` built on the host,
    ``"forward"`` dispatched, the result's ``"fetch"`` — and the numpy
    arrays that cross (a prime's result is ``[1, V]``: ``prime_prompt``
    asks for the last position only). This one watches nothing. The
    serving engine hands in its own as `io` (``serving.engine._HostIO``:
    a phase span per step, the bytes counted, and for a prime the
    positions its result held) for the one dispatch its cycle makes, on
    the cycling thread; any other caller on that thread (a draft net)
    passes none and stays inside the phase that is open."""

    def step(self, name: str) -> None:
        pass

    def h2d(self, x: np.ndarray) -> None:
        pass

    def d2h(self, p: np.ndarray) -> None:
        pass


_UNWATCHED = RoundTrip()


def _probs(out, io: RoundTrip = _UNWATCHED) -> np.ndarray:
    p = np.asarray(out[0] if isinstance(out, (list, tuple)) else out)
    io.d2h(p)
    return p


def _forward(net, x, io: RoundTrip, **kw):
    """``net.rnn_time_step(x)`` on a host-built input: ``io.h2d(x)`` sees
    the numpy array handed to the device here, ``io.d2h(p)`` (in
    ``_probs``) the numpy array that comes back."""
    io.step("forward")
    io.h2d(x)
    return net.rnn_time_step(x, **kw)


def filter_probs(probs, temperature,
                 top_k=None, top_p=None) -> np.ndarray:
    """The sampling distribution actually drawn from: temperature
    rescales first, then `top_k` keeps exactly the k most probable
    tokens (k = 1: the lowest-index maximum of `probs`, the module's
    greedy rule), then `top_p` (nucleus) keeps the smallest prefix of the
    sorted distribution whose mass reaches p (always at least one
    token); survivors renormalize. Shared by draw() and the
    speculative-decoding acceptance rule (which needs the filtered
    distributions themselves, not just a sample).

    `probs` is one row [V] or a batch [B, V]. For a batch,
    `temperature`/`top_k`/`top_p` may each be a scalar (shared) or a
    [B] array (PER-ROW — one serving arena can hold requests with
    mixed sampling configs). Per-row `top_k`/`top_p` entries <= 0
    disable that filter for that row; per-row temperature entries must
    be positive. The single-row form is the batch form at B=1, so
    batched filtering is row-for-row identical to the scalar path
    (test-pinned)."""
    probs = np.asarray(probs)
    if probs.ndim == 1:
        return _filter_rows(probs[None, :], temperature, top_k, top_p)[0]
    if probs.ndim != 2:
        raise ValueError(f"probs must be [V] or [B, V], got shape "
                         f"{probs.shape}")
    return _filter_rows(probs, temperature, top_k, top_p)


def _row_array(v, B: int, name: str):
    """Validate a scalar-or-[B] sampling parameter; returns (array or
    None, is_per_row)."""
    if v is None:
        return None, False
    a = np.asarray(v)
    if a.ndim == 0:
        return a, False
    if a.shape != (B,):
        raise ValueError(f"{name} must be a scalar or one value per row "
                         f"({a.shape} != ({B},))")
    return a, True


def _filter_rows(p2, temperature, top_k, top_p):
    """Vectorized filter over [B, V] rows (see filter_probs)."""
    B, V = p2.shape
    logits = np.log(np.clip(p2, 1e-9, None))
    t, t_rows = _row_array(temperature, B, "temperature")
    if t_rows:
        if (np.asarray(t) <= 0).any():
            raise ValueError("per-row temperature entries must be > 0")
        logits = logits / t.astype(logits.dtype)[:, None]
    else:
        logits = logits / t.astype(logits.dtype)
    p = np.exp(logits - logits.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    if top_k is not None:
        k, k_rows = _row_array(top_k, B, "top_k")
        if not k_rows and int(k) < 1:
            raise ValueError(f"top_k must be >= 1, got {int(k)}")
        krow = (np.where(k > 0, k, V) if k_rows
                else np.full(B, int(k))).astype(np.int64)
        row_on = krow < V
        if row_on.any():
            # exactly k indices per row (a value threshold would keep
            # every token TIED with the kth — e.g. a clipped flat tail —
            # and sample the whole vocab precisely when users reach for
            # top_k). This runs once per sampled token on the serving
            # hot path, so stay O(V): partition out the top kmax
            # candidates, sort only that slice, then cut each row at its
            # own k. Off rows bypass bit-exactly: keep all, divide by 1.
            one = krow == 1
            keep = np.zeros((B, V), bool)
            many = row_on & ~one
            if many.any():
                kmax = int(krow[many].max())
                part = np.argpartition(p, V - kmax, axis=-1)[:, V - kmax:]
                vals = np.take_along_axis(p, part, axis=-1)
                order = np.take_along_axis(
                    part, np.argsort(vals, axis=-1)[:, ::-1], axis=-1)
                np.put_along_axis(
                    keep, order,
                    np.arange(kmax)[None, :] < krow[:, None], axis=-1)
            if one.any():
                # the greedy rule (module docstring): the lowest-index
                # maximum of the distribution as handed in — the rescaled
                # p may round neighbours into a tie, and argpartition's
                # pick among ties is not the first
                keep[one] = False
                keep[one, p2[one].argmax(axis=-1)] = True
            keep |= ~row_on[:, None]
            p = np.where(keep, p, 0.0)
            denom = np.where(row_on, p.sum(axis=-1), 1.0)
            p = p / denom[:, None]
    if top_p is not None:
        tp, tp_rows = _row_array(top_p, B, "top_p")
        # host numpy over [B] thresholds (this module's one device
        # program is greedy_ids); f64 so 1.0 compares exactly
        # tpulint: disable=dtype-promotion
        tp = np.asarray(tp, np.float64)
        if tp_rows:
            if (tp > 1.0).any():
                raise ValueError(f"top_p entries must be <= 1, got "
                                 f"{tp.max()}")
            row_on = tp > 0                           # <= 0: filter off
            prow = np.where(row_on, tp, 1.0)
        else:
            if not 0.0 < float(tp) <= 1.0:
                raise ValueError(f"top_p must be in (0, 1], got "
                                 f"{float(tp)}")
            row_on = np.ones(B, bool)
            prow = np.full(B, float(tp))
        if row_on.any():
            order = np.argsort(p, axis=-1)[:, ::-1]
            ps = np.take_along_axis(p, order, axis=-1)
            csum = np.cumsum(ps, axis=-1)
            # keep the smallest prefix whose mass reaches p, never
            # empty: a sorted token survives iff the mass STRICTLY
            # BEFORE it is under top_p (the exact searchsorted-left
            # rule, shifted-cumsum form). Off rows bypass bit-exactly.
            before = np.concatenate(
                [np.zeros((B, 1), csum.dtype), csum[:, :-1]], axis=1)
            keep = np.zeros((B, V), bool)
            np.put_along_axis(keep, order, before < prow[:, None],
                              axis=-1)
            keep |= ~row_on[:, None]
            p = np.where(keep, p, 0.0)
            denom = np.where(row_on, p.sum(axis=-1), 1.0)
            p = p / denom[:, None]
    return p


def per_row_param(v, b: int):
    """Row `b`'s value of a scalar-or-per-row `top_k`/`top_p` parameter
    (per-row array entries <= 0 mean the filter is off for that row —
    returned as None, the scalar-API spelling of off)."""
    if v is None:
        return None
    a = np.asarray(v)
    if a.ndim == 0:
        return v
    x = a[b]
    if x <= 0:
        return None
    return int(x) if np.issubdtype(a.dtype, np.integer) else float(x)


def selects_one(top_k) -> bool:
    """Whether a row with this scalar `top_k` is greedy: its filter
    leaves one token, whatever its temperature and `top_p` (the module's
    greedy rule). What the serving engine asks of each request to decide
    whether the cycle needs the ``[S, V]`` block on the host at all."""
    return top_k is not None and np.ndim(top_k) == 0 and int(top_k) == 1


class ArgmaxRow:
    """A row of width `n` of which only the lowest-index maximum is
    known: what ``greedy_ids`` brings back in place of the ``[V]``
    distribution. ``draw`` returns ``first`` for it, so a caller whose
    argmax ran on the device (the serving engine) still takes every
    row's token from ``draw``; only a greedy row (``selects_one``) can
    be answered from one."""

    __slots__ = ("first", "n")

    def __init__(self, first: int, n: int):
        self.first, self.n = int(first), int(n)

    def __len__(self) -> int:
        return self.n


def draw(probs, temperature, rng,
         top_k=None, top_p=None):
    """Sample token ids from softmax distributions (the single draw
    implementation shared by every sampler); see filter_probs for the
    temperature/top_k/top_p semantics (incl. the per-row array forms).

    top_k=1 is greedy decoding regardless of temperature and top_p, by
    the module's greedy rule: the row takes ``np.argmax(probs)`` — the
    lowest index among exact ties — and its Generator is NOT consumed
    (its ``bit_generator.state`` is unchanged on return; `temperature`
    and `top_p` are not read). This replaced, in PR 27, the filter's
    answer for such rows (``argpartition``'s pick among exact ties, one
    ``rng.choice`` per token). Every other row draws exactly as before.
    A greedy row may be handed as an ``ArgmaxRow`` where the argmax was
    already taken on the device.

    One row [V] returns an int. A batch [B, V] returns a list of ints;
    `rng` is then either one Generator (consumed row-major, greedy rows
    skipped) or a sequence of one Generator per row — independent
    per-request streams. (The serving engine itself draws row-by-row
    through the single-row form so each request's rng consumption is
    positionally identical to its one-shot sample_stream run; both forms
    share ONE filter kernel, `_filter_rows`.)"""
    if isinstance(probs, ArgmaxRow):
        if not selects_one(top_k):
            raise ValueError("only a top_k=1 row can be drawn from its "
                             "argmax alone")
        return probs.first
    probs = np.asarray(probs)
    if probs.ndim == 2:
        B, V = probs.shape
        rngs = (list(rng) if isinstance(rng, (list, tuple))
                else [rng] * B)
        if len(rngs) != B:
            raise ValueError(f"need one rng per row "
                             f"({len(rngs)} != {B})")
        k, k_rows = _row_array(top_k, B, "top_k")
        one = (np.asarray(k) == 1 if k_rows
               else np.full(B, selects_one(top_k)))
        first = probs.argmax(axis=-1) if one.any() else None
        p = (None if one.all()
             else filter_probs(probs, temperature, top_k, top_p))
        return [int(first[b]) if one[b] else int(rngs[b].choice(V, p=p[b]))
                for b in range(B)]
    if selects_one(top_k):
        return int(np.argmax(probs))
    p = filter_probs(probs, temperature, top_k, top_p)
    return int(rng.choice(len(p), p=p))


def _check_seed(seed_ids, steps, max_length):
    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    if len(seed_ids) == 0:
        raise ValueError("seed_ids must contain at least one token")
    if max_length is not None and len(seed_ids) >= max_length:
        raise ValueError(f"seed of {len(seed_ids)} tokens leaves no room "
                         f"under max_length {max_length}")


#: largest priming chunk; every prompt decomposes into descending
#: powers of two <= this, so ALL prompt lengths share at most
#: log2(PRIME_CHUNK_MAX)+1 distinct jit shapes (vs one trace per length)
PRIME_CHUNK_MAX = 64


def set_prime_chunk_max(n: int) -> None:
    """Raise (or lower) the largest priming chunk. Long-prompt serving
    wants this high — a 1000-token prompt primes in 6 dispatches at 1024
    vs 17 at the default 64 — at the cost of one extra compile per new
    power-of-two shape the deployment actually sees. Exactness is
    unaffected: chunks are exact prompt slices (never padded), and
    stateful streaming makes any chunking == one-shot priming."""
    if n < 1 or (n & (n - 1)) != 0:
        raise ValueError(f"prime chunk max must be a power of two, got {n}")
    global PRIME_CHUNK_MAX
    PRIME_CHUNK_MAX = n


def _prime_chunks(n: int, chunk_max: int = None):
    """Greedy power-of-two decomposition of a prompt length, largest
    chunk first (serving-friendly: a new prompt length never costs a new
    compile once the shared chunk shapes are warm)."""
    out = []
    c = chunk_max or PRIME_CHUNK_MAX
    if c < 1 or (c & (c - 1)) != 0:
        raise ValueError(f"prime chunk max must be a power of two, got {c}")
    while n > 0:
        while c > n:
            c //= 2
        out.append(c)
        n -= c
    return out


def _prime(net, ids, vocab: int, chunk_max: int = None,
           io: RoundTrip = _UNWATCHED, last_only: bool = False):
    """Feed the seed through rnn_time_step in bucketed chunks; returns
    the final chunk's output (its last position is the next-token
    distribution). Stateful streaming makes chunked == one-shot priming
    (pinned by the streaming-vs-full-forward tests). `io` sees input
    and forward once per chunk. `last_only` is rnn_time_step's, asked of
    every chunk (one program a chunk shape; an earlier chunk's answer is
    read by nobody)."""
    at, out = 0, None
    for c in _prime_chunks(len(ids), chunk_max):
        io.step("input")
        x = _encode(net, np.asarray(ids[at:at + c])[None, :], vocab)
        out = _forward(net, x, io, last_only=last_only)
        at += c
    return out


def _width_bucket(w: int) -> int:
    """Round up to the next power of two — jit shapes are per-bucket,
    not per-value (beam widths for the decode step; prompt lengths for
    the padded prime)."""
    b = 1
    while b < w:
        b *= 2
    return b


def _stream_layers(net):
    """Every layer of `net` that may carry streaming state: the layer
    list of a MultiLayerNetwork, or the vertex-wrapped layers of a
    ComputationGraph."""
    for l in getattr(net, "layers", None) or []:
        yield l
    vertices = getattr(getattr(net, "conf", None), "vertices", None) or {}
    for v in vertices.values():
        l = getattr(v, "layer", None)
        if l is not None:
            yield l


def _prime_bucket_cap(net):
    """Largest safe padded-prime bucket: the smallest streaming capacity
    over the net's layers, counting a windowed (rolling-cache) layer's
    cache_length too — its FRESH priming chunk must fit the cache even
    though its stream is otherwise unbounded. None = uncapped (no
    capacity-bearing layers)."""
    cap = None
    for l in _stream_layers(net):
        if not getattr(l, "supports_streaming", False):
            continue
        for a in ("max_length", "cache_length"):
            v = getattr(l, a, 0)
            if v:
                cap = v if cap is None else min(cap, v)
    return cap


def _prime_padded(net, ids, vocab: int, chunk_max: int = None,
                  io: RoundTrip = _UNWATCHED, last_only: bool = False):
    """Single-dispatch priming: LEFT-pad the prompt to its power-of-two
    bucket and feed ONE rnn_time_step(pad_left=...) with packed pad
    accounting — pads never enter the streaming caches nor consume
    positions, so results are identical to chunked priming while every
    prompt length shares at most log2(max bucket) jit shapes and exactly
    one dispatch. The bucket is capped at the net's smallest streaming
    capacity (padding past it would trip static capacity checks); a
    prompt longer than that capacity — legal for rolling-window streams,
    whose length is unbounded — falls back to chunked priming, which has
    no minimum chunk shape. `last_only` is rnn_time_step's."""
    io.step("input")
    L = len(ids)
    P = _width_bucket(L)
    cap = _prime_bucket_cap(net)
    if cap is not None and P > cap:
        if cap < L:            # no padded bucket can hold this prompt
            return _prime(net, ids, vocab, chunk_max, io, last_only)
        P = cap                # pad exactly to capacity: still one shape
    pad = P - L
    x = _encode(net, np.asarray([0] * pad + list(ids))[None, :], vocab)
    if x.ndim == 3:
        x[:, :, :pad] = 0.0   # pads carry no token (masked anyway)
    return _forward(net, x, io, pad_left=pad, last_only=last_only)


def prime_prompt(net, ids, vocab_size: int, padded: bool = False,
                 chunk_max: Optional[int] = None,
                 io: RoundTrip = _UNWATCHED) -> np.ndarray:
    """Prefill: feed the whole prompt through the carried streaming
    state and return the next-token distribution [V]. `padded=True`
    primes in ONE left-padded bucketed dispatch (_prime_padded);
    otherwise chunked priming (_prime) — exactness is identical, pinned
    by the padded-prime tests. A prime reads the last position of what
    it fed and nothing else, and says so to every streaming call it
    makes (``rnn_time_step(last_only=True)``): the head answers for, and
    the host fetches, ``[1, V]`` — at a vocabulary of 49,152 a 4,096
    bucket's full float32 answer would be 805 MB to read 197 KB of. Does
    NOT clear previous state: the
    caller owns the stream lifecycle (sample_stream clears first; the
    serving engine primes into a fresh state it then joins to its slot
    arena). `io` (``RoundTrip``) sees the prime as input (padding; the
    one-hot only for a net that takes no ids), then forward (upload and
    launch; chunked priming alternates the two per chunk), then one fetch
    (the result coming back), which is left for the caller to end."""
    out = (_prime_padded if padded else _prime)(
        net, ids, vocab_size, chunk_max, io, last_only=True)
    io.step("fetch")
    return _last(_probs(out, io))[0]


def step_tokens(net, tokens, vocab_size: int,
                donate_state: bool = False,
                io: RoundTrip = _UNWATCHED) -> np.ndarray:
    """One incremental decode step for a batch of rows: feed one token
    per row in a single dispatch, return the next-token distributions
    [B, V]. The per-step unit shared by sample_stream (B=1),
    sample_stream_batch, and the serving engine's slot arena (B=S,
    canonical shape, zero retraces after the first step).

    ``donate_state=True`` is the paged-state protocol: the serving
    engine's direct-paged decode installs the KV page pools in
    ``net.state`` and donates them into the dispatch, so the one-token
    append updates the pool IN PLACE (TPU/GPU; a no-op on CPU). The
    caller must treat the pre-call state as consumed — the state the
    net carries after the call is the only live copy.

    `io` (``RoundTrip``) sees input while the ids (or the one-hot) are
    laid out, forward around the dispatch, fetch from the result coming
    back — left for the caller to end. A cycle passes it to ONE such
    call, on its own thread."""
    return _last(_decode(net, np.asarray(tokens, np.int64)[:, None],
                         vocab_size, donate_state, io))


def _dispatch(net, rows, vocab_size: int, donate_state: bool, io):
    io.step("input")
    x = _encode(net, rows, vocab_size)
    return _forward(net, x, io, donate_state=donate_state)


def _decode(net, rows, vocab_size: int, donate_state: bool, io):
    out = _dispatch(net, rows, vocab_size, donate_state, io)
    io.step("fetch")
    return _probs(out, io)


@jax.jit
def greedy_ids(out):
    """The greedy rule on the device: per row of the head's ``[B, V, T]``
    output (or the ``[B, V]`` of a head that gives the last position
    only), the lowest-index maximum at the last position, as int32 —
    the index ``np.argmax`` gives on the same values once fetched. Its
    own program (``jit_greedy_ids``), queued behind the forward; the
    streaming forward stays the one program named ``fwd``."""
    return jnp.argmax(_last(out), axis=-1).astype(jnp.int32)


def step_greedy(net, tokens, vocab_size: int,
                donate_state: bool = False,
                io: RoundTrip = _UNWATCHED,
                block: bool = False
                ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """step_tokens for a caller whose rows are greedy (``selects_one``):
    the same dispatch, with ``greedy_ids`` queued behind it over the very
    array step_tokens would have fetched. Returns ``(ids, probs)`` — the
    ``[B]`` int32 ids (4·B bytes to the host) and, only where `block`
    asks for it because some row samples, the ``[B, V]`` distributions
    as step_tokens returns them; otherwise None, and the block never
    leaves the device. `io` and `donate_state` as step_tokens."""
    out = _dispatch(net, np.asarray(tokens, np.int64)[:, None], vocab_size,
                    donate_state, io)
    out = out[0] if isinstance(out, (list, tuple)) else out
    ids = greedy_ids(out)
    io.step("fetch")
    ids = np.asarray(ids)
    io.d2h(ids)
    return ids, (_last(_probs(out, io)) if block else None)


def verify_tokens(net, chunks, vocab_size: int,
                  donate_state: bool = False,
                  io: RoundTrip = _UNWATCHED) -> np.ndarray:
    """One widened verify forward for a batch of token chunks: feed
    `chunks` [B, W] (W = 1 + gamma for engine speculation) in a single
    dispatch and return ALL per-position next-token distributions
    [B, V, W]. The speculative counterpart of step_tokens — position j's
    row is the distribution AFTER consuming chunk[:, :j+1]; causality
    makes trailing dummy tokens invisible to earlier positions, so a
    fixed-width chunk serves rows with fewer real proposals (the
    uniform-chunk trick of speculative_sample_batch). `donate_state`
    follows step_tokens' paged-state protocol — the widened chunk runs
    the same paged append/attend path at width W; `io` as
    step_tokens."""
    return _decode(net, np.asarray(chunks, np.int64), vocab_size,
                   donate_state, io)


def accept_proposals(proposals, p_dists, q_dists, p_bonus, rng
                     ) -> Tuple[int, int]:
    """The Leviathan et al. 2023 rejection walk, extracted as the ONE
    acceptance rule shared by speculative_sample,
    speculative_sample_batch, and the serving engine's in-engine
    speculation: accept proposal i with prob min(1, p_i[d]/q_i[d]); on
    the first rejection draw the replacement from the clipped residual
    max(p_i - q_i, 0) (falling back to p_i when q subsumes p); with
    every proposal accepted draw the bonus token from `p_bonus` (the
    target's distribution one past the proposals). Returns
    ``(accepted, next_token)`` — the committed tokens are
    ``proposals[:accepted] + [next_token]`` and the target's sampling
    distribution is exactly preserved.

    A ``q_dists`` entry of None means the proposer was DETERMINISTIC —
    a one-hot draft at the proposal under the rejection rule — handled
    without materializing the [V] one-hot: q_i[d] == 1, and the
    rejection residual is p_i with entry d zeroed. rng consumption
    order (one uniform per walked proposal, then exactly one choice) is
    part of the contract: per-row engine speculation must consume each
    request's rng identically to a per-prompt run."""
    for i, d in enumerate(proposals):
        p_i, q_i = p_dists[i], q_dists[i]
        qd = 1.0 if q_i is None else float(q_i[d])
        if rng.random() < min(1.0, float(p_i[d]) / max(qd, 1e-12)):
            continue
        if q_i is None:
            resid = np.array(p_i)
            resid[d] = 0.0
        else:
            resid = np.maximum(p_i - q_i, 0.0)
        total = resid.sum()
        if total <= 0:            # p subsumed by q: fall back to p_i
            resid, total = p_i, p_i.sum()
        return i, int(rng.choice(len(resid), p=resid / total))
    return len(proposals), int(rng.choice(len(p_bonus), p=p_bonus))


def stop_reason(token: int, n_ids: int, want: int,
                stop_set) -> Optional[str]:
    """Why generation ends after appending `token` as the n_ids-th id
    (None = keep going). EOS wins over length when both hit — the stop
    token is kept as the final id either way. The single copy of the
    retirement rule shared by sample_stream and the serving engine."""
    if token in stop_set:
        return "stop"
    if n_ids >= want:
        return "length"
    return None


def sample_stream(net, seed_ids, steps: int, vocab_size: int,
                  temperature: float = 1.0,
                  rng: Optional[np.random.Generator] = None,
                  max_length: Optional[int] = None,
                  prime_chunk_max: Optional[int] = None,
                  prime_padded: bool = False,
                  top_k: Optional[int] = None,
                  top_p: Optional[float] = None,
                  stop_tokens=()) -> List[int]:
    """Temperature sampling with KV-cache / stored-state incremental
    decoding: prime once with the seed, then one single-position forward
    per generated token (the reference's rnnTimeStep generation loop;
    identical distribution to a padded full forward — tested).
    `prime_chunk_max` overrides the process default (set_prime_chunk_max)
    for this call only; `prime_padded=True` instead primes the whole
    prompt in ONE left-padded dispatch (see _prime_padded). `top_k` /
    `top_p` filter each draw (see `draw`; top_k=1 is greedy).
    Generation ends early when a `stop_tokens` member is drawn (the stop
    token is kept as the final id — EOS semantics)."""
    _check_seed(seed_ids, steps, max_length)
    rng = rng or np.random.default_rng(0)
    stop_tokens = set(stop_tokens)
    ids = list(seed_ids)
    want = len(ids) + steps
    if max_length is not None:
        want = min(want, max_length)
    net.rnn_clear_previous_state()
    p = prime_prompt(net, ids, vocab_size, padded=prime_padded,
                     chunk_max=prime_chunk_max)
    for i in range(steps):
        if max_length is not None and len(ids) >= max_length:
            break
        nxt = draw(p, temperature, rng, top_k=top_k, top_p=top_p)
        ids.append(nxt)
        if stop_reason(nxt, len(ids), want, stop_tokens):
            break
        if i + 1 < steps:
            p = step_tokens(net, [nxt], vocab_size)[0]
    return ids


def prompt_lookup_proposer(ngram: int = 3):
    """Draft-FREE speculation proposer (prompt-lookup decoding): propose
    the continuation of the most recent earlier occurrence of the
    context's trailing n-gram. Costs zero device dispatches, so it wins
    even on dispatch-latency-bound serving paths whenever generation
    revisits earlier text (extraction, quoting, code, repetition);
    elsewhere it degrades gracefully to ~plain decoding. Pass the
    returned callable as speculative_sample's `draft`."""
    if ngram < 1:
        raise ValueError(f"ngram must be >= 1, got {ngram}")

    def propose(ids, gamma):
        if len(ids) <= ngram:
            return []
        tail = list(ids[-ngram:])
        for s in range(len(ids) - ngram - 1, -1, -1):
            if list(ids[s:s + ngram]) == tail:
                return list(ids[s + ngram:s + ngram + gamma])
        return []

    return propose


def sample_stream_batch(net, prompts, steps: int, vocab_size: int,
                        temperature: float = 1.0,
                        rng: Optional[np.random.Generator] = None,
                        max_length: Optional[int] = None,
                        top_k: Optional[int] = None,
                        top_p: Optional[float] = None,
                        stop_tokens=()) -> List[List[int]]:
    """Decode a BATCH of prompts simultaneously: mixed-length prompts
    LEFT-pad to the longest and prime in one masked forward (the carried
    kv_mask keeps pad keys invisible on every later step), then every
    decode step advances ALL rows in one dispatch — B times the serving
    throughput of per-prompt sample_stream for the same dispatch count.
    Shapes are bucketed like the rest of this module: the priming length
    pads to its power-of-two bucket (extra columns are fully masked) and
    the batch pads to a power-of-two row count, so serving reuses warm
    compiled shapes across request mixes.

    Per-row results match per-prompt sample_stream for greedy decoding
    (top_k=1 — test-pinned) for recurrences (masked pad steps pass h/c
    through) and attention with rope or no positions (a contiguous
    left-pad shifts a row's absolute positions uniformly; rope scores
    depend only on relative offsets). Under temperature SAMPLING the
    per-row distributions are the same but the shared rng interleaves
    draws across rows, so sequences differ from a per-prompt run with
    the same seed. Models with LEARNED positional tables need
    equal-length prompts (pads would shift the table lookups) —
    enforced here.

    `temperature`/`top_k`/`top_p` may each be PER-ROW [B] arrays (see
    filter_probs): one batch serves prompts with mixed sampling
    configs. Per-row top_k/top_p entries <= 0 switch that filter off
    for that row.

    The batch shares stream positions: every row consumes the padded
    prompt length plus one position per step, so rows stop early (with
    fewer than `steps` tokens) when the net's smallest streaming
    capacity fills — per-prompt decoding of a SHORT prompt can go
    further. A row also ends when it draws a `stop_tokens` member (kept
    as its final id — EOS semantics); other rows continue. Returns one
    continued token list per prompt."""
    if not prompts:
        return []
    rng = rng or np.random.default_rng(0)
    stop_tokens = set(stop_tokens)
    for p in prompts:
        _check_seed(p, steps, max_length)
    B, V = len(prompts), vocab_size
    for name, v in (("temperature", temperature), ("top_k", top_k),
                    ("top_p", top_p)):
        _row_array(v, B, name)         # validate per-row shapes early
    temp_rows = np.ndim(temperature) > 0
    if temp_rows and (np.asarray(temperature) <= 0).any():
        raise ValueError("per-row temperature entries must be > 0")
    out, T, B, Bb, cap = _batch_prime(net, prompts, V)
    probs = _probs(out)[:, :, -1]                           # [Bb, V]
    ids = [list(p) for p in prompts]
    stopped = [False] * B
    done = (lambda b: stopped[b] or (max_length is not None
                                     and len(ids[b]) >= max_length))
    for i in range(steps):
        tok = np.zeros(Bb, np.int64)
        for b in range(B):
            if done(b):
                continue
            tok[b] = draw(
                probs[b],
                float(np.asarray(temperature)[b]) if temp_rows
                else temperature,
                rng, top_k=per_row_param(top_k, b),
                top_p=per_row_param(top_p, b))
            ids[b].append(int(tok[b]))
            if tok[b] in stop_tokens:
                stopped[b] = True
        if all(done(b) for b in range(B)):
            break
        if i + 1 < steps:
            if cap is not None and T + i + 1 > cap:
                break                  # shared stream positions full
            probs = step_tokens(net, tok, V)
    return ids


def speculative_sample(net, draft, seed_ids, steps: int,
                       vocab_size: int,
                       gamma: int = 4,
                       temperature: float = 1.0,
                       rng: Optional[np.random.Generator] = None,
                       max_length: Optional[int] = None,
                       top_k: Optional[int] = None,
                       top_p: Optional[float] = None,
                       prime_padded: bool = False,
                       prime_chunk_max: Optional[int] = None,
                       stop_tokens=()) -> List[int]:
    """Speculative decoding (Leviathan et al. 2023 rejection scheme):
    `draft` proposes up to `gamma` tokens, the target `net` scores ALL
    of them in ONE forward, and the longest accepted prefix is kept —
    the target's sampling DISTRIBUTION is exactly preserved (with
    top_k=1 the output is bit-identical to greedy sample_stream,
    test-pinned), while the target runs once per ~(accepted+1) tokens
    instead of once per token.

    `draft` is either a same-vocab streaming net (model-based drafting —
    wins when the target's forward is much more expensive than the
    draft's, i.e. compute-bound serving) or a host callable
    `(ids, gamma) -> proposals` such as prompt_lookup_proposer()
    (draft-free — zero extra dispatches, wins whenever proposals are
    often right, even on dispatch-latency-bound paths; a deterministic
    proposer is a one-hot draft distribution under the rejection rule).

    Rollback of rejected positions uses rewind_stream_state, so the
    nets involved must carry only position-indexed streaming state
    (attention KV caches + positional offsets — LSTMs are rejected
    there). Acceptance compares the temperature/top_k/top_p-FILTERED
    distributions (standard practice, so the filters stay meaningful).
    Generation ends at the first `stop_tokens` member among the
    committed tokens (kept as the final id — identical cut to plain
    decoding with the same stops)."""
    from deeplearning4j_tpu.nn.conf.layers import (check_rewindable,
                                                   rewind_stream_state)
    if gamma < 1:
        raise ValueError(f"gamma must be >= 1, got {gamma}")
    _check_seed(seed_ids, steps, max_length)
    rng = rng or np.random.default_rng(0)
    V = vocab_size
    ids = list(seed_ids)
    draft_is_fn = not hasattr(draft, "rnn_time_step")
    if draft_is_fn and not callable(draft):
        raise TypeError("draft must be a streaming net or a callable "
                        "(ids, gamma) -> proposals")
    # fail fast: a non-rewindable net would otherwise only error at the
    # first data-dependent rejection, mid-generation
    check_rewindable(net, gamma)
    if not draft_is_fn:
        check_rewindable(draft, gamma)
    net.rnn_clear_previous_state()
    prime = _prime_padded if prime_padded else _prime
    out_t = prime(net, ids, V, prime_chunk_max)
    # p_next: target's (filtered) distribution for the NEXT token given
    # everything its cache has consumed so far
    p_next = filter_probs(_probs(out_t)[0, :, -1], temperature,
                          top_k, top_p)
    if not draft_is_fn:
        draft.rnn_clear_previous_state()
        out_d = prime(draft, ids, V, prime_chunk_max)
        q_next = filter_probs(_probs(out_d)[0, :, -1], temperature,
                              top_k, top_p)
    want = len(seed_ids) + steps
    if max_length is not None:
        want = min(want, max_length)
    stop_set = set(stop_tokens)

    def _stop_cut(start):
        """Index just past the first stop token at/after `start`, or -1."""
        for j in range(start, len(ids)):
            if ids[j] in stop_set:
                return j + 1
        return -1

    # the committed-but-not-yet-consumed LAST token of `ids` rides at
    # the FRONT of the next verify chunk instead of costing its own
    # dispatch: every round is exactly ONE target forward, so even at
    # zero acceptance the dispatch count never exceeds plain decoding's
    pending = None
    while len(ids) < want:
        g = min(gamma, want - len(ids))
        # --- draft proposes up to g tokens + its distributions --------
        if draft_is_fn:
            proposals = [int(t) for t in draft(ids, g)][:g]
            g = len(proposals)
            # deterministic proposer == one-hot draft distribution
            # (None entries — accept_proposals' materialization-free path)
            q_dists = [None] * g
        else:
            proposals, q_dists = [], []
            if pending is not None:
                out_d = draft.rnn_time_step(
                    _encode(draft, np.asarray([[pending]]), V))
                q_next = filter_probs(_probs(out_d)[0, :, -1],
                                      temperature, top_k, top_p)
            q = q_next
            for _ in range(g):
                d = int(rng.choice(V, p=q))
                proposals.append(d)
                q_dists.append(q)
                out_d = draft.rnn_time_step(
                    _encode(draft, np.asarray([[d]]), V))
                q = filter_probs(_probs(out_d)[0, :, -1], temperature,
                                 top_k, top_p)
        # --- target scores pending + all proposals in ONE forward -----
        chunk = ([] if pending is None else [pending]) + proposals
        if not chunk:                 # g == 0 and nothing pending
            nxt = int(rng.choice(V, p=p_next))
            ids.append(nxt)
            if stop_set and nxt in stop_set:
                return ids
            pending = nxt
            # p_next for the round after this comes from the verify
            # forward that consumes `pending` next round
            p_next = None
            continue
        out_t = net.rnn_time_step(
            _encode(net, np.asarray(chunk)[None, :], V))
        tp = _probs(out_t)[0]                      # [V, len(chunk)]
        off = len(chunk) - g                       # 1 when pending rode
        if pending is not None:
            # pending is already IN ids (committed last round); the
            # forward above just consumed it into the caches
            pending = None
            p_next = filter_probs(tp[:, off - 1], temperature,
                                  top_k, top_p)
        if g == 0:                    # plain step: sample from p_next
            nxt = int(rng.choice(V, p=p_next))
            ids.append(nxt)
            if stop_set and nxt in stop_set:
                return ids
            pending = nxt
            p_next = None
            continue
        p_dists = [p_next] + [
            filter_probs(tp[:, off + i], temperature, top_k, top_p)
            for i in range(g - 1)]
        p_bonus = filter_probs(tp[:, off + g - 1], temperature,
                               top_k, top_p)
        # --- standard acceptance walk (the shared rejection rule) -----
        accepted, nxt = accept_proposals(proposals, p_dists, q_dists,
                                         p_bonus, rng)
        base = len(ids)
        ids.extend(proposals[:accepted])
        ids.append(nxt)
        if stop_set:
            cut = _stop_cut(base)
            if cut >= 0:
                # cap at `want`: plain decoding would have stopped at
                # steps before ever reaching a later stop token
                return ids[:min(cut, want)]
        pending = nxt
        p_next = None
        # --- rollback rejected positions (pending rides the next
        # round's verify forward instead of a commit dispatch) ---------
        rewind_stream_state(net, g - accepted)
        if not draft_is_fn:
            rewind_stream_state(draft, g - accepted)
    return ids[:want]


def _batch_prime(net, prompts, vocab_size: int):
    """Shared masked left-padded batch prime (see sample_stream_batch for
    the exactness conditions): returns (out, T, B, Bb, cap)."""
    lens = [len(p) for p in prompts]
    from deeplearning4j_tpu.nn.conf.layers import PositionalEmbeddingLayer
    has_learned_pos = any(isinstance(l, PositionalEmbeddingLayer)
                          for l in _stream_layers(net))
    if len(set(lens)) > 1 and has_learned_pos:
        raise ValueError(
            "mixed-length batched decoding is not exact for "
            "learned positional tables (left-pads shift the "
            "lookups) — pad prompts to equal length, use a rope "
            "model, or decode per prompt")
    cap = _prime_bucket_cap(net)
    if has_learned_pos:
        T = max(lens)      # ANY left pad would shift the table lookups
    else:
        T = _width_bucket(max(lens))             # bucketed prime length
        if cap is not None and T > cap >= max(lens):
            T = cap
    B, V = len(prompts), vocab_size
    Bb = _width_bucket(B)                        # bucketed batch rows
    rows = np.zeros((Bb, T), np.int64)
    mask = np.zeros((Bb, T), np.float32)
    for b, p in enumerate(prompts):
        pad = T - len(p)
        rows[b, pad:] = p
        mask[b, pad:] = 1.0
    x = _encode(net, rows, V)
    if x.ndim == 3:
        x *= mask[:, None, :]      # a one-hot pad column is all zero
    net.rnn_clear_previous_state()
    if hasattr(net, "layers"):                   # MultiLayerNetwork
        out = net.rnn_time_step(x, mask=mask)
    else:                                        # ComputationGraph
        out = net.rnn_time_step(
            x, masks={net.conf.network_inputs[0]: mask})
    return out, T, B, Bb, cap


def _check_per_row_speculable(net, n: int) -> None:
    """Entry validation for batched speculation: everything per-row
    rewind needs, checked BEFORE any state is mutated (the fail-fast
    spirit of speculative_sample's check_rewindable call). `n` is the
    worst-case per-round rewind — the full uniform chunk, gamma + 1."""
    from deeplearning4j_tpu.nn.conf.layers import (
        PositionalEmbeddingLayer, check_rewindable,
    )
    check_rewindable(net, n)
    for l in _stream_layers(net):
        if isinstance(l, PositionalEmbeddingLayer):
            raise ValueError(
                "batched speculative decoding is attention-only: learned "
                "positional tables carry a shared pos_offset that cannot "
                "rewind per row (use a rope or position-free model)")
        # windowed (rolling-cache) attention is fine: per-row positions
        # write each row's own modular slots and kv_abs promotes to
        # [N, L] (SelfAttentionLayer._stream_attend_rolling vec branch);
        # check_rewindable above already enforced
        # cache_length >= window + gamma + 1


def speculative_sample_batch(net, draft, prompts, steps: int,
                             vocab_size: int,
                             gamma: int = 4,
                             temperature: float = 1.0,
                             rngs=None,
                             max_length: Optional[int] = None,
                             top_k: Optional[int] = None,
                             top_p: Optional[float] = None,
                             stop_tokens=()) -> List[List[int]]:
    """Batched speculative decoding: every prompt speculates
    simultaneously with PER-ROW acceptance — each round is one batched
    draft phase plus ONE batched target verify forward, and each row
    rewinds only its own rejected positions (rewind_stream_state with an
    array promotes the attention kv_pos to a per-row vector; subsequent
    cache writes land at each row's own slots). Composes the two serving
    multipliers: speculation's (accepted+1):1 dispatch ratio × batching's
    B rows per dispatch.

    `draft` is a host proposer callable `(ids, gamma) -> proposals`
    (e.g. prompt_lookup_proposer(); applied per row, zero dispatches) or
    a same-vocab streaming net (model drafting: the draft streams the
    same batch, g dispatches per round). `rngs` is one np Generator per
    prompt (default: fresh per-row default_rng(row)); each row consumes
    its own stream in the same order as a per-prompt speculative_sample
    run, so with top_k=1 (greedy — every accept/replace/bonus is
    deterministic) each row's output EQUALS its per-prompt
    speculative_sample output for rope / position-free models
    (test-pinned, both draft kinds). Under temperature sampling rows
    still draw from their own rngs, but float-level batch-vs-single
    differences can flip individual acceptance draws.

    Like sample_stream_batch, rows share stream capacity from the padded
    prompt length; per-row rewind is attention-only (LSTMs cannot
    rewind; learned positional tables are rejected by the layer checks).
    Windowed (rolling-cache) attention IS supported: each row writes its
    own modular slots and the slot->absolute-position map promotes to
    per-row on the first rewind (cache_length >= window + gamma + 1
    enforced at entry)."""
    from deeplearning4j_tpu.nn.conf.layers import rewind_stream_state
    if gamma < 1:
        raise ValueError(f"gamma must be >= 1, got {gamma}")
    if not prompts:
        return []
    for p in prompts:
        _check_seed(p, steps, max_length)
    B, V = len(prompts), vocab_size
    if rngs is None:
        rngs = [np.random.default_rng(b) for b in range(B)]
    if len(rngs) != B:
        raise ValueError(f"need one rng per prompt ({len(rngs)} != {B})")
    draft_is_fn = not hasattr(draft, "rnn_time_step")
    if draft_is_fn and not callable(draft):
        raise TypeError("draft must be a streaming net or a callable "
                        "(ids, gamma) -> proposals")
    # fail fast at entry: rounds rewind up to the FULL uniform chunk
    # (gamma + 1 — a frozen/zero-acceptance row keeps nothing), and the
    # per-row machinery is attention-only
    _check_per_row_speculable(net, gamma + 1)
    if not draft_is_fn:
        _check_per_row_speculable(draft, gamma + 1)

    out_t, T, B, Bb, cap = _batch_prime(net, prompts, V)
    if not draft_is_fn:
        out_d, *_ = _batch_prime(draft, prompts, V)
        q_next = [filter_probs(_probs(out_d)[b, :, -1], temperature,
                               top_k, top_p) for b in range(B)]
    p_next: List[Optional[np.ndarray]] = [
        filter_probs(_probs(out_t)[b, :, -1], temperature, top_k, top_p)
        for b in range(B)]

    ids = [list(p) for p in prompts]
    want = [len(p) + steps for p in prompts]
    if max_length is not None:
        want = [min(w, max_length) for w in want]
    stop_set = set(stop_tokens)
    done = [False] * B
    # positions consumed per row (for the shared-capacity guard): all
    # rows consumed T at prime; per-row rewinds subtract independently
    row_pos = [T] * B
    pending: List[Optional[int]] = [None] * B

    def _finish(b, cut=None):
        done[b] = True
        if cut is not None:
            ids[b] = ids[b][:cut]

    first_round = True
    while not all(done):
        g = gamma
        # --- draft proposes per row -----------------------------------
        # row b proposes at most min(g, room_b) tokens; the verify chunk
        # stays UNIFORM at 1+g slots (short rows pad with 0s, which sit
        # after their real tokens — causal attention means the dummies
        # never influence earlier positions — and are rewound)
        proposals: List[List[int]] = [[] for _ in range(B)]
        q_dists: List[List[np.ndarray]] = [[] for _ in range(B)]
        room = [max(0, want[b] - len(ids[b])) for b in range(B)]
        draft_writes = 0                    # positions the draft consumed
        if draft_is_fn:
            for b in range(B):
                if done[b]:
                    continue
                props = [int(x) for x in draft(ids[b], min(g, room[b]))]
                proposals[b] = props[:min(g, room[b])]
                q_dists[b] = [None] * len(proposals[b])
        else:
            # rounds >= 2: one dispatch consumes every row's pending
            # token into the draft cache (round 1 has no pendings — the
            # prime already produced q_next)
            if not first_round:
                toks = np.zeros(Bb, np.int64)
                for b in range(B):
                    if not done[b] and pending[b] is not None:
                        toks[b] = pending[b]
                out_d = draft.rnn_time_step(
                    _encode(draft, toks[:, None], V))
                draft_writes += 1
                for b in range(B):
                    if not done[b]:
                        q_next[b] = filter_probs(_probs(out_d)[b, :, -1],
                                                 temperature, top_k,
                                                 top_p)
            qs = list(q_next)
            # g batched sampling dispatches advance every row together
            for _ in range(g):
                toks = np.zeros(Bb, np.int64)
                for b in range(B):
                    if done[b] or len(proposals[b]) >= min(g, room[b]):
                        continue
                    d = int(rngs[b].choice(V, p=qs[b]))
                    proposals[b].append(d)
                    q_dists[b].append(qs[b])
                    toks[b] = d
                out_d = draft.rnn_time_step(
                    _encode(draft, toks[:, None], V))
                draft_writes += 1
                for b in range(B):
                    if not done[b]:
                        qs[b] = filter_probs(_probs(out_d)[b, :, -1],
                                             temperature, top_k, top_p)
        first_round = False
        # --- ONE batched target verify forward ------------------------
        chunk_len = 1 + g
        chunk = np.zeros((Bb, chunk_len), np.int64)
        offs = np.zeros(B, np.int32)        # 1 when pending rides slot 0
        for b in range(B):
            if done[b]:
                continue
            row = ([] if pending[b] is None else [pending[b]]) + \
                proposals[b]
            offs[b] = 0 if pending[b] is None else 1
            chunk[b, :len(row)] = row
        if cap is not None and max(row_pos) + chunk_len > cap:
            # shared stream capacity exhausted: stop everyone honestly
            if not draft_is_fn and draft_writes:
                rewind_stream_state(
                    draft, np.full(Bb, draft_writes, np.int32))
            break
        out_t = net.rnn_time_step(_encode(net, chunk, V))
        tp_all = _probs(out_t)               # [Bb, V, chunk_len]
        rew = np.zeros(B, np.int32)          # target rollback per row
        draft_keep = np.zeros(B, np.int32)   # draft slots to keep per row
        for b in range(B):
            if done[b]:
                rew[b] = chunk_len           # frozen rows keep no writes
                continue
            row_pos[b] += chunk_len
            tp = tp_all[b]
            g_b = len(proposals[b])
            off = int(offs[b])
            if off:                          # pending consumed into cache
                pending[b] = None
                p_next[b] = filter_probs(tp[:, off - 1], temperature,
                                         top_k, top_p)
            if g_b == 0:                     # plain step from p_next
                nxt = int(rngs[b].choice(V, p=p_next[b]))
                ids[b].append(nxt)
                rew[b] = chunk_len - off     # drop all proposal slots
                if (stop_set and nxt in stop_set) or \
                        len(ids[b]) >= want[b]:
                    _finish(b)
                else:
                    pending[b] = nxt
                    p_next[b] = None
                continue
            p_dists = [p_next[b]] + [
                filter_probs(tp[:, off + i], temperature, top_k, top_p)
                for i in range(g_b - 1)]
            p_bonus = filter_probs(tp[:, off + g_b - 1], temperature,
                                   top_k, top_p)
            accepted, nxt = accept_proposals(proposals[b], p_dists,
                                             q_dists[b], p_bonus, rngs[b])
            base = len(ids[b])
            ids[b].extend(proposals[b][:accepted])
            ids[b].append(nxt)
            rew[b] = chunk_len - off - accepted
            draft_keep[b] = accepted
            if stop_set:
                cut = next((j + 1 for j in range(base, len(ids[b]))
                            if ids[b][j] in stop_set), -1)
                if cut >= 0:
                    _finish(b, cut=min(cut, want[b]))
            if not done[b] and len(ids[b]) >= want[b]:
                ids[b] = ids[b][:want[b]]
                _finish(b)
            if not done[b]:
                pending[b] = ids[b][-1]
                p_next[b] = None
        # --- per-row rollback (one dispatch for all counters) ---------
        amounts = np.zeros(Bb, np.int32)
        amounts[:B] = rew
        amounts[B:] = chunk_len              # bucket-pad rows keep nothing
        for b in range(B):
            row_pos[b] -= int(rew[b])
        rewind_stream_state(net, amounts)
        if not draft_is_fn:
            d_am = np.full(Bb, draft_writes, np.int32)
            for b in range(B):
                if not done[b] or draft_keep[b]:
                    d_am[b] = draft_writes - int(draft_keep[b]) - \
                        int(offs[b])
            rewind_stream_state(draft, np.maximum(d_am, 0))
    return ids


def beam_search_batch(net, prompts, steps: int, vocab_size: int,
                      beam_width: int = 4,
                      max_length: Optional[int] = None,
                      stop_tokens=()
                      ) -> List[Tuple[List[int], float]]:
    """Beam search over a BATCH of prompts: the [prompts x beams] grid
    flattens onto the batch axis, so every decode step advances all
    prompts' beams in ONE dispatch (per-prompt beam_search costs a
    dispatch per prompt per step). Each prompt's search is independent —
    per-prompt results equal beam_search (test-pinned for rope /
    position-free models; the exactness conditions are
    sample_stream_batch's, since priming left-pads mixed-length prompts
    to a shared bucket). Returns [(best_sequence, log_prob)] per prompt,
    EOS semantics matching beam_search's `stop_tokens`."""
    if not prompts:
        return []
    V = vocab_size
    for p in prompts:
        _check_seed(p, steps, max_length)
    stop_tokens = set(stop_tokens)
    W = min(beam_width, V)
    n = len(prompts)
    out, T, _, Bb, cap = _batch_prime(net, prompts, V)
    # expand each prompt's primed state to its own W beam rows (+ pad
    # rows): flattened row layout is [prompt0 x Wb | prompt1 x Wb | ...]
    Wb = _width_bucket(W)
    expand = np.repeat(np.arange(Bb), Wb)      # [Bb*Wb]
    reorder_stream_state(net, expand)
    probs0 = _probs(out)                        # [Bb, V, T]
    out = np.repeat(probs0, Wb, axis=0)         # [Bb*Wb, V, T]

    beams = [[list(p) for _ in range(W)] for p in prompts]
    scores = np.zeros((n, W))
    alive = np.ones((n, W), bool)
    finished: List[List[Tuple[List[int], float]]] = [[] for _ in range(n)]
    searching = np.ones(n, bool)    # prompt-level: still extending
    first = True
    for i in range(steps):
        if max_length is not None and \
                all(len(beams[b][0]) >= max_length for b in range(n)):
            break
        probs = _probs(out)
        all_parents = np.zeros((n, W), np.int64)
        all_tokens = np.zeros((n, W), np.int64)
        for b in range(n):
            if not searching[b]:
                continue
            if max_length is not None and \
                    len(beams[b][0]) >= max_length:
                searching[b] = False
                continue
            logp = np.log(np.clip(
                probs[b * Wb:b * Wb + W, :, -1], 1e-12, None))  # [W,V]
            if first:
                top = np.argsort(logp[0])[::-1][:W]
                parents, tokens = np.zeros(W, np.int64), top
                scores[b] = logp[0][top]
                beams[b] = [beams[b][p] + [int(t)]
                            for p, t in zip(parents, tokens)]
                alive[b], stop_now = _beam_finish(
                    tokens, scores[b], alive[b], beams[b], stop_tokens,
                    finished[b], W)
            else:
                # the shared rule (_beam_update) per prompt — one copy
                # across beam_search / beam_search_batch / speculative
                parents, tokens, scores[b], alive[b], beams[b], \
                    stop_now = _beam_update(
                        logp, scores[b], alive[b], beams[b],
                        stop_tokens, finished[b], W, V)
            all_parents[b], all_tokens[b] = parents, tokens
            if stop_now:
                searching[b] = False
            # max_length reached AFTER this extension: stop eagerly so a
            # fully-capped batch skips the trailing decode dispatch
            if searching[b] and max_length is not None and \
                    len(beams[b][0]) >= max_length:
                searching[b] = False
        first = False
        if not searching.any():
            break
        if i + 1 < steps:
            if cap is not None and T + i + 1 > cap:
                break
            # flattened gather: prompt b's parents live at rows b*Wb+.
            pp = np.arange(Bb * Wb, dtype=np.int64)
            tok = np.zeros(Bb * Wb, np.int64)
            for b in range(n):
                pp[b * Wb:b * Wb + W] = b * Wb + all_parents[b]
                tok[b * Wb:b * Wb + W] = all_tokens[b]
            if not np.array_equal(pp, np.arange(Bb * Wb)):
                reorder_stream_state(net, pp)
            out = net.rnn_time_step(_encode(net, tok[:, None], V))
    results = []
    for b in range(n):
        live = [(beams[b][w], float(scores[b][w])) for w in range(W)
                if alive[b][w] and np.isfinite(scores[b][w])]
        pool = finished[b] if finished[b] else live
        if not pool:
            pool = [(beams[b][w], float(scores[b][w])) for w in range(W)]
        results.append(max(pool, key=lambda bs: bs[1]))
    return results


def beam_search(net, seed_ids, steps: int, vocab_size: int,
                beam_width: int = 4,
                max_length: Optional[int] = None,
                prime_chunk_max: Optional[int] = None,
                prime_padded: bool = False,
                stop_tokens=()
                ) -> Tuple[List[int], float]:
    """Highest-log-prob continuation of `seed_ids` by beam search.

    `net` needs rnn_time_step / rnn_clear_previous_state (MultiLayerNetwork
    or ComputationGraph, one input: the one-hot [N,V,T], or ids [N,T]
    for a net that ``takes_ids``). `max_length` bounds seed+generation
    (None = unbounded; required finite for models with positional
    tables or non-rolling caches). `prime_chunk_max`
    overrides the process default (set_prime_chunk_max) per call;
    `prime_padded=True` primes the whole prompt in ONE left-padded
    dispatch (see _prime_padded).

    `stop_tokens` enables standard beam EOS semantics: a hypothesis that
    extends with a stop token FINISHES (keeps the stop as its final id,
    stops extending, leaves its beam slot to live candidates); the
    search ends when every slot is finished, when no live hypothesis can
    still beat the best finished one (log-prob totals only decrease as
    hypotheses extend), or when the step budget runs out. The best
    finished hypothesis wins (falling back to the best live one if
    nothing finished)."""
    V = vocab_size
    _check_seed(seed_ids, steps, max_length)
    stop_tokens = set(stop_tokens)
    W = min(beam_width, V)     # top-k can't exceed the vocab
    Wb = _width_bucket(W)      # decode batch: per-bucket jit shape
    net.rnn_clear_previous_state()

    # prime ONCE at batch 1 (bucketed chunks), then broadcast the carried
    # state to the padded beam batch; pad rows never enter scoring (the
    # logp slice below keeps only the first W rows)
    out = (_prime_padded(net, seed_ids, V, prime_chunk_max)
           if prime_padded
           else _prime(net, seed_ids, V, prime_chunk_max))
    reorder_stream_state(net, np.zeros(Wb, np.int64))
    out = np.repeat(_probs(out)[:1], Wb, axis=0)
    beams = [list(seed_ids) for _ in range(W)]
    scores = np.zeros(W)
    alive = np.ones(W, bool)   # slots still extending (EOS finishes one)
    finished = []              # (sequence, score) hypotheses that hit EOS
    first = True
    for i in range(steps):
        if max_length is not None and len(beams[0]) >= max_length:
            break
        logp = np.log(np.clip(_probs(out)[:W, :, -1], 1e-12, None))  # [W,V]
        if first:
            # identical primed beams must diverge: top-W FIRST tokens of
            # beam 0, not W copies of the argmax
            top = np.argsort(logp[0])[::-1][:W]
            parents, tokens, scores = np.zeros(W, np.int64), top, \
                logp[0][top]
            first = False
            beams = [beams[p] + [int(t)] for p, t in zip(parents,
                                                         tokens)]
            alive, stop_now = _beam_finish(tokens, scores, alive, beams,
                                           stop_tokens, finished, W)
        else:
            parents, tokens, scores, alive, beams, stop_now = \
                _beam_update(logp, scores, alive, beams, stop_tokens,
                             finished, W, V)
        if stop_now:
            break
        more = i + 1 < steps and (max_length is None
                                  or len(beams[0]) < max_length)
        if more:
            # pad rows keep their own (discarded) state so the
            # identity-parents fast path still skips the cache gather
            pp = np.arange(Wb, dtype=np.int64)
            pp[:W] = parents
            if not np.array_equal(pp, np.arange(Wb)):
                reorder_stream_state(net, pp)   # inherit caches
            tok = np.zeros(Wb, np.int64)
            tok[:W] = tokens
            out = net.rnn_time_step(_encode(net, tok[:, None], V))
    live = [(beams[w], float(scores[w])) for w in range(W)
            if alive[w] and np.isfinite(scores[w])]
    pool = finished if finished else live
    if not pool:
        pool = [(beams[w], float(scores[w])) for w in range(W)]
    best_seq, best_score = max(pool, key=lambda bs: bs[1])
    return best_seq, best_score


def _beam_finish(tokens, scores, alive, beams, stop_set, finished, W):
    """The finishing/early-stop tail of one beam step (EOS hypotheses
    move to `finished`, their slots die; the search is decided when
    nothing live can beat the best finished). Shared by beam_search's
    both branches and speculative_beam_search so the rule has exactly
    one copy. Returns (alive, stop)."""
    stop = False
    if stop_set:
        alive = np.ones(W, bool)
        for w, t in enumerate(tokens):
            if int(t) in stop_set and np.isfinite(scores[w]):
                finished.append((beams[w], float(scores[w])))
                alive[w] = False
        if not alive.any():
            stop = True
        elif finished:
            best_fin = max(sc for _, sc in finished)
            if scores[alive].max() <= best_fin:
                stop = True
    return alive, stop


def _beam_update(logp, scores, alive, beams, stop_set, finished, W, V):
    """One beam-search scoring update (total/-inf masking, flat top-W,
    then _beam_finish) — the ONLY copy of the rule: beam_search's loop
    body and speculative_beam_search's host-side reconstruction both
    call it, so the speculative replay applies the same rule by
    construction. Returns (parents, tokens, scores, alive, beams, stop).
    Dtype note: `scores` stays the logp dtype (float32 from the net) —
    accumulation dtype is part of the parity contract."""
    total = scores[:, None] + logp
    total[~alive] = -np.inf             # finished slots never extend
    flat = np.argsort(total.ravel())[::-1][:W]
    parents, tokens = np.divmod(flat, V)
    scores = total.ravel()[flat]
    beams = [beams[p] + [int(t)] for p, t in zip(parents, tokens)]
    alive, stop = _beam_finish(tokens, scores, alive, beams, stop_set,
                               finished, W)
    return parents, tokens, scores, alive, beams, stop


def speculative_beam_search(net, draft, seed_ids, steps: int,
                            vocab_size: int,
                            beam_width: int = 4,
                            gamma: int = 4,
                            max_length: Optional[int] = None,
                            prime_chunk_max: Optional[int] = None,
                            stop_tokens=()
                            ) -> Tuple[List[int], float]:
    """Beam search accelerated by speculation — the last edge of the
    serving matrix (beam × speculative). Output EQUALS beam_search's
    (sequence, score) exactly (test-pinned); the target runs once per
    round instead of once per step.

    Structure: `draft` proposes a continuation for EVERY beam — either
    a host proposer callable `(ids, gamma) -> proposals` (e.g.
    prompt_lookup_proposer, zero extra dispatches) or a same-vocab
    streaming net (beam-synchronized greedy model draft: it streams the
    same W-row batch, mirroring every feed/rewind/reorder, and costs g
    draft dispatches per round — wins when the target's forward is much
    more expensive than the draft's); one batched target forward
    scores each beam's pending token plus all its proposals; the
    host-side walk then replays the exact beam-update rule
    (_beam_update) step by step from the verify logits. A drafted step
    is accepted while the true update extends each beam with its own
    proposal (identity parents, drafted tokens, nothing finishing) —
    the collective beam state advances exactly as drafted, so every
    row's cache is already correct. The first divergence applies the
    TRUE update from the same verify logits (no extra dispatch), the
    uniform over-consumed tail rewinds (scalar rewind_stream_state —
    composes with windowed rolling caches), and the corrected tokens
    ride the next round's verify chunk as the per-beam pending front.

    Acceptance is collective — beam reordering anywhere rejects the
    round's remainder — so speculation pays off on peaky/repetitive
    workloads where each beam confidently extends itself (extraction,
    quoting, memorized serving); elsewhere it degrades to plain beam's
    one-dispatch-per-step with identical output. Finished-slot rounds
    (EOS) also degrade gracefully: a dead slot makes identity parents
    impossible, so rounds commit one corrected step each, still never
    exceeding plain beam's dispatch count (+1 worst case).

    ref: the reference's beam decoding lives in its seq2seq examples;
    speculative verification is the Leviathan et al. 2023 scheme with
    the acceptance rule adapted from token-match to beam-state-match.
    """
    from deeplearning4j_tpu.nn.conf.layers import (check_rewindable,
                                                   rewind_stream_state)
    if gamma < 1:
        raise ValueError(f"gamma must be >= 1, got {gamma}")
    if not hasattr(draft, "rnn_time_step") and not callable(draft):
        raise TypeError(
            "draft must be a streaming net (beam-synchronized greedy "
            "model draft) or a host proposer callable "
            "(ids, gamma) -> proposals")
    V = vocab_size
    _check_seed(seed_ids, steps, max_length)
    check_rewindable(net, gamma)
    draft_is_fn = not hasattr(draft, "rnn_time_step")
    if not draft_is_fn:
        check_rewindable(draft, gamma)
    stop_set = set(stop_tokens)
    W = min(beam_width, V)
    Wb = _width_bucket(W)
    net.rnn_clear_previous_state()

    out = _prime(net, seed_ids, V, prime_chunk_max)
    reorder_stream_state(net, np.zeros(Wb, np.int64))
    logp0 = np.log(np.clip(_probs(out)[0, :, -1], 1e-12, None))
    if not draft_is_fn:
        # the draft streams the SAME beam batch, mirroring every feed,
        # rewind and reorder, so its caches always hold the committed
        # beam prefixes (the beam-synchronized draft stream)
        draft.rnn_clear_previous_state()
        _prime(draft, seed_ids, V, prime_chunk_max)
        reorder_stream_state(draft, np.zeros(Wb, np.int64))

    # first expansion: top-W first tokens of beam 0 (identical to
    # beam_search's `first` branch, incl. _beam_finish and the float32
    # score dtype — accumulation dtype is part of the parity contract);
    # the chosen tokens become the per-beam pending front of round 1
    top = np.argsort(logp0)[::-1][:W]
    beams = [list(seed_ids) + [int(t)] for t in top]
    scores = logp0[top]
    alive = np.ones(W, bool)
    finished = []
    pending = top.astype(np.int64)      # [W] committed, not yet consumed
    committed = 1
    want = steps
    if max_length is not None:
        want = min(want, max_length - len(seed_ids))
    alive, stop_now = _beam_finish(top, scores, alive, beams, stop_set,
                                   finished, W)
    decided = committed >= want or stop_now

    while not decided:
        # draft per live beam; collective acceptance needs a common
        # depth, so g is the shortest proposal list (0 => pure
        # correction round, one dispatch per token — plain beam's rate)
        g = min(gamma, want - committed - 1)
        proposals = None
        if g > 0 and alive.all():
            if draft_is_fn:
                plists = [[int(t) for t in draft(beams[w], g)][:g]
                          for w in range(W)]
                g = min(len(p) for p in plists)
                if g > 0:
                    proposals = np.asarray([p[:g] for p in plists],
                                           np.int64)      # [W, g]
            else:
                # greedy model draft: feed pending, then each argmax —
                # the draft consumes 1+g tokens exactly like the target
                # and rewinds/reorders with it below
                tok = np.zeros(Wb, np.int64)
                tok[:W] = pending
                out_d = draft.rnn_time_step(
                    _encode(draft, tok[:, None], V))
                props = []
                for _ in range(g):
                    nxt = _probs(out_d)[:W, :, -1].argmax(axis=1)
                    props.append(nxt.astype(np.int64))
                    tok = np.zeros(Wb, np.int64)
                    tok[:W] = nxt
                    out_d = draft.rnn_time_step(
                        _encode(draft, tok[:, None], V))
                proposals = np.stack(props, axis=1)       # [W, g]
        if proposals is None:
            g = 0
            if not draft_is_fn:
                # correction-only round: the draft still consumes the
                # pending front to stay position-synchronized
                tok = np.zeros(Wb, np.int64)
                tok[:W] = pending
                draft.rnn_time_step(_encode(draft, tok[:, None], V))

        chunk = np.zeros((Wb, 1 + g), np.int64)
        chunk[:W, 0] = pending
        if g:
            chunk[:W, 1:] = proposals
        out = net.rnn_time_step(_encode(net, chunk, V))
        tp = _probs(out)                                   # [Wb, V, 1+g]

        accepted = 0
        stop_now = False
        parents = tokens = None
        # invariant: committed + g + 1 <= want (g was clamped to
        # want - committed - 1 and only shrinks), so every walk step
        # below is within the budget
        for j in range(g + 1):
            logp = np.log(np.clip(tp[:W, :, j], 1e-12, None))
            parents, tokens, scores, alive, beams, stop_now = \
                _beam_update(logp, scores, alive, beams, stop_set,
                             finished, W, V)
            committed += 1
            if stop_now:
                break
            if (j < g
                    and np.array_equal(parents, np.arange(W))
                    and np.array_equal(tokens, proposals[:, j])
                    and alive.all()):
                accepted += 1
                parents = tokens = None   # state advanced as drafted
                continue
            break                         # divergence or bonus applied

        # drop the over-consumed drafted tail (uniform across rows: the
        # accepted prefix advanced every cache identically)
        over = g - accepted
        if over:
            rewind_stream_state(net, over)
            if not draft_is_fn:
                rewind_stream_state(draft, over)
        if committed >= want or stop_now:
            break
        # the walk always ends with a true update (the j == g bonus
        # step can't take the accept branch), so parents/tokens are set:
        # align caches to the new beam assignment; tokens become pending
        pp = np.arange(Wb, dtype=np.int64)
        pp[:W] = parents
        if not np.array_equal(pp, np.arange(Wb)):
            reorder_stream_state(net, pp)
            if not draft_is_fn:
                reorder_stream_state(draft, pp)
        pending = np.zeros(W, np.int64)
        pending[:] = tokens

    live = [(beams[w], float(scores[w])) for w in range(W)
            if alive[w] and np.isfinite(scores[w])]
    pool = finished if finished else live
    if not pool:
        pool = [(beams[w], float(scores[w])) for w in range(W)]
    best_seq, best_score = max(pool, key=lambda bs: bs[1])
    return best_seq, best_score
