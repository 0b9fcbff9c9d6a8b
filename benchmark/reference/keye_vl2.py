"""Plain reference for the ``keye-vl-2.0-30b-a3b`` configuration: the
language model's forward pass in straightforward ``jax.numpy``, float32,
``highest`` matmul precision, no cache, no kernels, no batching. Imports
nothing of the program.

Keye-VL-2.0-30B-A3B (Kwai-Keye/Keye-VL-2.0-30B-A3B config.json; its keys
are the Qwen3-MoE family's plus ``sa_config``): token embedding, then per
layer RMSNorm -> grouped-query attention with a sparse-selection indexer
-> residual, RMSNorm -> routed experts (a dense gated feed-forward in a
layer that ``mlp_only_layers`` names or ``decoder_sparse_step`` skips) ->
residual; a final RMSNorm and an untied linear head. No biases but the
index key's LayerNorm. The vision tower is not part of it
(``departures.vision_tower``); for text the three position streams of
``rope_scaling.mrope_section`` are equal and the rotation is plain rope.

Attention, for every position t (``h`` the normed input, ``H`` query
heads and ``G`` key-value heads of ``D`` = ``head_dim``, ``H D`` wider
than the hidden size):

    q = h W_q (H x D)     k = h W_k, v = h W_v (G x D)
    q <- RMSNorm_D(q) q_norm,  k <- RMSNorm_D(k) k_norm        (per head)
    q, k rotated over the whole head, pairs (i, i + D/2), base rope_theta
    o_t = sum_{s in S_t} softmax_s(q_t . k_s D^-1/2) v_s
    (query head j on key-value head j // (H / G));   out = o W_o

Selection (``sa_config``): ``q^I = h W_iq`` (Hi x Di), ``k^I =
LayerNorm(h W_ik)`` (one key for all index heads), both rotated over
their whole Di dims by the same rule, ``w = h W_iw Hi^-1/2 Di^-1/2``;
``I(t, s) = sum_j w_tj relu(q^I_tj . k^I_s)`` over s <= t; ``S_t`` is the
``min(topk, t + 1)`` positions of highest I, ties to the lower index, by
a stable sort. ``q_chunk_size`` / ``kv_chunk_size`` are read as tiles of
the source's score computation and change no result (``assumed``).

Experts: ``p = softmax(h W_r)`` over all ``num_experts``; the
``num_experts_per_tok`` largest (ties to the lower index), renormalised to
sum 1 where ``norm_topk_prob``; ``y = sum_e p_e W_down,e (silu(h
W_gate,e) * h W_up,e)``, every expert run over every token and weighted
by its gate (0 where not chosen). No shared expert.

``low`` is the 8-bit control as ``reference/quant.py`` defines it: both
operands of every product and every tensor an op hands on rounded to
float8; the head's logits and the two choices' scores (the index score I
and the router's p, which only order things) are computed from float8
operands and left wide themselves.

The model is walked piece by piece (a jitted function for the attention
of a layer, one for a feed-forward, one for the router), weights cast up
from their stored bfloat16 inside each, queries taken in blocks, so that
12,544 positions fit beside the bfloat16 leaves on a 16 GB chip.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.quant import stored

HI = lax.Precision.HIGHEST
#: queries a block of the attention takes at once: [H, block, T] scores
QUERY_BLOCK = 128


# ------------------------------------------------------------------ sizes
class Sizes(NamedTuple):
    """The configuration's sizes under short names (hashable: a static
    argument of the jitted pieces)."""
    e: int; v: int; h: int; g: int; d: int; hi: int; di: int    # noqa: E702
    topk: int; dense: int; moe: int; experts: int; per_tok: int  # noqa: E702
    layers: int                                                 # noqa: E702


def _sizes(cfg) -> Sizes:
    sa = cfg["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("one index key a token (indexer_num_kv_heads 1)")
    return Sizes(
        e=cfg["hidden_size"], v=cfg["vocab_size"],
        h=cfg["num_attention_heads"], g=cfg["num_key_value_heads"],
        d=cfg["head_dim"], hi=sa["indexer_num_heads"],
        di=sa["indexer_head_dim"], topk=sa["topk"],
        dense=cfg["intermediate_size"], moe=cfg["moe_intermediate_size"],
        experts=cfg["num_experts"], per_tok=cfg["num_experts_per_tok"],
        layers=cfg["num_hidden_layers"])


def routes(cfg, n: int) -> bool:
    """Whether layer ``n`` is a layer of routed experts (the family's
    rule)."""
    return (n not in cfg["mlp_only_layers"] and cfg["num_experts"] > 0
            and (n + 1) % cfg["decoder_sparse_step"] == 0)


def param_specs(cfg):
    """(name, shape, mean, std), ``name`` = ``<vertex>/<leaf>`` of the
    program's tree. Every matrix is [in, out] and drawn N(0, g^2 / in)
    (``assumed.init``): gain 1 keeps a unit-RMS input at unit RMS, and
    the products that write into the residual stream (W_o and the
    feed-forwards' W_down) take the gains that put each branch near the
    embedding's own scale."""
    z = _sizes(cfg)
    e = z.e

    def mat(name, a, b, gain=1.0, lead=()):
        return (name, tuple(lead) + (a, b), 0.0, gain / math.sqrt(a))

    def gain(name, n):
        return (name, (n,), 1.0, 0.02)

    specs = [("embed/W", (z.v, e), 0.0, 0.05)]
    for n in range(z.layers):
        a = f"attn{n}"
        specs += [
            gain(f"norm{n}a/gamma", e),
            mat(f"{a}/Wq", e, z.h * z.d), mat(f"{a}/Wk", e, z.g * z.d),
            mat(f"{a}/Wv", e, z.g * z.d), mat(f"{a}/Wo", z.h * z.d, e, 0.4),
            gain(f"{a}/q_norm", z.d), gain(f"{a}/k_norm", z.d),
            mat(f"{a}/Wiq", e, z.hi * z.di), mat(f"{a}/Wik", e, z.di),
            mat(f"{a}/Wiw", e, z.hi),
            gain(f"{a}/ik_gamma", z.di),
            (f"{a}/ik_beta", (z.di,), 0.0, 0.02),
            gain(f"norm{n}b/gamma", e)]
        if routes(cfg, n):
            m, i, g = f"moe{n}", z.moe, (z.experts,)
            specs += [mat(f"{m}/Wr", e, z.experts),
                      mat(f"{m}/Wg", e, i, lead=g),
                      mat(f"{m}/Wu", e, i, lead=g),
                      mat(f"{m}/Wd", i, e, 0.1, lead=g)]
        else:
            f, i = f"ffn{n}", z.dense
            specs += [mat(f"{f}/Wg", e, i), mat(f"{f}/Wu", e, i),
                      mat(f"{f}/Wd", i, e, 0.1)]
    specs += [gain("norm_f/gamma", e), mat("out/W", e, z.v)]
    return specs


# -------------------------------------------------------------- primitives
def _mm(x, w, low, keep_result=False):
    """x [.., a] @ w [a, b] in float32 at ``highest``; with ``low`` both
    operands, and the result unless ``keep_result``, are handed on in
    8-bit floats."""
    y = jnp.matmul(stored(x, low), stored(w.astype(jnp.float32), low),
                   precision=HI)
    return y if keep_result else stored(y, low)


def _rms_norm(x, gamma, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(ms + eps) * gamma.astype(jnp.float32)


def _layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return ((x - mean) * lax.rsqrt(var + eps) * gamma.astype(jnp.float32)
            + beta.astype(jnp.float32))


def _rope(x, pos, theta):
    """x [T, ..., d] rotated over the whole last axis, pairs (i, i + d/2),
    frequencies theta^(-2i/d)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv               # [T, d/2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    c, s = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, a * s + b * c], -1)


def _top_mask(scores, k):
    """[.., S] bool: the ``k`` highest of each row, ties to the lower
    index (a stable sort of the negated scores)."""
    order = jnp.argsort(-scores, axis=-1, stable=True)[..., :k]
    rows = jnp.arange(scores.shape[0])[:, None]
    return jnp.zeros(scores.shape, bool).at[rows, order].set(True)


# --------------------------------------------------------------- attention
@functools.partial(jax.jit, static_argnames=("z", "eps", "theta", "low"))
def attention(h, p, *, z, eps, theta, low):
    """The attention of one layer over one sequence, ``h`` [T, E] the
    normed input. Returns ``(out [T, E], selected [T, T] bool)``."""
    t = h.shape[0]
    pos = jnp.arange(t)
    q = _mm(h, p["Wq"], low).reshape(t, z.h, z.d)
    k = _mm(h, p["Wk"], low).reshape(t, z.g, z.d)
    v = _mm(h, p["Wv"], low).reshape(t, z.g, z.d)
    q = _rope(_rms_norm(q, p["q_norm"], eps), pos, theta)
    k = _rope(_rms_norm(k, p["k_norm"], eps), pos, theta)
    # the indexer: one key a token for all its heads
    qi = _rope(_mm(h, p["Wiq"], low).reshape(t, z.hi, z.di), pos, theta)
    ki = _rope(_layer_norm(_mm(h, p["Wik"], low), p["ik_gamma"],
                           p["ik_beta"], eps), pos, theta)
    w = _mm(h, p["Wiw"], low) * (z.hi ** -0.5 * z.di ** -0.5)
    q, k, v = stored(q, low), stored(k, low), stored(v, low)
    qi, ki, w = stored(qi, low), stored(ki, low), stored(w, low)
    # every query head beside its group's key-value head
    reps = z.h // z.g
    k, v = jnp.repeat(k, reps, axis=1), jnp.repeat(v, reps, axis=1)
    top = min(z.topk, t)

    def block(args):
        qb, qib, wb, pb = args                       # a block of queries
        causal = pb[:, None] >= pos[None, :]
        i = jnp.einsum("qhd,sd->qhs", qib, ki, precision=HI)
        i = jnp.sum(wb[:, :, None] * jax.nn.relu(i), axis=1)     # [B, T]
        sel = _top_mask(jnp.where(causal, i, -jnp.inf), top) & causal
        s = jnp.einsum("qhd,shd->hqs", qb, k, precision=HI) * z.d ** -0.5
        a = jax.nn.softmax(jnp.where(sel[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqs,shd->qhd", stored(a, low), v, precision=HI)
        return o, sel

    b = min(QUERY_BLOCK, t)
    if t % b:
        raise ValueError(f"{t} positions are no whole number of blocks "
                         f"of {b}: pad the sequence")

    def split(x):
        return x.reshape((t // b, b) + x.shape[1:])

    o, sel = lax.map(block, (split(q), split(qi), split(w), split(pos)))
    o = stored(o.reshape(t, z.h * z.d), low)
    return _mm(o, p["Wo"], low), sel.reshape(t, t)


# ------------------------------------------------------------ feed-forward
@functools.partial(jax.jit, static_argnames=("low",))
def gated(h, wg, wu, wd, *, low):
    """(silu(h W_gate) * h W_up) W_down."""
    a = stored(jax.nn.silu(_mm(h, wg, low)) * _mm(h, wu, low), low)
    return _mm(a, wd, low)


@functools.partial(jax.jit, static_argnames=("top_k", "norm", "low"))
def route(h, wr, *, top_k, norm, low):
    """Gates [T, experts] of the experts each token chose, 0 elsewhere."""
    p = jax.nn.softmax(_mm(h, wr, low, keep_result=True), axis=-1)
    picked = jnp.where(_top_mask(p, top_k), p, 0.0)
    return picked / jnp.sum(picked, axis=-1, keepdims=True) if norm \
        else picked


def experts(cfg, params, n, h, low, held=None):
    """sum over the experts ``held`` = (first, count) (default: all of
    them) of gate * E(h), every one run over every token."""
    m = f"moe{n}"
    first, count = held or (0, cfg["num_experts"])
    gates = route(h, params[f"{m}/Wr"], top_k=cfg["num_experts_per_tok"],
                  norm=bool(cfg["norm_topk_prob"]), low=low)
    y = jnp.zeros_like(h)
    for i in range(first, first + count):
        y = y + gates[:, i, None] * gated(
            h, params[f"{m}/Wg"][i], params[f"{m}/Wu"][i],
            params[f"{m}/Wd"][i], low=low)
    return stored(y, low)


# ------------------------------------------------------------- whole model
@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _normed(x, gamma, *, eps, low):
    return stored(_rms_norm(x, gamma, eps), low)


@functools.partial(jax.jit, static_argnames=("low",))
def _embed(ids, w, *, low):
    return stored(jnp.take(w, ids, axis=0).astype(jnp.float32), low)


def _attn_params(params, n):
    keys = ("Wq", "Wk", "Wv", "Wo", "q_norm", "k_norm", "Wiq", "Wik",
            "Wiw", "ik_gamma", "ik_beta")
    return {k: params[f"attn{n}/{k}"] for k in keys}


def _walk(cfg, params, ids, low):
    """The residual stream after the last layer [T, E], and every
    layer's selected sets [T, T]."""
    z = _sizes(cfg)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    # causal, and no token sees another but through attention: zeros past
    # the end change nothing before it
    ids = list(ids)
    ids += [0] * (-len(ids) % min(QUERY_BLOCK, len(ids)))
    x = _embed(jnp.asarray(ids, jnp.int32), params["embed/W"], low=low)
    selected = []
    for n in range(z.layers):
        h = _normed(x, params[f"norm{n}a/gamma"], eps=eps, low=low)
        a, sel = attention(h, _attn_params(params, n), z=z, eps=eps,
                           theta=theta, low=low)
        selected.append(sel)
        x = stored(x + a, low)
        h = _normed(x, params[f"norm{n}b/gamma"], eps=eps, low=low)
        if routes(cfg, n):
            f = experts(cfg, params, n, h, low)
        else:
            f = gated(h, params[f"ffn{n}/Wg"], params[f"ffn{n}/Wu"],
                      params[f"ffn{n}/Wd"], low=low)
        x = stored(x + f, low)
    return x, selected


def logits_at(cfg, params, ids, positions, low=False):
    """Logits [len(positions), V] that follow ``ids[:p + 1]`` for each p in
    ``positions``, from one causal pass over the whole of ``ids`` (padded
    here with zeros to whole blocks of queries)."""
    x, _ = _walk(cfg, params, ids, low)
    x = x[jnp.asarray(positions, jnp.int32)]
    h = _normed(x, params["norm_f/gamma"], eps=cfg["rms_norm_eps"],
                low=low)
    return _mm(h, params["out/W"], low, keep_result=True)


def selected_at(cfg, params, ids, positions):
    """[layers, len(positions), len(ids)] bool: the positions each query
    of ``positions`` attends, layer by layer."""
    _, selected = _walk(cfg, params, ids, False)
    p = jnp.asarray(positions, jnp.int32)
    return jnp.stack([s[p][:, :len(ids)] for s in selected])


# -------------------------------------------------------------- operations
def _per_token_flops(cfg) -> int:
    """Products a token needs outside the position-dependent parts, all
    layers, 2 a multiply-add: the four attention projections, the
    indexer's three, and the router with ``num_experts_per_tok`` experts
    (or the dense feed-forward)."""
    z = _sizes(cfg)
    e = z.e
    attn = 2 * e * z.h * z.d + 2 * e * z.g * z.d
    index = e * z.hi * z.di + e * z.di + e * z.hi
    moe = e * z.experts + z.per_tok * 3 * e * z.moe
    n_moe = sum(routes(cfg, n) for n in range(z.layers))
    return int(2 * (z.layers * (attn + index) + n_moe * moe
                    + (z.layers - n_moe) * 3 * e * z.dense))


def _position_flops(cfg, scored: int, attended: int) -> int:
    """What the position-dependent parts add, all layers: ``scored``
    (query, index key) pairs and ``attended`` (query, selected position)
    pairs, each of the latter a score and a value product a head."""
    z = _sizes(cfg)
    return 2 * z.layers * (scored * z.hi * z.di + attended * z.h * 2 * z.d)


def head_flops(cfg) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def prefill_flops(cfg, prompt: int) -> int:
    """A prompt of ``prompt`` tokens: query t scores the t + 1 positions
    it may see and attends ``min(topk, t + 1)`` of them; the embedding is
    a lookup and only the last position needs logits."""
    top = min(cfg["sa_config"]["topk"], prompt)
    return (prompt * _per_token_flops(cfg)
            + _position_flops(cfg, prompt * (prompt + 1) // 2,
                              top * (top + 1) // 2 + (prompt - top) * top)
            + head_flops(cfg))


def decode_flops(cfg, context: int) -> int:
    """One generated token whose query sees ``context`` positions (itself
    included) and attends the selected ones."""
    return (_per_token_flops(cfg)
            + _position_flops(cfg, context,
                              min(cfg["sa_config"]["topk"], context))
            + head_flops(cfg))


def expert_bytes(cfg) -> int:
    """Bytes of one expert's three matrices as the configuration keeps
    them (bfloat16): what a step reads of an expert it cannot do
    without."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * 2
