"""Plain reference for the ``deepseek-v3.2`` configuration: one chip's
share of the decoder's forward pass in straightforward ``jax.numpy``,
float32, ``highest`` matmul precision, no cache, no kernels, no batching.
Imports nothing of the program.

DeepSeek-V3.2 (deepseek-ai/DeepSeek-V3.2 config.json; the model card's
inference code for the equations): token embedding, then per layer
RMSNorm -> latent attention with the sparse-selection indexer inside it
-> residual, RMSNorm -> a gated (SiLU) feed-forward, dense in the leading
layers and routed experts plus one shared expert after them -> residual;
a final RMSNorm and an untied linear head. No biases but the router's
selection bias and the index key's LayerNorm.

Latent attention, per-head form, for every position t (``h`` is the
normed input, width 7,168):

    c_q = RMSNorm(h W_qa)                     q = c_q W_qb -> H x (nope | rope)
    [c_kv | k_r] = h W_kva                    c_kv <- RMSNorm(c_kv)
    [k_nope | v] = c_kv W_kvb (per head)      k = [k_nope | rope(k_r)]
    scores = (q_nope . k_nope + rope(q_rope) . rope(k_r)) s
    s = (nope + rope)^-1/2 m^2,   m = 0.1 mscale_all_dim ln(factor) + 1

with YaRN frequencies and interleaved pairs (2i, 2i + 1). The absorbed
form the program decodes with (``q' = q_nope W_UK`` against ``c_kv``) is
the same function; this file has the per-head form only.

Sparse selection: ``q^I = c_q W^I_q`` (Hi x Di, the first ``rope`` dims
rotated, half-split pairs (i, i + rope/2)), ``k^I = LayerNorm(h W^I_k)``
(same rotation), ``w = h W^I_w Hi^-1/2 Di^-1/2``;
``I(t, s) = sum_j w_tj relu(q^I_tj . k^I_s)`` over s <= t; position t
attends the ``min(index_topk, t + 1)`` positions of highest I, ties to the
lower index, by an explicit mask on the scores.

Experts: ``sigma = sigmoid(h W_r)`` over the router's published width;
choice by ``sigma + b``: groups of ``n / n_group``, a group's score the
sum of its two best, the ``topk_group`` best groups kept, the
``num_experts_per_tok`` best experts of those; gates
``sigma_i / sum sigma_i * routed_scaling_factor``. This chip holds experts
``0 .. n_routed_experts - 1`` of ``published.n_routed_experts``: the layer
gives ``shared(h) + sum over held i of g_i E_i(h)``; what the absent
experts would add is left out (the configuration's ``deployment``).

``low`` is the 8-bit control as ``reference/quant.py`` defines it: both
operands of every product and every tensor an op hands on rounded to
float8; the head's logits and the two selections' scores (the index score
I and the router's sigma + b, which only order things) are computed from
float8 operands and left wide themselves.

The model is walked piece by piece (a jitted function for the attention
of a layer, one for a feed-forward, one for each held expert), weights
cast up from their stored bfloat16 inside each, queries taken in blocks,
so that 8,192 positions fit beside the bfloat16 leaves of the whole share
on a 16 GB chip.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference.quant import stored

HI = lax.Precision.HIGHEST
#: queries a block of the attention takes at once: [H, block, T] scores
QUERY_BLOCK = 128


# ------------------------------------------------------------------ sizes
class Sizes(NamedTuple):
    """The configuration's sizes under short names (hashable: a static
    argument of the jitted pieces)."""
    e: int; v: int; h: int; ql: int; kl: int; dn: int; dr: int  # noqa: E702
    dv: int; hi: int; di: int; topk: int; dense: int; moe: int  # noqa: E702
    held: int; router: int; layers: int; first_dense: int       # noqa: E702


def _sizes(cfg) -> Sizes:
    routed = cfg["n_routed_experts"]
    return Sizes(
        e=cfg["hidden_size"], v=cfg["vocab_size"],
        h=cfg["num_attention_heads"], ql=cfg["q_lora_rank"],
        kl=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        hi=cfg["index_n_heads"], di=cfg["index_head_dim"],
        topk=cfg["index_topk"], dense=cfg["intermediate_size"],
        moe=cfg["moe_intermediate_size"], held=routed,
        router=cfg.get("published", {}).get("n_routed_experts", routed),
        layers=cfg["num_hidden_layers"],
        first_dense=cfg["first_k_dense_replace"])


def is_dense(cfg, n: int) -> bool:
    return n < cfg["first_k_dense_replace"]


def param_specs(cfg):
    """(name, shape, mean, std), ``name`` = ``<vertex>/<leaf>`` of the
    program's tree. Every matrix is [in, out] and drawn N(0, g^2 / in)
    (``assumed.init``): gain 1 keeps a unit-RMS input at unit RMS, and
    the three products that write into the residual stream (W_o and the
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
            mat(f"{a}/Wqa", e, z.ql), gain(f"{a}/q_gamma", z.ql),
            mat(f"{a}/Wqb", z.ql, z.h * (z.dn + z.dr)),
            mat(f"{a}/Wkva", e, z.kl + z.dr),
            gain(f"{a}/kv_gamma", z.kl),
            mat(f"{a}/Wkvb", z.kl, z.h * (z.dn + z.dv)),
            mat(f"{a}/Wo", z.h * z.dv, e, 0.4),
            mat(f"{a}/Wiq", z.ql, z.hi * z.di),
            mat(f"{a}/Wik", e, z.di),
            gain(f"{a}/ik_gamma", z.di),
            (f"{a}/ik_beta", (z.di,), 0.0, 0.02),
            mat(f"{a}/Wiw", e, z.hi),
            gain(f"norm{n}b/gamma", e)]
        if is_dense(cfg, n):
            f, i = f"ffn{n}", z.dense
            specs += [mat(f"{f}/Wg", e, i), mat(f"{f}/Wu", e, i),
                      mat(f"{f}/Wd", i, e, 0.1)]
        else:
            m, i, g = f"moe{n}", z.moe, (z.held,)
            specs += [mat(f"{m}/Wr", e, z.router),
                      (f"{m}/br", (z.router,), 0.0, 0.01),
                      mat(f"{m}/Wg", e, i, lead=g),
                      mat(f"{m}/Wu", e, i, lead=g),
                      mat(f"{m}/Wd", i, e, 0.1, lead=g),
                      mat(f"{m}/Ws_g", e, i), mat(f"{m}/Ws_u", e, i),
                      mat(f"{m}/Ws_d", i, e, 0.1)]
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


def yarn_inv_freq(cfg) -> np.ndarray:
    """The rotary frequencies, YaRN-corrected (Peng et al. 2023, as the
    source applies it): dimensions that turn more than ``beta_fast``
    times over the original context keep their frequency, those that turn
    fewer than ``beta_slow`` times are slowed by ``factor``, a linear ramp
    between."""
    rs, dim = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    base, orig = float(cfg["rope_theta"]), rs[
        "original_max_position_embeddings"]
    freq = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def turns_to_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(turns_to_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(turns_to_dim(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    keep = 1.0 - ramp
    return (freq / rs["factor"] * (1 - keep) + freq * keep).astype(
        np.float32)


def softmax_scale(cfg) -> float:
    rs = cfg["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    d = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return d ** -0.5 * m * m


def _angles(positions, inv_freq):
    ang = positions.astype(jnp.float32)[:, None] * inv_freq    # [T, d/2]
    return jnp.cos(ang), jnp.sin(ang)


def _rope_interleaved(x, cos, sin):
    """x [T, ..., d], pairs (2i, 2i + 1); cos, sin [T, d/2]."""
    shape = x.shape
    x = x.reshape(shape[:-1] + (shape[-1] // 2, 2))
    a, b = x[..., 0], x[..., 1]
    c = cos.reshape((shape[0],) + (1,) * (len(shape) - 2) + (-1,))
    s = sin.reshape(c.shape)
    return jnp.stack([a * c - b * s, a * s + b * c], -1).reshape(shape)


def _rope_half(x, cos, sin):
    """x [T, ..., d], pairs (i, i + d/2)."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    c = cos.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    s = sin.reshape(c.shape)
    return jnp.concatenate([a * c - b * s, a * s + b * c], -1)


def _top_mask(scores, k):
    """[.., S] bool: the ``k`` highest of each row, ties to the lower
    index (a stable sort of the negated scores)."""
    order = jnp.argsort(-scores, axis=-1, stable=True)[..., :k]
    rows = jnp.arange(scores.shape[0])[:, None]
    return jnp.zeros(scores.shape, bool).at[rows, order].set(True)


# --------------------------------------------------------------- attention
@functools.partial(jax.jit, static_argnames=("z", "eps", "scale", "low"))
def attention(h, p, inv_freq, *, z, eps, scale, low):
    """The latent attention of one layer over one sequence, ``h`` [T, E]
    the normed input. Returns ``(out [T, E], selected [T, T] bool)``."""
    t = h.shape[0]
    heads, dn, dr, dv = z.h, z.dn, z.dr, z.dv
    pos = jnp.arange(t)
    cos, sin = _angles(pos, inv_freq)
    cq = stored(_rms_norm(_mm(h, p["Wqa"], low), p["q_gamma"], eps), low)
    q = _mm(cq, p["Wqb"], low).reshape(t, heads, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], _rope_interleaved(q[..., dn:], cos, sin)], -1)
    kva = _mm(h, p["Wkva"], low)
    ckv = stored(_rms_norm(kva[:, :z.kl], p["kv_gamma"], eps), low)
    k_r = _rope_interleaved(kva[:, z.kl:], cos, sin)          # [T, dr]
    kv = _mm(ckv, p["Wkvb"], low).reshape(t, heads, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, None], (t, heads, dr))], -1)
    v = kv[..., dn:]
    # the indexer
    qi = _mm(cq, p["Wiq"], low).reshape(t, z.hi, z.di)
    qi = jnp.concatenate(
        [_rope_half(qi[..., :dr], cos, sin), qi[..., dr:]], -1)
    ki = _layer_norm(_mm(h, p["Wik"], low), p["ik_gamma"], p["ik_beta"],
                     eps)
    ki = jnp.concatenate(
        [_rope_half(ki[..., :dr], cos, sin), ki[..., dr:]], -1)
    w = _mm(h, p["Wiw"], low) * (z.hi ** -0.5 * z.di ** -0.5)
    q, k, v = stored(q, low), stored(k, low), stored(v, low)
    qi, ki, w = stored(qi, low), stored(ki, low), stored(w, low)
    top = min(z.topk, t)

    def block(args):
        qb, qib, wb, pb = args                       # a block of queries
        causal = pb[:, None] >= pos[None, :]
        i = jnp.einsum("qhd,sd->qhs", qib, ki, precision=HI)
        i = jnp.sum(wb[:, :, None] * jax.nn.relu(i), axis=1)     # [B, T]
        sel = _top_mask(jnp.where(causal, i, -jnp.inf), top) & causal
        s = jnp.einsum("qhd,shd->hqs", qb, k, precision=HI) * scale
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
    o = stored(o.reshape(t, heads * dv), low)
    return _mm(o, p["Wo"], low), sel.reshape(t, t)


# ------------------------------------------------------------ feed-forward
@functools.partial(jax.jit, static_argnames=("low",))
def gated(h, wg, wu, wd, *, low):
    """(silu(h W_gate) * h W_up) W_down."""
    a = stored(jax.nn.silu(_mm(h, wg, low)) * _mm(h, wu, low), low)
    return _mm(a, wd, low)


@functools.partial(jax.jit, static_argnames=("z", "n_group", "topk_group",
                                             "top_k", "factor", "low"))
def route(h, wr, br, *, z, n_group, topk_group, top_k, factor, low):
    """Gates [T, router] of the experts each token chose, 0 elsewhere."""
    n = z.router
    t = h.shape[0]
    sig = jax.nn.sigmoid(_mm(h, wr, low, keep_result=True))
    choice = sig + br.astype(jnp.float32)
    per = choice.reshape(t, n_group, n // n_group)
    group_score = jnp.sum(lax.top_k(per, 2)[0], axis=-1)         # [T, G]
    keep = _top_mask(group_score, topk_group)
    masked = jnp.where(jnp.repeat(keep, n // n_group, axis=1), choice,
                       -jnp.inf)
    chosen = _top_mask(masked, top_k)
    picked = jnp.where(chosen, sig, 0.0)
    return picked / jnp.sum(picked, axis=-1, keepdims=True) * factor


def experts(cfg, params, n, h, low, held=None):
    """shared(h) + sum over the held experts of gate * E(h); ``held``
    (first, count) is the range of the router's experts the leaves
    ``moe<n>/W[gud]`` hold (default: ``0 .. n_routed_experts``)."""
    z = _sizes(cfg)
    m = f"moe{n}"
    first, count = held or (0, z.held)
    gates = route(h, params[f"{m}/Wr"], params[f"{m}/br"],
                  z=z, n_group=cfg["n_group"],
                  topk_group=cfg["topk_group"],
                  top_k=cfg["num_experts_per_tok"],
                  factor=cfg["routed_scaling_factor"], low=low)
    y = gated(h, params[f"{m}/Ws_g"], params[f"{m}/Ws_u"],
              params[f"{m}/Ws_d"], low=low)
    for i in range(count):
        y = y + gates[:, first + i, None] * gated(
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
    keys = ("Wqa", "q_gamma", "Wqb", "Wkva", "kv_gamma", "Wkvb", "Wo",
            "Wiq", "Wik", "ik_gamma", "ik_beta", "Wiw")
    return {k: params[f"attn{n}/{k}"] for k in keys}


def _walk(cfg, params, ids, low):
    """The residual stream after the last layer [T, E], and every
    layer's selected sets [T, T]."""
    z = _sizes(cfg)
    eps = cfg["rms_norm_eps"]
    inv = jnp.asarray(yarn_inv_freq(cfg))
    # causal, and no token sees another but through attention: zeros past
    # the end change nothing before it, and a power of two keeps the
    # compiled lengths to one or two whatever the requests' own
    ids = list(ids) + [0] * ((1 << (len(ids) - 1).bit_length()) - len(ids))
    x = _embed(jnp.asarray(ids, jnp.int32), params["embed/W"], low=low)
    selected = []
    for n in range(z.layers):
        h = _normed(x, params[f"norm{n}a/gamma"], eps=eps, low=low)
        a, sel = attention(h, _attn_params(params, n), inv,
                           z=z, eps=eps,
                           scale=softmax_scale(cfg), low=low)
        selected.append(sel)
        x = stored(x + a, low)
        h = _normed(x, params[f"norm{n}b/gamma"], eps=eps, low=low)
        if is_dense(cfg, n):
            f = gated(h, params[f"ffn{n}/Wg"], params[f"ffn{n}/Wu"],
                      params[f"ffn{n}/Wd"], low=low)
        else:
            f = experts(cfg, params, n, h, low)
        x = stored(x + f, low)
    return x, selected


def logits_at(cfg, params, ids, positions, low=False):
    """Logits [len(positions), V] that follow ``ids[:p + 1]`` for each p in
    ``positions``, from one causal pass over the whole of ``ids`` (padded
    here with zeros to a power of two)."""
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
def _per_token_flops(cfg, absorbed: bool) -> int:
    """Products a token needs outside the position-dependent parts, all
    layers, 2 a multiply-add, at this chip's share."""
    z = _sizes(cfg)
    e, h = z.e, z.h
    attn = (e * z.ql + z.ql * h * (z.dn + z.dr)
            + e * (z.kl + z.dr) + h * z.dv * e
            # per-head: [k_nope | v] = c_kv W_kvb; absorbed: q_nope W_UK
            # and (p . c_kv) W_UV, the same count
            + z.kl * h * (z.dn + z.dv))
    index = z.ql * z.hi * z.di + e * z.di + e * z.hi
    dense = 3 * e * z.dense
    routed = cfg["num_experts_per_tok"] * z.held / z.router
    moe = e * z.router + 3 * e * z.moe * (1 + routed)
    n_dense = min(z.first_dense, z.layers)
    return int(2 * (z.layers * (attn + index) + n_dense * dense
                    + (z.layers - n_dense) * moe))


def _position_flops(cfg, scored: int, attended: int, absorbed: bool) -> int:
    """What the position-dependent parts add, all layers: ``scored``
    (query, index key) pairs and ``attended`` (query, selected position)
    pairs."""
    z = _sizes(cfg)
    width = (2 * z.kl + z.dr) if absorbed \
        else (z.dn + z.dr + z.dv)
    return 2 * z.layers * (scored * z.hi * z.di
                              + attended * z.h * width)


def head_flops(cfg) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def prefill_flops(cfg, prompt: int) -> int:
    """A prompt of ``prompt`` tokens, per-head attention: query t scores
    the t + 1 positions it may see and attends ``min(index_topk, t + 1)``
    of them; the embedding is a lookup and only the last position needs
    logits."""
    top = min(cfg["index_topk"], prompt)
    return (prompt * _per_token_flops(cfg, False)
            + _position_flops(
                cfg, prompt * (prompt + 1) // 2,
                top * (top + 1) // 2 + (prompt - top) * top, False)
            + head_flops(cfg))


def decode_flops(cfg, context: int) -> int:
    """One generated token whose query sees ``context`` positions
    (itself included), absorbed attention over the selected ones."""
    return (_per_token_flops(cfg, True)
            + _position_flops(cfg, context,
                              min(cfg["index_topk"], context), True)
            + head_flops(cfg))
