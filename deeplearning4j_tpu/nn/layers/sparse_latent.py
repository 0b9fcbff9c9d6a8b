"""Pieces of the layers that select what they attend (nn/conf/layers.py:
``LatentAttentionLayer``, and ``SelfAttentionLayer`` with an indexer) that
are plain functions of arrays: YaRN rotary frequencies, the two pairing
conventions of the rotation, the index scores, the exact top-k selection
as a mask, and how a prime's queries go in blocks.

The selection is a search for the k-th largest score's bit pattern, 32
counting passes over the row, and no sort: a row of 8,192 scores for each
of 8,192 queries is selected in the time of a few elementwise passes,
where a sort of every row would dominate the prefill.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: queries of a per-head block (``query_groups``): a block's
#: [N, H, 128, L] float32 scores are what exists at once, 0.5 GB at 128
#: heads against 8,192 slots
QUERY_BLOCK = 128

#: what a masked score is set to: finite, so that a row with no valid
#: position (a left pad) is garbage and not NaN
MASKED = -1e30


def query_groups(t: int, slots: int, aligned: bool, block: int):
    """How ``t`` queries are taken against ``slots`` key slots:
    ``(block, pad, [(blocks, seen), ...])`` — queries in blocks of
    ``block`` (``pad`` rows added to fill the last), and per group of
    consecutive blocks how many it has and how many leading slots their
    scores span. Unaligned: one group, every slot. Aligned (slot for
    query): up to four groups, each against the prefix that ends where
    its last query stands, so that a block's scores span on average 5/8
    of the chunk and not all of it."""
    b = min(block, t)
    pad = -t % b
    n_blocks = (t + pad) // b
    groups = next(g for g in (4, 2, 1) if n_blocks % g == 0) \
        if aligned else 1
    per = n_blocks // groups
    return b, pad, [(per, min(slots, (g + 1) * per * b) if aligned
                     else slots) for g in range(groups)]


def layer_norm(x, gamma, beta, eps: float):
    """LayerNorm over the last axis, float32 statistics, x's dtype out
    (the index key's norm)."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, axis=-1, keepdims=True)
    return ((xf - mean) * lax.rsqrt(var + eps)
            * gamma.astype(jnp.float32)
            + beta.astype(jnp.float32)).astype(x.dtype)


def yarn_inv_freq(dim: int, base: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """[dim / 2] float32 rotary frequencies with the YaRN correction:
    dimensions turning more than ``beta_fast`` times over ``original``
    positions keep ``base^(-2i/dim)``, those turning fewer than
    ``beta_slow`` times are slowed by ``factor``, a linear ramp between.
    ``factor`` 1 is plain rope."""
    # host arithmetic in Python floats (doubles), float32 out: the same
    # values to the last bit as a float64 numpy computation rounds to
    freq = [float(base) ** (-i / dim) for i in range(0, dim, 2)]
    if factor == 1.0:
        return np.asarray(freq, np.float32)

    def turns_to_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(turns_to_dim(beta_fast)), 0)
    high = min(math.ceil(turns_to_dim(beta_slow)), dim - 1)
    out = []
    for i, f in enumerate(freq):
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        keep = 1.0 - ramp
        out.append(f / factor * (1 - keep) + f * keep)
    return np.asarray(out, np.float32)


def rope_tables(positions, inv_freq):
    """cos, sin [..., T, d/2] float32 for integer ``positions`` [..., T]."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    return jnp.cos(ang), jnp.sin(ang)


def _over_heads(table, x):
    """cos/sin [N|1, T, d/2] against x [N, T, (H,) d]: a head axis of 1
    where x has one."""
    return table[:, :, None] if x.ndim == 4 else table


def rope_interleaved(x, cos, sin):
    """Rotate pairs (2i, 2i + 1) of the last axis. x [N, T, (H,) d]."""
    shape = x.shape
    xf = x.astype(jnp.float32).reshape(shape[:-1] + (shape[-1] // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    c, s = _over_heads(cos, x), _over_heads(sin, x)
    return jnp.stack([a * c - b * s, a * s + b * c], -1).reshape(
        shape).astype(x.dtype)


def rope_half(x, cos, sin):
    """Rotate pairs (i, i + d/2) of the last axis. x [N, T, (H,) d]."""
    half = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    c, s = _over_heads(cos, x), _over_heads(sin, x)
    return jnp.concatenate([a * c - b * s, a * s + b * c],
                           -1).astype(x.dtype)


def _ordered_bits(scores):
    """uint32 keys in the order of the float32 scores (-0.0 as +0.0)."""
    bits = lax.bitcast_convert_type(scores.astype(jnp.float32) + 0.0,
                                    jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def top_k_mask(scores, valid, k: int):
    """[..., S] bool: of the ``valid`` positions of each row the ``k`` of
    highest score (all of them where fewer are valid), ties to the lower
    index. Exactly the set a stable descending sort would put first."""
    if k >= scores.shape[-1]:
        return valid
    key = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))

    def narrow(i, v):
        cand = v | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, v)

    # the largest value that at least k keys reach: the k-th largest key
    kth = lax.fori_loop(0, 32, narrow,
                        jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = key > kth[..., None]
    room = k - jnp.sum(above, axis=-1)
    level = (key == kth[..., None]) & valid
    first = jnp.cumsum(level, axis=-1) <= room[..., None]
    return above | (level & first)


def index_scores(qi, ki, w):
    """I [N, T, S] float32 = sum_j w[n, t, j] relu(qi[n, t, j] . ki[n, s]):
    qi [N, T, Hi, Di], ki [N, S, Di], w [N, T, Hi]."""
    dots = jnp.einsum("nqhd,nsd->nqhs", qi, ki,
                      preferred_element_type=jnp.float32)
    return jnp.sum(w.astype(jnp.float32)[..., None] * jax.nn.relu(dots),
                   axis=2)
