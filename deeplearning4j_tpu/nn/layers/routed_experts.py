"""The arithmetic of ``RoutedExpertsLayer`` (nn/conf/layers.py): the
router over ALL of a model's experts (grouped sigmoid, or softmax), and
the product over the experts this device holds.

The held experts' part is a grouped product: the (token, held expert)
pairs are laid out expert by expert, each expert's run padded to whole
tiles of ``tile`` rows, and a loop over the tiles that hold a pair (its
trip count is data: an expert no token chose costs nothing, and no token
is dropped whatever the imbalance) multiplies each tile by its expert's
three matrices and adds the gated result into the tokens' rows. On one
device there is no exchange: the tokens are already here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: rows of a tile of the grouped product (a dispatch of fewer tokens takes
#: the power of two that holds them, 16 at least): the MXU's 128
GROUP_TILE = 128


def gated_ffn(x, wg, wu, wd):
    """(silu(x W_gate) * x W_up) W_down over the last axis; products
    accumulate in float32, the hidden tensor is handed on in x's dtype."""
    a = jnp.dot(x, wg, preferred_element_type=jnp.float32)
    b = jnp.dot(x, wu, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(a) * b).astype(x.dtype)
    return jnp.dot(h, wd, preferred_element_type=jnp.float32)


def router_gates(x, wr, br, *, groups: int, top_groups: int, top_k: int,
                 scale: float, scoring: str = "sigmoid",
                 norm_topk: bool = True):
    """Gates [T, R] float32 over all R experts of the router, 0 where a
    token did not choose the expert. The scores are computed in float32
    whatever the compute dtype (a choice among hundreds of scores is not
    made in 8 bits of mantissa). Two scorings:

    - ``"sigmoid"``: ``sigma = sigmoid(x W_r)``; choice by ``sigma + b``:
      the ``top_groups`` groups whose two best sum highest, then the
      ``top_k`` best experts of those, ties to the lower index; gates
      ``sigma_i``;
    - ``"softmax"``: ``p = softmax(x W_r)`` over all R; the ``top_k``
      largest, ties to the lower index; gates ``p_i``; no groups and no
      bias (``br`` is not read).

    The chosen gates are renormalised to sum 1 where ``norm_topk``, and
    scaled by ``scale``."""
    t, r = x.shape[0], wr.shape[1]
    logits = jnp.matmul(x.astype(jnp.float32), wr.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    if scoring == "softmax":
        score = choice = jax.nn.softmax(logits, axis=-1)
    else:
        score = jax.nn.sigmoid(logits)
        choice = score + br.astype(jnp.float32)
    if groups > 1:
        per = choice.reshape(t, groups, r // groups)
        group_score = jnp.sum(lax.top_k(per, 2)[0], axis=-1)
        kept = lax.top_k(group_score, top_groups)[1]              # [T, g]
        keep = jnp.zeros((t, groups), bool).at[
            jnp.arange(t)[:, None], kept].set(True)
        choice = jnp.where(jnp.repeat(keep, r // groups, axis=1), choice,
                           -jnp.inf)
    chosen = lax.top_k(choice, top_k)[1]                          # [T, k]
    picked = jnp.take_along_axis(score, chosen, axis=1)
    if norm_topk:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return jnp.zeros((t, r), jnp.float32).at[
        jnp.arange(t)[:, None], chosen].set(picked * scale)


def dense_experts(x, gates, wg, wu, wd):
    """sum_g gates[:, g] E_g(x) with every held expert run over every
    token: the differentiable form the training forward uses. x [T, E],
    gates [T, G], the matrices [G, ...]. float32 out."""
    a = jnp.einsum("te,gei->tgi", x, wg, preferred_element_type=jnp.float32)
    b = jnp.einsum("te,gei->tgi", x, wu, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(a) * b * gates[:, :, None]).astype(x.dtype)
    return jnp.einsum("tgi,gie->te", h, wd,
                      preferred_element_type=jnp.float32)


def grouped_experts(x, gates, wg, wu, wd, *, tile: int, max_per_token: int):
    """The same sum by a grouped product. x [T, E], gates [T, G] float32
    (0 = not routed; a token routes to at most ``max_per_token`` held
    experts). Returns ``(y [T, E] float32, stats)`` with ``stats`` int32
    [3]: pairs routed, rows computed (whole tiles), the fullest expert's
    load."""
    t, g = gates.shape
    routed = gates > 0
    sizes = jnp.sum(routed, axis=0, dtype=jnp.int32)               # [G]
    padded = -(-sizes // tile) * tile
    ends = jnp.cumsum(padded)
    # where each pair goes: its expert's run, in token order
    rank = jnp.cumsum(routed, axis=0, dtype=jnp.int32) - 1
    n_rows = -(-(t * min(max_per_token, g)) // tile) * tile + g * tile
    dest = jnp.where(routed, (ends - padded)[None, :] + rank, n_rows)
    tokens = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[:, None],
                              (t, g))
    row_token = jnp.full((n_rows,), t, jnp.int32).at[dest.ravel()].set(
        tokens.ravel(), mode="drop")
    row_gate = jnp.zeros((n_rows,), jnp.float32).at[dest.ravel()].set(
        gates.ravel(), mode="drop")
    n_tiles = ends[-1] // tile
    tile_expert = jnp.searchsorted(
        ends, jnp.arange(n_rows // tile, dtype=jnp.int32) * tile,
        side="right").astype(jnp.int32)

    def one_tile(i, y):
        rows = lax.dynamic_slice(row_token, (i * tile,), (tile,))
        gate = lax.dynamic_slice(row_gate, (i * tile,), (tile,))
        e = jnp.minimum(tile_expert[i], g - 1)
        xg = jnp.take(x, rows, axis=0, mode="fill", fill_value=0)
        o = gated_ffn(xg, wg[e], wu[e], wd[e])
        # a padding row names token t: out of range, dropped
        return y.at[rows].add(o * gate[:, None], mode="drop")

    y = lax.fori_loop(0, n_tiles, one_tile,
                      jnp.zeros((t, x.shape[1]), jnp.float32))
    stats = jnp.stack([jnp.sum(sizes), n_tiles * tile, jnp.max(sizes)])
    return y, stats.astype(jnp.int32)
