"""Expert parallelism: mixture-of-experts FFN with experts sharded over
an "expert" mesh axis.

The fifth parallelism axis (dp — parallel/wrapper, sp — parallel/sequence,
tp — parallel/tensor, pp — parallel/pipeline): each device owns ONE
expert's FFN parameters (the memory-scaling point of ep — total expert
capacity grows linearly with devices), a shared router picks the top-1
expert per token, every device computes its expert on the tokens routed
to it (gate-masked), and one psum combines the expert outputs. The
load-balancing auxiliary loss follows the standard Switch-Transformer
recipe (routing itself is deterministic — no router jitter).

Correctness-first formulation: computation per device is dense over the
token batch with routed-token masking (capacity == batch; the classic
all_to_all capacity-C dispatch is a throughput refinement on top of the
same math). Exactness vs the unsharded all-experts reference and
gradient equality are tested on the virtual mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def init_moe_params(key, embed_dim: int, ffn_dim: int, n_experts: int,
                    scale: float = 0.1) -> Dict:
    """Router + stacked expert FFN params (leading expert axis)."""
    ks = jax.random.split(key, 3)
    return {
        "Wg": (jax.random.normal(ks[0], (embed_dim, n_experts))
               * scale).astype(jnp.float32),
        "W1": (jax.random.normal(ks[1], (n_experts, embed_dim, ffn_dim))
               * scale).astype(jnp.float32),
        "b1": jnp.zeros((n_experts, ffn_dim), jnp.float32),
        "W2": (jax.random.normal(ks[2], (n_experts, ffn_dim, embed_dim))
               * scale).astype(jnp.float32),
        "b2": jnp.zeros((n_experts, embed_dim), jnp.float32),
    }


def shard_moe_params(params: Dict, mesh: Mesh, axis: str = "expert"):
    """Experts sharded over the axis; router replicated."""
    out = {}
    for k, v in params.items():
        if k == "Wg":
            out[k] = jax.device_put(v, NamedSharding(mesh, P()))
        else:
            out[k] = jax.device_put(v, NamedSharding(
                mesh, P(*([axis] + [None] * (v.ndim - 1)))))
    return out


def moe_reference(params: Dict, x, activation=jax.nn.gelu):
    """Unsharded top-1 MoE (the correctness oracle): every expert runs,
    each token takes its argmax expert's output scaled by the gate."""
    logits = x @ params["Wg"]                         # [B,T,N]
    probs = jax.nn.softmax(logits, axis=-1)
    best = jnp.argmax(probs, axis=-1)                 # [B,T]
    gate = jnp.take_along_axis(probs, best[..., None], -1)[..., 0]
    h = activation(jnp.einsum("bte,nef->btnf", x, params["W1"])
                   + params["b1"])
    y = jnp.einsum("btnf,nfe->btne", h, params["W2"]) + params["b2"]
    sel = jax.nn.one_hot(best, probs.shape[-1], dtype=x.dtype)
    return jnp.einsum("btne,btn->bte", y, sel) * gate[..., None]


def moe_mlp(params: Dict, x, mesh: Mesh, axis: str = "expert",
            activation=jax.nn.gelu, batch_axis: str = None):
    """Expert-parallel top-1 MoE FFN. x: [B,T,E]; params as in
    init_moe_params/shard_moe_params with n_experts == axis size.
    Returns (y, aux_loss) — aux is the Switch load-balance term
    (n_experts * sum_e fraction_e * prob_e)."""
    n = mesh.shape[axis]
    n_exp = params["W1"].shape[0]
    if n_exp != n:
        raise ValueError(f"{n_exp} experts but mesh axis '{axis}' has "
                         f"{n} devices (one expert per device)")
    xspec = P(batch_axis, None, None) if batch_axis else P()
    espec = lambda v: P(*([axis] + [None] * (v.ndim - 1)))  # noqa: E731

    @partial(shard_map, mesh=mesh,
             in_specs=(xspec, P(), espec(params["W1"]),
                       espec(params["b1"]), espec(params["W2"]),
                       espec(params["b2"])),
             out_specs=(xspec, P()), check_vma=False)
    def fwd(x, wg, w1, b1, w2, b2):
        me = jax.lax.axis_index(axis)
        logits = x @ wg                               # [b,T,N] (global N)
        probs = jax.nn.softmax(logits, axis=-1)
        best = jnp.argmax(probs, axis=-1)             # [b,T]
        gate = jnp.take_along_axis(probs, best[..., None], -1)[..., 0]
        mine = (best == me).astype(x.dtype)           # routed to my expert
        h = activation(x @ w1[0] + b1[0])
        y = (h @ w2[0] + b2[0]) * (gate * mine)[..., None]
        y = jax.lax.psum(y, axis)
        # Switch aux loss: n * sum_e (token fraction to e) * (mean prob e)
        frac = jax.lax.psum(
            jnp.mean(mine) * jax.nn.one_hot(me, n_exp), axis)
        mean_p = jnp.mean(probs, axis=(0, 1))
        if batch_axis:
            frac = jax.lax.pmean(frac, batch_axis)
            mean_p = jax.lax.pmean(mean_p, batch_axis)
        aux = n_exp * jnp.sum(frac * mean_p)
        return y, aux

    return fwd(x, params["Wg"], params["W1"], params["b1"],
               params["W2"], params["b2"])
