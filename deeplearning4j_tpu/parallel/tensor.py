"""Tensor (model) parallelism: Megatron-style sharded transformer blocks.

Beyond the reference's data-parallel-only scale-out (SURVEY §2.5 — all
four reference strategies shard the BATCH), TPU meshes make intra-layer
model sharding first-class: this module shards attention heads and FFN
hidden units over a "model" mesh axis with the canonical Megatron
layout —

- attention: Wq/Wk/Wv column-sharded (each device owns H/n heads, runs
  its heads' attention locally), Wo row-sharded, one psum to rebuild the
  residual stream;
- MLP: W1 column-sharded (hidden/n per device), W2 row-sharded, one psum.

Two collectives per block, both riding ICI. Composes with the "data"
axis (dp x tp meshes) and with sequence parallelism (parallel/sequence)
on the same mesh. Exactness vs the single-device math is tested on the
virtual 8-device mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.sequence import blockwise_attention


def _gqa_kv_sharded(n_kv_heads, tp) -> bool:
    """Can the KV heads themselves be column-sharded over tp devices?
    Yes when each device owns n_kv_heads/tp whole KV heads; otherwise
    (tp > n_kv_heads) KV params stay replicated and each device slices
    its group's head locally (GQA KV params are small by design)."""
    return n_kv_heads % tp == 0


def _validate_gqa(n_heads, n_kv_heads, tp) -> None:
    if n_heads % n_kv_heads:
        raise ValueError(f"n_heads {n_heads} not divisible by n_kv_heads "
                         f"{n_kv_heads}")
    if not _gqa_kv_sharded(n_kv_heads, tp) and tp % n_kv_heads:
        raise ValueError(
            f"tensor-parallel GQA needs n_kv_heads ({n_kv_heads}) "
            f"divisible by tp ({tp}) or tp divisible by n_kv_heads "
            "(head-group replication would straddle devices otherwise)")


def shard_mha_params(params: Dict, mesh: Mesh, axis: str = "model",
                     n_kv_heads=None, n_heads=None):
    """Place MultiHeadSelfAttention-style params {wq,wk,wv,wo} (or the
    SelfAttentionLayer spelling {Wq,...,bq,...}) with the Megatron
    layout: q/k/v column-sharded, o row-sharded.

    Grouped-query attention (Wk/Wv narrower than Wq): pass `n_kv_heads`
    (+ `n_heads` for validation). KV params column-shard when each
    device owns whole KV heads (n_kv_heads % tp == 0); with tp >
    n_kv_heads the KV heads are REPLICATED and tp_mha slices each
    device's group head locally — q/o sharding is unchanged either way."""
    tp = mesh.shape[axis]
    wq = next((v for k, v in params.items() if k.lower() == "wq"), None)
    wk = next((v for k, v in params.items() if k.lower() == "wk"), None)
    gqa = (wq is not None and wk is not None and wq.shape != wk.shape)
    if gqa:
        if n_kv_heads is None:
            raise ValueError(
                "grouped-query attention params (Wk width "
                f"{wk.shape[1]} != Wq width {wq.shape[1]}): pass "
                "n_kv_heads to shard_mha_params")
        if n_heads is None:
            # infer from the widths: d = Wk_width / n_kv_heads
            d, rem = divmod(wk.shape[1], n_kv_heads)
            if rem or wq.shape[1] % d:
                raise ValueError(
                    f"Wk width {wk.shape[1]} not divisible by n_kv_heads "
                    f"{n_kv_heads} (or Wq width {wq.shape[1]} not a "
                    "multiple of the head dim)")
            n_heads = wq.shape[1] // d
        _validate_gqa(n_heads, n_kv_heads, tp)
    kv_col = (not gqa) or _gqa_kv_sharded(n_kv_heads, tp)
    col = NamedSharding(mesh, P(None, axis))
    row = NamedSharding(mesh, P(axis, None))
    vec = NamedSharding(mesh, P(axis))
    rep = NamedSharding(mesh, P())
    out = {}
    for k, v in params.items():
        lk = k.lower()
        if lk == "wq":
            out[k] = jax.device_put(v, col)
        elif lk in ("wk", "wv"):
            out[k] = jax.device_put(v, col if kv_col else rep)
        elif lk == "wo":
            out[k] = jax.device_put(v, row)
        elif lk == "bq":
            out[k] = jax.device_put(v, vec)
        elif lk in ("bk", "bv"):
            out[k] = jax.device_put(v, vec if kv_col else rep)
        else:  # bo and anything else replicated
            out[k] = jax.device_put(v, rep)
    return out


def tp_mha(params: Dict, x, mesh: Mesh, n_heads: int,
           axis: str = "model", causal: bool = True,
           block_size: int = 512, batch_axis: str = None,
           n_kv_heads: int = None):
    """Tensor-parallel multi-head self-attention.

    x: [B,T,E]; params as in shard_mha_params (keys wq/wk/wv/wo +
    optional biases, any capitalization; missing biases are treated as
    zero). Each device computes its H/n heads with the blockwise kernel;
    the row-sharded output projection psums (over the model axis only)
    back to the full residual. `batch_axis` additionally shards B over a
    data axis of the same mesh (dp x tp composition). Output == the
    unsharded math.

    Grouped-query attention: pass `n_kv_heads` < n_heads (Wk/Wv of width
    n_kv_heads*head_dim). With n_kv_heads % tp == 0 the KV heads are
    column-sharded like Q; with tp > n_kv_heads each device holds the
    replicated KV params and slices the ONE head its query group reads
    (head-group replication). Q-head blocks stay aligned with their KV
    group either way because both shards are contiguous."""
    n = mesh.shape[axis]
    if n_heads % n:
        raise ValueError(f"n_heads {n_heads} not divisible by mesh axis "
                         f"'{axis}' size {n}")
    gqa = n_kv_heads is not None and n_kv_heads != n_heads
    if gqa:
        _validate_gqa(n_heads, n_kv_heads, n)
    kv_col = (not gqa) or _gqa_kv_sharded(n_kv_heads, n)
    E = x.shape[-1]
    d = E // n_heads
    kv_width = (n_kv_heads if gqa else n_heads) * d
    keys = {k.lower(): k for k in params}

    def get(name, width):
        if name in keys:
            return params[keys[name]]
        return jnp.zeros((width,), x.dtype)  # absent bias = zero

    xspec = P(batch_axis, None, None) if batch_axis else P()
    col, row, colb, rep = P(None, axis), P(axis, None), P(axis), P()
    kvspec = col if kv_col else rep
    kvbspec = colb if kv_col else rep

    @partial(shard_map, mesh=mesh,
             in_specs=(xspec, col, kvspec, kvspec, row, colb, kvbspec,
                       kvbspec, rep),
             out_specs=xspec, check_vma=False)
    def fwd(x, wq, wk, wv, wo, bq, bk, bv, bo):
        B, T, _ = x.shape
        h_local = n_heads // n

        def heads(y):
            return y.reshape(B, T, -1, d).transpose(0, 2, 1, 3)

        q = heads(x @ wq + bq)                  # [B, h_local, T, d]
        k = heads(x @ wk + bk)                  # [B, kv_local, T, d]
        v = heads(x @ wv + bv)
        if gqa:
            if kv_col:
                # device owns n_kv_heads/n whole KV heads; its q heads
                # [i*h_local, (i+1)*h_local) group onto exactly those
                reps = n_heads // n_kv_heads
            else:
                # replicated KV: this device's whole q block reads ONE
                # head — slice it by model-axis position
                group = jax.lax.axis_index(axis) // (n // n_kv_heads)
                k = jax.lax.dynamic_slice_in_dim(k, group, 1, axis=1)
                v = jax.lax.dynamic_slice_in_dim(v, group, 1, axis=1)
                reps = h_local
            k = jnp.repeat(k, reps, axis=1)
            v = jnp.repeat(v, reps, axis=1)
        o = blockwise_attention(q, k, v, causal=causal,
                                block_size=block_size)
        o = o.transpose(0, 2, 1, 3).reshape(B, T, E // n)
        out = jax.lax.psum(o @ wo, axis)  # row-parallel projection
        return out + bo

    return fwd(x, params[keys["wq"]], params[keys["wk"]],
               params[keys["wv"]], params[keys["wo"]],
               get("bq", E), get("bk", kv_width), get("bv", kv_width),
               get("bo", E))


def tp_mlp(params: Dict, x, mesh: Mesh, axis: str = "model",
           activation=jax.nn.gelu, batch_axis: str = None):
    """Tensor-parallel position-wise MLP: W1 [E,F] column-sharded,
    W2 [F,E] row-sharded, biases b1 sharded / b2 replicated. One psum
    (over the model axis only — composes with `batch_axis` dp)."""
    xspec = P(batch_axis, None, None) if batch_axis else P()

    @partial(shard_map, mesh=mesh,
             in_specs=(xspec, P(None, axis), P(axis), P(axis, None), P()),
             out_specs=xspec, check_vma=False)
    def fwd(x, w1, b1, w2, b2):
        h = activation(x @ w1 + b1)
        return jax.lax.psum(h @ w2, axis) + b2

    return fwd(x, params["W1"], params["b1"], params["W2"], params["b2"])


def make_tp_mesh(n_data: int, n_model: int, devices=None) -> Mesh:
    """2-D dp x tp mesh ("data", "model") — the composed layout the
    dryrun exercises. Thin wrapper over parallel.mesh.make_mesh (which
    validates the device count)."""
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    devices = devices if devices is not None \
        else jax.devices()[:n_data * n_model]
    return make_mesh(shape=(n_data, n_model),
                     axis_names=("data", "model"), devices=devices)
