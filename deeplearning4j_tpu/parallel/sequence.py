"""Sequence/context parallelism: ring attention + all-to-all (Ulysses).

The reference (2017-era DL4J) has no attention and no sequence parallelism
(SURVEY §5 "Long-context"): its long-sequence story is truncated BPTT +
masking, which this framework already implements. This module is the
forward-looking long-context subsystem the TPU build treats as first-class:

- **Ring attention** (blockwise attention with KV rotation over the ICI
  ring): each device holds a sequence shard; K/V blocks rotate around the
  mesh axis via ``jax.lax.ppermute`` while a streaming (online-softmax)
  accumulator keeps the attention numerically exact. Memory per device is
  O(T_local²-free): only the local Q block and one in-flight KV block live
  in HBM, so context length scales linearly with the number of devices.
- **Ulysses / all-to-all attention**: ``jax.lax.all_to_all`` reshards from
  sequence-sharded to head-sharded, runs full local attention on each
  device's head slice, then reshards back. Cheaper collectives for models
  with enough heads; attention itself is unchanged.

Both are exact — outputs match single-device attention to float tolerance
(tested on an 8-device CPU mesh).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() gradients clean



def _validate_window(window, causal) -> None:
    """Shared gate for every sliding-window entry point."""
    if window is None:
        return
    if not causal:
        raise ValueError("window attention requires causal=True")
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")


def reference_attention(q, k, v, causal: bool = False):
    """Plain single-device scaled-dot-product attention, [B,H,T,D] layout.
    The correctness oracle for both parallel paths."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool))
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def blockwise_attention(q, k, v, causal: bool = False,
                        block_size: int = 512, key_mask=None,
                        use_pallas: Optional[bool] = None,
                        window: Optional[int] = None):
    """Single-device flash-style attention: lax.scan over KV blocks with
    an online-softmax accumulator — O(T·block) live memory instead of the
    [T,T] score matrix, so one chip handles long contexts that would OOM
    the naive path (32k+ at bf16). Exact to float tolerance vs
    reference_attention; XLA keeps each block's QK^T / PV matmuls on the
    MXU and the running (m, l, o) update fuses into their epilogue.

    On TPU, supported shapes dispatch to the Pallas flash-attention
    kernel (nn/layers/pallas_attention.py — ~4x faster at T=8k: the
    (m,l,acc) state stays in VMEM scratch across KV steps and causal
    blocks above the diagonal are skipped; see PERF.md). `use_pallas`
    None=auto, False=always scan, True=require the kernel. The kernel
    picks its own tuned block sizes; `block_size` governs the scan path.

    q,k,v: [B,H,T,D]. T is padded internally to a block multiple; padded
    keys are masked with NEG_INF so results are unaffected. `key_mask`
    [B,T] (1=valid) additionally NEG_INF-masks padded KEY positions of
    variable-length batches (zeroing K/V would still receive softmax
    mass — score 0 can exceed valid negative scores). `window=W` (causal
    only) restricts each query to its W most recent keys — Mistral-style
    local attention. On the Pallas kernel path, blocks fully outside the
    window are SKIPPED, so cost is O(T·W); the scan fallback applies the
    mask but still visits every block (O(T²) semantics-only).
    """
    from deeplearning4j_tpu.nn.layers.pallas_attention import (
        flash_attention, flash_attention_supported)
    _validate_window(window, causal)
    if use_pallas is None:
        use_pallas = (jax.default_backend() == "tpu"
                      and flash_attention_supported(q.shape))
    if use_pallas:
        return flash_attention(q, k, v, causal=causal, key_mask=key_mask,
                               window=window)
    B, H, T, D = q.shape
    Tk = k.shape[2]                     # may differ (cross attention)
    if causal and T != Tk:
        raise ValueError(f"causal attention needs Tq == Tk ({T} vs {Tk})")
    bs = int(min(block_size, Tk))
    pad = (-Tk) % bs
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    if key_mask is not None:
        km = jnp.pad(key_mask.astype(bool), ((0, 0), (0, pad)))
        kmb = km.reshape(B, -1, bs).transpose(1, 0, 2)   # [n_blocks,B,bs]
    n_blocks = (Tk + pad) // bs
    scale = jnp.float32(1.0 / np.sqrt(D))
    qf = q.astype(jnp.float32)
    kb = k.reshape(B, H, n_blocks, bs, D).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, n_blocks, bs, D).transpose(2, 0, 1, 3, 4)
    q_pos = jnp.arange(T)

    def body(carry, blk):
        m, l, o = carry
        if key_mask is not None:
            kc, vc, idx, kmc = blk
        else:
            kc, vc, idx = blk
            kmc = None
        s = jnp.einsum("bhqd,bhkd->bhqk", qf,
                       kc.astype(jnp.float32)) * scale
        k_pos = idx * bs + jnp.arange(bs)
        valid = k_pos < Tk                               # pad mask
        if causal:
            valid = valid[None, :] & (q_pos[:, None] >= k_pos[None, :])
            if window is not None:
                # sliding window: query i sees keys (i-window, i]
                valid = valid & (q_pos[:, None] - k_pos[None, :] < window)
        else:
            valid = jnp.broadcast_to(valid[None, :], (T, bs))
        s = jnp.where(valid[None, None], s, NEG_INF)
        if kmc is not None:  # variable-length key mask [B,bs]
            s = jnp.where(kmc[:, None, None, :], s, NEG_INF)
        blk_max = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, blk_max)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vc.astype(jnp.float32))
        return (m_new, l_new, o_new), None

    m0 = jnp.full((B, H, T), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, T), jnp.float32)
    o0 = jnp.zeros((B, H, T, D), jnp.float32)
    # remat the block body: reverse-mode through a plain scan would save
    # every block's [T, block] score/softmax matrices (OOM at long T);
    # checkpointing recomputes them in backward so only the (m, l, o)
    # carries persist — the flash-attention backward memory profile.
    xs = (kb, vb, jnp.arange(n_blocks))
    if key_mask is not None:
        xs = xs + (kmb,)
    (m, l, o), _ = jax.lax.scan(jax.checkpoint(body), (m0, l0, o0), xs)
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def _ring_steps_needed(n: int, T: int, window: Optional[int]) -> int:
    """How many ring steps any device can need. Without a window: all n.
    With a sliding window W, the chunk s hops back starts (s-1)*T+1
    positions before the oldest query on every device — once that
    exceeds W-1 no device can see ANY of it, so the loop (and its
    ppermutes) stops: O(W) work and traffic per device."""
    if window is None:
        return n
    steps = 1
    while steps < n and (steps - 1) * T + 1 < window:
        steps += 1
    return steps


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool, n: int,
                          window: Optional[int] = None):
    """Per-shard ring attention body (runs under shard_map).

    q,k,v: [B,H,T_local,D] — this device's sequence shard. K/V blocks
    rotate ring-wise; a streaming softmax (running max m, normalizer l,
    weighted sum o) accumulates exact attention over the full sequence.
    The step loop is a Python loop over the STATIC axis size so a sliding
    window truncates it (and its ppermutes) at _ring_steps_needed."""
    my = jax.lax.axis_index(axis_name)
    scale = jnp.float32(1.0 / np.sqrt(q.shape[-1]))
    B, H, T, D = q.shape
    qf = q.astype(jnp.float32)

    m0 = jnp.full((B, H, T), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, T), jnp.float32)
    o0 = jnp.zeros((B, H, T, D), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    q_pos = my * T + jnp.arange(T)                     # global query positions
    steps = _ring_steps_needed(n, T, window) if causal else n

    @jax.checkpoint  # flash-style backward: recompute per-step scores
    def attend(step, k_c, v_c, m, l, o):
        src = (my - step) % n                          # origin shard of k_c
        s = jnp.einsum("bhqd,bhkd->bhqk", qf,
                       k_c.astype(jnp.float32)) * scale
        if causal:
            k_pos = src * T + jnp.arange(T)
            mask = q_pos[:, None] >= k_pos[None, :]    # [T,T]
            if window is not None:
                mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
            s = jnp.where(mask[None, None], s, NEG_INF)
        blk_max = jnp.max(s, axis=-1)                  # [B,H,T]
        m_new = jnp.maximum(m, blk_max)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_c.astype(jnp.float32))
        return m_new, l_new, o_new

    if steps == n:
        # full ring: the original rolled loop (one compiled body, not n)
        def body(step, carry):
            k_c, v_c, m, l, o = carry
            m, l, o = attend(step, k_c, v_c, m, l, o)
            k_r = jax.lax.ppermute(k_c, axis_name, perm)
            v_r = jax.lax.ppermute(v_c, axis_name, perm)
            return k_r, v_r, m, l, o

        _, _, m, l, o = jax.lax.fori_loop(0, n, body, (k, v, m0, l0, o0))
    else:
        # window-truncated ring: unrolled so the loop (and its
        # ppermutes) STOPS after `steps` hops — O(W) per device
        m, l, o = m0, l0, o0
        k_c, v_c = k, v
        for step in range(steps):
            m, l, o = attend(jnp.int32(step), k_c, v_c, m, l, o)
            if step < steps - 1:
                k_c = jax.lax.ppermute(k_c, axis_name, perm)
                v_c = jax.lax.ppermute(v_c, axis_name, perm)
    # fully-masked rows (can't happen for causal with step 0 = own block,
    # but guard anyway) normalize to zero
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _ring_attention_local_flash(q, k, v, *, axis_name: str, causal: bool,
                                interpret: bool, n: int,
                                window: Optional[int] = None):
    """Ring attention with the Pallas flash kernel as the per-chunk
    engine: each ring step computes (o_i, lse_i) for this device's
    queries against the visiting KV chunk and merges with the running
    accumulator by the logaddexp rule.

    The step loop is a Python loop over the STATIC axis size, so the
    per-step chunk distance is a compile-time constant: step s attends
    the chunk s hops back as BANDED attention (causal + window masks with
    q_offset = s*T — the kernel's block skip then prunes out-of-band
    blocks), devices whose chunk would wrap (future chunk) take a
    lax.cond skip, and with a sliding window the loop itself stops at
    _ring_steps_needed — O(W) compute AND ppermute traffic per device."""
    from deeplearning4j_tpu.nn.layers.pallas_attention import (
        flash_attention_lse)
    my = jax.lax.axis_index(axis_name)
    B, H, T, D = q.shape

    o0 = jnp.zeros((B, H, T, D), jnp.float32)
    lse0 = jnp.full((B, H, T), NEG_INF, jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    steps = _ring_steps_needed(n, T, window) if causal else n

    def merge(o, lse, o_i, lse_i):
        lse_new = jnp.logaddexp(lse, lse_i)
        w_old = jnp.exp(lse - lse_new)[..., None]
        w_new = jnp.exp(lse_i - lse_new)[..., None]
        return o * w_old + o_i * w_new, lse_new

    if window is None:
        # full ring: rolled loop with the full/diag/skip trichotomy —
        # exactly TWO kernel specializations regardless of ring size
        def _full(ops):
            o, lse = flash_attention_lse(q, ops[0], ops[1], causal=False,
                                         interpret=interpret)
            return o.astype(jnp.float32), lse

        def _diag(ops):
            o, lse = flash_attention_lse(q, ops[0], ops[1], causal=True,
                                         interpret=interpret)
            return o.astype(jnp.float32), lse

        def _skip(ops):
            return o0, lse0

        def body(step, carry):
            k_c, v_c, o, lse = carry
            src = (my - step) % n                  # origin shard of k_c
            if causal:
                branch = jnp.where(src < my, 0, jnp.where(src == my, 1, 2))
                o_i, lse_i = jax.lax.switch(branch, [_full, _diag, _skip],
                                            (k_c, v_c))
            else:
                o_i, lse_i = _full((k_c, v_c))
            o, lse = merge(o, lse, o_i, lse_i)
            k_r = jax.lax.ppermute(k_c, axis_name, perm)
            v_r = jax.lax.ppermute(v_c, axis_name, perm)
            return k_r, v_r, o, lse

        _, _, o, lse = jax.lax.fori_loop(0, n, body, (k, v, o0, lse0))
        return o.astype(q.dtype)

    # windowed ring: unrolled over the (window-truncated) static step
    # count — each step's chunk distance is a compile-time constant, so
    # step s runs as BANDED attention with q_offset = s*T (the kernel's
    # block skip prunes out-of-band blocks) and the loop + ppermutes stop
    # at _ring_steps_needed: O(W) compute AND ring traffic per device
    o, lse = o0, lse0
    k_c, v_c = k, v
    for step in range(steps):
        if step == 0:
            o_i, lse_i = flash_attention_lse(q, k_c, v_c, causal=True,
                                             window=window,
                                             interpret=interpret)
            o_i = o_i.astype(jnp.float32)
        else:
            def _band(ops, _step=step):
                oo, ll = flash_attention_lse(
                    q, ops[0], ops[1], causal=True, window=window,
                    q_offset=_step * T, interpret=interpret)
                return oo.astype(jnp.float32), ll

            def _skipw(ops):
                return o0, lse0

            # devices whose chunk-s-back wraps around see a FUTURE chunk
            o_i, lse_i = jax.lax.cond(my >= step, _band, _skipw, (k_c, v_c))
        o, lse = merge(o, lse, o_i, lse_i)
        if step < steps - 1:
            k_c = jax.lax.ppermute(k_c, axis_name, perm)
            v_c = jax.lax.ppermute(v_c, axis_name, perm)
    return o.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, axis: str = "data",
                   causal: bool = False,
                   use_flash: Optional[bool] = None,
                   interpret: bool = False,
                   window: Optional[int] = None):
    """Exact attention over a sequence sharded on ``mesh[axis]``.

    q/k/v: [B,H,T,D] global arrays (T divisible by the axis size). Returns
    [B,H,T,D]. Under jit the ppermutes ride ICI neighbor links — the
    canonical ring schedule.

    `window=W` (causal only) gives Mistral-style sliding-window local
    attention under sequence parallelism: ring chunks fully outside the
    window are never visited (the step loop stops once the chunk distance
    exceeds W), making cost — compute and ring traffic — O(W) per device
    instead of O(T).

    On TPU with supported shapes the per-chunk engine is the Pallas flash
    kernel (_ring_attention_local_flash: per-chunk (o, lse) merged by
    logaddexp, with banded q_offset chunks under a window); otherwise the
    lax online-softmax body. `use_flash` None=auto, and `interpret=True`
    runs the kernel in interpreter mode (tests on CPU)."""
    from deeplearning4j_tpu.nn.layers.pallas_attention import (
        flash_attention_supported)
    _validate_window(window, causal)
    size = mesh.shape[axis]
    if use_flash is None:
        local = (q.shape[0], q.shape[1], q.shape[2] // size, q.shape[3])
        use_flash = (jax.default_backend() == "tpu"
                     and flash_attention_supported(local))
    spec = P(None, None, axis, None)
    if use_flash:
        local_fn = functools.partial(_ring_attention_local_flash,
                                     axis_name=axis, causal=causal,
                                     interpret=interpret, n=size,
                                     window=window)
    else:
        local_fn = functools.partial(_ring_attention_local, axis_name=axis,
                                     causal=causal, n=size, window=window)
    fn = shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v)


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool,
                   window: Optional[int] = None):
    """Per-shard Ulysses body: all_to_all seq→head shards, local full
    attention, all_to_all back. q,k,v: [B,H,T_local,D]; H divisible by n."""
    def seq_to_heads(x):
        # [B,H,T_local,D] -> [B,H/n,T_global,D]
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # blockwise core: O(T·block) memory for the full-length local
    # attention (the naive [T,T] score matrix defeats the point of
    # sharding long sequences), and the Pallas flash kernel on TPU.
    # No fp32 pre-cast: both engines accumulate in fp32 internally, and
    # bf16 inputs keep the MXU rate / halve the gathered-copy traffic.
    # A sliding window passes straight through: after the head reshard
    # each device holds the FULL sequence, so the engine's own block
    # skipping delivers the O(T·W) cost.
    out = blockwise_attention(qh, kh, vh, causal=causal, window=window)
    return heads_to_seq(out.astype(q.dtype))


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "data",
                      causal: bool = False, window: Optional[int] = None):
    """All-to-all (DeepSpeed-Ulysses-style) sequence-parallel attention.
    Requires num_heads % axis_size == 0."""
    n = mesh.shape[axis]
    if q.shape[1] % n != 0:
        raise ValueError(
            f"ulysses needs heads ({q.shape[1]}) divisible by mesh axis "
            f"'{axis}' size ({n}); use ring_attention otherwise")
    _validate_window(window, causal)
    spec = P(None, None, axis, None)
    fn = shard_map(
        functools.partial(_ulysses_local, axis_name=axis, causal=causal,
                          window=window),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


class MultiHeadSelfAttention:
    """Minimal MHA block wired for sequence parallelism: projections are
    plain (replicated) matmuls; the attention core is ring/ulysses/local.

    x: [B,T,E] → [B,T,E]. A post-parity extension (the reference has no
    attention layer); exists so long-context models can be built and the
    sequence-parallel paths exercised end-to-end in training steps.
    """

    def __init__(self, embed_dim: int, num_heads: int,
                 impl: str = "ring", causal: bool = True,
                 window: Optional[int] = None):
        if embed_dim % num_heads:
            raise ValueError("embed_dim must divide by num_heads")
        _validate_window(window, causal)
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        if impl not in ("ring", "ulysses", "local", "blockwise", "flash"):
            raise ValueError(f"unknown attention impl {impl!r}")
        self.impl = impl
        self.causal = causal
        self.window = window

    def init(self, rng: jax.Array):
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        s = 1.0 / np.sqrt(self.embed_dim)
        E = self.embed_dim
        return {
            "wq": jax.random.normal(k1, (E, E)) * s,
            "wk": jax.random.normal(k2, (E, E)) * s,
            "wv": jax.random.normal(k3, (E, E)) * s,
            "wo": jax.random.normal(k4, (E, E)) * s,
        }

    def apply(self, params, x, mesh: Optional[Mesh] = None,
              axis: str = "data"):
        B, T, E = x.shape
        H, D = self.num_heads, self.head_dim

        def heads(u):  # [B,T,E] -> [B,H,T,D]
            return u.reshape(B, T, H, D).transpose(0, 2, 1, 3)

        q, k, v = (heads(x @ params[w]) for w in ("wq", "wk", "wv"))
        # no mesh: ring/ulysses fall back to the single-device blockwise
        # kernel (exact to float tolerance; memory-safe for long T)
        if self.impl == "flash":
            from deeplearning4j_tpu.nn.layers.pallas_attention import (
                flash_attention, flash_attention_supported)
            if not flash_attention_supported(q.shape):
                raise ValueError(
                    f"impl='flash' unsupported for q shape {q.shape}: head "
                    "dim must be one of (64, 128, 256) and T >= 128")
            if jax.default_backend() != "tpu":
                o = blockwise_attention(q, k, v, causal=self.causal,
                                        use_pallas=False,  # CPU fallback
                                        window=self.window)
            else:
                o = flash_attention(q, k, v, causal=self.causal,
                                    window=self.window)
        elif self.impl == "blockwise" or \
                (mesh is None and self.impl != "local"):
            o = blockwise_attention(q, k, v, causal=self.causal,
                                    window=self.window)
        elif self.impl == "local":
            if self.window is not None:
                raise ValueError("impl='local' does not support window")
            o = reference_attention(q, k, v, causal=self.causal)
        elif self.impl == "ring":
            o = ring_attention(q, k, v, mesh, axis=axis, causal=self.causal,
                               window=self.window)
        else:
            o = ulysses_attention(q, k, v, mesh, axis=axis,
                                  causal=self.causal, window=self.window)
        o = o.transpose(0, 2, 1, 3).reshape(B, T, E)
        return o @ params["wo"]
