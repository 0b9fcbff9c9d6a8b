"""Pallas TPU flash attention: fused multi-head attention kernel.

The framework's long-context attention hot op. The lax.scan blockwise path
(parallel/sequence.py blockwise_attention) is exact but leaves perf on the
table: every scan step computes scores for ALL T queries against one KV
block (no query blocking), fully-masked causal blocks are still computed,
and the accumulators round-trip through HBM between steps. This kernel is
the standard flash-attention schedule on the TPU memory hierarchy:

- grid (B, H, nq, nk), KV innermost: the [bq, D] query block and the
  (m, l, acc) online-softmax state live in VMEM scratch across all KV
  steps — one HBM read per Q/K/V block, one HBM write per output block.
- causal blocks strictly above the diagonal are skipped (roughly 2x for
  long causal sequences), and in-block masking handles the diagonal.
- blocks that need no masking at all (fully below the diagonal, no key
  padding, no user mask) take a fast path with zero mask VPU ops — the
  exp is the VPU bottleneck, so iota/compare/select per score matter.
- QK^T / PV matmuls run on the MXU in the input dtype (bf16) with fp32
  accumulation; softmax statistics are fp32 throughout.
- backward is the recompute form (Dao et al. 2022): forward saves only
  the [B,H,T] logsumexp; dq and dk/dv kernels rebuild the probabilities
  per block — the same memory profile the cuDNN fused-attention path
  gives the reference's GPU stack (SURVEY §2.1 fused-op parity row).

Layout [B, H, T, D], same as parallel/sequence.py. Exactness vs
reference_attention is covered by tests/test_pallas_attention.py; the
real-chip numbers live in PERF.md.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite: (-inf) - (-inf) = nan inside exp would poison rows

LOG2E = float(np.log2(np.e))   # fwd runs the online softmax in base 2:
LN2 = float(np.log(2.0))       # exp2((s-m)*log2e) == exp(s-m) exactly, but
#                                exp2 skips the VPU's internal x*log2e step
#                                (one multiply per score); lse converts back
#                                to natural log at the block boundary

# 1024/1024 measured fastest on v5e at T=8k/D=128 (sweep in PERF.md)
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024

# 1024x1024 blocks hold [bq, bk] fp32 score/probability temporaries of
# 4 MiB each; with float32 inputs at D=256 the kernels need more than
# Mosaic's 16 MiB default scoped limit (measured on v5e, jax 0.9.0: the
# forward and both backward kernels are refused at T >= 2048 without
# this). v5e has 128 MiB of VMEM.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


def _causal_needed(i, j, bq, bk, window=None, q_offset=0):
    """Is KV block j visible to any query in Q block i? (block-skip test:
    causal upper bound, plus the sliding-window lower bound when set).
    `q_offset` (static) shifts query positions — ring attention runs past
    KV chunks as banded attention with q_offset = chunk distance."""
    q0 = q_offset + i * bq
    needed = q0 + bq - 1 >= j * bk
    if window is not None:
        # some key in the block is within (q - window, q] for some query
        needed = jnp.logical_and(needed,
                                 j * bk + bk - 1 > q0 - window)
    return needed


def _block_mask(i, j, bq, bk, causal: bool, kmask_row, window=None,
                q_offset=0):
    """[bq, bk] validity mask for one (Q block, KV block) pair.
    kmask_row: [1, bk]."""
    valid = jnp.broadcast_to(kmask_row.astype(bool), (bq, bk))
    if causal:
        q_pos = (q_offset + i * bq
                 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = valid & (q_pos >= k_pos)
        if window is not None:
            valid = valid & (q_pos - k_pos < window)
    return valid


def _dispatch(i, j, fast_fn, masked_fn, *, causal, bq, bk, nk,
              first_pad, user_mask, window=None, q_offset=0):
    """Run the fast (no mask VPU ops) or masked block body.

    Masking is needed only for diagonal-straddling causal blocks, blocks
    straddling a sliding-window edge, KV blocks containing padded keys
    (j >= first_pad — padding can span multiple tail blocks when the
    block sizes differ), or when a user key mask exists (then always).
    Blocks fully above the causal diagonal or fully OUTSIDE the window
    are skipped entirely — with `window` set, cost is O(T*W)."""
    if user_mask:
        if causal:
            pl.when(_causal_needed(i, j, bq, bk, window,
                                   q_offset))(masked_fn)
        else:
            masked_fn()
        return
    tail = (j >= first_pad) if first_pad is not None else None
    if causal:
        needed = _causal_needed(i, j, bq, bk, window, q_offset)
        q0 = q_offset + i * bq
        interior = q0 >= j * bk + bk - 1       # no in-block causal mask
        if window is not None:
            # every pair also inside the window: max(q) - min(k) < W
            interior = jnp.logical_and(
                interior, q0 + bq - 1 - j * bk < window)
        fast = jnp.logical_and(needed, interior)
        if tail is not None:
            fast = jnp.logical_and(fast, jnp.logical_not(tail))
        pl.when(fast)(fast_fn)
        pl.when(jnp.logical_and(needed, jnp.logical_not(fast)))(masked_fn)
    elif tail is None:
        fast_fn()
    else:
        pl.when(jnp.logical_not(tail))(fast_fn)
        pl.when(tail)(masked_fn)


def _fwd_kernel(q_ref, k_ref, v_ref, km_ref, o_ref, lse_ref,
                acc_scr, m_scr, l_scr, *, scale, causal, bq, bk, nk,
                first_pad, user_mask, window=None, q_offset=0):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute(masked: bool):
        # scores in BASE-2 units (scale folds in log2(e)); p values are
        # bit-for-bit the same softmax weights, m/l carry base-2 maxima
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (scale * LOG2E)
        if masked:
            valid = _block_mask(i, j, bq, bk, causal, km_ref[0], window,
                                q_offset)
            s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[:][:, :1]                               # [bq, 1]
        l_prev = l_scr[:][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp2(s - m_new)
        if masked:
            # explicit zeroing: if a whole row is masked,
            # exp2(NEG_INF - NEG_INF) would be 1 — keep such rows at p=0
            p = p * valid.astype(jnp.float32)
        corr = jnp.exp2(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bq, D]
        acc_scr[:] = acc_scr[:] * corr + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    _dispatch(i, j, lambda: _compute(False), lambda: _compute(True),
              causal=causal, bq=bq, bk=bk, nk=nk, first_pad=first_pad,
              user_mask=user_mask, window=window, q_offset=q_offset)

    @pl.when(j == nk - 1)
    def _finish():
        m = m_scr[:][:, :1]                    # base-2 running max
        l = l_scr[:][:, :1]
        o_ref[0, 0] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # public lse stays NATURAL log (backward + ring combine contract)
        lse_ref[0, 0] = m * LN2 + jnp.log(jnp.maximum(l, 1e-30))


def _bwd_dq_kernel(q_ref, k_ref, v_ref, km_ref, do_ref, lse_ref, d_ref,
                   dq_ref, dq_scr, *, scale, causal, bq, bk, nk,
                   first_pad, user_mask, window=None, q_offset=0):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute(masked: bool):
        # base-2 probabilities like the forward: exp2(s*log2e - lse*log2e)
        # == exp(s - lse); ds keeps the NATURAL scale (chain rule)
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (scale * LOG2E)
        if masked:
            # mask BEFORE exp (as forward does): a masked raw score above
            # the row lse would overflow exp to inf and 0*inf = NaN
            valid = _block_mask(i, j, bq, bk, causal, km_ref[0], window,
                                q_offset)
            s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp2(s - lse_ref[0, 0] * LOG2E)
        if masked:
            p = p * valid.astype(jnp.float32)
        dp = jax.lax.dot_general(
            do_ref[0, 0], v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bq, bk]
        ds = p * (dp - d_ref[0, 0]) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _dispatch(i, j, lambda: _compute(False), lambda: _compute(True),
              causal=causal, bq=bq, bk=bk, nk=nk, first_pad=first_pad,
              user_mask=user_mask, window=window, q_offset=q_offset)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, km_ref, do_ref, lse_ref, d_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, bq, bk, nq, nk,
                    first_pad, user_mask, window=None, q_offset=0):
    j, i = pl.program_id(2), pl.program_id(3)   # Q innermost here

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute(masked: bool):
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (scale * LOG2E)
        if masked:
            valid = _block_mask(i, j, bq, bk, causal, km_ref[0], window,
                                q_offset)
            s = jnp.where(valid, s, NEG_INF)   # see _bwd_dq_kernel note
        p = jnp.exp2(s - lse_ref[0, 0] * LOG2E)
        if masked:
            p = p * valid.astype(jnp.float32)
        pt = p.astype(do_ref.dtype)
        dv_scr[:] += jax.lax.dot_general(
            pt, do_ref[0, 0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bk, D]
        dp = jax.lax.dot_general(
            do_ref[0, 0], v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - d_ref[0, 0]) * scale).astype(q_ref.dtype)
        dk_scr[:] += jax.lax.dot_general(
            ds, q_ref[0, 0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bk, D]

    _dispatch(i, j, lambda: _compute(False), lambda: _compute(True),
              causal=causal, bq=bq, bk=bk, nk=nk, first_pad=first_pad,
              user_mask=user_mask, window=window, q_offset=q_offset)

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _qkv_spec(bq_or_bk, D, axis):
    """Block spec for q/k/v: (1,1,block,D), selecting grid axis 2 or 3."""
    if axis == 2:
        return pl.BlockSpec((1, 1, bq_or_bk, D),
                            lambda b, h, i, j: (b, h, i, 0))
    return pl.BlockSpec((1, 1, bq_or_bk, D),
                        lambda b, h, i, j: (b, h, j, 0))


def _row_spec(block, axis):
    """Block spec for per-row stats [B,H,T,1]: (1,1,block,1) — trailing
    dim 1 satisfies the Mosaic tiling rule (block dim == array dim)."""
    if axis == 2:
        return pl.BlockSpec((1, 1, block, 1), lambda b, h, i, j: (b, h, i, 0))
    return pl.BlockSpec((1, 1, block, 1), lambda b, h, i, j: (b, h, j, 0))


def _km_spec(bk, axis):
    """Block spec for the key mask [B,1,T]: (1,1,bk), KV-indexed."""
    if axis == 3:
        return pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j))
    return pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, i))


def _pad_t(x, bs):
    pad = (-x.shape[2]) % bs
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return x



def _run_bwd_kernels(q, k, v, key_mask, do, lse, d_eff, *, causal, bq, bk,
                     first_pad, user_mask, interpret, window=None,
                     q_offset=0):
    """The dq and dk/dv pallas calls shared by both VJPs. `d_eff` sits in
    the delta slot: plain backward passes delta = rowsum(do*o); the
    lse-differentiable variant passes delta - dlse. Query and key lengths
    are independent (cross-/chunked attention)."""
    B, H, T, D = q.shape
    Tk = k.shape[2]
    scale = float(1.0 / np.sqrt(D))
    nq, nk = T // bq, Tk // bk

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, first_pad=first_pad,
                          user_mask=user_mask, window=window,
                          q_offset=q_offset),
        grid=(B, H, nq, nk),
        in_specs=[_qkv_spec(bq, D, 2), _qkv_spec(bk, D, 3),
                  _qkv_spec(bk, D, 3), _km_spec(bk, 3),
                  _qkv_spec(bq, D, 2), _row_spec(bq, 2), _row_spec(bq, 2)],
        out_specs=_qkv_spec(bq, D, 2),
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v, key_mask, do, lse, d_eff)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, nk=nk, first_pad=first_pad,
                          user_mask=user_mask, window=window,
                          q_offset=q_offset),
        # KV block is the carried axis; Q innermost
        grid=(B, H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, j, i: (b, 0, j)),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, H, Tk, D), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Tk, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v, key_mask, do, lse, d_eff)
    return dq, dk, dv


def _flash_fwd(q, k, v, key_mask, causal, bq, bk, first_pad, user_mask,
               interpret, window=None, q_offset=0):
    B, H, T, D = q.shape
    scale = float(1.0 / np.sqrt(D))
    nq, nk = T // bq, k.shape[2] // bk
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nk=nk, first_pad=first_pad,
                               user_mask=user_mask, window=window,
                               q_offset=q_offset)
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[_qkv_spec(bq, D, 2), _qkv_spec(bk, D, 3),
                  _qkv_spec(bk, D, 3), _km_spec(bk, 3)],
        out_specs=[_qkv_spec(bq, D, 2), _row_spec(bq, 2)],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, T, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v, key_mask)
    return o, (q, k, v, key_mask, o, lse)


# -- (o, lse) variant: for cross-chunk combination (ring attention) --------
#
# Exposing the logsumexp differentiably costs one line of math:
# d lse_i / d s_ij = p_ij, so the score cotangent becomes
# ds = p * (dp - delta + dlse) = p * (dp - (delta - dlse)) — the existing
# backward kernels run unchanged with d_eff = delta - dlse in the delta
# slot (dv is independent of lse).


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash_lse(q, k, v, key_mask, causal, bq, bk, first_pad, user_mask,
               interpret, window, q_offset):
    (o, lse), _ = _flash_lse_fwd(q, k, v, key_mask, causal, bq, bk,
                                 first_pad, user_mask, interpret, window,
                                 q_offset)
    return o, lse


def _flash_lse_fwd(q, k, v, key_mask, causal, bq, bk, first_pad, user_mask,
                   interpret, window, q_offset):
    o, res = _flash_fwd(q, k, v, key_mask, causal, bq, bk, first_pad,
                        user_mask, interpret, window, q_offset)
    lse = res[-1]
    return (o, lse), res


def _flash_lse_bwd(causal, bq, bk, first_pad, user_mask, interpret, window,
                   q_offset, res, cotangents):
    do, dlse = cotangents
    q, k, v, key_mask, o, lse = res
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    d_eff = delta - dlse.astype(jnp.float32)
    dq, dk, dv = _run_bwd_kernels(q, k, v, key_mask, do, lse, d_eff,
                                  causal=causal, bq=bq, bk=bk,
                                  first_pad=first_pad, user_mask=user_mask,
                                  interpret=interpret, window=window,
                                  q_offset=q_offset)
    return dq, dk, dv, jnp.zeros_like(key_mask)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_lse(q, k, v, causal: bool = False, key_mask=None,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False,
                        window: Optional[int] = None,
                        q_offset: int = 0):
    """Like flash_attention but also returns the per-row logsumexp
    [B,H,Tq] (fp32) — differentiable through both outputs, for combining
    attention over KV chunks (ring attention: merge (o_i, lse_i) pairs
    with the standard logaddexp rule).

    `q_offset` (static int) shifts query positions for the causal/window
    masks: windowed ring attention runs a PAST chunk as banded attention
    with q_offset = (global query start) - (global key start); blocks
    outside the band are skipped, so a mostly-out-of-window chunk costs
    almost nothing."""
    if window is not None and not causal:
        raise ValueError("window attention requires causal=True")
    if q_offset and not causal:
        raise ValueError("q_offset only shifts the causal/window masks; "
                         "it requires causal=True")
    q, k, v, km, bq, bk, first_pad, user_mask, Tq = _prep(
        q, k, v, key_mask, causal, block_q, block_k,
        allow_unaligned_causal=q_offset != 0)
    o, lse = _flash_lse(q, k, v, km, causal, bq, bk, first_pad, user_mask,
                        interpret, window, int(q_offset))
    return o[:, :, :Tq, :], lse[:, :, :Tq, 0]


def _prep(q, k, v, key_mask, causal, block_q, block_k,
          allow_unaligned_causal=False):
    """Pad q to a block_q multiple and k/v to a block_k multiple
    (independently — Tq need not equal Tk for non-causal / chunked use),
    build the padded-key mask, and pick tile-aligned block sizes."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    if causal and not allow_unaligned_causal and Tq != Tk:
        raise ValueError("causal flash attention needs Tq == Tk "
                         f"(got {Tq} vs {Tk})")
    bq = int(min(block_q, ((Tq + 127) // 128) * 128))
    bk = int(min(block_k, ((Tk + 127) // 128) * 128))
    q = _pad_t(q, bq)
    k, v = _pad_t(k, bk), _pad_t(v, bk)
    Tkp = k.shape[2]
    first_pad = (Tk // bk) if Tkp != Tk else None
    user_mask = key_mask is not None
    if key_mask is None:
        km = (jnp.arange(Tkp) < Tk).astype(jnp.float32)[None, None, :]
        km = jnp.broadcast_to(km, (B, 1, Tkp))
    else:
        km = key_mask.astype(jnp.float32)[:, None, :]
        km = jnp.pad(km, ((0, 0), (0, 0), (0, Tkp - km.shape[2])))
    return q, k, v, km, bq, bk, first_pad, user_mask, Tq


def flash_attention_supported(q_shape: Tuple[int, ...],
                              block_q: int = DEFAULT_BLOCK_Q,
                              block_k: int = DEFAULT_BLOCK_K) -> bool:
    """Shape gate (mirrors pallas_lstm_supported's role): head dim must be
    lane-tileable and T large enough to block."""
    if len(q_shape) != 4:
        return False
    _, _, T, D = q_shape
    return D in (64, 128, 256) and T >= 128


def flash_attention(q, k, v, causal: bool = False, key_mask=None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False,
                    window: Optional[int] = None):
    """Fused flash attention. q: [B,H,Tq,D]; k,v: [B,H,Tk,D]; key_mask:
    [B,Tk] (1=valid). Tq and Tk may differ (cross-/chunked attention)
    except under causal, which requires aligned lengths.

    Lengths are padded internally to block multiples (padded keys masked
    out, padded query rows sliced off). Differentiable via the
    recompute-form custom VJP. Use `interpret=True` on CPU (tests)."""
    if window is not None and not causal:
        raise ValueError("window attention requires causal=True")
    q, k, v, km, bq, bk, first_pad, user_mask, Tq = _prep(
        q, k, v, key_mask, causal, block_q, block_k)
    # single custom_vjp serves both entry points: when the lse output is
    # unused JAX feeds a zeros cotangent, so d_eff = delta - 0 = delta
    out, _ = _flash_lse(q, k, v, km, causal, bq, bk, first_pad, user_mask,
                        interpret, window, 0)
    return out[:, :, :Tq, :]
