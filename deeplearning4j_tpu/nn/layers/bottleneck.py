"""Full fused ResNet bottleneck: conv1x1→BN→ReLU→conv3x3→BN→ReLU→conv1x1→
BN→(+residual)→ReLU as a chain of Pallas kernels with a recompute backward.

Why (PERF.md round-3 profile): the ResNet50 step is HBM-bound on BatchNorm
traffic — for every conv output XLA runs a separate stats-reduction pass
and a normalize pass, and the backward re-reads everything again for the
BN reductions. The round-2/3 prologue-only fusion (fused.py) measurably
LOST: it removed one normalize pass but its pallas_call boundary broke
XLA's surrounding fusions while the stats reductions stayed. This module
removes the stats passes themselves:

- every fused conv kernel emits per-channel Σout and Σout² as an EPILOGUE
  of the pass that produces the output — batch stats cost zero extra HBM
  traffic;
- the normalize+ReLU of each BN rides the NEXT conv's prologue;
- the backward is ONE pallas pass per stage: stage k's backward kernel
  computes dW_k and dz_{k-1} and, as its epilogue, the per-channel sums
  stage k-1's BN backward needs — so no separate reduction passes there
  either. All intermediates are RECOMPUTED from the saved raw conv
  outputs (which are the kernels' inputs anyway): nothing extra persists.

Kernel geometry: NHWC, grid over the batch dimension, one FULL image per
grid step resident in VMEM (ResNet50 bottleneck interiors are at most
56×56×64 ≈ 0.4 MB and weights at most 512×2048 ≈ 2 MB bf16 — far under
the ~16 MB VMEM budget), channel-sum accumulators in fp32 VMEM scratch
carried across the sequential TPU grid. The 3×3 conv is nine statically
shifted [H·W, Cin]·[Cin, Cout] matmuls over the in-VMEM zero-padded
image — MXU-shaped, no halo exchange, no dynamic shapes.

Scope: identity bottlenecks (stride 1, identity skip) AND downsample
entry blocks (stride-2 conv_a + conv shortcut with its own BN — the
ResNet50 convBlock layout); ReLU activations, NHWC, train or inference.

Backward kernels whose resident weight+fp32-dW or recompute buffers
would exceed the VMEM budget (ResNet50 stage-5 3x3 backward:
[9,512,512] w + fp32 dW ~ 14 MB; the entry-block conv-skip backwards)
run CHANNEL-SPLIT: grid (n_cb, n) with a C_in-slice of the weight, dW,
dz, recompute buffers and BN sums per step. A conv backward partitions
exactly over input channels — dW rows, dz slices, the relu' mask and
the sum epilogues are all C-local; only dy (a function of the full
K-dim gradient) is recomputed per slice, which at the affected 7x7/14x14
resolutions is noise. cb is the OUTER grid dim so each dW/sums slice
stays VMEM-resident across the whole image sweep and is written back
exactly once — no HBM accumulation revisits anywhere. With the split,
all 16 ResNet50 blocks pass the gate (fused_bottleneck_supported).

ref: the reference's fused-conv ambition lives in
deeplearning4j-cuda/.../CudnnConvolutionHelper.java:54-480 (cuDNN
conv+bias+activation fusion) and CudnnBatchNormalizationHelper.java:45-234;
this plan fuses strictly more (stats + normalize + both backward
reduction families) because on TPU the whole chain shares one memory
hierarchy.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: v1 supports the ResNet50 interior-block shapes; the gate keeps the
#: whole-image blocks + weights inside a conservative VMEM budget
_VMEM_BUDGET = 12 * 1024 * 1024

#: what Mosaic may actually use per kernel. The budget model above counts
#: each block once at its logical size; the compiler double-buffers every
#: BlockSpec operand and pads 64-channel minor dims to the 128 lanes, so
#: a block the model admits at 12 MiB can need ~4x that — over the 16 MiB
#: default scoped limit (measured on v5e: the 56x56x64 3x3 backward asked
#: for 23.7 MiB). v5e has 128 MiB of VMEM.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


class BnParams(NamedTuple):
    gamma: jax.Array          # [C]
    beta: jax.Array           # [C]
    running_mean: jax.Array   # [C] fp32
    running_var: jax.Array    # [C] fp32


def _fwd_vmem(taps, h, w, c, k, bpe, stride=1):
    """Per-grid-step VMEM estimate for a forward conv+stats kernel:
    one image [h,w,c] + fp32 prologue buffer, fp32 accumulator + stored
    output at [h/s,w/s,k], and the full weight."""
    ho, wo = h // stride, w // stride
    if taps == 9:
        return ((h + 2) * (w + 2) * c * 4      # padded z fp32
                + h * w * c * bpe              # x image
                + h * w * k * (4 + bpe)        # acc fp32 + stored out
                + 9 * c * k * bpe)
    return (h * w * c * (4 + bpe)              # x + fp32 affine buffer
            + ho * wo * k * (4 + bpe)          # acc fp32 + stored out
            + c * k * bpe)


def _bwd_vmem(taps, h, w, c_b, k, bpe, stride=1, identity_prologue=False):
    """Per-grid-step VMEM estimate for a backward kernel holding a
    C_b-slice of the input channels. The full-K buffers (yk, g, dy) do
    not shrink with the split; everything C-indexed does. The identity
    prologue (stage-a / conv-skip backward: z_prev IS the block input)
    skips the affine/relu recompute buffers and the sums math."""
    ho, wo = h // stride, w // stride
    if taps == 9:
        return ((h + 2) * (w + 2) * (c_b + k) * 4   # z_pad slice + dy_pad
                + h * w * k * (4 + 2 * bpe)         # dy fp32 + yk + g
                + h * w * c_b * (2 * bpe + 8)       # yprev, dz, dzp/yhat f32
                + 9 * c_b * k * (bpe + 4))          # w + fp32 dW slice
    full = h * w * c_b
    recompute = 4 if identity_prologue else 12      # fp32 z-recompute bufs
    # at stride 1 dzp aliases dzs and the strided views don't exist
    strided = 0 if stride == 1 else ho * wo * c_b * 8 + full * 4
    return (full * (bpe + recompute + 4 + bpe)      # yprev, rcmp, dzs, dz
            + strided
            + ho * wo * k * (4 + 2 * bpe)           # dy fp32 + yk + g
            + c_b * k * (bpe + 4))                  # w + fp32 dW slice


def _pick_csplit(taps, h, w, c, k, bpe, stride=1, identity_prologue=False):
    """Smallest input-channel split whose per-step footprint fits the
    VMEM budget. Slices must stay lane-aligned (C_b a multiple of 128)
    — returns None when no aligned split fits (caller falls back to the
    unfused graph)."""
    split = 1
    while True:
        if _bwd_vmem(taps, h, w, c // split, k, bpe, stride,
                     identity_prologue) <= _VMEM_BUDGET:
            return split
        split *= 2
        if c % split or (c // split) % 128:
            return None


def fused_bottleneck_supported(x_shape, c_mid: int, c_out: int,
                               dtype, stride: int = 1,
                               has_skip: bool = False) -> bool:
    """VMEM gate, per-kernel: every forward pass must fit whole-image,
    and every backward stage must fit either whole-image or via an
    aligned channel split (_pick_csplit). Strided forms also require
    exact stride divisibility (the kernels subsample exactly)."""
    if len(x_shape) != 4:
        return False
    n, h, w, c_in = x_shape
    if stride > 1 and (h % stride or w % stride):
        return False          # kernels require exact stride divisibility
    if isinstance(dtype, str) and dtype in ("bf16", "bfloat16"):
        dtype = jnp.bfloat16
    bpe = jnp.dtype(dtype).itemsize
    ho, wo = h // stride, w // stride
    fwd = [_fwd_vmem(1, h, w, c_in, c_mid, bpe, stride),      # conv_a
           _fwd_vmem(9, ho, wo, c_mid, c_mid, bpe),           # conv_b
           _fwd_vmem(1, ho, wo, c_mid, c_out, bpe)]           # conv_c
    if has_skip:
        fwd.append(_fwd_vmem(1, h, w, c_in, c_out, bpe, stride))
    if max(fwd) > _VMEM_BUDGET:
        return False
    # (taps, h, w, C=yprev channels, K, stride, identity_prologue)
    bwd = [(1, ho, wo, c_mid, c_out, 1, False),               # stage c
           (9, ho, wo, c_mid, c_mid, 1, False),               # stage b
           (1, h, w, c_in, c_mid, stride, True)]              # stage a
    if has_skip:
        bwd.append((1, h, w, c_in, c_out, stride, True))      # conv skip
    return all(_pick_csplit(t, hh, ww, c, k, bpe, s, ident) is not None
               for t, hh, ww, c, k, s, ident in bwd)


# ---------------------------------------------------------------------------
# forward kernels
# ---------------------------------------------------------------------------


def _fwd1x1_kernel(x_ref, sc_ref, bb_ref, w_ref, o_ref, s1_ref, s2_ref,
                   *, act, n_img, stride=1):
    """One image: o = affine+act(x)[::stride, ::stride] @ w, with Σo / Σo²
    channel epilogue.

    x_ref [1,H,W,C]; sc/bb [1,C] fp32 (identity prologue = (1,0));
    w [C,K]; o [1,H/stride,W/stride,K]; s1/s2 [1,K] fp32 accumulated
    ACROSS the grid directly in the (constant-index, VMEM-resident)
    output blocks — no separate scratch doubles the accumulator
    footprint. stride=2 is the entry-block downsample (a strided 1x1
    conv just subsamples rows before the channel matmul).
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    _, h, w_dim, c = x_ref.shape
    k = w_ref.shape[1]
    ho, wo = h // stride, w_dim // stride
    xs = x_ref[...].reshape(h, w_dim, c)
    if stride > 1:
        xs = xs[::stride, ::stride, :]
    xf = xs.reshape(ho * wo, c).astype(jnp.float32)
    z = xf * sc_ref[...] + bb_ref[...]
    if act == "relu":
        z = jnp.maximum(z, 0.0)
    out = lax.dot_general(z.astype(w_ref.dtype), w_ref[...],
                          (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)  # [HW, K]
    o_ref[...] = out.astype(o_ref.dtype).reshape(1, ho, wo, k)
    # stats of the *stored* (dtype-rounded) output: the consumer
    # normalizes the rounded tensor, so the stats must see the same values
    of = o_ref[...].reshape(ho * wo, k).astype(jnp.float32)
    s1_ref[...] += jnp.sum(of, axis=0, keepdims=True)
    s2_ref[...] += jnp.sum(of * of, axis=0, keepdims=True)


def _fwd3x3_kernel(x_ref, sc_ref, bb_ref, w_ref, o_ref, s1_ref, s2_ref,
                   *, act, n_img):
    """One image: 3x3 same-pad conv of affine+act(x), stats epilogue.

    w_ref [9, C, K] (tap-major: dy*3+dx); the conv is nine shifted
    matmuls over the in-VMEM zero-padded image.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    _, h, w_dim, c = x_ref.shape
    k = w_ref.shape[2]
    xf = x_ref[...].reshape(h, w_dim, c).astype(jnp.float32)
    z = xf * sc_ref[...][0][None, None, :] + bb_ref[...][0][None, None, :]
    if act == "relu":
        z = jnp.maximum(z, 0.0)
    zp = jnp.pad(z, ((1, 1), (1, 1), (0, 0)))
    acc = jnp.zeros((h * w_dim, k), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            xs = zp[dy:dy + h, dx:dx + w_dim, :].reshape(h * w_dim, c)
            acc += lax.dot_general(
                xs.astype(w_ref.dtype), w_ref[dy * 3 + dx],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype).reshape(1, h, w_dim, k)
    of = o_ref[...].reshape(h * w_dim, k).astype(jnp.float32)
    s1_ref[...] += jnp.sum(of, axis=0, keepdims=True)
    s2_ref[...] += jnp.sum(of * of, axis=0, keepdims=True)


def _img_spec(h, w, c):
    return pl.BlockSpec((1, h, w, c), lambda i: (i, 0, 0, 0))


def _bcast_spec(r, c):
    return pl.BlockSpec((r, c), lambda i: (0, 0))


def _bcast_spec3(a, b, c):
    return pl.BlockSpec((a, b, c), lambda i: (0, 0, 0))


def _fwd_conv_stats(x, sc, bb, w, *, taps: int, act: str,
                    interpret: bool, stride: int = 1):
    """Dispatch one fused conv+stats pass. x [N,H,W,C]; w [C,K] (1x1) or
    [9,C,K] (3x3, stride-1 only). Returns (out [N,H/s,W/s,K], s1 [K],
    s2 [K])."""
    n, h, wd, c = x.shape
    k = w.shape[-1]
    if taps == 1:
        kern = functools.partial(_fwd1x1_kernel, stride=stride)
        w_spec = _bcast_spec(c, k)
    else:
        assert stride == 1, "3x3 stage is stride-1 in ResNet bottlenecks"
        kern = _fwd3x3_kernel
        w_spec = _bcast_spec3(9, c, k)
    ho, wo = h // stride, wd // stride
    out, s1, s2 = pl.pallas_call(
        functools.partial(kern, act=act, n_img=n),
        grid=(n,),
        in_specs=[_img_spec(h, wd, c), _bcast_spec(1, c), _bcast_spec(1, c),
                  w_spec],
        out_specs=[_img_spec(ho, wo, k), _bcast_spec(1, k),
                   _bcast_spec(1, k)],
        out_shape=[jax.ShapeDtypeStruct((n, ho, wo, k), x.dtype),
                   jax.ShapeDtypeStruct((1, k), jnp.float32),
                   jax.ShapeDtypeStruct((1, k), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(x, sc[None, :], bb[None, :], w)
    return out, s1[0], s2[0]


# ---------------------------------------------------------------------------
# backward kernels — one pass per stage
# ---------------------------------------------------------------------------
#
# Stage k (output y_k = conv_k(z_{k-1}), z_k = relu(sc_k∘y_k + bb_k)):
# given dz0_k = (∂L/∂z_k)∘relu'(·) and stage-k BN-backward sums
# (m1 = mean(dz0_k), m2 = mean(dz0_k∘ŷ_k) over the batch), the gradient
# w.r.t. the raw conv output is the standard training-BN backward
#     dy_k = sc_k ∘ (dz0_k − m1 − ŷ_k∘m2)        ŷ_k = (y_k − μ)·inv
# The kernel then computes in the same pass
#     dW_k  += z_{k-1}ᵀ @ dy_k           (recomputing z_{k-1} from y_{k-1})
#     dz0_{k-1} = (dy_k @ W_kᵀ) ∘ relu'(z0_{k-1})
# and EMITS the next stage's sums Σdz0_{k-1}, Σdz0_{k-1}∘ŷ_{k-1} as its
# epilogue, so stage k-1 starts with its reductions already done.


def _bwd1x1_kernel(yk_ref, g_ref, yprev_ref, w_ref,
                   aff_k_ref, aff_p_ref,
                   dz_ref, dw_ref, sums_ref,
                   *, act_prev, n_img, gmode, stride=1, img_axis=0):
    """One image (or one image × C-slice) of stage-k backward (k a 1x1
    conv).

    yk_ref    [1,H,W,K]  raw conv_k output (for ŷ_k / relu' recompute)
    g_ref     [1,H,W,K]  dz0_k when gmode=='dz0' (already relu-masked),
                         or dy_k directly when gmode=='dy'
    yprev_ref [1,H,W,C]  raw stage k-1 output (recompute z_{k-1})
    w_ref     [C,K]      conv_k weight
    aff_k_ref [6,K] fp32 rows: sc_k, bb_k(unused), inv_k, mu_k, m1, m2
    aff_p_ref [4,C] fp32 rows: sc_{k-1}, bb_{k-1}, inv_{k-1}, mu_{k-1}
    dz_ref    [1,H,W,C]  OUT: dz0_{k-1}
    dw_ref    [C,K]      OUT: dW_k
    sums_ref  [2,C] fp32 OUT: Σdz0_{k-1}, Σdz0_{k-1}∘ŷ_{k-1}

    Under a channel split every C-dim ref carries a C_b slice and the
    grid is (n_cb, n) with img_axis=1: the math is identical because a
    1x1 conv backward is C-local (dz columns, dw rows, the mask and the
    sums all partition; only dy spans K and is recomputed per slice).

    act_prev == "identity" asserts the FULL identity prologue (stage-a /
    conv-skip backward: z_{k-1} IS the block input, affine rows are
    (1,0) by construction) — the kernel then skips the affine/mask
    recompute and leaves the (caller-discarded) sums at zero.
    """
    i = pl.program_id(img_axis)
    identity = act_prev == "identity"

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    _, h, wd, c = yprev_ref.shape
    k = yk_ref.shape[3]
    ho, wo = h // stride, wd // stride
    hw_o = ho * wo
    g = g_ref[...].reshape(hw_o, k).astype(jnp.float32)
    if gmode == "dz0":
        yk = yk_ref[...].reshape(hw_o, k).astype(jnp.float32)
        sck = aff_k_ref[0, :][None, :]
        invk = aff_k_ref[2, :][None, :]
        muk = aff_k_ref[3, :][None, :]
        m1 = aff_k_ref[4, :][None, :]
        m2 = aff_k_ref[5, :][None, :]
        yhat = (yk - muk) * invk
        dy = sck * (g - m1 - yhat * m2)                     # [HWo, K]
    else:
        dy = g
    # recompute z_{k-1} (full resolution; the conv consumed the
    # ::stride subsample)
    yp3 = yprev_ref[...].reshape(h, wd, c).astype(jnp.float32)
    if identity:
        z0p3 = zp3 = yp3
    else:
        scp = aff_p_ref[0, :][None, None, :]
        bbp = aff_p_ref[1, :][None, None, :]
        z0p3 = yp3 * scp + bbp
        zp3 = jnp.maximum(z0p3, 0.0) if act_prev == "relu" else z0p3
    if stride > 1:
        zp_s = zp3[::stride, ::stride, :].reshape(hw_o, c)
    else:
        zp_s = zp3.reshape(hw_o, c)
    dw_ref[...] += lax.dot_general(
        zp_s.astype(yk_ref.dtype), dy.astype(yk_ref.dtype),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dzs = lax.dot_general(dy.astype(w_ref.dtype), w_ref[...],
                          (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32)  # [HWo, C]
    if act_prev == "relu":
        z0_s = (z0p3[::stride, ::stride, :].reshape(hw_o, c)
                if stride > 1 else z0p3.reshape(hw_o, c))
        dzs = jnp.where(z0_s > 0, dzs, 0.0)
    if stride > 1:
        # interleave back to full resolution (gradient is zero at the
        # positions the strided conv never read): pad+reshape, no scatter
        dz3 = dzs.reshape(ho, 1, wo, 1, c)
        dz3 = jnp.pad(dz3, ((0, 0), (0, stride - 1), (0, 0),
                            (0, stride - 1), (0, 0)))
        dzp = dz3.reshape(h, wd, c).reshape(h * wd, c)
    else:
        dzp = dzs
    dz_ref[...] = dzp.astype(dz_ref.dtype).reshape(1, h, wd, c)
    if identity:
        return    # sums are only consumed by a real BN prologue
    invp = aff_p_ref[2, :][None, :]
    mup = aff_p_ref[3, :][None, :]
    # sums over the full-res dz (zero at unread positions, so summing
    # the strided values with strided yhat is exact)
    if stride > 1:
        yhat_s = (yp3[::stride, ::stride, :].reshape(hw_o, c) - mup) * invp
        sums_ref[0:1, :] += jnp.sum(dzs, axis=0, keepdims=True)
        sums_ref[1:2, :] += jnp.sum(dzs * yhat_s, axis=0, keepdims=True)
    else:
        yhat_p = (yp3.reshape(h * wd, c) - mup) * invp
        sums_ref[0:1, :] += jnp.sum(dzp, axis=0, keepdims=True)
        sums_ref[1:2, :] += jnp.sum(dzp * yhat_p, axis=0, keepdims=True)


def _bwd3x3_kernel(yk_ref, g_ref, yprev_ref, w_ref,
                   aff_k_ref, aff_p_ref,
                   dz_ref, dw_ref, sums_ref,
                   *, act_prev, n_img, gmode, img_axis=0):
    """3x3 twin of _bwd1x1_kernel: w_ref [9,C,K];
    dW via nine shifted-input matmuls, dz_{k-1} via the transposed taps
    (full-correlation with the flipped kernel). Channel-split form as in
    _bwd1x1_kernel (the zero-padding, tap shifts and mask are C-local;
    the 3x3 stage always has a real BN prologue, so no identity path)."""
    i = pl.program_id(img_axis)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    _, h, wd, c = yprev_ref.shape
    k = yk_ref.shape[3]
    hw = h * wd
    g = g_ref[...].reshape(hw, k).astype(jnp.float32)
    if gmode == "dz0":
        yk = yk_ref[...].reshape(hw, k).astype(jnp.float32)
        sck = aff_k_ref[0, :][None, :]
        invk = aff_k_ref[2, :][None, :]
        muk = aff_k_ref[3, :][None, :]
        m1 = aff_k_ref[4, :][None, :]
        m2 = aff_k_ref[5, :][None, :]
        yhat = (yk - muk) * invk
        dy = sck * (g - m1 - yhat * m2)
    else:
        dy = g
    yp = yprev_ref[...].reshape(h, wd, c).astype(jnp.float32)
    scp = aff_p_ref[0, :][None, None, :]
    bbp = aff_p_ref[1, :][None, None, :]
    z0p = yp * scp + bbp
    zp = jnp.maximum(z0p, 0.0) if act_prev == "relu" else z0p
    zp_pad = jnp.pad(zp, ((1, 1), (1, 1), (0, 0)))
    dy3 = dy.reshape(h, wd, k)
    dy_pad = jnp.pad(dy3, ((1, 1), (1, 1), (0, 0)))
    dzp = jnp.zeros((hw, c), jnp.float32)
    for t in range(9):
        dyy, dxx = divmod(t, 3)
        # dW tap t sums z_{k-1}[shifted] · dy
        xs = zp_pad[dyy:dyy + h, dxx:dxx + wd, :].reshape(hw, c)
        dw_ref[t, :, :] += lax.dot_general(
            xs.astype(yk_ref.dtype), dy.astype(yk_ref.dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        # dz tap: correlation with the mirrored offset (2-dy, 2-dx)
        gs = dy_pad[2 - dyy:2 - dyy + h,
                    2 - dxx:2 - dxx + wd, :].reshape(hw, k)
        dzp += lax.dot_general(gs.astype(w_ref.dtype), w_ref[t],
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    z0f = z0p.reshape(hw, c)
    if act_prev == "relu":
        dzp = jnp.where(z0f > 0, dzp, 0.0)
    dz_ref[...] = dzp.astype(dz_ref.dtype).reshape(1, h, wd, c)
    invp = aff_p_ref[2, :][None, :]
    mup = aff_p_ref[3, :][None, :]
    yhat_p = (yp.reshape(hw, c) - mup) * invp
    sums_ref[0:1, :] += jnp.sum(dzp, axis=0, keepdims=True)
    sums_ref[1:2, :] += jnp.sum(dzp * yhat_p, axis=0, keepdims=True)


def _bwd_stage(yk, g, yprev, w, aff_k, aff_p, *, taps, act_prev, gmode,
               interpret, stride: int = 1):
    """One backward stage pass. Returns (dz0_prev [N,H,W,C] full-res, dW,
    sums [2,C] = (Σdz0_prev, Σdz0_prev∘ŷ_prev)).

    Picks the channel split from the same VMEM model as the support
    gate: split == 1 is the whole-image kernel on grid (n,); split > 1
    runs grid (split, n) — cb OUTER, so each dW/sums slice is resident
    across the image sweep and written back once. The two forms are
    arithmetically identical (same fp32 accumulation order per slice)."""
    n, h, wd, c = yprev.shape
    k = yk.shape[3]
    ho, wo = h // stride, wd // stride
    bpe = jnp.dtype(yprev.dtype).itemsize
    split = _pick_csplit(taps, h, wd, c, k, bpe, stride,
                         act_prev == "identity")
    if split is None:
        raise ValueError(
            f"no aligned channel split fits VMEM for backward stage "
            f"taps={taps} h={h} w={wd} c={c} k={k} stride={stride} — "
            "fused_bottleneck_supported should have rejected this block")
    dw_shape = (c, k) if taps == 1 else (9, c, k)
    if split == 1:
        if taps == 1:
            kern = functools.partial(_bwd1x1_kernel, stride=stride)
            w_spec = _bcast_spec(c, k)
            dw_spec = _bcast_spec(c, k)
        else:
            assert stride == 1
            kern = _bwd3x3_kernel
            w_spec = _bcast_spec3(9, c, k)
            dw_spec = _bcast_spec3(9, c, k)
        grid = (n,)
        in_specs = [_img_spec(ho, wo, k), _img_spec(ho, wo, k),
                    _img_spec(h, wd, c), w_spec,
                    _bcast_spec(6, k), _bcast_spec(4, c)]
        out_specs = [_img_spec(h, wd, c), dw_spec, _bcast_spec(2, c)]
    else:
        c_b = c // split
        if taps == 1:
            kern = functools.partial(_bwd1x1_kernel, stride=stride,
                                     img_axis=1)
            w_spec = pl.BlockSpec((c_b, k), lambda cb, i: (cb, 0))
            dw_spec = pl.BlockSpec((c_b, k), lambda cb, i: (cb, 0))
        else:
            assert stride == 1
            kern = functools.partial(_bwd3x3_kernel, img_axis=1)
            w_spec = pl.BlockSpec((9, c_b, k), lambda cb, i: (0, cb, 0))
            dw_spec = pl.BlockSpec((9, c_b, k), lambda cb, i: (0, cb, 0))
        grid = (split, n)
        in_specs = [
            pl.BlockSpec((1, ho, wo, k), lambda cb, i: (i, 0, 0, 0)),
            pl.BlockSpec((1, ho, wo, k), lambda cb, i: (i, 0, 0, 0)),
            pl.BlockSpec((1, h, wd, c_b), lambda cb, i: (i, 0, 0, cb)),
            w_spec,
            pl.BlockSpec((6, k), lambda cb, i: (0, 0)),
            pl.BlockSpec((4, c_b), lambda cb, i: (0, cb)),
        ]
        out_specs = [
            pl.BlockSpec((1, h, wd, c_b), lambda cb, i: (i, 0, 0, cb)),
            dw_spec,
            pl.BlockSpec((2, c_b), lambda cb, i: (0, cb)),
        ]
    dz, dw, sums = pl.pallas_call(
        functools.partial(kern, act_prev=act_prev, n_img=n, gmode=gmode),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((n, h, wd, c), yprev.dtype),
                   jax.ShapeDtypeStruct(dw_shape, jnp.float32),
                   jax.ShapeDtypeStruct((2, c), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(yk, g, yprev, w, aff_k, aff_p)
    return dz, dw, sums


# ---------------------------------------------------------------------------
# the bottleneck orchestration (custom_vjp)
# ---------------------------------------------------------------------------


def _finalize_stats(s1, s2, count):
    mean = s1 / count
    var = jnp.maximum(s2 / count - mean * mean, 0.0)
    return mean, var


def _affine(gamma, beta, mean, var, eps):
    inv = lax.rsqrt(var + eps)
    sc = gamma * inv
    bb = beta - mean * sc
    return sc, bb, inv


def _aff_rows_k(sc, bb, inv, mu, m1, m2):
    return jnp.stack([sc, bb, inv, mu, m1, m2]).astype(jnp.float32)


def _aff_rows_p(sc, bb, inv, mu):
    return jnp.stack([sc, bb, inv, mu]).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _bottleneck_core(cfg, x, wa, wb, wc, ga, be_a, gb, be_b, gc, be_c):
    """Returns (out, batch_stats6). cfg = (eps, interpret). The
    batch-stat outputs are NON-differentiable byproducts: their
    cotangents are ignored in the vjp — they only feed running-average
    state, which no loss differentiates through (same contract as
    fused.py keeping stats outside its vjp)."""
    out, res = _bottleneck_fwd_impl(cfg, x, wa, wb, wc, ga, be_a, gb,
                                    be_b, gc, be_c)
    return out, res[4]


def _bottleneck_fwd_impl(cfg, x, wa, wb, wc, ga, be_a, gb, be_b, gc,
                         be_c):
    eps, interpret = cfg
    n, h, wd, _ = x.shape
    count = n * h * wd
    ones_c = jnp.ones((x.shape[3],), jnp.float32)
    zeros_c = jnp.zeros((x.shape[3],), jnp.float32)
    # stage a: identity prologue (x is the block input, already activated)
    ya, s1a, s2a = _fwd_conv_stats(x, ones_c, zeros_c, wa, taps=1,
                                   act="identity", interpret=interpret)
    mua, vara = _finalize_stats(s1a, s2a, count)
    sca, bba, inva = _affine(ga, be_a, mua, vara, eps)
    # stage b: 3x3
    yb, s1b, s2b = _fwd_conv_stats(ya, sca, bba, wb, taps=9, act="relu",
                                   interpret=interpret)
    mub, varb = _finalize_stats(s1b, s2b, count)
    scb, bbb, invb = _affine(gb, be_b, mub, varb, eps)
    # stage c: 1x1
    yc, s1c, s2c = _fwd_conv_stats(yb, scb, bbb, wc, taps=1, act="relu",
                                   interpret=interpret)
    muc, varc = _finalize_stats(s1c, s2c, count)
    scc, bbc, invc = _affine(gc, be_c, muc, varc, eps)
    # tail: norm_c + residual + relu (pure elementwise — XLA fuses)
    pre = yc.astype(jnp.float32) * scc + bbc + x.astype(jnp.float32)
    out = jnp.maximum(pre, 0.0).astype(x.dtype)
    stats = (mua, vara, mub, varb, muc, varc)
    # residuals: raw conv outputs only — `pre` is recomputed in the
    # backward from yc and x (saving it would persist a full fp32
    # activation tensor per block, against the module's design)
    return out, (x, ya, yb, yc, stats)


def _bottleneck_vjp_fwd(cfg, x, wa, wb, wc, ga, be_a, gb, be_b, gc,
                        be_c):
    out, res = _bottleneck_fwd_impl(cfg, x, wa, wb, wc, ga, be_a, gb,
                                    be_b, gc, be_c)
    return (out, res[4]), \
        res + ((wa, wb, wc, ga, gb, gc, be_a, be_b, be_c),)


def _bottleneck_vjp_bwd(cfg, res, cts):
    eps, interpret = cfg
    g, _stat_cts = cts     # stats feed running averages only: cotangents
    #                        ignored by contract (see _bottleneck_core)
    x, ya, yb, yc, stats, weights = res
    wa, wb, wc, ga, gb, gc, be_a, be_b, be_c = weights
    mua, vara, mub, varb, muc, varc = stats
    n, h, wd, _ = x.shape
    count = n * h * wd
    sca, bba, inva = _affine(ga, be_a, mua, vara, eps)
    scb, bbb, invb = _affine(gb, be_b, mub, varb, eps)
    scc, bbc, invc = _affine(gc, be_c, muc, varc, eps)

    # tail backward (elementwise + 2 channel reductions; XLA fuses):
    # dz_c0 = g∘relu'(pre); the same tensor is the skip gradient.
    # pre recomputed from the saved raw tensors (elementwise, fuses)
    pre = yc.astype(jnp.float32) * scc + bbc + x.astype(jnp.float32)
    gz = jnp.where(pre > 0, g.astype(jnp.float32), 0.0)   # [N,H,W,K3]
    dx_skip = gz
    ycf = yc.astype(jnp.float32)
    yhat_c = (ycf - muc) * invc
    m1c = jnp.mean(gz, axis=(0, 1, 2))
    m2c = jnp.mean(gz * yhat_c, axis=(0, 1, 2))
    dgc = jnp.sum(gz * yhat_c, axis=(0, 1, 2))
    dbc = jnp.sum(gz, axis=(0, 1, 2))

    # stage c backward (one pass): consumes dz0_c (gz), recomputes z_b,
    # emits dW_c, dz0_b and stage-b sums
    aff_c = _aff_rows_k(scc, bbc, invc, muc, m1c, m2c)
    aff_b = _aff_rows_p(scb, bbb, invb, mub)
    dz0b, dwc, sums_b = _bwd_stage(yc, gz.astype(yc.dtype), yb, wc,
                                   aff_c, aff_b, taps=1, act_prev="relu",
                                   gmode="dz0", interpret=interpret)
    m1b = sums_b[0] / count
    m2b = sums_b[1] / count
    dgb = sums_b[1]
    dbb_ = sums_b[0]

    # stage b backward (3x3)
    aff_bk = _aff_rows_k(scb, bbb, invb, mub, m1b, m2b)
    aff_a = _aff_rows_p(sca, bba, inva, mua)
    dz0a, dwb, sums_a = _bwd_stage(yb, dz0b, ya, wb, aff_bk, aff_a,
                                   taps=9, act_prev="relu", gmode="dz0",
                                   interpret=interpret)
    m1a = sums_a[0] / count
    m2a = sums_a[1] / count
    dga = sums_a[1]
    dba = sums_a[0]

    # stage a backward: prologue was identity (z_prev = x), so act_prev
    # is identity and the emitted sums are unused
    aff_ak = _aff_rows_k(sca, bba, inva, mua, m1a, m2a)
    c_in = x.shape[3]
    aff_x = _aff_rows_p(jnp.ones((c_in,)), jnp.zeros((c_in,)),
                        jnp.ones((c_in,)), jnp.zeros((c_in,)))
    dx_main, dwa, _ = _bwd_stage(ya, dz0a, x, wa, aff_ak, aff_x, taps=1,
                                 act_prev="identity", gmode="dz0",
                                 interpret=interpret)
    dx = (dx_main.astype(jnp.float32) + dx_skip).astype(x.dtype)
    return (dx, dwa.astype(wa.dtype), dwb.astype(wb.dtype),
            dwc.astype(wc.dtype), dga.astype(ga.dtype),
            dba.astype(be_a.dtype), dgb.astype(gb.dtype),
            dbb_.astype(be_b.dtype), dgc.astype(gc.dtype),
            dbc.astype(be_c.dtype))


_bottleneck_core.defvjp(_bottleneck_vjp_fwd, _bottleneck_vjp_bwd)


# ---------------------------------------------------------------------------
# downsample (entry) blocks: conv skip + stride
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _bottleneck_ds_core(cfg, x, wa, wb, wc, ws, ga, be_a, gb, be_b, gc,
                        be_c, gs, be_s):
    """Downsample bottleneck: stride on conv_a and on the conv shortcut
    (ws + its own BN). cfg = (eps, stride, interpret). Returns
    (out, batch_stats8); stat cotangents ignored as in
    _bottleneck_core."""
    out, res = _bottleneck_ds_fwd_impl(cfg, x, wa, wb, wc, ws, ga, be_a,
                                       gb, be_b, gc, be_c, gs, be_s)
    return out, res[5]


def _bottleneck_ds_fwd_impl(cfg, x, wa, wb, wc, ws, ga, be_a, gb, be_b,
                            gc, be_c, gs, be_s):
    eps, stride, interpret = cfg
    n, h, wd, _ = x.shape
    ho, wo = h // stride, wd // stride
    count = n * ho * wo
    ones_c = jnp.ones((x.shape[3],), jnp.float32)
    zeros_c = jnp.zeros((x.shape[3],), jnp.float32)
    ya, s1a, s2a = _fwd_conv_stats(x, ones_c, zeros_c, wa, taps=1,
                                   act="identity", interpret=interpret,
                                   stride=stride)
    mua, vara = _finalize_stats(s1a, s2a, count)
    sca, bba, inva = _affine(ga, be_a, mua, vara, eps)
    yb, s1b, s2b = _fwd_conv_stats(ya, sca, bba, wb, taps=9, act="relu",
                                   interpret=interpret)
    mub, varb = _finalize_stats(s1b, s2b, count)
    scb, bbb, invb = _affine(gb, be_b, mub, varb, eps)
    yc, s1c, s2c = _fwd_conv_stats(yb, scb, bbb, wc, taps=1, act="relu",
                                   interpret=interpret)
    muc, varc = _finalize_stats(s1c, s2c, count)
    scc, bbc, invc = _affine(gc, be_c, muc, varc, eps)
    # conv shortcut: same input, own stride + BN
    ys, s1s, s2s = _fwd_conv_stats(x, ones_c, zeros_c, ws, taps=1,
                                   act="identity", interpret=interpret,
                                   stride=stride)
    mus, vars_ = _finalize_stats(s1s, s2s, count)
    scs, bbs, invs = _affine(gs, be_s, mus, vars_, eps)
    pre = (yc.astype(jnp.float32) * scc + bbc
           + ys.astype(jnp.float32) * scs + bbs)
    out = jnp.maximum(pre, 0.0).astype(x.dtype)
    stats = (mua, vara, mub, varb, muc, varc, mus, vars_)
    return out, (x, ya, yb, yc, ys, stats)


def _bottleneck_ds_vjp_fwd(cfg, x, wa, wb, wc, ws, ga, be_a, gb, be_b,
                           gc, be_c, gs, be_s):
    out, res = _bottleneck_ds_fwd_impl(cfg, x, wa, wb, wc, ws, ga, be_a,
                                       gb, be_b, gc, be_c, gs, be_s)
    return (out, res[5]), \
        res + ((wa, wb, wc, ws, ga, gb, gc, gs, be_a, be_b, be_c, be_s),)


def _bottleneck_ds_vjp_bwd(cfg, res, cts):
    eps, stride, interpret = cfg
    g, _stat_cts = cts
    x, ya, yb, yc, ys, stats, weights = res
    wa, wb, wc, ws, ga, gb, gc, gs, be_a, be_b, be_c, be_s = weights
    mua, vara, mub, varb, muc, varc, mus, vars_ = stats
    n, h, wd, _ = x.shape
    ho, wo = h // stride, wd // stride
    count = n * ho * wo
    sca, bba, inva = _affine(ga, be_a, mua, vara, eps)
    scb, bbb, invb = _affine(gb, be_b, mub, varb, eps)
    scc, bbc, invc = _affine(gc, be_c, muc, varc, eps)
    scs, bbs, invs = _affine(gs, be_s, mus, vars_, eps)

    pre = (yc.astype(jnp.float32) * scc + bbc
           + ys.astype(jnp.float32) * scs + bbs)
    gz = jnp.where(pre > 0, g.astype(jnp.float32), 0.0)
    ycf = yc.astype(jnp.float32)
    yhat_c = (ycf - muc) * invc
    m1c = jnp.mean(gz, axis=(0, 1, 2))
    m2c = jnp.mean(gz * yhat_c, axis=(0, 1, 2))
    dgc = jnp.sum(gz * yhat_c, axis=(0, 1, 2))
    dbc = jnp.sum(gz, axis=(0, 1, 2))
    ysf = ys.astype(jnp.float32)
    yhat_s = (ysf - mus) * invs
    m1s = jnp.mean(gz, axis=(0, 1, 2))
    m2s = jnp.mean(gz * yhat_s, axis=(0, 1, 2))
    dgs = jnp.sum(gz * yhat_s, axis=(0, 1, 2))
    dbs = jnp.sum(gz, axis=(0, 1, 2))

    gzt = gz.astype(yc.dtype)
    aff_c = _aff_rows_k(scc, bbc, invc, muc, m1c, m2c)
    aff_b = _aff_rows_p(scb, bbb, invb, mub)
    dz0b, dwc, sums_b = _bwd_stage(yc, gzt, yb, wc, aff_c, aff_b, taps=1,
                                   act_prev="relu", gmode="dz0",
                                   interpret=interpret)
    m1b = sums_b[0] / count
    m2b = sums_b[1] / count
    dgb = sums_b[1]
    dbb_ = sums_b[0]

    aff_bk = _aff_rows_k(scb, bbb, invb, mub, m1b, m2b)
    aff_a = _aff_rows_p(sca, bba, inva, mua)
    dz0a, dwb, sums_a = _bwd_stage(yb, dz0b, ya, wb, aff_bk, aff_a,
                                   taps=9, act_prev="relu", gmode="dz0",
                                   interpret=interpret)
    m1a = sums_a[0] / count
    m2a = sums_a[1] / count
    dga = sums_a[1]
    dba = sums_a[0]

    c_in = x.shape[3]
    aff_id = _aff_rows_p(jnp.ones((c_in,)), jnp.zeros((c_in,)),
                         jnp.ones((c_in,)), jnp.zeros((c_in,)))
    aff_ak = _aff_rows_k(sca, bba, inva, mua, m1a, m2a)
    dx_main, dwa, _ = _bwd_stage(ya, dz0a, x, wa, aff_ak, aff_id, taps=1,
                                 act_prev="identity", gmode="dz0",
                                 interpret=interpret, stride=stride)
    aff_sk = _aff_rows_k(scs, bbs, invs, mus, m1s, m2s)
    dx_skip, dws, _ = _bwd_stage(ys, gzt, x, ws, aff_sk, aff_id, taps=1,
                                 act_prev="identity", gmode="dz0",
                                 interpret=interpret, stride=stride)
    dx = (dx_main.astype(jnp.float32)
          + dx_skip.astype(jnp.float32)).astype(x.dtype)
    return (dx, dwa.astype(wa.dtype), dwb.astype(wb.dtype),
            dwc.astype(wc.dtype), dws.astype(ws.dtype),
            dga.astype(ga.dtype), dba.astype(be_a.dtype),
            dgb.astype(gb.dtype), dbb_.astype(be_b.dtype),
            dgc.astype(gc.dtype), dbc.astype(be_c.dtype),
            dgs.astype(gs.dtype), dbs.astype(be_s.dtype))


_bottleneck_ds_core.defvjp(_bottleneck_ds_vjp_fwd, _bottleneck_ds_vjp_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def fused_bottleneck(
    x: jax.Array,
    wa: jax.Array, bn_a: BnParams,
    wb: jax.Array, bn_b: BnParams,
    wc: jax.Array, bn_c: BnParams,
    *,
    train: bool,
    w_skip: jax.Array = None, bn_skip: BnParams = None,
    stride: int = 1,
    eps: float = 1e-5,
    decay: float = 0.9,
    interpret: bool = False,
) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """ResNet bottleneck, fully fused.

    x [N,H,W,Cin] NHWC (already post-ReLU block input); wa [Cin,Cmid],
    wb [9,Cmid,Cmid] (tap-major 3x3), wc [Cmid,Cout].

    Identity form (w_skip=None, stride=1, Cout == Cin): out =
    relu(norm_c(conv_c(...)) + x). Downsample (entry) form: w_skip
    [Cin,Cout] + bn_skip give the conv shortcut, and `stride` applies to
    conv_a AND the shortcut (the ResNet50 layout) — out =
    relu(norm_c(...) + norm_s(conv_s(x))).

    Returns (out, new_running_stats): 6 entries (mean/var for a,b,c) or
    8 (+ skip) fp32, decayed like layers.BatchNormalization
    (`new = decay·old + (1−decay)·batch`, rounding decay·old through
    x.dtype exactly like the unfused plan).

    Inference (train=False) uses running stats — the chain is then pure
    elementwise+matmul with no stats dependency.
    """
    ds = w_skip is not None
    if ds != (bn_skip is not None):
        raise ValueError("w_skip and bn_skip go together")
    if stride != 1 and not ds:
        raise ValueError("stride != 1 requires the conv shortcut")

    def _decayed(pairs):
        # decay*old ROUNDS through x.dtype exactly like the unfused
        # BatchNormalization (fused.py precision-chain note): under bf16
        # the persistent running stats would otherwise drift apart
        # between the two execution plans
        return tuple(
            (decay * old.astype(x.dtype) + (1.0 - decay) * new)
            .astype(jnp.float32) for old, new in pairs)

    if train:
        if ds:
            cfg = (eps, stride, interpret)
            out, bs = _bottleneck_ds_core(
                cfg, x, wa, wb, wc, w_skip, bn_a.gamma, bn_a.beta,
                bn_b.gamma, bn_b.beta, bn_c.gamma, bn_c.beta,
                bn_skip.gamma, bn_skip.beta)
            mua, vara, mub, varb, muc, varc, mus, vars_ = bs
            return out, _decayed((
                (bn_a.running_mean, mua), (bn_a.running_var, vara),
                (bn_b.running_mean, mub), (bn_b.running_var, varb),
                (bn_c.running_mean, muc), (bn_c.running_var, varc),
                (bn_skip.running_mean, mus),
                (bn_skip.running_var, vars_)))
        cfg = (eps, interpret)
        out, batch_stats = _bottleneck_core(
            cfg, x, wa, wb, wc, bn_a.gamma, bn_a.beta, bn_b.gamma,
            bn_b.beta, bn_c.gamma, bn_c.beta)
        mua, vara, mub, varb, muc, varc = batch_stats
        return out, _decayed((
            (bn_a.running_mean, mua), (bn_a.running_var, vara),
            (bn_b.running_mean, mub), (bn_b.running_var, varb),
            (bn_c.running_mean, muc), (bn_c.running_var, varc)))
    # inference: running-stat affines, no stats needed
    sca, bba, _ = _affine(bn_a.gamma.astype(jnp.float32),
                          bn_a.beta.astype(jnp.float32),
                          bn_a.running_mean, bn_a.running_var, eps)
    scb, bbb, _ = _affine(bn_b.gamma.astype(jnp.float32),
                          bn_b.beta.astype(jnp.float32),
                          bn_b.running_mean, bn_b.running_var, eps)
    scc, bbc, _ = _affine(bn_c.gamma.astype(jnp.float32),
                          bn_c.beta.astype(jnp.float32),
                          bn_c.running_mean, bn_c.running_var, eps)
    ones_c = jnp.ones((x.shape[3],), jnp.float32)
    zeros_c = jnp.zeros((x.shape[3],), jnp.float32)
    ya, _, _ = _fwd_conv_stats(x, ones_c, zeros_c, wa, taps=1,
                               act="identity", interpret=interpret,
                               stride=stride)
    yb, _, _ = _fwd_conv_stats(ya, sca, bba, wb, taps=9, act="relu",
                               interpret=interpret)
    yc, _, _ = _fwd_conv_stats(yb, scb, bbb, wc, taps=1, act="relu",
                               interpret=interpret)
    if ds:
        scs, bbs, _ = _affine(bn_skip.gamma.astype(jnp.float32),
                              bn_skip.beta.astype(jnp.float32),
                              bn_skip.running_mean, bn_skip.running_var,
                              eps)
        ys, _, _ = _fwd_conv_stats(x, ones_c, zeros_c, w_skip, taps=1,
                                   act="identity", interpret=interpret,
                                   stride=stride)
        shortcut = ys.astype(jnp.float32) * scs + bbs
    else:
        shortcut = x.astype(jnp.float32)
    pre = yc.astype(jnp.float32) * scc + bbc + shortcut
    out = jnp.maximum(pre, 0.0).astype(x.dtype)
    stats = (bn_a.running_mean, bn_a.running_var, bn_b.running_mean,
             bn_b.running_var, bn_c.running_mean, bn_c.running_var)
    if ds:
        stats = stats + (bn_skip.running_mean, bn_skip.running_var)
    return out, stats


def reference_bottleneck(x, wa, bn_a, wb, bn_b, wc, bn_c, *, train,
                         w_skip=None, bn_skip=None, stride=1,
                         eps=1e-5, decay=0.9):
    """Unfused jnp composition with IDENTICAL semantics — the equivalence
    oracle for the kernel chain (autodiff supplies its backward)."""
    def conv1x1(z, w, s=1):
        if s > 1:
            z = z[:, ::s, ::s, :]
        return jnp.einsum("nhwc,ck->nhwk", z, w,
                          preferred_element_type=jnp.float32)

    def conv3x3(z, w9):
        zp = jnp.pad(z, ((0, 0), (1, 1), (1, 1), (0, 0)))
        acc = 0
        for t in range(9):
            dy, dx = divmod(t, 3)
            acc = acc + jnp.einsum(
                "nhwc,ck->nhwk",
                zp[:, dy:dy + z.shape[1], dx:dx + z.shape[2], :], w9[t],
                preferred_element_type=jnp.float32)
        return acc

    def bn(y, p, train):
        yf = y.astype(jnp.float32)
        if train:
            mean = jnp.mean(yf, axis=(0, 1, 2))
            var = jnp.maximum(
                jnp.mean(yf * yf, axis=(0, 1, 2)) - mean * mean, 0.0)
        else:
            mean, var = p.running_mean, p.running_var
        inv = lax.rsqrt(var + eps)
        out = (yf - mean) * inv * p.gamma.astype(jnp.float32) \
            + p.beta.astype(jnp.float32)
        new_mean = decay * p.running_mean + (1 - decay) * mean
        new_var = decay * p.running_var + (1 - decay) * var
        return out, (mean, var) if train else (p.running_mean,
                                               p.running_var), \
            (new_mean, new_var)

    ya = conv1x1(x.astype(jnp.float32), wa.astype(jnp.float32),
                 stride).astype(x.dtype)
    za, (mua, vara), ra = bn(ya, bn_a, train)
    za = jnp.maximum(za, 0.0)
    yb = conv3x3(za.astype(x.dtype).astype(jnp.float32),
                 wb.astype(jnp.float32)).astype(x.dtype)
    zb, (mub, varb), rb = bn(yb, bn_b, train)
    zb = jnp.maximum(zb, 0.0)
    yc = conv1x1(zb.astype(x.dtype).astype(jnp.float32),
                 wc.astype(jnp.float32)).astype(x.dtype)
    zc, (muc, varc), rc = bn(yc, bn_c, train)
    if w_skip is not None:
        ys = conv1x1(x.astype(jnp.float32), w_skip.astype(jnp.float32),
                     stride).astype(x.dtype)
        zs, _, rs = bn(ys, bn_skip, train)
        shortcut = zs
    else:
        shortcut = x.astype(jnp.float32)
    out = jnp.maximum(zc + shortcut, 0.0).astype(x.dtype)
    stats = (ra[0], ra[1], rb[0], rb[1], rc[0], rc[1])
    if w_skip is not None:
        stats = stats + (rs[0], rs[1])
    return out, stats
