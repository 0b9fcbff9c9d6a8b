"""Pallas TPU paged-attention decode kernel (+ the XLA reference).

The direct-paged-decode counterpart of ``nn/layers/pallas_attention.py``:
where that module fuses the *training/prefill* attention schedule, this
one fuses the *serving decode* read path over the block-paged KV pool
(``serving/paging.py``). The engine's steady-state step used to wrap the
canonical decode in a full-arena ``gather_pages → dispatch →
scatter_pages`` round trip — every generated token moved 2× the entire
token-budget pool per attention leaf through HBM regardless of how much
context was actually live. Here the page table IS the access path
(cuDNN's fused-primitive lesson, PAPERS.md: fold the memory movement
into the consuming op):

- grid ``(slot, kv-head, page-block)`` with the per-slot page table and
  per-row lengths prefetched as SCALAR refs
  (``pltpu.PrefetchScalarGridSpec``): the K/V block specs index the pool
  *through the table* (``table[s, b]``), so each grid step DMAs exactly
  one mapped page into VMEM — the pool is never materialized densely.
- online-softmax accumulators (m, l, acc) live in VMEM scratch across
  the page-block axis: one HBM read per live page, one HBM write per
  output block (the flash-attention schedule applied to paged decode).
- blocks at or past a row's length are skipped (``pl.when``) — dead
  table entries point at the reserved null page 0, so even their
  prefetch touches only the one always-resident page. Cost is
  O(active context), not O(token budget).
- the query axis is ``reps × W`` rows per kv head (GQA grouping ×
  query width), with W static: W = 1 is the plain decode step and
  W = 1 + γ is the widened speculative verify dispatch ``[S, V, 1+γ]``
  — the SAME kernel serves both, so brownout gamma changes and
  speculation toggles never switch kernels. In-block causality masks
  query w to keys ≤ length - W + w.
- ``interpret=True`` runs the kernel on CPU for the exactness suite
  (tests/test_serving_paged_kernel.py), mirroring pallas_attention's
  testing contract.

The XLA fallback for the same seam lives in
``SelfAttentionLayer._stream_attend_paged`` (nn/conf/layers.py): it
folds the ``pool[table]`` gather into the attention dispatch and shares
``_grouped_attend`` with the dense arena bit-for-bit.
``paged_ref_attention`` here is the standalone dense-gather reference
the kernel tests compare against.

Appends are NOT this kernel's job: the new token's K/V lands in the
pool via a one-token ``[S, Hkv, W, D]`` scatter at ``(page, offset)``
computed from each row's position (the layer does it before attending),
replacing the donated full-arena ``scatter_pages`` with an
O(one-token) write. Prefix-shared read-only blocks stay safe by block
alignment: a slot only ever appends at positions ≥ its own fresh
blocks (copy-on-extend falls out of the allocation math, the same
argument as the legacy scatter's).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30   # finite: exp(NEG_INF - NEG_INF) inside a fully-masked
#                   row must not produce NaN (explicit re-zeroing below)

__all__ = ["paged_attention", "paged_attention_supported",
           "paged_ref_attention"]


def _decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_scr, m_scr, l_scr, *, ps, qw, nb, scale):
    """One (slot, kv-head, page-block) grid step: score the row's
    grouped queries against ONE mapped page, fold into the online
    softmax, emit at the last block."""
    s, b = pl.program_id(0), pl.program_id(2)

    @pl.when(b == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = len_ref[s]

    @pl.when(b * ps < length)
    def _compute():
        qb = q_ref[0, 0]                              # [reps*W, D]
        sblk = jax.lax.dot_general(
            qb, k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [reps*W, ps]
        rw = qb.shape[0]
        kpos = b * ps + jax.lax.broadcasted_iota(jnp.int32, (rw, ps), 1)
        # query row r = rep * W + w sits at absolute position
        # length - W + w; causality within the appended chunk means
        # query w sees keys ≤ its own position (kpos < length follows:
        # the last query position IS length - 1)
        w = jax.lax.broadcasted_iota(jnp.int32, (rw, ps), 0) % qw
        valid = kpos <= length - qw + w
        sblk = jnp.where(valid, sblk, NEG_INF)
        m_prev = m_scr[:][:, :1]
        l_prev = l_scr[:][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sblk, axis=1, keepdims=True))
        # explicit zeroing: a row whose whole block is masked would see
        # exp(NEG_INF - NEG_INF) = 1 — keep those probabilities at 0
        p = jnp.exp(sblk - m_new) * valid.astype(jnp.float32)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [reps*W, D]
        acc_scr[:] = acc_scr[:] * corr + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(b == nb - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[:] / jnp.maximum(l_scr[:][:, :1], 1e-30)
                       ).astype(o_ref.dtype)


def _decode_kernel_quant(tbl_ref, len_ref, ks_ref, vs_ref, q_ref, k_ref,
                         v_ref, o_ref, acc_scr, m_scr, l_scr, *, ps, qw,
                         nb, scale):
    """The int8-pool variant of _decode_kernel: K/V blocks arrive in
    VMEM as int8 (the DMA moves half the bytes — the real win, not
    just the model's), with the per-(page, head) amax scales riding
    the scalar prefetch (ks/vs: [P, Hkv] float32 in SMEM, indexed by
    the very page id the table prefetch routed this block through).
    Dequantization folds into the existing fp32 math for free: the
    K scale multiplies the score block alongside 1/sqrt(d), and the
    V scale multiplies the block's pv contribution before it enters
    the accumulator — per-page-constant scales commute with both
    dots, so this IS dequant(int8) attention, not an approximation
    of it."""
    s, h, b = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(b == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = len_ref[s]

    @pl.when(b * ps < length)
    def _compute():
        page = tbl_ref[s, b]
        sk = ks_ref[page, h]
        sv = vs_ref[page, h]
        qb = q_ref[0, 0].astype(jnp.float32)          # [reps*W, D]
        # int8 operands are EXPLICITLY widened before any arithmetic
        # (the int8-promotion-in-dispatch lint contract): the dot runs
        # in fp32, the page's scale rides the existing score scaling
        sblk = jax.lax.dot_general(
            qb, k_ref[0, 0].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (scale * sk)
        rw = qb.shape[0]
        kpos = b * ps + jax.lax.broadcasted_iota(jnp.int32, (rw, ps), 1)
        w = jax.lax.broadcasted_iota(jnp.int32, (rw, ps), 0) % qw
        valid = kpos <= length - qw + w
        sblk = jnp.where(valid, sblk, NEG_INF)
        m_prev = m_scr[:][:, :1]
        l_prev = l_scr[:][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sblk, axis=1, keepdims=True))
        p = jnp.exp(sblk - m_new) * valid.astype(jnp.float32)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * sv      # [reps*W, D]
        acc_scr[:] = acc_scr[:] * corr + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(b == nb - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[:] / jnp.maximum(l_scr[:][:, :1], 1e-30)
                       ).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, table, lengths, *, query_width: int,
                    interpret: bool = False, k_scales=None,
                    v_scales=None):
    """Paged-attention decode over the block-paged KV pool.

    - ``q``: ``[S, Hkv, reps*W, D]`` — queries grouped by kv head (GQA:
      ``reps = n_heads // n_kv_heads`` query heads share each kv head),
      W = ``query_width`` appended positions per row, rope already
      applied. Row ``rep * W + w`` sits at absolute position
      ``lengths[s] - W + w``.
    - ``k_pool`` / ``v_pool``: ``[P, Hkv, page_size, D]`` — the pools,
      already holding this step's appended tokens (append-then-attend,
      the dense ``_stream_attend`` order).
    - ``table``: ``[S, n_max]`` int32 page ids (0 = reserved null page —
      dead blocks all route there).
    - ``lengths``: ``[S]`` int32 valid KV positions per row INCLUDING
      the appended chunk (engine: ``kv_pos + W``).
    - ``k_scales`` / ``v_scales``: ``[P, Hkv]`` float32 — the int8
      pool's per-(page, head) amax-scale sidecars (serving/quant.py).
      Passing them selects the quantized kernel: pools must be int8,
      blocks DMA at half the bytes, and dequantization happens in
      VMEM with the scales riding the scalar-prefetch refs.

    Returns ``[S, Hkv, reps*W, D]`` in ``q.dtype`` (fp32 accumulation).
    Free/garbage rows produce finite garbage the engine discards — the
    same contract as the dense arena's idle slots.
    """
    S, hkv, rw, d = q.shape
    _, _, ps, _ = k_pool.shape
    nb = table.shape[1]
    qw = int(query_width)
    if qw < 1 or rw % qw:
        raise ValueError(f"query rows {rw} not divisible by "
                         f"query_width {qw}")
    quant = k_scales is not None or v_scales is not None
    if quant and (k_scales is None or v_scales is None):
        raise ValueError("k_scales and v_scales travel together")
    if quant and k_pool.dtype != jnp.int8:
        raise ValueError(
            f"scale sidecars describe an int8 pool, got "
            f"{k_pool.dtype}")
    scale = float(1.0 / np.sqrt(d))
    if quant:
        kernel = functools.partial(_decode_kernel_quant, ps=ps, qw=qw,
                                   nb=nb, scale=scale)
        n_pref = 4
        pref = (jnp.asarray(table, jnp.int32),
                jnp.asarray(lengths, jnp.int32),
                jnp.asarray(k_scales, jnp.float32),
                jnp.asarray(v_scales, jnp.float32))
    else:
        kernel = functools.partial(_decode_kernel, ps=ps, qw=qw, nb=nb,
                                   scale=scale)
        n_pref = 2
        pref = (jnp.asarray(table, jnp.int32),
                jnp.asarray(lengths, jnp.int32))

    def _q_map(s, h, b, tbl, *_):
        return (s, h, 0, 0)

    def _pool_map(s, h, b, tbl, *_):
        # the page table IS the index map: block b of row s loads
        # pool page table[s, b] — the paged read path, fused
        return (tbl[s, b], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pref,
        grid=(S, hkv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, rw, d), _q_map),
            pl.BlockSpec((1, 1, ps, d), _pool_map),
            pl.BlockSpec((1, 1, ps, d), _pool_map),
        ],
        out_specs=pl.BlockSpec((1, 1, rw, d), _q_map),
        scratch_shapes=[pltpu.VMEM((rw, d), jnp.float32),
                        pltpu.VMEM((rw, 128), jnp.float32),
                        pltpu.VMEM((rw, 128), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, hkv, rw, d), q.dtype),
        interpret=interpret,
    )(*pref, q, k_pool, v_pool)


#: SMEM the int8 kernel may spend on its two [P, Hkv] float32 scale
#: sidecars. They ride the scalar prefetch, and a 2-D SMEM array pads its
#: minor dim to 128 lanes, so each costs P * 512 bytes however few kv
#: heads there are. v5e has 1 MiB of SMEM, shared with the page table:
#: measured there (jax 0.9.0), a 257-page pool compiles and a 1025-page
#: pool is refused ("Used 1.01M of 1.00M smem").
_SMEM_SCALE_BUDGET = 768 * 1024


def paged_attention_supported(pool_shape: Tuple[int, ...],
                              query_rows: int, *,
                              kv_dtype: str = "bf16") -> bool:
    """Shape gate for the REAL-CHIP kernel path (mirrors
    flash_attention_supported): what Mosaic compiles on a v5e under jax
    0.9.0, established by compiling each side of every bound there
    (PERF.md "PR 21"). ``pool_shape`` is the pool leaf's
    ``(P, Hkv, page_size, D)``.

    - native-dtype pools (``kv_dtype="bf16"`` — float32 or bfloat16
      storage): head dim lane-tileable, page rows a multiple of 8.
      8-row bfloat16 page blocks compile although bf16 packs 16 rows
      per tile.
    - int8 pools: the (32, 128) int8 tile, and the scale sidecars must
      fit SMEM (``_SMEM_SCALE_BUDGET``) — which bounds the POOL SIZE,
      not only the block shape. A larger int8 pool decodes on the XLA
      path.

    Interpret mode (CPU tests) has no such limits — this gate only
    decides the ``decode_impl="auto"`` resolution on a TPU backend."""
    if len(pool_shape) != 4:
        return False
    pages, hkv, ps, d = pool_shape
    if kv_dtype == "int8":
        lanes = -(-hkv // 128) * 128
        return (d in (128, 256) and ps % 32 == 0 and query_rows >= 1
                and 2 * pages * lanes * 4 <= _SMEM_SCALE_BUDGET)
    return d in (64, 128, 256) and ps % 8 == 0 and query_rows >= 1


def paged_ref_attention(q, k_pool, v_pool, table, lengths, *,
                        query_width: int):
    """Dense-gather XLA reference for the kernel tests: materialize
    ``pool[table]``, mask keys past each query's position, softmax in
    fp32 — the same math ``SelfAttentionLayer._grouped_attend`` runs on
    the gathered view, as a standalone function."""
    S, hkv, rw, d = q.shape
    _, _, ps, _ = k_pool.shape
    nb = table.shape[1]
    qw = int(query_width)
    kd = jnp.moveaxis(k_pool[table], 2, 1).reshape(S, hkv, nb * ps, d)
    vd = jnp.moveaxis(v_pool[table], 2, 1).reshape(S, hkv, nb * ps, d)
    kpos = jnp.arange(nb * ps)
    qpos = (jnp.asarray(lengths)[:, None] - qw
            + jnp.arange(rw)[None, :] % qw)              # [S, rw]
    valid = kpos[None, None, :] <= qpos[..., None]       # [S, rw, L]
    s = jnp.einsum("nhrd,nhld->nhrl", q.astype(jnp.float32),
                   kd.astype(jnp.float32)) / np.sqrt(d)
    s = jnp.where(valid[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("nhrl,nhld->nhrd", p, vd.astype(jnp.float32))
    return o.astype(q.dtype)
