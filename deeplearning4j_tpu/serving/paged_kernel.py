"""Pallas TPU paged-attention decode kernel, its gate, the chooser
between it and the XLA read, and the XLA reference.

The direct-paged-decode counterpart of ``nn/layers/pallas_attention.py``:
where that module fuses the *training/prefill* attention schedule, this
one fuses the *serving decode* read path over the block-paged KV pool
(``serving/paging.py``). A dense ``gather_pages → dispatch →
scatter_pages`` round trip would move 2× the entire token-budget pool
per attention leaf through HBM for every generated token, regardless of
how much context is live. Here the page table IS the access path
(cuDNN's fused-primitive lesson, PAPERS.md: fold the memory movement
into the consuming op):

- grid ``(slot, page-group)`` with the per-slot page table and per-row
  lengths prefetched as SCALAR refs (``pltpu.PrefetchScalarGridSpec``).
  The pools stay in HBM (``pl.ANY``): one grid step serves a group of G
  consecutive table entries of a row and copies each mapped page
  ``pool[table[s, b*G + g]]`` into VMEM itself
  (``pltpu.make_async_copy``; a page's ``[Hkv, page_size, D]`` is
  contiguous, so one copy brings every kv head) — the pool is never
  materialized densely. A one-page-a-step grid (the walk before PR 30)
  paid ~0.4 µs of step overhead for 8 KB; G pages a step pay it once.
- the copies of the NEXT live group (the row's next, or the next row's
  first) are started before the current one is scored: two slots of
  page buffers and DMA semaphores, the slot carried in SMEM, so a
  row's walk is bound by the copies and not by their latency. The
  chain needs the grid in order: both axes are sequential.
- a step scores ``[reps × W, G × page_size]`` per kv head — 512 keys
  wide, not one page — and folds it into online-softmax accumulators
  (m, l, acc) that live in VMEM scratch across the group axis: one
  HBM read per live page, one HBM write per output block.
- G comes from the static shapes alone (``pages_per_step``): 512 keys a
  step, fewer under a VMEM budget, never more than the table is wide.
- groups wholly past a row's length start no copy and do no arithmetic;
  inside a partly live group only the pages that hold keys are copied,
  and the buffer rows they leave stale are masked out of the scores and
  selected out of V. Cost is O(active context), not O(token budget).
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
``choose_paged_read`` is the one place that picks between the two (the
engine's constructor calls it once and records the answer on its net's
attention layers); ``paged_attention_supported`` is the kernel's shape
gate it consults. ``paged_ref_attention`` here is the standalone
dense-gather reference the kernel tests compare against.

Appends are NOT this kernel's job: the new token's K/V lands in the
pool via a one-token ``[S, Hkv, W, D]`` scatter at ``(page, offset)``
computed from each row's position (the layer does it before attending):
an O(one-token) write. Prefix-shared read-only blocks stay safe by block
alignment: a slot only ever appends at positions ≥ its own fresh
blocks (copy-on-extend falls out of the allocation math).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import jaxpr_as_fun

NEG_INF = -1e30   # finite: exp(NEG_INF - NEG_INF) inside a fully-masked
#                   row must not produce NaN (explicit re-zeroing below)

__all__ = ["PLAIN_LEAVES", "choose_paged_read", "pages_per_step",
           "paged_attention", "paged_attention_supported",
           "paged_ref_attention"]

#: the cache leaves the kernel (and the int8 sidecar) can read: keys and
#: values, [Hkv, D] a token, as ``SelfAttentionLayer.paged_leaves()``
#: declares them
PLAIN_LEAVES = frozenset({"kv_k", "kv_v"})


#: keys one grid step scores per kv head: the lane width of the score
#: block. 512 keys are four MXU tiles a product, and at page_size 16 the
#: 32 page copies of a step are in flight together.
_GROUP_KEYS = 512

#: VMEM the page buffers may take: two slots of K and of V, each
#: ``[G, Hkv, page_size, D]``. v5e scopes 16 MiB to a kernel by default;
#: the query, output and accumulator blocks are small beside this.
_GROUP_VMEM_BYTES = 4 * 1024 * 1024


def pages_per_step(pool_shape: Tuple[int, ...], n_max: int,
                   itemsize: int) -> int:
    """G: how many consecutive table entries of a row one grid step
    copies and scores, resolved from the static shapes alone —
    ``_GROUP_KEYS`` keys a step, fewer where two slots of K and V pages
    ``(Hkv, page_size, D)`` of ``itemsize`` bytes an element would pass
    ``_GROUP_VMEM_BYTES``, never more than the table is wide."""
    _, hkv, ps, d = pool_shape
    page_bytes = hkv * ps * d * itemsize
    g = min(_GROUP_KEYS // ps, _GROUP_VMEM_BYTES // (4 * page_bytes))
    return max(1, min(g, n_max))


def _decode_kernel(*refs, ps, qw, nb, G, scale, quant):
    """One (slot, page-group) grid step: wait for the group's pages
    (copied by the step before), start the copies of the next live
    group, score the row's grouped queries of every kv head against the
    group's ``G * ps`` keys, fold into the online softmax, emit at the
    row's last step.

    ``quant``: the pools are int8 and two more scalar-prefetch refs
    (ks/vs: ``[P, Hkv]`` float32 in SMEM) hold the per-(page, head)
    amax scales, indexed by the very page id the table routed the copy
    through. Dequantization folds into the fp32 math: a page's K scale
    multiplies its ``ps`` score columns alongside 1/sqrt(d), its V
    scale its ``ps`` probability columns before the PV product —
    per-page-constant scales commute with both dots, so this IS
    dequant(int8) attention, not an approximation of it."""
    if quant:
        tbl_ref, len_ref, ks_ref, vs_ref = refs[:4]
        refs = refs[4:]
    else:
        tbl_ref, len_ref = refs[:2]
        refs = refs[2:]
    (q_ref, k_hbm, v_hbm, o_ref,
     kbuf, vbuf, sems, slot_scr, acc_scr, m_scr, l_scr) = refs
    s, b = pl.program_id(0), pl.program_id(1)
    n_rows, n_groups = pl.num_programs(0), pl.num_programs(1)
    hkv, rw, d = acc_scr.shape
    gk = G * ps
    length = len_ref[s]

    def live_pages(row_len, grp):
        # table entries of group grp that hold keys of a row this long
        return jnp.clip((row_len + ps - 1) // ps - grp * G, 0,
                        jnp.minimum(G, nb - grp * G))

    def live_groups(row_len):
        # a row's group 0 is always walked (it carries the copy chain
        # across rows), the others only while they hold keys
        return jnp.clip((row_len + gk - 1) // gk, 1, n_groups)

    def each_page_copy(row, grp, slot, act):
        """``act`` on the K and the V copy of every live page of a
        group (started by one step, waited for by the next)."""
        def body(g, carry):
            page = tbl_ref[row, grp * G + g]
            # a page's [Hkv, ps, D] is contiguous in the pool: one copy
            # brings every kv head
            act(pltpu.make_async_copy(k_hbm.at[page], kbuf.at[slot, g],
                                      sems.at[slot, 0]))
            act(pltpu.make_async_copy(v_hbm.at[page], vbuf.at[slot, g],
                                      sems.at[slot, 1]))
            return carry
        jax.lax.fori_loop(0, live_pages(len_ref[row], grp), body, 0)

    def start_group(row, grp, slot):
        each_page_copy(row, grp, slot, lambda copy: copy.start())

    @pl.when(b == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(b < live_groups(length))
    def _walk():
        @pl.when((s == 0) & (b == 0))
        def _first():
            slot_scr[0] = 0
            start_group(s, b, 0)

        slot = slot_scr[0]
        more = b + 1 < live_groups(length)
        nxt_row = jnp.where(more, s, s + 1)
        nxt_grp = jnp.where(more, b + 1, 0)

        @pl.when(nxt_row < n_rows)
        def _prefetch():
            # the next live group's pages fly while this one is scored
            start_group(nxt_row, nxt_grp, 1 - slot)

        slot_scr[0] = 1 - slot
        each_page_copy(s, b, slot, lambda copy: copy.wait())

        kpos = b * gk + jax.lax.broadcasted_iota(jnp.int32, (rw, gk), 1)
        # query row r = rep * W + w sits at absolute position
        # length - W + w; causality within the appended chunk means
        # query w sees keys ≤ its own position (kpos < length follows:
        # the last query position IS length - 1)
        w = jax.lax.broadcasted_iota(jnp.int32, (rw, gk), 0) % qw
        valid = kpos <= length - qw + w
        if nb % G:
            # the last group is wider than the table: a row coasting
            # past its capacity must not see the columns beyond it
            valid &= kpos < nb * ps
        # pages past the row's length were never copied: their buffer
        # rows are stale (0 × NaN in the PV product is NaN), so V is
        # selected, not only the scores masked
        vrow = b * gk + jax.lax.broadcasted_iota(jnp.int32, (gk, d), 0)
        v_live = vrow < length
        if quant:
            col_page = jax.lax.broadcasted_iota(jnp.int32, (1, gk), 1) // ps
            pages = [tbl_ref[s, jnp.minimum(b * G + g, nb - 1)]
                     for g in range(G)]

            def page_scales(sc_ref, h):
                # [1, G*ps]: column j carries the scale of page j // ps
                row = jnp.zeros((1, gk), jnp.float32)
                for g, page in enumerate(pages):
                    row = jnp.where(col_page == g, sc_ref[page, h], row)
                return row

        for h in range(hkv):
            qb = q_ref[0, h]                              # [reps*W, D]
            kb = kbuf[slot, :, h].reshape(gk, d)
            vb = vbuf[slot, :, h].reshape(gk, d)
            if quant:
                # int8 operands are EXPLICITLY widened before any
                # arithmetic (the int8-promotion-in-dispatch lint
                # contract): the dots run in fp32
                qb = qb.astype(jnp.float32)
                kb = kb.astype(jnp.float32)
                vb = vb.astype(jnp.float32)
            sblk = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [rw, G*ps]
            if quant:
                sblk = sblk * page_scales(ks_ref, h)
            sblk = jnp.where(valid, sblk, NEG_INF)
            m_prev = m_scr[h][:, :1]
            l_prev = l_scr[h][:, :1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(sblk, axis=1, keepdims=True))
            # explicit zeroing: a row whose whole group is masked would
            # see exp(NEG_INF - NEG_INF) = 1 — keep those at 0
            p = jnp.exp(sblk - m_new) * valid.astype(jnp.float32)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
            if quant:
                p = p * page_scales(vs_ref, h)
            pv = jax.lax.dot_general(
                p.astype(vb.dtype), jnp.where(v_live, vb, 0),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # [reps*W, D]
            acc_scr[h] = acc_scr[h] * corr + pv
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(b == n_groups - 1)
    def _finish():
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:][:, :, :1], 1e-30)
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
      Passing them selects the quantized form of the same walk: pools
      must be int8, pages copy at half the bytes, and dequantization
      happens in VMEM with the scales riding the scalar-prefetch refs.

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
    pref = (jnp.asarray(table, jnp.int32), jnp.asarray(lengths, jnp.int32))
    if quant:
        pref += (jnp.asarray(k_scales, jnp.float32),
                 jnp.asarray(v_scales, jnp.float32))
    call = _traced_call(q.shape, q.dtype.name, k_pool.shape,
                        k_pool.dtype.name, nb, qw, quant, bool(interpret))
    out, = jaxpr_as_fun(call)(*pref, q, k_pool, v_pool)
    return out


@functools.lru_cache(maxsize=32)
def _traced_call(q_shape, q_dtype, pool_shape, pool_dtype, nb, qw, quant,
                 interpret):
    """The ``pallas_call`` of one decode shape as a closed jaxpr, traced
    ONCE a process: every attention layer of a decode program binds the
    same equation, so the kernel is traced, and lowered to Mosaic
    (jax caches a lowering by the equation's parameters), once a
    program and not once a layer — with 30 layers the difference is
    ~10 s of a serving process's set-up on the chip's host."""
    S, hkv, rw, d = q_shape
    pages, _, ps, _ = pool_shape
    G = pages_per_step(pool_shape, nb, jnp.dtype(pool_dtype).itemsize)
    kernel = functools.partial(
        _decode_kernel, ps=ps, qw=qw, nb=nb, G=G,
        scale=float(1.0 / np.sqrt(d)), quant=quant)

    def _row_map(s, b, *_):
        return (s, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if quant else 2,
        grid=(S, pl.cdiv(nb, G)),
        in_specs=[
            pl.BlockSpec((1, hkv, rw, d), _row_map),
            # the pools stay in HBM: the kernel copies the pages the
            # table names itself — the paged read path, fused
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hkv, rw, d), _row_map),
        scratch_shapes=[pltpu.VMEM((2, G, hkv, ps, d), pool_dtype),
                        pltpu.VMEM((2, G, hkv, ps, d), pool_dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((hkv, rw, d), jnp.float32),
                        pltpu.VMEM((hkv, rw, 128), jnp.float32),
                        pltpu.VMEM((hkv, rw, 128), jnp.float32)],
    )
    call = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_shape, q_dtype),
        interpret=interpret)
    sds = jax.ShapeDtypeStruct
    pref = (sds((S, nb), jnp.int32), sds((S,), jnp.int32))
    if quant:
        pref += (sds((pages, hkv), jnp.float32),) * 2
    return jax.make_jaxpr(call)(*pref, sds(q_shape, q_dtype),
                                sds(pool_shape, pool_dtype),
                                sds(pool_shape, pool_dtype))


#: SMEM the int8 form may spend on its two [P, Hkv] float32 scale
#: sidecars. They ride the scalar prefetch, and a 2-D SMEM array pads its
#: minor dim to 128 lanes, so each costs P * 512 bytes however few kv
#: heads there are. v5e has 1 MiB of SMEM, shared with the page table:
#: measured there (jax 0.9.0), a 761-page pool ran and a 1025-page
#: pool is refused ("Used 1.01M of 1.00M smem").
_SMEM_SCALE_BUDGET = 768 * 1024


def paged_attention_supported(pool_shape: Tuple[int, ...],
                              query_rows: int, *,
                              kv_dtype: str = "bf16") -> bool:
    """Shape gate for the REAL-CHIP kernel path (mirrors
    flash_attention_supported): what Mosaic compiles on a v5e under jax
    0.9.0, established by compiling and running each side of every
    bound there against the dense-gather reference (PERF.md "PR 30").
    ``pool_shape`` is the pool leaf's ``(P, Hkv, page_size, D)``.

    One rule for every pool dtype, because the kernel copies whole
    pages out of HBM itself and a copy's slice must sit on the pool's
    tiling:

    - head dim a multiple of the 128 lanes (128, 256 and 512 ran;
      64 is refused: "Slice shape along dimension 3 must be aligned to
      tiling (128)" — such a model decodes on the XLA path);
    - page rows a multiple of 8 (8, 16, 24 ran in bfloat16, 8 in
      float32, 8 to 64 in int8; 12 is refused in bfloat16. 4 compiles
      too: the bound is sufficient, not tight);
    - int8 pools besides: the scale sidecars must fit SMEM
      (``_SMEM_SCALE_BUDGET``) — which bounds the POOL SIZE, not the
      page. A larger int8 pool decodes on the XLA path.

    Interpret mode (CPU tests) has no such limits — this gate only
    decides ``decode_impl="auto"`` on a TPU backend
    (``choose_paged_read``)."""
    if len(pool_shape) != 4:
        return False
    pages, hkv, ps, d = pool_shape
    if d % 128 or ps % 8 or query_rows < 1:
        return False
    if kv_dtype == "int8":
        lanes = -(-hkv // 128) * 128
        return 2 * pages * lanes * 4 <= _SMEM_SCALE_BUDGET
    return True


def choose_paged_read(leaf_keys, pool_shapes, *, kv_dtype: str,
                      decode_impl: str, kernel_interpret: bool,
                      backend: str) -> Tuple[str, bool]:
    """The one place that answers which code reads the page pool in a
    decode step: ``(impl, interpret)``, what the engine records as its
    net's layers' ``paged_read``. From what can be observed —
    ``leaf_keys``, the cache leaves the net's layers declare;
    ``pool_shapes``, each attention layer's ``(P, Hkv, page_size, D)``;
    the pool's ``kv_dtype``; the ``backend`` — and what
    ``PagedKVConfig`` asked:

    - leaves other than plain keys and values: no kernel reads them and
      no int8 sidecar scales them, the layers' own paged form runs with
      its gathers folded into the dispatch (``"xla"``). ``"pallas"`` or
      ``"int8"`` asked of such a net is an error;
    - ``"auto"``: the kernel iff the backend is a TPU and every pool
      passes ``paged_attention_supported``, else ``"xla"``;
    - ``"xla"`` / ``"pallas"``: taken as asked (``kernel_interpret``
      runs the kernel off the chip: the tests' reference)."""
    if not PLAIN_LEAVES.issuperset(leaf_keys):
        if kv_dtype == "int8" or decode_impl == "pallas":
            raise ValueError(
                "the int8 sidecar and the paged-attention kernel know "
                "[Hkv, D] keys and values only; this net's layers "
                f"declare {sorted(set(leaf_keys))} (use kv_dtype='bf16',"
                " decode_impl='xla')")
        return "xla", False
    if decode_impl == "auto":
        decode_impl = "pallas" if backend == "tpu" and all(
            paged_attention_supported(shape, 1, kv_dtype=kv_dtype)
            for shape in pool_shapes) else "xla"
    return decode_impl, bool(kernel_interpret) and decode_impl == "pallas"


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
