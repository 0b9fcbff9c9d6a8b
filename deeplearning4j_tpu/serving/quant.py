"""int8 KV page-pool quantization (``PagedKVConfig(kv_dtype="int8")``).

Paged decode is memory-bandwidth-bound: after PR 10 removed the
gather/scatter round trip, what every step still moves is the pool
bytes themselves. Storing the pool in symmetric int8 halves that
traffic AND doubles the token budget a fixed byte budget admits — the
arithmetic-intensity lever of the reference framework's compression
subsystem (``Nd4j.getCompressor()``) applied to serving KV state.

Scheme — symmetric per-(page, kv-head) power-of-two scales:

- each kv leaf's pool becomes ``[P, Hkv, page_size, D]`` **int8** with
  a ``[P, Hkv]`` float32 amax-scale sidecar (page 0 stays the null
  page; its scale stays whatever collided writes left — nothing valid
  ever reads through it);
- a page's scale is established from its BASE token (the token at
  ``q_pos % page_size == 0``): ``sigma = pow2ceil(amax / 127)``. Every
  later token of the page quantizes with the base's sigma —
  ``q = clip(round(x / sigma), -127, 127)`` — so a page is priced
  once and never rescaled (quantize-once: re-quantizing on every
  append would make pool bytes depend on visit order);
- power-of-two sigma makes ``dequant(q) = q * sigma`` EXACT in float
  (a mantissa shift), and exactly representable even in bf16
  (|q| <= 127 needs 7 mantissa bits) — so reading a page twice, or
  re-priming the same committed tokens after a rebuild / migration,
  reproduces bit-identical dequantized values. That is what keeps the
  prefix-cache hit==miss and ledger-rebuild pins bitwise under int8.

Accuracy is an explicitly pinned ENVELOPE (greedy-divergence step +
logit MAE on the test models — tests/test_serving_quant.py), never
bit-parity with bf16: the round-trip error per element is bounded by
sigma / 2 <= amax * 2 / 127 (pow2ceil at most doubles amax / 127).

The write path lives in ``SelfAttentionLayer._stream_attend_paged``:
quantize_chunk below prices a chunk of a leaf and ratchets its sidecar,
and the int8 rows then go into the pool through the same
``_paged_append`` the bf16 pool uses (one scatter a leaf: a [D] row
for each ``(page, head, offset)`` into the leaf seen as rows; the
``[P, Hkv]`` sidecar has its own small scatter over pages). The read
paths dequant
in ``_stream_attend_paged``'s folded gather (XLA) and in
``serving/paged_kernel.py``'s VMEM inner loop (Pallas, scales riding
the scalar-prefetch refs).
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax.numpy as jnp

__all__ = ["KV_DTYPES", "dequantize", "kv_page_bytes", "pool_leaves",
           "pow2ceil", "quantize", "quantize_chunk"]

#: the PagedKVConfig.kv_dtype vocabulary: "bf16" = the unquantized
#: pool in the net's native leaf dtype (the name of the default, not a
#: cast); "int8" = this module
KV_DTYPES = ("bf16", "int8")


def pow2ceil(x):
    """Smallest power of two >= x, elementwise (x >= 0; 0 -> 0).

    frexp writes x = m * 2**e with m in [0.5, 1): an exact power of
    two has m == 0.5 (its own value), anything else rounds up to 2**e.
    Built from frexp/ldexp rather than log2/exp2 so the result is
    exact for every representable input — the scale must be a true
    power of two for dequantization to be a mantissa shift."""
    x = jnp.asarray(x, jnp.float32)
    m, e = jnp.frexp(x)
    out = jnp.ldexp(jnp.ones_like(x), jnp.where(m == 0.5, e - 1, e))
    return jnp.where(x > 0, out, 0.0)


def quantize(x, sigma):
    """Symmetric int8 quantization of ``x`` under (broadcastable)
    scales ``sigma``: clip(round(x / sigma), -127, 127). sigma == 0
    (an all-zero page base) quantizes to 0."""
    sigma = jnp.asarray(sigma, jnp.float32)
    safe = jnp.where(sigma > 0, sigma, 1.0)
    q = jnp.round(jnp.asarray(x, jnp.float32) / safe)
    q = jnp.where(sigma > 0, q, 0.0)
    return jnp.clip(q, -127, 127).astype(jnp.int8)


def dequantize(q, sigma, dtype=jnp.float32):
    """q * sigma — exact for power-of-two sigma (and exactly
    representable in bf16: |q| <= 127 fits 7 mantissa bits)."""
    out = jnp.asarray(q, jnp.float32) * jnp.asarray(sigma, jnp.float32)
    return out.astype(dtype)


def quantize_chunk(xt, scales, page, q_pos, pos, writable, *, page_size,
                   chunk0):
    """Quantize one appended chunk of a kv leaf and ratchet the scale
    sidecar — the per-leaf worker of the paged append
    (``_stream_attend_paged``).

    - ``xt``: [N, T, Hkv, D] — the chunk's k or v, rope applied,
      already transposed to the pool's write layout;
    - ``scales``: [P, Hkv] float32 sidecar (pre-chunk);
    - ``page``: [N, T] int32 target page per token (already masked to
      the null page 0 for non-writable positions);
    - ``q_pos``: [N, T] absolute position per token (pads: pos - 1);
    - ``pos``: [N] each row's pre-chunk stream position;
    - ``writable``: [N, T] bool — real, in-capacity tokens;
    - ``chunk0``: chunk index of the first REAL token (pad_left for a
      left-padded prime chunk, 0 otherwise; may be traced).

    Returns ``(xq [N,T,Hkv,D] int8, new_scales [P,Hkv])``.

    A token's scale is its page BASE's sigma. The base is either in
    this very chunk (prefill / wide speculative verify: look it up by
    chunk index — the base token of position b sits at chunk index
    chunk0 + (b - pos)) or already committed (plain decode appends mid
    page: read the sidecar). Base tokens OVERWRITE their page's
    sidecar entry, so a speculative rewind that re-appends a different
    base re-prices the page from the token that actually committed —
    pool bytes stay a pure function of the committed token stream."""
    n, t, _, _ = xt.shape
    ps = page_size
    amax = jnp.max(jnp.abs(xt.astype(jnp.float32)), axis=-1)  # [N,T,Hkv]
    s_tok = pow2ceil(amax / 127.0)
    base_pos = (q_pos // ps) * ps
    in_chunk = base_pos >= pos[:, None]                       # [N, T]
    idx = jnp.clip(base_pos - pos[:, None] + chunk0, 0, t - 1)
    idx3 = jnp.broadcast_to(idx[:, :, None], s_tok.shape)
    s_base = jnp.take_along_axis(s_tok, idx3.astype(jnp.int32), axis=1)
    sigma = jnp.where(in_chunk[:, :, None], s_base, scales[page])
    xq = quantize(xt, sigma[:, :, :, None])
    is_base = (q_pos % ps == 0) & writable
    # non-base (and pad) rows collide at the null page 0 — garbage
    # there is never dequantized into anything a validity mask shows
    upd = jnp.where(is_base, page, 0)
    return xq, scales.at[upd].set(s_tok)


def kv_page_bytes(leaf_dims: Sequence[Tuple[int, int]], page_size: int,
                  kv_dtype: str, native_dtype: str) -> int:
    """Bytes ONE pool page costs across every kv leaf (k and v per
    attention layer — ``leaf_dims`` holds one (Hkv, D) per LAYER),
    including the int8 scale-sidecar rows. The unit of
    ``PagedKVConfig(total_bytes=...)`` capacity resolution: the same
    byte budget admits ~2x the pages under int8."""
    if kv_dtype == "int8":
        item, scale = 1, 4
    else:
        item = 2 if native_dtype in ("bfloat16", "bf16", "float16") else 4
        scale = 0
    total = 0
    for hkv, d in leaf_dims:
        total += 2 * (hkv * int(page_size) * d * item + hkv * scale)
    return total


def pool_leaves(total_pages: int, page_size: int,
                leaf_dims: Sequence[Tuple[int, int]]) -> Tuple[List, List]:
    """Freshly zeroed int8 pools + scale sidecars, two leaves (k, v)
    per (Hkv, D) layer entry, in layer order — the engine's eager
    store build (int8 pools must exist BEFORE the first prime: the
    prefill itself writes through the paged path)."""
    pools, scales = [], []
    for hkv, d in leaf_dims:
        for _ in ("kv_k", "kv_v"):
            pools.append(jnp.zeros((total_pages, hkv, int(page_size), d),
                                   jnp.int8))
            scales.append(jnp.zeros((total_pages, hkv), jnp.float32))
    return pools, scales
