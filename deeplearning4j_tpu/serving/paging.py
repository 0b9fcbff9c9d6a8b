"""Block-paged KV storage for the generation engine (vLLM-style).

PR 5's arena sized every slot for the worst-case sequence, so admitted
concurrency was capped at S and a short request stranded the HBM of the
positions it never used. Here the authoritative KV storage is a **page
pool**: per attention leaf, a ``[P, Hkv, page_size, D]`` array of
fixed-size token pages (or whatever the layer declares it keeps per
token — ``nn.conf.layers.PagedLeaf``: a latent-attention layer's three
``[P, page_size, W]`` leaves), plus one per-slot **page table** mapping the
slot's token blocks to pool pages, shared by all of a layer's leaves. Capacity becomes a *token* budget
(the µ-cuDNN memory-budget decomposition applied to serving state):

- admission checks ``prompt_len + max_new_tokens`` against **free
  pages**, not free slots — short requests hold few pages, so a pool
  sized like the old S-slot arena admits far more short requests;
- retirement returns the slot's pages to the pool immediately (host
  list ops — no device work);
- pages are refcounted, so the prefix cache can map one physical page
  into many slots' tables read-only (``serving/prefix_cache.py``).

The per-step dispatch works DIRECTLY on the pool: the attention step
reads K/V straight through the page table (the XLA read folds the
``pool[table]`` gather into the dispatch; the ``serving/paged_kernel.py``
Pallas kernel reads only live pages via scalar-prefetched tables) and
the new token's K/V appends with an O(one-token) in-dispatch write — one
fixed-shape dispatch per step, nothing materialized densely, zero
retraces after warmup (see ARCHITECTURE.md "Paged decode fast path").
This module's ``gather_pages`` / ``scatter_pages`` serve admission only:
a prime's dense ``[1, Hkv, L, D]`` row is scattered into the request's
pages, and a prefix hit's shared pages are gathered into the one-row
view the suffix prime attends — valid positions carry the exact bytes
the slot arena would hold.

Page 0 is the reserved **null page**: table entries beyond a slot's
allocation point at it, so gathers read garbage that position-validity
masks (``kv_pos``) keep invisible, and colliding scatter writes land
harmlessly where nothing is ever read.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.serving.quant import KV_DTYPES

__all__ = ["PagePool", "PageExhausted", "PagedKVConfig", "allocate_pools",
           "gather_pages", "pages_needed", "scatter_pages", "set_page"]


class PageExhausted(RuntimeError):
    """The pool cannot satisfy an allocation (admission should have
    head-blocked — reaching this mid-admission is an engine bug, except
    under chaos-seized pools)."""


@dataclass
class PagedKVConfig:
    """Knobs for the block-paged arena.

    ``page_size`` tokens per page; capacity comes from ``total_pages``,
    ``total_tokens`` or ``total_bytes`` (whichever is given —
    ``total_tokens`` rounds down to whole pages; ``total_bytes`` is a
    BYTE budget the engine divides by the per-page cost of the net's kv
    leaves (each layer's declared ``paged_leaves()``: whatever it keeps per
    token) incl. any int8 scale sidecar, so the same budget admits ~2x
    the pages under ``kv_dtype="int8"``), defaulting to the old slot
    arena's worst case (slots × ceil(L / page_size)) so switching
    paging on never shrinks capacity. ``prefix_cache`` enables
    shared-prompt page reuse.

    ``kv_dtype`` selects the pool's authoritative storage precision:
    ``"bf16"`` (default) keeps the net's native leaf dtype — the name
    of the unquantized path, not a cast; ``"int8"`` stores symmetric
    per-(page, kv-head) int8 with a ``[P, Hkv]`` amax-scale sidecar
    per leaf (``serving/quant.py`` — quantize-once on write,
    dequantize-on-read in both decode impls).

    Decode operates DIRECTLY on the page pool: the attention step reads
    K/V through the page table and the new token appends with an
    O(one-token) in-dispatch write (ARCHITECTURE.md "Paged decode fast
    path"). ``decode_impl`` asks for the read: ``"xla"`` (any backend —
    the gather folds into the dispatch), ``"pallas"`` (the
    serving/paged_kernel.py TPU paged-attention kernel;
    ``kernel_interpret=True`` emulates it on CPU for exactness tests),
    or ``"auto"`` (the kernel on a TPU where the shapes pass its gate,
    xla otherwise). ``paged_kernel.choose_paged_read`` is the one place
    that answers, from this and from what the net's layers declare."""

    page_size: int = 8
    total_pages: Optional[int] = None
    total_tokens: Optional[int] = None
    total_bytes: Optional[int] = None
    prefix_cache: bool = True
    decode_impl: str = "auto"
    kernel_interpret: bool = False
    kv_dtype: str = "bf16"

    def __post_init__(self):
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got "
                             f"{self.page_size}")
        if self.decode_impl not in ("auto", "xla", "pallas"):
            raise ValueError(
                f"decode_impl must be 'auto', 'xla' or 'pallas', got "
                f"{self.decode_impl!r}")
        if self.kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {KV_DTYPES}, got "
                f"{self.kv_dtype!r}")
        given = [k for k in ("total_pages", "total_tokens",
                             "total_bytes")
                 if getattr(self, k) is not None]
        if len(given) > 1:
            raise ValueError(
                f"give at most one capacity knob, got {given}")
        if self.total_pages is not None and self.total_pages < 1:
            raise ValueError(f"total_pages must be >= 1, got "
                             f"{self.total_pages}")
        if self.total_tokens is not None and \
                self.total_tokens < self.page_size:
            raise ValueError(
                f"total_tokens {self.total_tokens} is less than one "
                f"page ({self.page_size} tokens)")
        if self.total_bytes is not None and self.total_bytes < 1:
            raise ValueError(f"total_bytes must be >= 1, got "
                             f"{self.total_bytes}")

    def resolve_pages_bytes(self, page_bytes: int) -> int:
        """Pages the ``total_bytes`` budget buys at ``page_bytes`` per
        page (the engine computes page_bytes from the net's kv leaves
        via quant.kv_page_bytes — scale sidecars included)."""
        n = int(self.total_bytes) // max(1, int(page_bytes))
        if n < 1:
            raise ValueError(
                f"total_bytes {self.total_bytes} buys no page "
                f"({page_bytes} bytes/page)")
        return n

    def resolve_pages(self, slots: int, n_max: int) -> int:
        if self.total_pages is not None:
            return int(self.total_pages)
        if self.total_tokens is not None:
            return int(self.total_tokens) // self.page_size
        return int(slots) * int(n_max)


def pages_needed(total_tokens: int, page_size: int) -> int:
    """Pages a request holding `total_tokens` KV positions needs. The
    final drawn token is never fed back (the request retires on it), so
    a request of want = prompt + steps ids stores want - 1 positions —
    callers pass that."""
    return max(1, -(-int(total_tokens) // int(page_size)))


class PagePool:
    """Host-side page accounting: free list, per-page refcounts, and the
    chaos seize/restore seam. Deterministic: pages allocate in LIFO
    order, so a replayed trace maps the same physical pages.

    Refcount protocol: ``alloc`` hands out pages at refcount 1 (the
    allocating slot's reference); ``retain``/``release`` adjust for
    additional holders (the prefix cache, other slots mapping a shared
    page); a page returns to the free list when its count hits 0."""

    def __init__(self, total_pages: int, page_size: int):
        if total_pages < 2:
            raise ValueError(
                f"need >= 2 pages (page 0 is the reserved null page), "
                f"got {total_pages}")
        self.page_size = int(page_size)
        self.total_pages = int(total_pages)
        #: allocatable pages (page 0 reserved)
        self.usable = self.total_pages - 1
        self._free: List[int] = list(range(self.total_pages - 1, 0, -1))
        self._ref = [0] * self.total_pages
        self._seized: List[int] = []

    # -- accounting ----------------------------------------------------
    def free_count(self) -> int:
        return len(self._free)

    def used_count(self) -> int:
        return self.usable - len(self._free) - len(self._seized)

    def refcount(self, page: int) -> int:
        return self._ref[page]

    # -- allocation ----------------------------------------------------
    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise PageExhausted(
                f"need {n} pages, {len(self._free)} free "
                f"(pool of {self.usable})")
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._ref[p] = 1
        return out

    def retain(self, page: int) -> None:
        if self._ref[page] < 1:
            raise ValueError(f"retain of unallocated page {page}")
        self._ref[page] += 1

    def release(self, page: int) -> None:
        if self._ref[page] < 1:
            raise ValueError(f"release of unallocated page {page}")
        self._ref[page] -= 1
        if self._ref[page] == 0:
            self._free.append(page)

    # -- chaos seam (resilience.chaos.PageExhaustionInjector) ----------
    def seize(self, n: int) -> List[int]:
        """Remove `n` free pages from circulation (fault injection: a
        neighbouring tenant / fragmentation eating the pool). Seized
        pages are not 'used' — they are simply gone until restore()."""
        n = max(0, min(int(n), len(self._free)))
        taken = [self._free.pop() for _ in range(n)]
        self._seized.extend(taken)
        return taken

    def restore(self, pages=None) -> None:
        """Return seized pages (default: all of them) to the free list."""
        back = list(self._seized) if pages is None else list(pages)
        for p in back:
            self._seized.remove(p)
            self._free.append(p)


# ---------------------------------------------------------------------------
# the jitted pool <-> dense-view moves of admission
# ---------------------------------------------------------------------------

def allocate_pools(total_pages: int, page_size: int, leaves, dtypes):
    """One zeroed device page array per declared leaf
    (``nn.conf.layers.PagedLeaf``): ``[P, *token_shape with page_size at
    the leaf's token axis]`` in the leaf's dtype — ``[P, Hkv, ps, D]`` for
    an attention layer's keys and values, ``[P, ps, W]`` for a leaf that
    is one vector a token."""
    return [jnp.zeros(leaf.shape(total_pages, page_size), dt)
            for leaf, dt in zip(leaves, dtypes)]


def _token_axes(pools, axes):
    """The pool axis that counts a page's tokens, per leaf: 2 (the
    ``[P, Hkv, ps, D]`` layout) where the caller names none."""
    return (2,) * len(pools) if axes is None else tuple(axes)


@partial(jax.jit, static_argnames=("length", "axes"))
def gather_pages(pools, table, *, length: int, axes=None):
    """Materialize the dense per-slot view from the pool: for each leaf
    ``[P, ..., ps, ...]`` (tokens on pool axis ``axes[i]``; default 2:
    ``[P, Hkv, ps, D]``), gather ``table`` ([S, n_max] page ids, 0 =
    null) into ``[S, ..., n_max*ps, ...]`` and slice to the layer cache
    length. Unmapped blocks read the null page — garbage the kv_pos
    validity masks keep invisible."""
    out = []
    for pool, ax in zip(pools, _token_axes(pools, axes)):
        g = pool[table]                      # [S, n, *leaf]
        g = jnp.moveaxis(g, 1, ax)           # n next to (before) ps
        g = g.reshape(g.shape[:ax] + (-1,) + g.shape[ax + 2:])
        out.append(jax.lax.slice_in_dim(g, 0, length, axis=ax))
    return out


@partial(jax.jit, donate_argnums=(0,))
def set_page(pool, idx, leaf):
    """Write ONE page's block into `pool` at dynamic index `idx`
    (donated: updated in place). The fleet page-import write: a shipped
    ``[Hkv, ps, D]`` KV block (or ``[Hkv]`` scale row) lands in the
    local pool without a dense round trip. `idx` is a traced scalar so
    every page of a pool shares one compiled scatter — warmup primes it
    by writing zeros to the null page."""
    return pool.at[idx].set(leaf.astype(pool.dtype))


@partial(jax.jit, donate_argnums=(0,), static_argnames=("axes",))
def scatter_pages(pools, dense, table, axes=None):
    """Commit the updated dense views back to their mapped pages
    (donated: the pool buffer is updated in place). Only pages in
    `table` are written; free pages and unmapped cache entries keep
    their bytes. Duplicate page ids (prefix-shared blocks) collide with
    bit-identical values — the dense view was gathered from the same
    page and decode never rewrites old positions — so write order is
    immaterial. Blocks past a slot's allocation write the null page."""
    out = []
    s, n = table.shape
    for pool, d, ax in zip(pools, dense, _token_axes(pools, axes)):
        ps = pool.shape[ax]
        pad = [(0, 0)] * d.ndim
        pad[ax] = (0, n * ps - d.shape[ax])
        dp = jnp.pad(d, pad)
        dp = dp.reshape(d.shape[:ax] + (n, ps) + d.shape[ax + 1:])
        dp = jnp.moveaxis(dp, ax, 1)         # [S, n, *leaf]
        out.append(pool.at[table].set(dp.astype(pool.dtype)))
    return out
