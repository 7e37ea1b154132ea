"""Decode caches: paged KV pools, dense slabs, ring-buffer windows, SSM states.

Cache pytree layout mirrors the parameter layout so it scans with the layers:

  cache = {
    "pos":    [B] int32  — number of tokens already processed per slot,
    "prefix": {str(i): layer_cache},
    "groups": {f"sub{j}": layer_cache with leading n_groups dim},
    "suffix": {str(i): layer_cache},
  }

Layer caches by mixer kind:
  global attn (dense): {"k": [B, T_slab, K, dh], "v": ...}  (slot t = position t)
  global attn (paged): {"k_pages": [P, K, page_size, dh], "v_pages": ...}
                       shared pool; per-request block tables map position
                       p -> (table[p // page_size], p % page_size)
  local attn:  {"k": [B, W, K, dh], "v": ...}               (ring: slot = p % W)
  mamba:       {"conv": [B, K-1, conv_dim], "ssm": [B, H, P, N]}
  hybrid:      {"k","v" (ring), "conv","ssm"}

Paged pools are managed host-side by :class:`PagedKVAllocator` — a free-list
page allocator with per-page reference counts so GRPO siblings share their
prompt's pages copy-on-write (one prompt prefill per group).  Page 0 is the
reserved garbage page: padded / inactive writes are routed there, so block
tables can always be padded with 0.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.ssm import init_mamba_cache

GARBAGE_PAGE = 0


class OutOfPages(RuntimeError):
    """Pool exhausted — callers grow the pool or reject the request."""


class PagedKVAllocator:
    """Host-side block/page-table allocator for the paged KV pools.

    Pages hold ``page_size`` token positions.  A request's block table is a
    python list of page ids; position p lives at (table[p // ps], p % ps).
    Reference counts implement copy-on-write prompt sharing: ``fork`` increfs
    every page of the source table, and ``writable_page`` copies a page out
    (returning the (src, dst) pair for the device-side copy) the first time a
    sharer writes into it.
    """

    def __init__(self, num_pages: int, page_size: int,
                 max_pages: Optional[int] = None):
        assert num_pages >= 2 and page_size >= 1
        assert max_pages is None or max_pages >= num_pages
        self.page_size = page_size
        self.num_pages = num_pages              # includes the garbage page 0
        self.max_pages = max_pages              # growth cap (None = unbounded)
        self.ref = np.zeros((num_pages,), np.int32)
        # LIFO free list, page 0 reserved as garbage
        self._free = list(range(num_pages - 1, 0, -1))

    # ------------------------------------------------------------------ #
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def capacity_tokens(self) -> int:
        return (self.num_pages - 1) * self.page_size

    def pages_for(self, n_tokens: int) -> int:
        return -(-max(n_tokens, 0) // self.page_size)

    # ------------------------------------------------------------------ #
    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise OutOfPages(f"need {n} pages, {len(self._free)} free")
        pages = [self._free.pop() for _ in range(n)]
        self.ref[pages] = 1
        return pages

    def alloc_table(self, n_tokens: int) -> List[int]:
        """Fresh block table covering n_tokens positions."""
        return self.alloc(self.pages_for(n_tokens))

    def free_page(self, page: int):
        assert page != GARBAGE_PAGE and self.ref[page] > 0, page
        self.ref[page] -= 1
        if self.ref[page] == 0:
            self._free.append(page)

    def free_table(self, table: List[int]):
        for p in table:
            self.free_page(p)
        table.clear()

    # ------------------------------------------------------------------ #
    def fork(self, table: List[int]) -> List[int]:
        """Share every page of ``table`` with a new table (COW)."""
        for p in table:
            self.ref[p] += 1
        return list(table)

    def incref(self, page: int):
        """Add one reference to an already-allocated page (refcount
        adoption: a migrated GRPO group's shared prompt page is allocated
        once on import and then incref'd per adopting sibling table)."""
        assert page != GARBAGE_PAGE and self.ref[page] > 0, page
        self.ref[page] += 1

    def ensure_capacity(self, table: List[int], n_tokens: int):
        """Append fresh pages until the table covers n_tokens positions."""
        need = self.pages_for(n_tokens) - len(table)
        if need > 0:
            table.extend(self.alloc(need))

    def writable_page(self, table: List[int], pos: int
                      ) -> Tuple[int, Optional[Tuple[int, int]]]:
        """Page for writing position ``pos``; COW-copies a shared page.

        Returns (page, copy) where copy is a (src, dst) pair the caller must
        apply to the device pools before writing, or None.
        """
        idx = pos // self.page_size
        page = table[idx]
        if self.ref[page] > 1:                   # shared — copy out
            new = self.alloc(1)[0]
            self.ref[page] -= 1
            table[idx] = new
            return new, (page, new)
        return page, None

    def reserve_decode(self, table: List[int], start: int, n: int
                       ) -> List[Tuple[int, int]]:
        """Reserve the decode write window [start, start + n) in one call.

        Appends fresh pages until the table covers ``start + n`` positions
        AND copy-on-writes every shared page the window overlaps, so the
        fused multi-token decode loop can run ``n`` steps with no allocator
        interaction (no COW, no capacity check) mid-horizon.  Atomic w.r.t.
        :class:`OutOfPages`: the pool state is untouched when it raises, so
        callers may grow the pool and retry.

        Returns the (src, dst) page-copy pairs the caller must apply to the
        device pools before the first write.
        """
        ps = self.page_size
        need_cap = self.pages_for(start + n) - len(table)
        lo, hi = start // ps, (start + max(n, 1) - 1) // ps
        shared = [i for i in range(lo, min(hi + 1, len(table)))
                  if self.ref[table[i]] > 1]
        if need_cap + len(shared) > self.n_free:
            raise OutOfPages(
                f"reserve_decode needs {need_cap + len(shared)} pages, "
                f"{self.n_free} free")
        copies: List[Tuple[int, int]] = []
        for i in shared:
            page = table[i]
            new = self.alloc(1)[0]
            self.ref[page] -= 1
            table[i] = new
            copies.append((page, new))
        if need_cap > 0:
            table.extend(self.alloc(need_cap))
        return copies

    # ------------------------------------------------------------------ #
    def grow(self, new_num_pages: int) -> int:
        """Extend the pool to ``new_num_pages`` (clamped to ``max_pages``
        when a cap is set).  Raises :class:`OutOfPages` when the pool is
        already at its cap — callers surface that as admission
        backpressure rather than doubling without bound.  Returns the
        actual new pool size."""
        if self.max_pages is not None:
            new_num_pages = min(new_num_pages, self.max_pages)
        if new_num_pages <= self.num_pages:
            raise OutOfPages(
                f"page pool at max_pages={self.max_pages} cap "
                f"({self.num_pages} pages, {self.n_free} free)")
        self._free.extend(range(new_num_pages - 1, self.num_pages - 1, -1))
        self.ref = np.concatenate(
            [self.ref, np.zeros((new_num_pages - self.num_pages,), np.int32)])
        self.num_pages = new_num_pages
        return self.num_pages


def attn_cache_shape(cfg, mixer: str, batch: int, slab_len: int):
    if mixer == "global":
        T = slab_len
    else:  # local / hybrid ring buffer
        T = min(cfg.window, slab_len) if cfg.window else slab_len
    return (batch, T, cfg.n_kv_heads, cfg.head_dim)


def init_layer_cache(cfg, mixer: str, batch: int, slab_len: int, dtype):
    c: Dict = {}
    if mixer in ("global", "local", "hybrid"):
        shape = attn_cache_shape(cfg, mixer, batch, slab_len)
        c["k"] = jnp.zeros(shape, dtype)
        c["v"] = jnp.zeros(shape, dtype)
    if mixer in ("mamba", "hybrid"):
        c.update(init_mamba_cache(cfg, batch))
    return c


def init_cache(cfg, batch: int, slab_len: int, dtype=jnp.bfloat16):
    """Fresh decode cache for the whole model."""
    mixers = cfg.layer_mixers()
    cache = {"pos": jnp.zeros((batch,), jnp.int32),
             "prefix": {}, "groups": {}, "suffix": {}}
    for i in range(cfg.first_k_dense):
        cache["prefix"][str(i)] = init_layer_cache(cfg, mixers[i], batch,
                                                   slab_len, dtype)
    G = cfg.n_groups
    for j, mixer in enumerate(cfg.pattern):
        one = init_layer_cache(cfg, mixer, batch, slab_len, dtype)
        cache["groups"][f"sub{j}"] = jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (G,) + t.shape).copy()
            if G else t[None][:0], one)
    n_pre = cfg.first_k_dense + G * cfg.group_size
    for i, mixer in enumerate(cfg.suffix_pattern):
        cache["suffix"][str(i)] = init_layer_cache(cfg, mixer, batch,
                                                   slab_len, dtype)
    return cache


def init_paged_layer_cache(cfg, mixer: str, batch: int, num_pages: int,
                           page_size: int, ring_len: int, dtype):
    """Like init_layer_cache but global-attn KV lives in a shared page pool."""
    c: Dict = {}
    if mixer == "global":
        # [page_size, dh] minor: a (page, KV head) block is one Mosaic tile
        shape = (num_pages, cfg.n_kv_heads, page_size, cfg.head_dim)
        c["k_pages"] = jnp.zeros(shape, dtype)
        c["v_pages"] = jnp.zeros(shape, dtype)
    elif mixer in ("local", "hybrid"):
        shape = attn_cache_shape(cfg, mixer, batch, ring_len)
        c["k"] = jnp.zeros(shape, dtype)
        c["v"] = jnp.zeros(shape, dtype)
    if mixer in ("mamba", "hybrid"):
        c.update(init_mamba_cache(cfg, batch))
    return c


def init_paged_cache(cfg, batch: int, num_pages: int, page_size: int,
                     ring_len: int = 128, dtype=jnp.float32):
    """Decode cache with paged global-attn pools + per-slot state leaves.

    ``batch`` sizes the per-slot leaves (decode concurrency); the pool is
    shared by all slots and bounded by ``num_pages`` (page 0 = garbage).
    """
    mixers = cfg.layer_mixers()
    cache = {"pos": jnp.zeros((batch,), jnp.int32),
             "prefix": {}, "groups": {}, "suffix": {}}
    mk = lambda m: init_paged_layer_cache(cfg, m, batch, num_pages, page_size,
                                          ring_len, dtype)
    for i in range(cfg.first_k_dense):
        cache["prefix"][str(i)] = mk(mixers[i])
    G = cfg.n_groups
    for j, mixer in enumerate(cfg.pattern):
        one = mk(mixer)
        cache["groups"][f"sub{j}"] = jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (G,) + t.shape).copy()
            if G else t[None][:0], one)
    for i, mixer in enumerate(cfg.suffix_pattern):
        cache["suffix"][str(i)] = mk(mixer)
    return cache


def _batch_axis(path) -> int:
    """Batch dim index for a cache leaf (group-stacked leaves lead with G)."""
    pstr = jax.tree_util.keystr(path)
    return 1 if "'groups'" in pstr else 0


def _is_pool(path) -> bool:
    pstr = jax.tree_util.keystr(path)
    return "k_pages" in pstr or "v_pages" in pstr


def gather_rows(cache, idx):
    """Per-slot leaves: rows at ``idx`` [n] (traced ok); pool leaves pass
    through whole (they are shared, not per-slot).  OOB indices clamp."""
    def f(p, c):
        if _is_pool(p):
            return c
        return jnp.take(c, idx, axis=_batch_axis(p), mode="clip")
    return jax.tree_util.tree_map_with_path(f, cache)


def scatter_rows(cache, rows, idx):
    """Write gathered rows back at slot positions ``idx``; pool leaves in
    ``rows`` replace the old pools wholesale.  OOB indices are dropped, so
    padding rows can use idx == batch."""
    def f(p, c, r):
        if _is_pool(p):
            return r
        ax = _batch_axis(p)
        r = r.astype(c.dtype)
        if ax == 0:
            return c.at[idx].set(r, mode="drop")
        return c.at[:, idx].set(r, mode="drop")
    return jax.tree_util.tree_map_with_path(f, cache, rows)


def copy_pool_pages(cache, src, dst):
    """pool[dst] = pool[src] on every pool leaf (COW page materialisation).

    src/dst: [m] int32; duplicate or garbage entries are harmless (dst may
    repeat GARBAGE_PAGE for padding).
    """
    def f(p, c):
        if not _is_pool(p):
            return c
        if _batch_axis(p) == 1:                 # group-stacked pool [G, P, ...]
            return c.at[:, dst].set(c[:, src])
        return c.at[dst].set(c[src])
    return jax.tree_util.tree_map_with_path(f, cache)


def gather_pages(cache, page_ids) -> "Dict[str, np.ndarray]":
    """Host copies of the pool pages at ``page_ids`` from every pool leaf
    (KV-migration export).  Keys are ``jax.tree_util.keystr`` paths; values
    are ``[n, K, page_size, dh]`` (group-stacked pools: ``[G, n, ...]``)."""
    ids = np.asarray(page_ids, np.int32)
    out: Dict[str, np.ndarray] = {}

    def f(p, c):
        if _is_pool(p):
            ax = 1 if _batch_axis(p) == 1 else 0
            out[jax.tree_util.keystr(p)] = np.asarray(
                jnp.take(c, ids, axis=ax))
        return c

    jax.tree_util.tree_map_with_path(f, cache)
    return out


def scatter_pages(cache, pages: "Dict[str, np.ndarray]", page_ids):
    """Write exported page payloads into the pools at ``page_ids`` (KV-
    migration import; inverse of :func:`gather_pages` up to page renames)."""
    ids = jnp.asarray(page_ids, jnp.int32)

    def f(p, c):
        if not _is_pool(p):
            return c
        v = jnp.asarray(pages[jax.tree_util.keystr(p)], c.dtype)
        if _batch_axis(p) == 1:
            return c.at[:, ids].set(v)
        return c.at[ids].set(v)

    return jax.tree_util.tree_map_with_path(f, cache)


def gather_slot_rows(cache, slot: int) -> "Dict[str, np.ndarray]":
    """Host copies of the per-slot leaves (ring-buffer K/V, SSM conv/ssm
    state) at batch row ``slot`` — the non-paged half of a request's
    generation state; rides along in the same migration manifest."""
    out: Dict[str, np.ndarray] = {}

    def f(p, c):
        pstr = jax.tree_util.keystr(p)
        if _is_pool(p) or pstr == "['pos']":
            return c
        if _batch_axis(p) == 1:
            out[pstr] = np.asarray(c[:, slot])
        else:
            out[pstr] = np.asarray(c[slot])
        return c

    jax.tree_util.tree_map_with_path(f, cache)
    return out


def scatter_slot_rows(cache, rows: "Dict[str, np.ndarray]", slot: int):
    """Write exported per-slot rows back at batch row ``slot``."""
    def f(p, c):
        pstr = jax.tree_util.keystr(p)
        if _is_pool(p) or pstr == "['pos']" or pstr not in rows:
            return c
        v = jnp.asarray(rows[pstr], c.dtype)
        if _batch_axis(p) == 1:
            return c.at[:, slot].set(v)
        return c.at[slot].set(v)

    return jax.tree_util.tree_map_with_path(f, cache)


def grow_pool(cache, new_num_pages: int):
    """Extend every pool leaf to ``new_num_pages`` pages (zero-filled tail)."""
    def f(p, c):
        if not _is_pool(p):
            return c
        ax = 1 if _batch_axis(p) == 1 else 0
        pad = [(0, 0)] * c.ndim
        pad[ax] = (0, new_num_pages - c.shape[ax])
        return jnp.pad(c, pad)
    return jax.tree_util.tree_map_with_path(f, cache)


def slice_batch(cache, idx, size: int = 1):
    """Slice `size` batch rows at `idx` (traced ok) from every cache leaf."""
    return jax.tree_util.tree_map_with_path(
        lambda p, c: jax.lax.dynamic_slice_in_dim(c, idx, size,
                                                  _batch_axis(p)), cache)


def update_batch(cache, row, idx):
    """Write a sliced row (batch size 1) back at batch position idx."""
    return jax.tree_util.tree_map_with_path(
        lambda p, c, r: jax.lax.dynamic_update_slice_in_dim(
            c, r.astype(c.dtype), idx, _batch_axis(p)), cache, row)


def ring_positions(pos, W: int):
    """Absolute position stored in each ring slot; -1 for empty.

    pos: [B] current length. Returns [B, W] int32.
    """
    s = jnp.arange(W, dtype=jnp.int32)[None, :]
    p = ((pos[:, None] - 1 - s) // W) * W + s
    return jnp.where(p >= 0, p, -1)


def slab_positions(pos, T: int):
    """[B, T]: slot t holds position t if t < pos else -1."""
    t = jnp.arange(T, dtype=jnp.int32)[None, :]
    return jnp.where(t < pos[:, None], t, -1)


def write_decode_kv(cache_k, cache_v, new_k, new_v, pos, *, ring: bool, W: int):
    """Write one token's K/V at per-slot positions.

    cache_k/v: [B, T, K, dh]; new_k/v: [B, 1, K, dh]; pos: [B].
    """
    B = cache_k.shape[0]
    idx = (pos % W) if ring else pos
    bidx = jnp.arange(B)
    cache_k = cache_k.at[bidx, idx].set(new_k[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[bidx, idx].set(new_v[:, 0].astype(cache_v.dtype))
    return cache_k, cache_v


def prefill_fill_slab(cache_k, cache_v, k, v):
    """Place prefill K/V [B, L, K, dh] at slab slots 0..L-1."""
    L = k.shape[1]
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k.astype(cache_k.dtype), 0, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v.astype(cache_v.dtype), 0, axis=1)
    return cache_k, cache_v


def prefill_fill_ring(cache_k, cache_v, k, v, W: int, lens=None):
    """Fill a ring buffer from a full prefill: position p -> slot p % W.

    lens [B]: true lengths for right-padded prefill (slots map to the last
    real positions, not padding)."""
    B, L = k.shape[0], k.shape[1]
    if lens is None:
        lens = jnp.full((B,), L, jnp.int32)
    s = jnp.arange(W, dtype=jnp.int32)[None, :]
    p = ((lens[:, None] - 1 - s) // W) * W + s      # [B, W]; <0 => empty
    valid = p >= 0
    src = jnp.clip(p, 0, max(L - 1, 0))
    kk = jnp.take_along_axis(k, src[:, :, None, None], axis=1)
    vv = jnp.take_along_axis(v, src[:, :, None, None], axis=1)
    m = valid[:, :, None, None]
    cache_k = jnp.where(m, kk.astype(cache_k.dtype), cache_k)
    cache_v = jnp.where(m, vv.astype(cache_v.dtype), cache_v)
    return cache_k, cache_v
