"""Slot-based KV-cache pool for continuous batching (DESIGN.md §7).

The per-family cache-layout knowledge that used to live inside
``models/lm.py`` (``extend_caches``) is concentrated here: how each cache
kind grows along its sequence axis, and how the kinds that *don't* grow
(sliding-window rings, SSM recurrent state, static cross-attention K/V)
pass through. ``models.lm.extend_caches`` now delegates to
:func:`pad_caches_to`.

Cache kinds, by leaf signature:

* ``{"k", "v"}``            GQA append cache — pad along the seq axis.
* ``{"k", "v", "pos"}``     sliding-window ring — fixed modulus ``W``; a
                            smaller prefill ring is re-laid-out into the
                            target ring by the ``slot = pos % W`` invariant.
* ``{"ckv", "krope"}``      MLA compressed latents — pad along seq.
* anything else             SSM state / conv stream / static encoder K/V —
                            fixed size, pass through.

:class:`SlotKVCache` pools these per-sequence caches: one big buffer tree
whose leading axis is the *slot* index, each slot holding a batch-1 cache of
length ``max_len``. Sequences of different lengths then share one padded
decode batch — the engine vmaps the model's single-token ``decode_step``
over the slot axis with a per-slot write index.

:class:`PagedKVCache` (DESIGN.md §13) replaces the flat per-slot layout
with a pool of fixed-size *pages*: every growable leaf (GQA append K/V, MLA
latents) is stored in pages, with a free-list of physical page ids and a
per-slot page table; fixed-size leaves (SSM state, sliding-window rings,
static encoder K/V) stay slot-indexed exactly as in the flat cache. Prefill
installs only the pages a prompt actually covers (O(pages touched), not
O(max_len)), and growth is appending one page id to a table row. The decode
tick reads GQA K/V in place, through the page table
(:class:`~repro.models.attention.PagedKV`), and writes each lane's new token
into its page; MLA latents are still reassembled into each slot's logical
cache by a gather — bit-identical to the flat layout because unmapped
table entries point at a reserved always-zero page.
"""
from __future__ import annotations

import math
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# per-family cache walks (pure, traceable)
# ---------------------------------------------------------------------------


def _is_gqa(node: Any) -> bool:
    return isinstance(node, dict) and "k" in node and "v" in node


def _is_mla(node: Any) -> bool:
    return isinstance(node, dict) and "ckv" in node


def _pad_seq(arr: jax.Array, axis: int, extra: int) -> jax.Array:
    pad = [(0, 0)] * arr.ndim
    pad[axis] = (0, extra)
    return jnp.pad(arr, pad)


def _scatter_seq(dst: jax.Array, src: jax.Array, idx: jax.Array, axis: int) -> jax.Array:
    """``dst`` with ``src`` scattered at positions ``idx`` along ``axis``."""
    dst_m = jnp.moveaxis(dst, axis, 0)
    src_m = jnp.moveaxis(src, axis, 0)
    return jnp.moveaxis(dst_m.at[idx].set(src_m), 0, axis)


def _grow_ring(node: dict, target_w: int) -> dict:
    """Re-lay a ring cache of modulus ``W0`` into modulus ``target_w``.

    The ring invariant is "absolute position p lives at slot p % W". A
    prefill over a prompt shorter than the window returns a ring of modulus
    ``W0 = S < W``; re-scatter each entry to ``pos % W`` and mark empty
    slots with pos = -1 (masked by the decode bias). The stored positions
    are a contiguous run of length W0 <= W, hence distinct mod W.
    """
    pos = node["pos"]
    w0 = pos.shape[-1]
    if w0 == target_w:
        return node
    if w0 > target_w:
        raise ValueError(f"ring cache modulus {w0} exceeds slot capacity {target_w}")
    # positions are identical across any stacked (layers) prefix
    flat_pos = pos.reshape(-1, w0)[0].astype(jnp.int32)
    idx = jnp.mod(flat_pos, target_w)
    out = {}
    for key in ("k", "v"):
        arr = node[key]
        ax = arr.ndim - 3  # (..., B, W, KV, Dh)
        dst = jnp.zeros(arr.shape[:ax] + (target_w,) + arr.shape[ax + 1 :], arr.dtype)
        out[key] = _scatter_seq(dst, arr, idx, ax)
    dst_pos = jnp.full(pos.shape[:-1] + (target_w,), -1, pos.dtype)
    out["pos"] = _scatter_seq(dst_pos, pos, idx, pos.ndim - 1)
    return out


def pad_caches_to(caches: dict, extra: int, *, ring_w: Optional[int] = None) -> dict:
    """Grow every growable cache leaf by ``extra`` positions.

    Attention K/V and MLA latents are zero-padded along their sequence axis;
    ring buffers are re-laid to modulus ``ring_w`` when given (else passed
    through); SSM state, conv streams and static cross-attention K/V pass
    through untouched. Handles scan-stacked leaves (leading layers dim).
    """

    def walk(node):
        if _is_gqa(node):
            if "pos" in node:  # ring buffer: fixed modulus
                return _grow_ring(node, ring_w) if ring_w is not None else node
            ax = node["k"].ndim - 3  # (..., B, S, KV, Dh): seq axis
            return {
                "k": _pad_seq(node["k"], ax, extra),
                "v": _pad_seq(node["v"], ax, extra),
            }
        if _is_mla(node):
            ax = node["ckv"].ndim - 2  # (..., B, S, L): seq axis
            return {
                "ckv": _pad_seq(node["ckv"], ax, extra),
                "krope": _pad_seq(node["krope"], ax, extra),
            }
        if isinstance(node, dict):
            # cross-attn caches hold static encoder K/V: never grown
            return {k: (v if k == "cross" else walk(v)) for k, v in node.items()}
        return node  # SSM state / conv stream: fixed size

    return walk(caches)


def _ring_modulus(node: Any, acc: list) -> None:
    if _is_gqa(node) and "pos" in node:
        acc.append(node["pos"].shape[-1])
    elif isinstance(node, dict):
        for v in node.values():
            _ring_modulus(v, acc)


def ring_modulus(caches: dict) -> Optional[int]:
    """Modulus of the sliding-window ring leaves, or None if there are none."""
    acc: list = []
    _ring_modulus(caches, acc)
    return acc[0] if acc else None


# ---------------------------------------------------------------------------
# slot pool
# ---------------------------------------------------------------------------


class SlotKVCache:
    """A pool of ``max_slots`` per-sequence caches sharing one buffer tree.

    Every leaf of ``buffers`` has shape ``(max_slots, *leaf_b1)`` where
    ``leaf_b1`` is the model's batch-1 cache shape at length ``max_len``
    (from ``model.cache_shapes(1, max_len)``). Allocation is a free-list;
    ``write`` pads a freshly prefilled batch-1 cache out to ``max_len`` and
    overwrites one slot in a single donated jit (no host round-trip).

    Thread safety: alloc/free/evict are lock-protected; ``write`` and the
    engine's decode tick mutate ``buffers`` and must be serialized by the
    caller (the engine's tick chain does this).
    """

    def __init__(self, model, max_slots: int, max_len: int) -> None:
        if max_slots < 1 or max_len < 1:
            raise ValueError("max_slots and max_len must be >= 1")
        self.max_slots = max_slots
        self.max_len = max_len
        self._slot_shapes = model.cache_shapes(1, max_len)
        self.buffers = jax.tree.map(
            lambda s: jnp.zeros((max_slots, *s.shape), s.dtype), self._slot_shapes
        )
        rings: list = []
        _ring_modulus(self._slot_shapes, rings)
        self._ring_w = rings[0] if rings else None
        self._lock = threading.Lock()
        self._free = list(range(max_slots - 1, -1, -1))  # pop() -> lowest slot
        self._live: set[int] = set()
        self.allocs = 0
        self.frees = 0
        self.evictions = 0
        self.peak_live = 0
        # tokens each live slot is provisioned to hold (written prefill +
        # decode growth intent) — powers the fragmentation stat: a flat
        # slot always reserves max_len, whatever the sequence needs
        self._target_len = [0] * max_slots

        def _write(buffers, new_cache, slot, prefill_len):
            padded = pad_caches_to(
                new_cache, self.max_len - prefill_len, ring_w=self._ring_w
            )
            return jax.tree.map(lambda b, n: b.at[slot].set(n), buffers, padded)

        # one jit; retraces per distinct prefill length (bucketed upstream)
        self._write_jit = jax.jit(_write, donate_argnums=(0,), static_argnums=(3,))

    # -- slot lifecycle -------------------------------------------------------

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_live(self) -> int:
        return len(self._live)

    def pages_for(self, length: int) -> int:
        """Pages a sequence of ``length`` tokens needs. A flat slot is one
        indivisible max_len-sized page, so the answer is always 1."""
        return 1

    def capacity_tokens(self, slot: int) -> int:
        """Token positions currently backed by storage for ``slot``."""
        return self.max_len

    def alloc(self, npages: int = 1) -> Optional[int]:
        """Claim a slot, or None when the pool is exhausted.

        ``npages`` is accepted for interface parity with
        :class:`PagedKVCache`; a flat slot always provisions max_len.
        """
        with self._lock:
            if not self._free:
                return None
            slot = self._free.pop()
            self._live.add(slot)
            self.allocs += 1
            self.peak_live = max(self.peak_live, len(self._live))
            return slot

    def grow_to(self, slot: int, length: int) -> bool:
        """Extend ``slot``'s provisioned length. Flat slots pre-provision
        max_len, so growth within capacity always succeeds."""
        if length > self.max_len:
            return False
        with self._lock:
            self._target_len[slot] = max(self._target_len[slot], length)
        return True

    def free(self, slot: int) -> None:
        """Return a slot to the pool (retired sequence)."""
        with self._lock:
            if slot not in self._live:
                raise ValueError(f"slot {slot} is not live")
            self._live.remove(slot)
            self._free.append(slot)
            self._target_len[slot] = 0
            self.frees += 1

    def evict(self, slot: int) -> None:
        """Forcibly free a live slot (capacity eviction); counted separately."""
        self.free(slot)
        with self._lock:
            self.evictions += 1

    # -- data movement --------------------------------------------------------

    def write(self, slot: int, cache: dict, prefill_len: int) -> None:
        """Install a batch-1 prefill cache (length ``prefill_len``) into ``slot``.

        Caller must hold the engine's tick serialization (buffers are
        donated). The cache is padded/re-laid out to ``max_len`` on device.
        """
        if slot not in self._live:
            raise ValueError(f"slot {slot} is not live")
        if prefill_len > self.max_len:
            raise ValueError(f"prefill length {prefill_len} exceeds max_len {self.max_len}")
        with self._lock:
            self._target_len[slot] = max(self._target_len[slot], prefill_len)
        self.buffers = self._write_jit(
            self.buffers, cache, jnp.asarray(slot, jnp.int32), prefill_len
        )

    def read_slot(self, slot: int) -> dict:
        """The batch-1 cache tree currently stored in ``slot`` (for tests)."""
        return jax.tree.map(lambda b: b[slot], self.buffers)

    def stats(self) -> dict:
        """Lifecycle counters plus the §13 occupancy/fragmentation pair.

        For the flat layout one slot == one max_len-sized page:
        ``page_occupancy`` is slot occupancy and ``fragmentation`` is the
        fraction of provisioned token capacity the live sequences don't
        actually need — the over-allocation the paged cache exists to
        eliminate.
        """
        with self._lock:
            live = len(self._live)
            used = sum(self._target_len[s] for s in self._live)
            cap = live * self.max_len
            return {
                "max_slots": self.max_slots,
                "live": live,
                "free": len(self._free),
                "allocs": self.allocs,
                "frees": self.frees,
                "evictions": self.evictions,
                "peak_live": self.peak_live,
                "page_size": self.max_len,
                "pages_total": self.max_slots,
                "pages_live": live,
                "pages_free": len(self._free),
                "page_occupancy": live / self.max_slots,
                "fragmentation": (1.0 - used / cap) if cap else 0.0,
            }


# ---------------------------------------------------------------------------
# paged pool (DESIGN.md §13)
# ---------------------------------------------------------------------------


class _LeafSpec:
    """Per-leaf storage classification for the paged layout.

    ``kind`` is ``"kv"`` for GQA append K/V (paged, read in place by the
    decode tick), ``"page"`` for MLA latents (paged, gathered into a logical
    cache each tick) and ``"slot"`` for fixed-size leaves (SSM state, conv
    streams, ring K/V/pos, static cross-attention K/V). ``ax`` is the
    sequence axis inside the batch-1 slot layout for paged leaves.
    """

    __slots__ = ("kind", "ax")

    def __init__(self, kind: str, ax: int = -1) -> None:
        self.kind = kind
        self.ax = ax

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"_LeafSpec({self.kind!r}, ax={self.ax})"


def _leaf_specs(shapes: dict) -> Any:
    """Mirror of the :func:`pad_caches_to` walk emitting a `_LeafSpec` tree
    with the exact structure of ``shapes`` (one spec per array leaf)."""

    def walk(node, static=False):
        if isinstance(node, dict):
            if not static and _is_gqa(node) and "pos" not in node:
                ax = node["k"].ndim - 3  # (..., B, S, KV, Dh)
                return {k: _LeafSpec("kv", ax) for k in node}
            if not static and _is_mla(node):
                ax = node["ckv"].ndim - 2  # (..., B, S, L)
                return {k: _LeafSpec("page", ax) for k in node}
            return {k: walk(v, static or k == "cross") for k, v in node.items()}
        return _LeafSpec("slot")

    return walk(shapes)


def _is_spec(node: Any) -> bool:
    return isinstance(node, _LeafSpec)


def _is_kv_node(spec: Any) -> bool:
    """A ``{"k", "v"}`` node read in place (one PagedKV view)."""
    return isinstance(spec, dict) and bool(spec) and all(
        _is_spec(s) and s.kind == "kv" for s in spec.values()
    )


def _layers(shape: tuple, ax: int) -> int:
    """Stacked layers of a GQA leaf: everything before its sequence axis
    (the scan-stacked layer axis, if any, and the batch-1 axis)."""
    return math.prod(shape[:ax])


class PagedKVCache:
    """Block-pooled KV cache: fixed-size pages, per-slot page tables.

    Storage layout (DESIGN.md §13):

    * GQA append K/V live in a page pool of shape
      ``(layers, RESERVED + num_pages, kv_heads, page_size, head_dim)``:
      one page of one layer is contiguous, as the paged decode kernel reads
      it (``layers`` is the leaf's stacked layer count, 1 for an unstacked
      layer);
    * MLA latents live in a page pool of shape
      ``(RESERVED + num_pages, ..., page_size, ...)`` where the sequence
      axis of the batch-1 slot layout is replaced by ``page_size`` and the
      physical page id leads;
    * *fixed-size* leaves (SSM recurrent state, conv streams, sliding-window
      rings, static encoder K/V) keep the flat ``(max_slots, ...)`` layout —
      they never grow, so paging them buys nothing;
    * two physical pages are reserved: page 0 is the **zero page** (never
      written; every unmapped page-table entry points at it, so a gathered
      logical cache is zero-padded exactly like the flat layout — the
      bit-identity invariant), page 1 is the **scratch page** (decode
      writes from inactive batch lanes land there and are never read).

    Allocation is a free-list of physical page ids; the per-slot page table
    is a host-side ``(max_slots, pages_per_seq)`` int32 array shipped to the
    device each tick (a few hundred bytes). ``write`` installs only the
    pages a prefill actually covers; ``grow_to`` appends page ids to a table
    row; ``free`` returns the row's pages. All O(pages touched).

    ``lane_caches``/``lane_view``/``commit`` are pure functions traced
    inside the engine's decode-tick jit: a lane reads GQA K/V through a
    :class:`~repro.models.attention.PagedKV` view of the pools and its table
    row, MLA latents through ``gather`` (each slot's logical ``max_len``
    cache reassembled from its pages, unmapped tail → zero page); commit
    writes each lane's new GQA token into its page and the single MLA page
    containing its write index (inactive lanes → scratch page).

    Thread safety matches :class:`SlotKVCache`: page/slot accounting is
    lock-protected; ``write`` and the decode tick mutate ``pools`` and must
    be serialized by the caller (the engine's tick chain does this).
    """

    ZERO_PAGE = 0
    SCRATCH_PAGE = 1
    RESERVED = 2

    def __init__(
        self,
        model,
        max_slots: int,
        max_len: int,
        *,
        page_size: int = 64,
        num_pages: Optional[int] = None,
    ) -> None:
        if max_slots < 1 or max_len < 1 or page_size < 1:
            raise ValueError("max_slots, max_len and page_size must be >= 1")
        self.max_slots = max_slots
        self.max_len = max_len
        self.page_size = min(page_size, max_len)
        self.pages_per_seq = math.ceil(max_len / self.page_size)
        if num_pages is None:
            num_pages = max_slots * self.pages_per_seq
        if num_pages < self.pages_per_seq:
            raise ValueError(
                f"num_pages={num_pages} cannot hold one full sequence "
                f"({self.pages_per_seq} pages of {self.page_size} tokens)"
            )
        self.num_pages = num_pages

        self._slot_shapes = model.cache_shapes(1, max_len)
        self._spec_tree = _leaf_specs(self._slot_shapes)
        # how the decode tick reads each paged leaf: in place or gathered
        kinds = [s.kind for s in jax.tree.leaves(self._spec_tree, is_leaf=_is_spec)]
        self._leaves = {k: kinds.count(k) for k in ("kv", "page")}
        rings: list = []
        _ring_modulus(self._slot_shapes, rings)
        self._ring_w = rings[0] if rings else None

        ps, nphys = self.page_size, self.RESERVED + num_pages

        def make_pool(spec: _LeafSpec, s) -> jax.Array:
            if spec.kind == "slot":
                return jnp.zeros((max_slots, *s.shape), s.dtype)
            shp = s.shape
            if spec.kind == "kv":  # (..., 1, S, KV, Dh)
                return jnp.zeros((_layers(shp, spec.ax), nphys, shp[-2], ps, shp[-1]), s.dtype)
            return jnp.zeros(
                (nphys, *shp[: spec.ax], ps, *shp[spec.ax + 1 :]), s.dtype
            )

        self.pools = jax.tree.map(make_pool, self._spec_tree, self._slot_shapes)

        self._lock = threading.Lock()
        self._free_slots = list(range(max_slots - 1, -1, -1))
        self._live: set[int] = set()
        self._free_pages = list(range(nphys - 1, self.RESERVED - 1, -1))
        self._table = np.zeros((max_slots, self.pages_per_seq), np.int32)
        self._npages = [0] * max_slots
        self._target_len = [0] * max_slots
        self.allocs = 0
        self.frees = 0
        self.evictions = 0
        self.peak_live = 0
        self.page_allocs = 0
        self.page_frees = 0
        self.peak_pages_live = 0

        def _write(pools, new_cache, page_ids, slot, pad_len):
            npg = math.ceil(pad_len / ps)
            grown = pad_caches_to(new_cache, npg * ps - pad_len, ring_w=self._ring_w)

            def up(spec: _LeafSpec, pool, leaf):
                if spec.kind == "slot":
                    return pool.at[slot].set(leaf)
                shp = leaf.shape
                if spec.kind == "kv":
                    r = leaf.reshape(_layers(shp, spec.ax), npg, ps, *shp[-2:])
                    return pool.at[:, page_ids].set(r.transpose(0, 1, 3, 2, 4))
                r = leaf.reshape(*shp[: spec.ax], npg, ps, *shp[spec.ax + 1 :])
                return pool.at[page_ids].set(jnp.moveaxis(r, spec.ax, 0))

            return jax.tree.map(up, self._spec_tree, pools, grown)

        # one jit; retraces per distinct prefill length (bucketed upstream)
        self._write_jit = jax.jit(_write, donate_argnums=(0,), static_argnums=(4,))

    # -- page/slot accounting -------------------------------------------------

    @property
    def num_free(self) -> int:
        return len(self._free_slots)

    @property
    def num_live(self) -> int:
        return len(self._live)

    @property
    def free_pages(self) -> int:
        return len(self._free_pages)

    @property
    def pages_live(self) -> int:
        return self.num_pages - len(self._free_pages)

    def pages_for(self, length: int) -> int:
        """Pages a sequence of ``length`` tokens needs."""
        return max(1, math.ceil(length / self.page_size))

    def capacity_tokens(self, slot: int) -> int:
        """Token positions currently backed by physical pages for ``slot``."""
        return self._npages[slot] * self.page_size

    def alloc(self, npages: int = 1) -> Optional[int]:
        """Claim a slot backed by ``npages`` pages, or None when either the
        slot pool or the page pool cannot satisfy the request."""
        if npages > self.pages_per_seq:
            return None
        with self._lock:
            if not self._free_slots or len(self._free_pages) < npages:
                return None
            slot = self._free_slots.pop()
            self._live.add(slot)
            for i in range(npages):
                self._table[slot, i] = self._free_pages.pop()
            self._npages[slot] = npages
            self.allocs += 1
            self.page_allocs += npages
            self.peak_live = max(self.peak_live, len(self._live))
            self.peak_pages_live = max(self.peak_pages_live, self.pages_live)
            return slot

    def grow_to(self, slot: int, length: int) -> bool:
        """Back ``slot`` with pages covering ``length`` tokens.

        All-or-nothing: returns False (allocating nothing) when the free
        list cannot cover the missing pages — the engine's page-pressure
        preemption path. O(pages appended).
        """
        if length > self.max_len:
            return False
        need = self.pages_for(length)
        with self._lock:
            if slot not in self._live:
                raise ValueError(f"slot {slot} is not live")
            have = self._npages[slot]
            extra = need - have
            if extra <= 0:
                self._target_len[slot] = max(self._target_len[slot], length)
                return True
            if len(self._free_pages) < extra:
                return False
            for i in range(have, need):
                self._table[slot, i] = self._free_pages.pop()
            self._npages[slot] = need
            self._target_len[slot] = max(self._target_len[slot], length)
            self.page_allocs += extra
            self.peak_pages_live = max(self.peak_pages_live, self.pages_live)
            return True

    def free(self, slot: int) -> None:
        """Return a slot and all its pages to the pools (O(pages held))."""
        with self._lock:
            if slot not in self._live:
                raise ValueError(f"slot {slot} is not live")
            self._live.remove(slot)
            self._free_slots.append(slot)
            npg = self._npages[slot]
            for i in range(npg):
                self._free_pages.append(int(self._table[slot, i]))
            self._table[slot, :] = self.ZERO_PAGE
            self._npages[slot] = 0
            self._target_len[slot] = 0
            self.page_frees += npg
            self.frees += 1

    def evict(self, slot: int) -> None:
        """Forcibly free a live slot (capacity eviction); counted separately."""
        self.free(slot)
        with self._lock:
            self.evictions += 1

    # -- data movement --------------------------------------------------------

    def write(self, slot: int, cache: dict, prefill_len: int) -> None:
        """Install a batch-1 prefill cache (length ``prefill_len``) into
        ``slot``'s pages. Only ``ceil(prefill_len / page_size)`` pages are
        touched; the caller must hold the engine's tick serialization
        (pools are donated)."""
        if prefill_len > self.max_len:
            raise ValueError(f"prefill length {prefill_len} exceeds max_len {self.max_len}")
        npg = self.pages_for(prefill_len)
        with self._lock:
            if slot not in self._live:
                raise ValueError(f"slot {slot} is not live")
            if self._npages[slot] < npg:
                raise ValueError(
                    f"slot {slot} holds {self._npages[slot]} pages, prefill needs {npg}"
                )
            page_ids = jnp.asarray(self._table[slot, :npg])
            self._target_len[slot] = max(self._target_len[slot], prefill_len)
        self.pools = self._write_jit(
            self.pools, cache, page_ids, jnp.asarray(slot, jnp.int32), prefill_len
        )

    def gather(self, pools, tables: jax.Array, *, in_place: bool = True):
        """Reassemble the ``(max_slots, ...)`` logical cache tree from pages.

        Pure/traceable; ``tables`` is the device copy of the page table.
        Unmapped entries point at the zero page, so the result is
        bit-identical to the flat slot layout. With ``in_place=False`` the
        leaves the decode tick reads in place are left out (None).
        """
        ps = self.page_size

        def g(spec: _LeafSpec, pool, shape):
            if spec.kind == "slot":
                return pool
            if spec.kind == "kv":
                if not in_place:
                    return None
                pages = jnp.moveaxis(pool[:, tables], 0, 1)  # (slots, layers, P, KV, page, Dh)
                pages = pages.swapaxes(-3, -2)  # (slots, layers, P, page, KV, Dh)
                n = pages.shape[0]
                return pages.reshape(n, *shape.shape[: spec.ax], -1, *shape.shape[-2:])
            pages = pool[tables]  # (slots, P, *pre, page, *post)
            pages = jnp.moveaxis(pages, 1, 1 + spec.ax)  # (slots, *pre, P, page, *post)
            shp = pages.shape
            return pages.reshape(
                *shp[: 1 + spec.ax], shp[1 + spec.ax] * ps, *shp[3 + spec.ax :]
            )

        return jax.tree.map(g, self._spec_tree, pools, self._slot_shapes)

    def lane_caches(self, pools, tables: jax.Array):
        """What the decode tick maps over its lanes, slots leading: slot
        leaves whole, MLA latents gathered from their pages, and nothing for
        the GQA K/V that each lane reads in place (:meth:`lane_view`)."""
        return self.gather(pools, tables, in_place=False)

    def lane_view(self, pools, lane: Any, table: jax.Array, length: jax.Array):
        """One lane's cache tree for ``Model.decode_step``: its entries of
        :meth:`lane_caches`, and for each GQA K/V node a
        :class:`~repro.models.attention.PagedKV` over the whole pools with
        this lane's table row and write index."""
        from repro.models.attention import PagedKV

        def walk(spec, pool, part):
            if _is_kv_node(spec):
                return PagedKV(pool["k"], pool["v"], table, length)
            if isinstance(spec, dict):
                return {k: walk(spec[k], pool[k], part[k]) for k in spec}
            return part

        return walk(self._spec_tree, pools, lane)

    def commit(self, pools, updated, dest_ids: jax.Array, idx: jax.Array):
        """Write a decode tick's results into the pools.

        Pure/traceable; ``updated`` is the tick's output cache tree, lanes
        leading, and a decode step only writes position ``idx[slot]``. A
        GQA K/V node holds just that token (``k_new``/``v_new``, every
        layer), written at its offset in physical page ``dest_ids[slot]``;
        an MLA leaf holds its whole logical cache, of which the single page
        containing the position goes to ``dest_ids[slot]``. Inactive lanes
        write to the scratch page. Fixed-size leaves are replaced wholesale,
        exactly like the flat layout.
        """
        ps = self.page_size
        off = idx % ps
        start = idx - off

        def token(pool, new):  # pool (layers, pages, KV, page, Dh)
            L, _, KV, _, Dh = pool.shape
            vals = new.reshape(-1, L, 1, KV, 1, Dh).astype(pool.dtype)
            # one in-place update per lane: a scatter over the page and
            # offset axes makes XLA re-lay the whole pool out around it
            zero = jnp.zeros((), jnp.int32)
            for lane in range(vals.shape[0]):
                at = (zero, dest_ids[lane], zero, off[lane], zero)
                pool = jax.lax.dynamic_update_slice(pool, vals[lane], at)
            return pool

        def page(spec, pool, upd):
            def one(u, st):
                return jax.lax.dynamic_slice_in_dim(u, st, ps, axis=spec.ax)

            return pool.at[dest_ids].set(jax.vmap(one)(upd, start))

        def walk(spec, pool, upd):
            if _is_kv_node(spec):
                return {"k": token(pool["k"], upd["k_new"]), "v": token(pool["v"], upd["v_new"])}
            if isinstance(spec, dict):
                return {k: walk(spec[k], pool[k], upd[k]) for k in spec}
            return upd if spec.kind == "slot" else page(spec, pool, upd)

        return walk(self._spec_tree, pools, updated)

    def tick_inputs(self, feed: dict) -> tuple:
        """Host-side per-tick arrays: ``(page_table, dest_ids)``.

        ``feed`` maps live slot -> write index for this tick. ``dest_ids``
        routes each lane's written page: the physical page containing the
        write index for live lanes, the scratch page for idle lanes.
        """
        with self._lock:
            tables = self._table.copy()
        dest = np.full((self.max_slots,), self.SCRATCH_PAGE, np.int32)
        for slot, fi in feed.items():
            dest[slot] = tables[slot, fi // self.page_size]
        return tables, dest

    def read_slot(self, slot: int) -> dict:
        """The batch-1 logical cache currently mapped by ``slot`` (tests)."""
        gathered = self.gather(self.pools, jnp.asarray(self._table))
        return jax.tree.map(lambda b: b[slot], gathered)

    def stats(self) -> dict:
        """Lifecycle counters plus §13 page-occupancy and fragmentation.

        ``page_occupancy``: fraction of the usable page pool currently
        mapped by live sequences. ``fragmentation``: fraction of the token
        capacity inside those live pages that no sequence needs (internal
        fragmentation — bounded by ``page_size - 1`` tokens per sequence,
        versus up to ``max_len - prompt`` per sequence for the flat layout).
        ``leaves_in_place`` / ``leaves_gathered``: how many paged cache
        leaves (a scan-stacked leaf counts once) the decode tick reads in
        place through the page table, and how many it still gathers into a
        logical cache every tick.
        """
        with self._lock:
            live_pages = self.num_pages - len(self._free_pages)
            used = sum(self._target_len[s] for s in self._live)
            cap = live_pages * self.page_size
            return {
                "max_slots": self.max_slots,
                "live": len(self._live),
                "free": len(self._free_slots),
                "leaves_in_place": self._leaves["kv"],
                "leaves_gathered": self._leaves["page"],
                "allocs": self.allocs,
                "frees": self.frees,
                "evictions": self.evictions,
                "peak_live": self.peak_live,
                "page_size": self.page_size,
                "pages_total": self.num_pages,
                "pages_live": live_pages,
                "pages_free": len(self._free_pages),
                "page_allocs": self.page_allocs,
                "page_frees": self.page_frees,
                "peak_pages_live": self.peak_pages_live,
                "page_occupancy": live_pages / self.num_pages,
                "fragmentation": (1.0 - used / cap) if cap else 0.0,
            }
