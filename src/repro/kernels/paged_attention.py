"""Paged decode attention Pallas TPU kernel: one new token per lane against
its KV pages, read in place through the page table.

The serving engine keeps K/V in a pool of fixed-size pages (DESIGN.md §13),
``(layers, physical_pages, kv_heads, page_size, head_dim)`` per leaf, and a
per-lane page table. A decode tick used to rebuild every lane's whole
logical cache from its pages before attending; this kernel reads only the
pages a lane's live length covers, straight from the pool.

Grid: (lanes, kv_head_blocks, pages). The page table, the lane lengths and
the layer index are scalar-prefetched; each K/V block is one page of one
head block (every KV head whose page fits ``BLOCK_BYTES``), fetched by the
pipeline at ``(layer, page, head_block)``. A
page past a lane's length maps to the block already resident (the previous
step's), so the pipeline issues no copy for it: an idle lane (length 0)
reads nothing, and a lane reads ``ceil(length / page_size)`` pages however
long its table row is. The online softmax runs in f32 across the page axis,
as ``flash_attention.py`` does across K blocks; the lane's own new token,
not yet in its pages, is folded in after the last page. The engine writes
that token's K/V into its page after the layer loop.

The pure-jnp oracle is ``ref.paged_attention_ref``.
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_BYTES = 512 * 1024  # one K or V page block in VMEM (double-buffered)


def _kernel(
    fetch_ref,  # (lanes * pages,) int32, SMEM: the page each grid step reads
    len_ref,  # (lanes,) int32, SMEM: tokens already in each lane's pages
    layer_ref,  # (1,) int32, SMEM
    q_ref,  # (1, hb, G, Dh)
    kn_ref,  # (1, hb, 1, Dh): the lane's new key
    vn_ref,  # (1, hb, 1, Dh)
    k_ref,  # (hb, page, Dh): one page of the pool
    v_ref,  # (hb, page, Dh)
    o_ref,  # (1, hb, G, Dh)
    m_scr,  # (hb, G, 1) f32 running max
    l_scr,  # (hb, G, 1) f32 running denominator
    acc_scr,  # (hb, G, Dh) f32 accumulator
    *,
    scale: float,
    page_size: int,
):
    del fetch_ref, layer_ref  # used by the index maps only
    b = pl.program_id(0)
    j = pl.program_id(2)
    length = len_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * page_size < length)
    def _page():
        s = jnp.einsum(
            "hgd,htd->hgt", q_ref[0], k_ref[...], preferred_element_type=jnp.float32
        ) * scale
        pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum(
            "hgt,htd->hgd", p, v_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = alpha * acc_scr[...] + pv
        m_scr[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        # the new token: one score per query head, against its own key
        q = q_ref[0].astype(jnp.float32)
        s = jnp.sum(q * kn_ref[0].astype(jnp.float32), axis=-1, keepdims=True) * scale
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[...] + p
        acc = alpha * acc_scr[...] + p * vn_ref[0].astype(jnp.float32)
        o_ref[0] = (acc / l_new).astype(o_ref.dtype)


def fetch_pages(table: jax.Array, lengths: jax.Array, page_size: int) -> jax.Array:
    """The physical page each (lane, page) grid step reads, flattened.

    A step inside the lane's length reads its table entry; any other step
    repeats the previous step's page (the first needed page before any), so
    the pipeline sees an unchanged block index and copies nothing.
    """
    lanes, pages = table.shape
    need = jnp.arange(pages)[None, :] < ((lengths + page_size - 1) // page_size)[:, None]
    need = need.reshape(-1)
    steps = jnp.arange(lanes * pages)
    last = jax.lax.cummax(jnp.where(need, steps, -1))
    first = jnp.argmax(need)  # 0 when no step reads a page
    return table.reshape(-1)[jnp.where(last >= 0, last, first)]


def paged_decode_attention(
    q: jax.Array,  # (N, KV, G, Dh): one query token per lane
    k_new: jax.Array,  # (N, KV, Dh): each lane's new key, not in its pages
    v_new: jax.Array,  # (N, KV, Dh)
    k_pages: jax.Array,  # (L, num_physical_pages, KV, page_size, Dh)
    v_pages: jax.Array,  # (L, num_physical_pages, KV, page_size, Dh)
    table: jax.Array,  # (N, pages_per_lane) int32 physical page ids
    lengths: jax.Array,  # (N,) int32 tokens in each lane's pages
    layer: jax.Array,  # () int32 which of the L layers to read
    *,
    interpret: bool = False,
) -> jax.Array:
    """Core entry point; returns (N, KV, G, Dh) in q's dtype.

    Lane ``n`` attends to positions ``[0, lengths[n])`` of its pages plus
    its new token (``k_new[n]``, ``v_new[n]``), which is what a decode step
    at write index ``lengths[n]`` attends to.
    """
    N, KV, G, Dh = q.shape
    page_size = k_pages.shape[3]
    P = table.shape[1]
    page_bytes = page_size * Dh * k_pages.dtype.itemsize  # one head's page
    hb = max(
        (h for h in range(1, KV + 1) if KV % h == 0 and h * page_bytes <= BLOCK_BYTES), default=1
    )
    fetch = fetch_pages(table.astype(jnp.int32), lengths.astype(jnp.int32), page_size)
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)

    def lane_map(b, h, j, fetch_ref, len_ref, layer_ref):
        return (b, h, 0, 0)

    def page_map(b, h, j, fetch_ref, len_ref, layer_ref):
        return (layer_ref[0], fetch_ref[b * P + j], h, 0, 0)

    kernel = functools.partial(_kernel, scale=Dh**-0.5, page_size=page_size)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N, KV // hb, P),
            in_specs=[
                pl.BlockSpec((1, hb, G, Dh), lane_map),
                pl.BlockSpec((1, hb, 1, Dh), lane_map),
                pl.BlockSpec((1, hb, 1, Dh), lane_map),
                pl.BlockSpec((None, None, hb, page_size, Dh), page_map),
                pl.BlockSpec((None, None, hb, page_size, Dh), page_map),
            ],
            out_specs=pl.BlockSpec((1, hb, G, Dh), lane_map),
            scratch_shapes=[
                pltpu.VMEM((hb, G, 1), jnp.float32),
                pltpu.VMEM((hb, G, 1), jnp.float32),
                pltpu.VMEM((hb, G, Dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((N, KV, G, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(fetch, lengths.astype(jnp.int32), layer, q, k_new[:, :, None], v_new[:, :, None],
      k_pages, v_pages)
    return out
