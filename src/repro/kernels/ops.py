"""jit'd public wrappers around the Pallas kernels.

These adapt the model-layer layouts ((B, S, H, Dh) activations) to the
kernel layouts and pick block sizes. They compile for the TPU; a caller off
the chip (the CPU tests) passes ``interpret=True`` explicitly, so a kernel
never drops into the Pallas interpreter unasked (DESIGN.md §6).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_bhsd
from .paged_attention import paged_decode_attention
from .ssd import ssd_bshp


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jax.Array,  # (B, Sq, H, Dh) — model layout
    k: jax.Array,  # (B, Sk, KV, Dh)
    v: jax.Array,  # (B, Sk, KV, Dh)
    bias: Optional[jax.Array] = None,  # ignored: masks via causal/window
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    del bias
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_bhsd(
        qt,
        kt,
        vt,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
    )
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H)
    A: jax.Array,  # (H,)
    Bm: jax.Array,  # (B, S, N)
    Cm: jax.Array,  # (B, S, N)
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> jax.Array:
    return ssd_bshp(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


def paged_attention(
    q: jax.Array,  # (N, H, Dh) — model layout, one token per lane
    k_new: jax.Array,  # (N, KV, Dh)
    v_new: jax.Array,  # (N, KV, Dh)
    k_pages: jax.Array,  # (L, num_physical_pages, KV, page_size, Dh)
    v_pages: jax.Array,
    table: jax.Array,  # (N, pages_per_lane) int32
    lengths: jax.Array,  # (N,) int32
    layer: jax.Array,  # () int32
    *,
    interpret: bool = False,
) -> jax.Array:
    """Paged decode attention in the model's head layout; (N, H, Dh).

    Under ``jax.vmap`` (the engine maps a decode step over its lanes) the
    mapped lanes join the kernel's own lane axis: one kernel call reads
    every lane, and the pools, shared by all lanes, are never broadcast.
    """
    return _lane_batched(interpret)(q, k_new, v_new, k_pages, v_pages, table, lengths, layer)


@functools.cache
def _lane_batched(interpret: bool):
    @jax.custom_batching.custom_vmap
    def call(q, k_new, v_new, k_pages, v_pages, table, lengths, layer):
        N, H, Dh = q.shape
        KV = k_new.shape[1]
        out = paged_decode_attention(
            q.reshape(N, KV, H // KV, Dh), k_new, v_new, k_pages, v_pages, table, lengths,
            layer, interpret=interpret,
        )
        return out.reshape(N, H, Dh)

    @call.def_vmap
    def _rule(axis_size, in_batched, q, k_new, v_new, k_pages, v_pages, table, lengths, layer):
        if in_batched[3] or in_batched[4] or in_batched[7]:
            raise NotImplementedError("paged_attention maps over lanes, not pools or layers")

        def lanes(x, batched):
            x = x if batched else jnp.broadcast_to(x, (axis_size, *x.shape))
            return x.reshape(-1, *x.shape[2:])

        q, k_new, v_new, table, lengths = (
            lanes(x, b) for x, b in zip(
                (q, k_new, v_new, table, lengths), (*in_batched[:3], *in_batched[5:7])
            )
        )
        out = call(q, k_new, v_new, k_pages, v_pages, table, lengths, layer)
        return out.reshape(axis_size, -1, *out.shape[1:]), True

    return call
