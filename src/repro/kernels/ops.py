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
