"""Mamba2 SSD (state-space duality) Pallas TPU kernel.

The SSD chunked scan is two matmul-shaped contractions per chunk plus a tiny
sequential state recurrence — ideal MXU work if the chunk is tiled into VMEM.

Grid: (batch, head_blocks, num_chunks). TPU grids execute sequentially
(row-major, last dim fastest), so the inter-chunk state carry lives in a
VMEM scratch buffer (hb, P, N) f32 that persists across the chunk axis and
is reset whenever a new (batch, head block) begins — the same
scratch-as-carry idiom as the flash kernel. The head-block axis keeps the
per-step (cl, cl) decay tiles of one block, not of every head, in VMEM.

The wrapper discretizes outside the kernel (``xdt = dt * x``, ``dA = dt *
A``, heads-major layout) so the kernel body is plain 2-D tiles. Per head,
with one chunk resident in VMEM:
  cum     = dA @ U                   inclusive prefix sum as a triangular
                                     matmul (Pallas TPU lowers no cumsum)
  L       = exp(cum_i - cum_j), j<=i (cl, cl) intra-chunk decay
  y_intra = (C Bᵀ ∘ L) @ xdt         (cl,cl)@(cl,P)
  y_inter = (C @ stateᵀ) * exp(cum)  (cl,N)@(N,P)
  state   = state * exp(Σ dA) + (exp(suffix) * xdt)ᵀ @ B
where suffix_i = Σ_{k>i} dA_k. The chunk total and the suffix sums are
matmuls too, so every sum comes out as a whole row or column (Mosaic will
not broadcast a single element across both sublanes and lanes).

The pure-jnp oracle is models/ssm.ssd_reference (re-exported in ref.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _dot(a, b, dims):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), precision=_HI, preferred_element_type=jnp.float32
    )


def _kernel(
    xdt_ref,  # (1, hb, cl, P) f32, dt-weighted inputs
    da_ref,  # (1, hb, cl) f32, dt * A
    b_ref,  # (1, cl, N)
    c_ref,  # (1, cl, N)
    y_ref,  # (1, hb, cl, P)
    state_scr,  # (hb, P, N) f32 carry across chunks
    *,
    cl: int,
    hb: int,
):
    @pl.when(pl.program_id(2) == 0)
    def _reset():
        state_scr[...] = jnp.zeros_like(state_scr)

    row = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 1)
    tril = row >= col  # tril[i, j]: j <= i
    lower = tril.astype(jnp.float32)
    upper = (row <= col).astype(jnp.float32)  # upper[j, i]: j <= i
    later = (row < col).astype(jnp.float32)  # later[i, k]: k > i
    ones = jnp.ones((cl, b_ref.shape[-1]), jnp.float32)

    Bm = b_ref[0].astype(jnp.float32)  # (cl, N)
    Cm = c_ref[0].astype(jnp.float32)  # (cl, N)
    scores = _dot(Cm, Bm, ((1,), (1,)))  # (cl, cl) = C Bᵀ, shared by heads
    da = da_ref[0]  # (hb, cl)

    for h in range(hb):
        da_h = da[h : h + 1, :]  # (1, cl)
        cum_row = _dot(da_h, upper, ((1,), (0,)))  # (1, cl)
        cum_col = _dot(lower, da_h, ((1,), (1,)))  # (cl, 1)
        suffix = _dot(later, da_h, ((1,), (1,)))  # (cl, 1)
        total = _dot(da_h, ones, ((1,), (0,)))  # (1, N), every lane the sum
        L = jnp.where(tril, jnp.exp(cum_col - cum_row), 0.0)  # (cl, cl)
        xdt = xdt_ref[0, h]  # (cl, P)
        y = _dot(scores * L, xdt, ((1,), (0,)))  # (cl, P)
        state = state_scr[h]  # (P, N)
        y = y + _dot(Cm, state, ((1,), (1,))) * jnp.exp(cum_col)
        y_ref[0, h] = y.astype(y_ref.dtype)
        xw = xdt * jnp.exp(suffix)  # (cl, P)
        state_scr[h] = state * jnp.exp(total) + _dot(xw, Bm, ((0,), (0,)))


def ssd_bshp(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H) f32
    A: jax.Array,  # (H,) f32
    Bm: jax.Array,  # (B, S, N)
    Cm: jax.Array,  # (B, S, N)
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> jax.Array:
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    cl = min(chunk, S)
    assert S % cl == 0, (S, cl)
    nc = S // cl
    # a head block is a sublane tile (8) where H allows, else every head
    hb = 8 if H % 8 == 0 else H

    dt = dt.astype(jnp.float32)
    xdt = (x.astype(jnp.float32) * dt[..., None]).transpose(0, 2, 1, 3)  # (B,H,S,P)
    da = (dt * A.astype(jnp.float32)).transpose(0, 2, 1)  # (B,H,S)

    kernel = functools.partial(_kernel, cl=cl, hb=hb)
    out = pl.pallas_call(
        kernel,
        grid=(B, H // hb, nc),
        in_specs=[
            pl.BlockSpec((1, hb, cl, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, hb, cl), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, cl, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, cl, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, hb, cl, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((hb, P, N), jnp.float32)],
        interpret=interpret,
    )(xdt, da, Bm, Cm)
    return out.transpose(0, 2, 1, 3)
