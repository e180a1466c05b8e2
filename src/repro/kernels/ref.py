"""Pure-jnp oracles for the Pallas kernels (per-kernel allclose targets)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.ssm import ssd_reference  # noqa: F401  (the SSD oracle)


def attention_ref(
    q: jax.Array,  # (B, H, Sq, Dh)
    k: jax.Array,  # (B, KV, Sk, Dh)
    v: jax.Array,  # (B, KV, Sk, Dh)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    k_len: Optional[int] = None,
) -> jax.Array:
    """Dense f32 softmax attention with GQA head grouping."""
    B, H, Sq, Dh = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, Sq, Dh).astype(jnp.float32) * Dh**-0.5
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qg, kf)
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if k_len is not None:
        mask &= k_pos < k_len
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bksd->bkgqd", w, vf)
    return o.reshape(B, H, Sq, Dh).astype(q.dtype)


def paged_attention_ref(
    q: jax.Array,  # (N, H, Dh): one query token per lane
    k_new: jax.Array,  # (N, KV, Dh): each lane's new key, not in its pages
    v_new: jax.Array,  # (N, KV, Dh)
    k_pages: jax.Array,  # (L, num_physical_pages, KV, page_size, Dh)
    v_pages: jax.Array,  # (L, num_physical_pages, KV, page_size, Dh)
    table: jax.Array,  # (N, pages_per_lane) int32
    lengths: jax.Array,  # (N,) int32 tokens in each lane's pages
    layer: jax.Array,  # () int32
) -> jax.Array:
    """The paged kernel's read, in plain jnp: each lane's pages laid end to
    end as one cache, its new token written at ``lengths[n]``, and the dense
    decode attention of ``models.attention`` over positions ``<= lengths[n]``
    (f32 scores and softmax, weights in q's dtype). Off the chip the serving
    tick runs this in the kernel's place, so it matches the flat cache's
    decode bit for bit."""
    from repro.models.attention import attend
    from repro.models.common import causal_mask_bias

    ps = k_pages.shape[3]

    def lane(q1, kn, vn, row, n):
        def cache(pages, new):
            c = pages[layer][row]  # (P, KV, ps, Dh)
            P, KV, _, Dh = c.shape
            c = c.transpose(0, 2, 1, 3).reshape(1, P * ps, KV, Dh)
            return jax.lax.dynamic_update_slice_in_dim(c, new[None, None], n, axis=1)

        kc, vc = cache(k_pages, kn), cache(v_pages, vn)
        bias = causal_mask_bias(n[None], jnp.arange(kc.shape[1]), valid_len=n + 1)[None]
        return attend(q1[None, None], kc, vc, bias)[0, 0]

    return jax.vmap(lane)(q, k_new, v_new, table, lengths)
