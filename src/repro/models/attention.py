"""Attention variants: GQA/MQA (RoPE, optional window/bias), and MLA
(DeepSeek-V2 multi-head latent attention with compressed KV cache).

All functions are pure; caches are dict pytrees suitable for scan-stacking.
The einsum reference path is what the dry-run lowers; on TPU,
``repro.kernels.flash_attention`` replaces the core when cfg.use_kernels.

A GQA decode cache may instead be a :class:`PagedKV`: the serving engine's
page pools, read in place through the lane's page table (DESIGN.md §13).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .common import Alloc, apply_rope, causal_mask_bias, rms_norm

# ---------------------------------------------------------------------------
# core attend (reference path; kernel hook)
# ---------------------------------------------------------------------------


ATTN_CHUNK = 2048  # q-block size for the chunked reference path


def attend(
    q: jax.Array,  # (B, Sq, H, Dh)
    k: jax.Array,  # (B, Sk, KV, Dh)
    v: jax.Array,  # (B, Sk, KV, Dv)
    bias: jax.Array,  # (B or 1, Sq, Sk) additive f32
    *,
    use_kernel: bool = False,
    causal_hint: bool = False,
) -> jax.Array:
    B, Sq, H, Dh = q.shape
    KV = k.shape[2]
    if use_kernel and Sq > 1:
        from repro.kernels import ops as kops

        return kops.flash_attention(q, k, v, bias=bias, causal=causal_hint)
    if Sq > ATTN_CHUNK and Sq % ATTN_CHUNK == 0:
        # q-chunked reference path: never materialises the (Sq, Sk) score
        # matrix for the whole sequence at once — the XLA-fallback analogue
        # of the flash kernel's VMEM streaming (EXPERIMENTS §Perf). The
        # Pallas kernel replaces this on real TPUs.
        nq = Sq // ATTN_CHUNK
        qc = q.reshape(B, nq, ATTN_CHUNK, H, Dh).transpose(1, 0, 2, 3, 4)
        bc = bias.reshape(bias.shape[0], nq, ATTN_CHUNK, -1).transpose(1, 0, 2, 3)

        def one(args):
            qq, bb = args
            return _attend_dense(qq, k, v, bb)

        out = jax.lax.map(one, (qc, bc))
        return out.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, v.shape[-1])
    return _attend_dense(q, k, v, bias)


def _attend_dense(q, k, v, bias):
    B, Sq, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = Dh**-0.5
    qg = q.reshape(B, Sq, KV, G, Dh)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32)
    scores = scores * scale + bias[:, None, None, :, :]
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
    return out.reshape(B, Sq, H, v.shape[-1])


# ---------------------------------------------------------------------------
# paged decode view (the serving engine's KV pages, read in place)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PagedKV:
    """One decode lane's GQA cache as the serving engine's page pools.

    The pools hold every lane's pages for every layer of a stack group and
    are shared by all lanes; ``table`` and ``length`` are this lane's. A
    decode step given this view in place of a ``{"k", "v"}`` cache attends
    over the lane's pages where they lie and returns only the new token's
    K/V (``{"k_new", "v_new"}``), which the engine writes into its page.
    """

    k_pages: Optional[jax.Array]  # (L, num_physical_pages, KV, page_size, Dh)
    v_pages: Optional[jax.Array]
    table: Optional[jax.Array]  # (pages_per_lane,) int32 physical page ids
    length: Optional[jax.Array]  # () int32 tokens in the pages: the write index
    layer: Optional[jax.Array] = None  # () int32 layer of L to read; None = 0

    def layer_steps(self, count: int) -> "PagedKV":
        """What a layer scan slices: the layer index alone, never the pools."""
        return PagedKV(None, None, None, None, jnp.arange(count, dtype=jnp.int32))

    def at_layer(self, step: "PagedKV") -> "PagedKV":
        return dataclasses.replace(self, layer=step.layer)


def paged_attend(q: jax.Array, k: jax.Array, v: jax.Array, view: PagedKV) -> jax.Array:
    """Decode attention of one lane over its pages plus its new token.

    q: (1, 1, H, Dh); k, v: (1, 1, KV, Dh), the token at write index
    ``view.length``. On TPU the Pallas paged kernel reads the pages in
    place; elsewhere its jnp oracle (``kernels.ref.paged_attention_ref``).
    """
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref

    dt = view.k_pages.dtype
    args = (
        q[:, 0], k[:, 0].astype(dt), v[:, 0].astype(dt), view.k_pages, view.v_pages,
        view.table[None], view.length[None],
        jnp.zeros((), jnp.int32) if view.layer is None else view.layer,
    )
    out = jax.lax.platform_dependent(
        *args, tpu=kops.paged_attention, default=kref.paged_attention_ref
    )
    return out[:, None]


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def _pad_param(a: Alloc, name, real_shape, padded_shape, axes, pad_axis: int, **kw):
    """A param stored at ``padded_shape`` whose pad region is exactly zero.

    In init mode the real-shaped tensor is initialized and zero-padded; in
    abstract/axes modes only the padded shape matters. Works under
    StackedAlloc (leading layers dim shifts the pad axis).
    """
    if a.mode != "init" or real_shape == padded_shape:
        return a.param(name, padded_shape, axes, **kw)
    real = a.param(name, real_shape, axes, **kw)
    offset = real.ndim - len(real_shape)  # stacked layers prefix
    pads = [(0, 0)] * real.ndim
    pads[pad_axis + offset] = (0, padded_shape[pad_axis] - real_shape[pad_axis])
    return jnp.pad(real, pads)


def gqa_params(cfg, a: Alloc) -> dict:
    d, Dh = cfg.d_model, cfg.head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    Hp, KVp = cfg.heads_padded, cfg.kv_heads_padded
    p = {
        "wq": _pad_param(a, "wq", (d, H, Dh), (d, Hp, Dh), ("embed", "heads", None), 1),
        "wk": _pad_param(a, "wk", (d, KV, Dh), (d, KVp, Dh), ("embed", "kv", None), 1),
        "wv": _pad_param(a, "wv", (d, KV, Dh), (d, KVp, Dh), ("embed", "kv", None), 1),
        "wo": _pad_param(a, "wo", (H, Dh, d), (Hp, Dh, d), ("heads", None, "embed"), 0),
    }
    if cfg.qkv_bias:
        p["bq"] = a.param("bq", (Hp, Dh), ("heads", None), init="zeros")
        p["bk"] = a.param("bk", (KVp, Dh), ("kv", None), init="zeros")
        p["bv"] = a.param("bv", (KVp, Dh), ("kv", None), init="zeros")
    return p


# The serving form of a GQA block (DESIGN.md §13): the Q/K/V projections held
# head-major, (..., H, d, Dh), under keys of their own. A dot under the layer
# scan then reads each layer's slice where it lies; from the stored
# (..., d, H, Dh) XLA copies every layer's slice into that order first.
HEAD_MAJOR = {"wq": "wq_hm", "wk": "wk_hm", "wv": "wv_hm"}


def gqa_head_major(p: dict) -> dict:
    """The head-major leaves of a stored GQA block's projections (stacked
    layers, if any, lead; padded heads stay as they are)."""
    return {hm: jnp.swapaxes(p[k], -3, -2) for k, hm in HEAD_MAJOR.items()}


def _project(p: dict, name: str, x: jax.Array) -> jax.Array:
    """``x`` (B, S, d) through projection ``name``, held stored or head-major."""
    if HEAD_MAJOR[name] in p:
        return jnp.einsum("bsd,hdk->bshk", x, p[HEAD_MAJOR[name]])
    return jnp.einsum("bsd,dhk->bshk", x, p[name])


def gqa_cache_shape(cfg, batch: int, seq: int, dtype, *, ring: bool = False) -> dict:
    KV, Dh = cfg.kv_heads_padded, cfg.head_dim
    c = {
        "k": jax.ShapeDtypeStruct((batch, seq, KV, Dh), dtype),
        "v": jax.ShapeDtypeStruct((batch, seq, KV, Dh), dtype),
    }
    if ring:  # sliding-window ring buffer: absolute position of each slot
        c["pos"] = jax.ShapeDtypeStruct((seq,), jnp.int32)
    return c


def gqa_attention(
    cfg,
    p: dict,
    x: jax.Array,  # (B, S, d)
    positions: jax.Array,  # (S,) absolute positions of x
    *,
    window: Optional[int] = None,
    prefix_len: Optional[jax.Array] = None,
    bidirectional: bool = False,
    cache: Optional[dict] = None,
    cache_index: Optional[jax.Array] = None,  # write offset into the cache
    return_cache: bool = False,
    emit_slices: bool = False,  # decode: return only the written K/V slice
    use_kernel: bool = False,
) -> Tuple[jax.Array, Optional[dict]]:
    """Full-sequence (prefill/train) or single-token (decode) attention.

    ``emit_slices`` avoids materialising a second full cache inside layer
    scans: the scan emits (B, 1, KV, Dh) slices and the stack merges them
    into the donated cache with ONE dynamic_update_slice per leaf outside
    the loop (EXPERIMENTS §Perf).

    decode: pass ``cache`` + ``cache_index``; x has S=1 and keys/values are
    written at ``cache_index`` then attended over the whole (masked) cache.
    A cache carrying ``pos`` is a sliding-window ring buffer: writes go to
    slot ``cache_index % W`` and masking uses the stored absolute positions.
    """
    B, S, d = x.shape
    q, k, v = (_project(p, name, x) for name in ("wq", "wk", "wv"))
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if isinstance(cache, PagedKV):
        # decode over the engine's pages, read in place; a paged cache is
        # never a ring, so any window covers the whole cache
        out = paged_attend(q, k, v, cache)
        new_cache = {"k_new": k, "v_new": v}
    elif cache is not None:
        Sk = cache["k"].shape[1]
        if "pos" in cache:  # ring buffer (S must be 1)
            slot = jnp.mod(cache_index, Sk)
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), slot, axis=1
            )
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), slot, axis=1
            )
            pos_buf = jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], positions.astype(jnp.int32), slot, axis=0
            )
            q_pos = positions[0]
            ok = (pos_buf >= 0) & (pos_buf <= q_pos)
            if window is not None:
                ok = ok & (pos_buf > q_pos - window)
            bias = jnp.where(ok, 0.0, -1e30).astype(jnp.float32)[None, None, :]
            out = attend(q, k_cache, v_cache, bias)
            if emit_slices:
                new_cache = {"k_new": k, "v_new": v}
            else:
                new_cache = {"k": k_cache, "v": v_cache, "pos": pos_buf}
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), cache_index, axis=1
            )
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), cache_index, axis=1
            )
            k_pos = jnp.arange(Sk)
            bias = causal_mask_bias(
                positions, k_pos, window=window, prefix_len=prefix_len,
                valid_len=cache_index + S,
            )[None]
            out = attend(q, k_cache, v_cache, bias)
            if emit_slices:
                new_cache = {"k_new": k, "v_new": v}
            else:
                new_cache = {"k": k_cache, "v": v_cache}
    else:
        if bidirectional:
            bias = jnp.zeros((1, S, S), jnp.float32)
        else:
            bias = causal_mask_bias(
                positions, positions, window=window, prefix_len=prefix_len
            )[None]
        causal_hint = prefix_len is None and window is None and not bidirectional
        out = attend(q, k, v, bias, use_kernel=use_kernel, causal_hint=causal_hint)
        if return_cache:
            if window is not None:  # return a ring cache of the last W keys,
                # laid out so position p lives at slot p % W (the decode
                # write invariant): roll the linear tail into ring order.
                W = min(window, S)
                shift = (S - W) % W
                new_cache = {
                    "k": jnp.roll(k[:, S - W :], shift, axis=1),
                    "v": jnp.roll(v[:, S - W :], shift, axis=1),
                    "pos": jnp.roll(positions[S - W :].astype(jnp.int32), shift),
                }
            else:
                new_cache = {"k": k, "v": v}
        else:
            new_cache = None

    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype), p["wo"])
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): compressed latent KV cache
# ---------------------------------------------------------------------------


def mla_params(cfg, a: Alloc) -> dict:
    d, H = cfg.d_model, cfg.num_heads
    nope, rope_d, v_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    lq, lkv = cfg.q_lora_rank, cfg.kv_lora_rank
    p = {}
    if lq:
        p["wq_a"] = a.param("wq_a", (d, lq), ("embed", "lora"))
        p["q_norm"] = a.param("q_norm", (lq,), ("lora",), init="zeros")
        p["wq_b"] = a.param("wq_b", (lq, H, nope + rope_d), ("lora", "heads", None))
    else:
        p["wq"] = a.param("wq", (d, H, nope + rope_d), ("embed", "heads", None))
    p["wkv_a"] = a.param("wkv_a", (d, lkv + rope_d), ("embed", "lora"))
    p["kv_norm"] = a.param("kv_norm", (lkv,), ("lora",), init="zeros")
    p["wk_b"] = a.param("wk_b", (lkv, H, nope), ("lora", "heads", None))
    p["wv_b"] = a.param("wv_b", (lkv, H, v_d), ("lora", "heads", None))
    p["wo"] = a.param("wo", (H, v_d, d), ("heads", None, "embed"))
    return p


def mla_cache_shape(cfg, batch: int, seq: int, dtype) -> dict:
    return {
        "ckv": jax.ShapeDtypeStruct((batch, seq, cfg.kv_lora_rank), dtype),
        "krope": jax.ShapeDtypeStruct((batch, seq, cfg.qk_rope_head_dim), dtype),
    }


def _mla_qkv(cfg, p, x, positions):
    B, S, _ = x.shape
    H = cfg.num_heads
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        cq = rms_norm(jnp.einsum("bsd,dl->bsl", x, p["wq_a"]), p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsl,lhk->bshk", cq, p["wq_b"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    kv_a = jnp.einsum("bsd,dl->bsl", x, p["wkv_a"])
    ckv = rms_norm(kv_a[..., : cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = kv_a[..., cfg.kv_lora_rank :]  # (B, S, rope_d) shared across heads
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return q_nope, q_rope, ckv, k_rope


def mla_attention(
    cfg,
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    cache: Optional[dict] = None,
    cache_index: Optional[jax.Array] = None,
    return_cache: bool = False,
    emit_slices: bool = False,
    use_kernel: bool = False,
    **_unused,
) -> Tuple[jax.Array, Optional[dict]]:
    B, S, d = x.shape
    H = cfg.num_heads
    nope, rope_d, v_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    scale = (nope + rope_d) ** -0.5
    q_nope, q_rope, ckv, k_rope = _mla_qkv(cfg, p, x, positions)

    if cache is not None:
        # decode: absorbed form — score/value directly against the compressed
        # cache; per-token cache traffic is kv_lora+rope (576) instead of
        # 2*H*Dh (32768 for 128 heads): the paper-faithful 93% KV reduction.
        ckv_c = jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), cache_index, axis=1
        )
        krope_c = jax.lax.dynamic_update_slice_in_dim(
            cache["krope"], k_rope.astype(cache["krope"].dtype), cache_index, axis=1
        )
        Sk = ckv_c.shape[1]
        q_eff = jnp.einsum("bqhn,lhn->bqhl", q_nope, p["wk_b"])  # absorb W_UK
        scores = (
            jnp.einsum("bqhl,bsl->bhqs", q_eff, ckv_c, preferred_element_type=jnp.float32)
            + jnp.einsum("bqhr,bsr->bhqs", q_rope, krope_c, preferred_element_type=jnp.float32)
        ) * scale
        bias = causal_mask_bias(positions, jnp.arange(Sk), valid_len=cache_index + S)[None]
        scores = scores + bias[:, None, :, :]
        w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
        ctx = jnp.einsum("bhqs,bsl->bqhl", w, ckv_c)
        out = jnp.einsum("bqhl,lhv->bqhv", ctx, p["wv_b"])  # absorb W_UV
        if emit_slices:
            new_cache = {"ckv_new": ckv, "krope_new": k_rope}
        else:
            new_cache = {"ckv": ckv_c, "krope": krope_c}
    else:
        # prefill/train: expanded form (better matmul shapes at long Sq)
        k_nope = jnp.einsum("bsl,lhn->bshn", ckv, p["wk_b"])
        v = jnp.einsum("bsl,lhv->bshv", ckv, p["wv_b"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, rope_d))], axis=-1
        )
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        bias = causal_mask_bias(positions, positions)[None]
        out = attend(q, k, v, bias, use_kernel=use_kernel, causal_hint=True)
        new_cache = {"ckv": ckv, "krope": k_rope} if return_cache else None

    y = jnp.einsum("bshv,hvd->bsd", out.astype(x.dtype), p["wo"])
    return y, new_cache
