"""Mixture-of-Experts layer: top-k router + two execution paths.

``dense``    every token through every expert, gate-weighted sum. Exact,
             mesh-agnostic; used by CPU smoke tests and as the numerical
             oracle for the EP path.

``ep``       production expert parallelism under ``shard_map``: tokens are
             sharded over (pod, data) × model (sequence), experts over
             `model`. Dispatch is gather/scatter (no GShard dispatch-einsum
             FLOPs): per-shard capacity buffers are filled by scatter, sent
             expert-major with ``all_to_all`` over the model axis, run
             through grouped GEMMs, and returned. Capacity overflow drops
             (GShard semantics); tests pick capacity_factor high enough that
             ep == dense exactly.

Router + auxiliary load-balancing loss are computed OUTSIDE the shard_map so
gradients and the aux term stay in plain global-land.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import Alloc, act_fn


def moe_params(cfg, a: Alloc) -> dict:
    d, E, ff = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    p = {
        "router": a.param("router", (d, E), ("embed", "experts"), dtype=jnp.float32),
        "w_gate": a.param("w_gate", (E, d, ff), ("experts", "embed", "expert_mlp")),
        "w_up": a.param("w_up", (E, d, ff), ("experts", "embed", "expert_mlp")),
        "w_down": a.param("w_down", (E, ff, d), ("experts", "expert_mlp", "embed")),
    }
    if cfg.num_shared_experts:
        sff = cfg.num_shared_experts * ff
        p["shared"] = {
            "w_gate": a.param("shared_w_gate", (d, sff), ("embed", "mlp")),
            "w_up": a.param("shared_w_up", (d, sff), ("embed", "mlp")),
            "w_down": a.param("shared_w_down", (sff, d), ("mlp", "embed")),
        }
    return p


def route(cfg, p: dict, x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing. Returns (weights (B,S,K) f32, ids (B,S,K) i32, aux)."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg.experts_per_token)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)  # renormalize
    # Switch-style load-balancing auxiliary loss
    E = cfg.num_experts
    density = jnp.mean(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=(0, 1, 2))
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = cfg.router_aux_loss * E * jnp.sum(density * mean_prob)
    return weights, ids, aux


def _expert_ffn(cfg, w_gate, w_up, w_down, xs: jax.Array) -> jax.Array:
    """Grouped SwiGLU: xs (E, C, d) with per-expert weights (E, d, ff)."""
    act = act_fn(cfg.act if cfg.act in ("silu", "gelu") else "silu")
    g = jnp.einsum("ecd,edf->ecf", xs, w_gate)
    u = jnp.einsum("ecd,edf->ecf", xs, w_up)
    return jnp.einsum("ecf,efd->ecd", act(g) * u, w_down)


# ---------------------------------------------------------------------------
# dense path (oracle / smoke tests)
# ---------------------------------------------------------------------------


def moe_dense(cfg, p: dict, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    B, S, d = x.shape
    weights, ids, aux = route(cfg, p, x)
    act = act_fn(cfg.act if cfg.act in ("silu", "gelu") else "silu")
    g = jnp.einsum("bsd,edf->ebsf", x, p["w_gate"])
    u = jnp.einsum("bsd,edf->ebsf", x, p["w_up"])
    y_all = jnp.einsum("ebsf,efd->ebsd", act(g) * u, p["w_down"])  # (E,B,S,d)
    onehot = jax.nn.one_hot(ids, cfg.num_experts, dtype=x.dtype)  # (B,S,K,E)
    combine = jnp.einsum("bske,bsk->ebs", onehot, weights.astype(x.dtype))
    y = jnp.einsum("ebs,ebsd->bsd", combine, y_all)
    if cfg.num_shared_experts:
        sp = p["shared"]
        y = y + jnp.einsum(
            "bsf,fd->bsd",
            act(jnp.einsum("bsd,df->bsf", x, sp["w_gate"]))
            * jnp.einsum("bsd,df->bsf", x, sp["w_up"]),
            sp["w_down"],
        )
    return y, aux


# ---------------------------------------------------------------------------
# expert-parallel path (shard_map)
# ---------------------------------------------------------------------------


def _dispatch_local(cfg, x2d, ids, capacity: int):
    """Per-shard gather/scatter dispatch.

    x2d: (T, d); ids: (T, K). Returns (buffer (E, C, d), slot (T*K,),
    keep (T, K)). No dispatch-einsum FLOPs — pure scatter.
    """
    T, d = x2d.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    flat_ids = ids.reshape(-1)  # (T*K,) expert of each copy
    onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)  # (T*K, E)
    pos = jnp.cumsum(onehot, axis=0) - 1  # position within expert
    pos = jnp.sum(pos * onehot, axis=-1)  # (T*K,)
    keep = pos < capacity
    slot = flat_ids * capacity + pos  # index into (E*C) buffer
    slot = jnp.where(keep, slot, E * capacity)  # overflow -> scratch row
    token_of_copy = jnp.repeat(jnp.arange(T), K)
    buf = jnp.zeros((E * capacity + 1, d), x2d.dtype).at[slot].add(x2d[token_of_copy])
    return buf[:-1].reshape(E, capacity, d), slot, keep.reshape(T, K)


def _combine_local(y_buf, weights, slot, keep):
    """Inverse of dispatch: gather each copy's expert output, gate, sum.

    y_buf: (E, C, d); weights/keep: (T, K); slot: (T*K,) into E*C (+scratch).
    """
    E, C, d = y_buf.shape
    T, K = keep.shape
    flat = jnp.concatenate([y_buf.reshape(E * C, d), jnp.zeros((1, d), y_buf.dtype)])
    y_copies = flat[slot].reshape(T, K, d)
    w = (weights * keep).astype(y_buf.dtype)
    return jnp.einsum("tkd,tk->td", y_copies, w)


def capacity_for(cfg, tokens_per_shard: int) -> int:
    c = math.ceil(tokens_per_shard * cfg.experts_per_token / cfg.num_experts * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to 8 for layout friendliness


def moe_ep(cfg, p: dict, x: jax.Array, ctx) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE. ``ctx`` is a repro.parallel.ParallelCtx."""
    B, S, d = x.shape
    weights, ids, aux = route(cfg, p, x)
    mesh = ctx.mesh
    model_axis = ctx.model_axis
    n_model = mesh.shape[model_axis]
    batch_axes = ctx.batch_axes  # e.g. ('pod', 'data')
    n_data = 1
    for ax in batch_axes:
        n_data *= mesh.shape[ax]
    E = cfg.num_experts
    assert E % n_model == 0, f"{E} experts not divisible by model={n_model}"
    K = cfg.experts_per_token
    seq_sharded = S % n_model == 0 and S >= n_model  # train/prefill: SP tokens
    T_local = (B // n_data) * (S // n_model if seq_sharded else S)
    if seq_sharded:
        C = capacity_for(cfg, T_local)
    else:
        # decode: capacity must cover the worst case (all local tokens on one
        # expert) — dropping a decode token corrupts its stream.
        C = max(8, -(-T_local // 8) * 8)

    x_spec = P(batch_axes, model_axis if seq_sharded else None, None)
    # 2D expert sharding (deepseek-v2): per-expert hidden dim lives sharded
    # over the data axis (ZeRO-3 style) and is all-gathered just-in-time
    # inside the body — transient full weights, persistent 1/n_data storage.
    ff_axis = dict(cfg.sharding_rules or ()).get("expert_mlp")
    if ff_axis is not None:
        wg_spec = P(model_axis, None, ff_axis)  # (E, d, ff)
        wd_spec = P(model_axis, ff_axis, None)  # (E, ff, d)
    else:
        wg_spec = wd_spec = P(model_axis)

    from jax.ad_checkpoint import checkpoint_name

    def body(x_l, w_l, ids_l, w_gate, w_up, w_down):
        if ff_axis is not None:  # FSDP gather of the expert FFN weights
            w_gate = checkpoint_name(
                jax.lax.all_gather(w_gate, ff_axis, axis=2, tiled=True), "moe_fsdp_gather")
            w_up = checkpoint_name(
                jax.lax.all_gather(w_up, ff_axis, axis=2, tiled=True), "moe_fsdp_gather")
            w_down = checkpoint_name(
                jax.lax.all_gather(w_down, ff_axis, axis=1, tiled=True), "moe_fsdp_gather")
        Bl, Sl, _ = x_l.shape
        Tl = Bl * Sl
        x2d = x_l.reshape(Tl, d)
        buf, slot, keep = _dispatch_local(cfg, x2d, ids_l.reshape(Tl, K), C)
        if seq_sharded:
            # expert-major exchange: (E,C,d) -> (E/n, n*C, d) per model rank
            buf = checkpoint_name(
                jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=1, tiled=True),
                "moe_a2a")
            y_buf = _expert_ffn(cfg, w_gate, w_up, w_down, buf)
            y_buf = checkpoint_name(
                jax.lax.all_to_all(y_buf, model_axis, split_axis=1, concat_axis=0, tiled=True),
                "moe_a2a")
        else:
            # decode: tokens replicated over model; each rank runs its local
            # expert slice then psums the scattered outputs back together.
            e_loc = E // n_model
            idx = jax.lax.axis_index(model_axis) * e_loc
            buf_l = jax.lax.dynamic_slice_in_dim(buf, idx, e_loc, axis=0)
            y_l = _expert_ffn(cfg, w_gate, w_up, w_down, buf_l)
            y_full = jnp.zeros((E, C, d), y_l.dtype)
            y_full = jax.lax.dynamic_update_slice_in_dim(y_full, y_l, idx, axis=0)
            y_buf = jax.lax.psum(y_full, model_axis)
        y2d = _combine_local(y_buf, w_l.reshape(Tl, K), slot, keep)
        return y2d.reshape(Bl, Sl, d)

    y = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(x_spec, x_spec, x_spec, wg_spec, wg_spec, wd_spec),
        out_specs=x_spec,
        check_vma=False,  # no varying-axes rule for checkpoint_name
    )(x, weights, ids, p["w_gate"], p["w_up"], p["w_down"])

    if cfg.num_shared_experts:
        sp = p["shared"]
        act = act_fn(cfg.act if cfg.act in ("silu", "gelu") else "silu")
        y = y + jnp.einsum(
            "bsf,fd->bsd",
            act(jnp.einsum("bsd,df->bsf", x, sp["w_gate"]))
            * jnp.einsum("bsd,df->bsf", x, sp["w_up"]),
            sp["w_down"],
        )
    return y, aux


def moe_apply(cfg, p: dict, x: jax.Array, ctx=None) -> Tuple[jax.Array, jax.Array]:
    if ctx is not None and ctx.expert_parallel:
        return moe_ep(cfg, p, x, ctx)
    return moe_dense(cfg, p, x)
