"""Top-level models: decoder-only LMs (dense/moe/ssm/hybrid/vlm) and the
whisper-style encoder-decoder, with train loss, prefill and decode steps.

Layer stacks are grouped by a ``stack_plan``: runs of identical layers scan
over stacked params (O(1) compile in depth); heterogeneous layers (hymba's
global-attention layers, deepseek-v2's leading dense layer) are standalone
groups so their caches/params can differ in shape.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .attention import HEAD_MAJOR, PagedKV, gqa_head_major
from .blocks import StackedAlloc, block_apply, block_cache_shape, block_params, _norm, _norm_params
from .common import Alloc, DTYPES


# ---------------------------------------------------------------------------
# stack plan
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class StackGroup:
    kind: str  # scan | single
    count: int
    name: str
    moe: bool
    is_global: bool  # full attention (ignores cfg.window)


def stack_plan(
    cfg, num_layers: Optional[int] = None, *, block_kind: str = "decoder"
) -> list[StackGroup]:
    L = num_layers if num_layers is not None else cfg.num_layers
    g_set = set(cfg.global_layers) if block_kind != "encoder" else set()
    first_dense = cfg.first_dense_layers if block_kind == "decoder" else L + 1

    def attrs(layer: int) -> tuple[bool, bool]:
        is_global = layer in g_set
        is_moe = cfg.is_moe and block_kind == "decoder" and layer >= first_dense
        return is_global, is_moe

    groups: list[StackGroup] = []
    i = 0
    while i < L:
        is_global, is_moe = attrs(i)
        if is_global:
            groups.append(StackGroup("single", 1, f"g{len(groups)}", is_moe, True))
            i += 1
        else:
            j = i
            while j < L and attrs(j) == (False, is_moe):
                j += 1
            groups.append(StackGroup("scan", j - i, f"s{len(groups)}", is_moe, False))
            i = j
    return groups


def stack_params(cfg, a, plan: list[StackGroup], *, block_kind: str = "decoder") -> dict:
    p = {}
    for grp in plan:
        with a.scope(grp.name):
            alloc = StackedAlloc(a, grp.count) if grp.kind == "scan" else a
            p[grp.name] = block_params(cfg, alloc, kind=block_kind, moe_layer=grp.moe)
    return p


def stack_cache_shapes(cfg, plan, batch: int, seq: int, dtype, *, xdec_enc_seq=None) -> dict:
    out = {}
    for grp in plan:
        one = block_cache_shape(
            cfg, batch, seq, dtype, is_global=grp.is_global, xdec_enc_seq=xdec_enc_seq
        )
        if grp.kind == "scan":
            one = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((grp.count, *s.shape), s.dtype), one
            )
        out[grp.name] = one
    return out


def _merge_decode_cache(cache_in, emitted, index):
    """Apply scan-emitted decode slices to the donated cache (one
    dynamic_update_slice per leaf, outside the layer loop)."""
    dus = jax.lax.dynamic_update_slice_in_dim

    def merge(node_in, node_em):
        if isinstance(node_in, PagedKV):
            return node_em  # the new token's K/V: its page is written in place
        if isinstance(node_em, dict):
            if "k_new" in node_em:
                Sk = node_in["k"].shape[-3]
                ring = "pos" in node_in
                slot = jnp.mod(index, Sk) if ring else index
                ax = node_in["k"].ndim - 3
                out = {
                    "k": dus(
                        node_in["k"], node_em["k_new"].astype(node_in["k"].dtype), slot, axis=ax
                    ),
                    "v": dus(
                        node_in["v"], node_em["v_new"].astype(node_in["v"].dtype), slot, axis=ax
                    ),
                }
                if ring:
                    pax = node_in["pos"].ndim - 1
                    upd = jnp.full((*node_in["pos"].shape[:-1], 1), index, node_in["pos"].dtype)
                    out["pos"] = dus(node_in["pos"], upd, slot, axis=pax)
                return out
            if "ckv_new" in node_em:
                ax = node_in["ckv"].ndim - 2
                return {
                    "ckv": dus(
                        node_in["ckv"],
                        node_em["ckv_new"].astype(node_in["ckv"].dtype),
                        index,
                        axis=ax,
                    ),
                    "krope": dus(
                        node_in["krope"],
                        node_em["krope_new"].astype(node_in["krope"].dtype),
                        index,
                        axis=ax,
                    ),
                }
            return {k: merge(node_in[k], node_em.get(k)) for k in node_in}
        if isinstance(node_in, dict) or node_em is None:
            # sentinel (possibly scan-stacked to (L,)) for static caches:
            # reuse the donated input unchanged (cross-attention encoder K/V)
            return node_in
        return node_em  # full replacement (SSM state / conv stream)

    return merge(cache_in, emitted)


def _is_paged(node) -> bool:
    return isinstance(node, PagedKV)


def _scan_slices(cache: dict, count: int) -> dict:
    """The part of a stack group's cache a layer scan slices: a paged view's
    pools stay whole (each layer reads its own through the layer index), so
    the scan carries only that index."""
    return jax.tree.map(
        lambda n: n.layer_steps(count) if _is_paged(n) else n, cache, is_leaf=_is_paged
    )


def _layer_cache(step: dict, cache: dict) -> dict:
    """One layer's cache inside the scan: its slices, and the whole paged
    views at that layer."""
    return jax.tree.map(
        lambda s, c: c.at_layer(s) if _is_paged(s) else s, step, cache, is_leaf=_is_paged
    )


def stack_apply(
    cfg,
    p: dict,
    plan: list[StackGroup],
    x: jax.Array,
    positions: jax.Array,
    *,
    mode: str = "forward",  # forward | prefill | decode
    caches: Optional[dict] = None,
    cache_index: Optional[jax.Array] = None,
    prefix_len: Optional[int] = None,
    bidirectional: bool = False,
    enc_out: Optional[jax.Array] = None,
    ctx=None,
    remat: bool = False,
    remat_policy: str = "full",
    unroll: bool = False,
) -> Tuple[jax.Array, Optional[dict], jax.Array]:
    """Returns (x, caches_out, aux_loss_sum)."""
    if remat_policy == "save_collectives":
        # don't recompute cross-device work in the backward pass: keep the
        # MoE all-to-all outputs and FSDP weight gathers (EXPERIMENTS §Perf)
        policy = jax.checkpoint_policies.save_only_these_names(
            "moe_fsdp_gather", "moe_a2a"
        )
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    total_aux = jnp.zeros((), jnp.float32)
    caches_out: dict = {}
    constrain = ctx.constrain_activations if ctx is not None else (lambda y: y)
    x = constrain(x)

    for grp in plan:
        gp = p[grp.name]
        window = None if grp.is_global else cfg.window

        def run_block(params, cache, xx):
            return block_apply(
                cfg,
                params,
                xx,
                positions,
                bidirectional=bidirectional,
                prefix_len=prefix_len,
                cache=cache,
                cache_index=cache_index,
                return_cache=(mode == "prefill"),
                emit_slices=(mode == "decode"),
                enc_out=enc_out,
                ctx=ctx,
                window=window,
            )

        if grp.kind == "single":
            fn = run_block
            if remat:
                fn = jax.checkpoint(fn, policy=policy, static_argnums=())
            cache_in = caches.get(grp.name) if caches else None
            x, nc, aux = fn(gp, cache_in, x)
            x = constrain(x)
            total_aux = total_aux + aux
            if nc is not None:
                if mode == "decode":
                    nc = _merge_decode_cache(cache_in, nc, cache_index)
                caches_out[grp.name] = nc
        else:
            cache_in = caches.get(grp.name) if caches else None

            def body(carry, xs):
                params, cache = xs
                if cache is not None:
                    cache = _layer_cache(cache, cache_in)
                xx, _ = carry
                xx, nc, aux = run_block(params, cache, xx)
                xx = constrain(xx)
                emit_cache = nc if nc is not None else 0
                return (xx, None), (emit_cache, aux)

            scan_body = body
            if remat:
                scan_body = jax.checkpoint(body, policy=policy)
            xs = (gp, cache_in) if cache_in is not None else (gp, None)
            if cache_in is None:
                # scan requires xs leaves with a leading dim: wrap params only
                (x, _), (ncs, auxs) = jax.lax.scan(
                    lambda c, params: scan_body(c, (params, None)), (x, None), gp,
                    unroll=unroll,
                )
            else:
                (x, _), (ncs, auxs) = jax.lax.scan(
                    scan_body, (x, None), (gp, _scan_slices(cache_in, grp.count)),
                    unroll=unroll,
                )
            total_aux = total_aux + jnp.sum(auxs)
            if mode == "decode":
                caches_out[grp.name] = _merge_decode_cache(cache_in, ncs, cache_index)
            elif mode == "prefill":
                caches_out[grp.name] = ncs
    return x, (caches_out if caches_out else None), total_aux


# ---------------------------------------------------------------------------
# embeddings / heads
# ---------------------------------------------------------------------------


def sinusoidal_emb(positions: jax.Array, d: int) -> jax.Array:
    half = d // 2
    freqs = jnp.exp(-np.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1))
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def cross_entropy(
    logits: jax.Array, targets: jax.Array, mask: Optional[jax.Array]
) -> Tuple[jax.Array, jax.Array]:
    """Token-mean CE in f32. Returns (loss, token_count)."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
    ce = lse - ll
    if mask is None:
        return jnp.mean(ce), jnp.array(ce.size, jnp.float32)
    m = mask.astype(jnp.float32)
    n = jnp.maximum(jnp.sum(m), 1.0)
    return jnp.sum(ce * m) / n, n


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


class Model:
    """Family-dispatching functional model. All methods are pure.

    ``scan_probe``: override every multi-layer scan group's count (used by
    the dry-run to correct XLA's count-while-bodies-once cost analysis via
    two-point depth extrapolation — see launch/dryrun.py).
    """

    def __init__(self, cfg, scan_probe: Optional[int] = None, scan_unroll: bool = False):
        self.cfg = cfg
        self.scan_unroll = scan_unroll
        self.plan = stack_plan(cfg)
        self.enc_plan = (
            stack_plan(cfg, cfg.encoder_layers, block_kind="encoder") if cfg.is_encdec else None
        )
        if scan_probe is not None:
            probe = lambda plan: [
                StackGroup(g.kind, scan_probe if (g.kind == "scan" and g.count > 1) else g.count,
                           g.name, g.moe, g.is_global)
                for g in plan
            ]
            self.plan = probe(self.plan)
            if self.enc_plan is not None:
                self.enc_plan = probe(self.enc_plan)
        self.dtype = DTYPES[cfg.dtype]

    def scan_group_stats(self) -> dict:
        """(#multi-layer scan groups, total layers in them) across plans."""
        groups, layers = 0, 0
        for plan in [self.plan] + ([self.enc_plan] if self.enc_plan else []):
            for g in plan:
                if g.kind == "scan" and g.count > 1:
                    groups += 1
                    layers += g.count
        return {"groups": groups, "layers": layers}

    # -- params ---------------------------------------------------------------

    def _build(self, a: Alloc) -> dict:
        cfg = self.cfg
        d, V = cfg.d_model, cfg.vocab_size
        p: dict[str, Any] = {}
        p["embed"] = a.param("embed", (V, d), ("vocab", "embed"), init="embed", scale=d**-0.5)
        block_kind = "xdecoder" if cfg.is_encdec else "decoder"
        with a.scope("decoder"):
            p["layers"] = stack_params(cfg, a, self.plan, block_kind=block_kind)
        p["final_norm"] = _norm_params(cfg, a, "final_norm")
        if not cfg.tie_embeddings:
            p["lm_head"] = a.param("lm_head", (d, V), ("embed", "vocab"))
        if cfg.is_encdec:
            with a.scope("encoder"):
                p["enc_layers"] = stack_params(cfg, a, self.enc_plan, block_kind="encoder")
            p["enc_norm"] = _norm_params(cfg, a, "enc_norm")
        if cfg.family == "vlm":
            p["vision_proj"] = a.param("vision_proj", (cfg.vision_dim, d), (None, "embed"))
        return p

    def init(self, key: jax.Array) -> dict:
        return self._build(Alloc("init", key, dtype=self.dtype))

    def abstract_params(self) -> dict:
        return self._build(Alloc("abstract", dtype=self.dtype))

    def logical_axes(self) -> dict:
        return self._build(Alloc("axes", dtype=self.dtype))

    def serving_params(self, p: dict) -> dict:
        """The stored tree ``p`` in the form the serving programs read.

        Every decoder GQA block's ``wq``/``wk``/``wv`` are held head-major
        instead (``attention.HEAD_MAJOR``), made by one jitted transpose of
        the stacked leaves; every other leaf is the same array as in ``p``,
        and ``p`` is left as it was. A tree with no GQA block comes back as
        it is. Prefill and decode read either form; training keeps the
        stored one (DESIGN.md §13).
        """
        if self.cfg.attention != "gqa":
            return p
        stored = {g: {k: b["attn"][k] for k in HEAD_MAJOR} for g, b in p["layers"].items()}
        held = jax.jit(_head_major)(stored)
        layers = {}
        for g, b in p["layers"].items():
            attn = {k: v for k, v in b["attn"].items() if k not in HEAD_MAJOR}
            layers[g] = {**b, "attn": {**attn, **held[g]}}
        return {**p, "layers": layers}

    # -- embedding helpers -------------------------------------------------------

    def _embed_tokens(self, p, tokens):
        cfg = self.cfg
        x = jnp.take(p["embed"], tokens, axis=0).astype(self.dtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(np.sqrt(cfg.d_model), self.dtype)
        return x

    def _input_states(self, p, batch) -> Tuple[jax.Array, Optional[int]]:
        """Token embedding (+ vlm patch prefix). Returns (x, prefix_len)."""
        cfg = self.cfg
        x = self._embed_tokens(p, batch["tokens"])
        prefix_len = None
        if cfg.family == "vlm" and "patches" in batch:
            pv = jnp.einsum("bnv,vd->bnd", batch["patches"].astype(self.dtype), p["vision_proj"])
            x = jnp.concatenate([pv, x], axis=1)
            prefix_len = cfg.num_image_tokens
        if not cfg.use_rope:
            S = x.shape[1]
            x = x + sinusoidal_emb(jnp.arange(S), cfg.d_model).astype(self.dtype)[None]
        return x, prefix_len

    def _encode(self, p, frames, ctx=None, remat=False):
        cfg = self.cfg
        x = frames.astype(self.dtype)
        S = x.shape[1]
        x = x + sinusoidal_emb(jnp.arange(S), cfg.d_model).astype(self.dtype)[None]
        x, _, _ = stack_apply(
            cfg, p["enc_layers"], self.enc_plan, x, jnp.arange(S),
            mode="forward", bidirectional=True, ctx=ctx, remat=remat,
            unroll=self.scan_unroll,
        )
        return _norm(cfg, p["enc_norm"], x)

    def _head(self, p, x):
        if self.cfg.tie_embeddings:
            return jnp.einsum("bsd,vd->bsv", x, p["embed"])
        return jnp.einsum("bsd,dv->bsv", x, p["lm_head"])

    # -- train -----------------------------------------------------------------

    def loss(self, p, batch, ctx=None) -> Tuple[jax.Array, dict]:
        cfg = self.cfg
        remat = cfg.remat != "none"
        enc_out = None
        if cfg.is_encdec:
            enc_out = self._encode(p, batch["frames"], ctx=ctx, remat=remat)
        x, prefix_len = self._input_states(p, batch)
        S = x.shape[1]
        x, _, aux = stack_apply(
            cfg, p["layers"], self.plan, x, jnp.arange(S),
            mode="forward", prefix_len=prefix_len, enc_out=enc_out, ctx=ctx, remat=remat,
            remat_policy=cfg.remat, unroll=self.scan_unroll,
        )
        x = _norm(cfg, p["final_norm"], x)
        targets = batch["targets"]
        mask = batch.get("loss_mask")
        if prefix_len:  # vlm: loss only over the text suffix
            x = x[:, prefix_len:]
        if cfg.loss_chunk and S > cfg.loss_chunk:
            ce, n = self._chunked_ce(p, x, targets, mask, cfg.loss_chunk)
        else:
            logits = self._head(p, x)
            ce, n = cross_entropy(logits, targets, mask)
        loss = ce + aux
        return loss, {"ce": ce, "aux": aux, "tokens": n}

    def _chunked_ce(self, p, x, targets, mask, chunk: int):
        B, S, _ = x.shape
        nc = S // chunk
        xc = x[:, : nc * chunk].reshape(B, nc, chunk, -1).transpose(1, 0, 2, 3)
        tc = targets[:, : nc * chunk].reshape(B, nc, chunk).transpose(1, 0, 2)
        mc = (
            mask[:, : nc * chunk].reshape(B, nc, chunk).transpose(1, 0, 2)
            if mask is not None
            else jnp.ones_like(tc, jnp.float32)
        )

        @jax.checkpoint
        def one(args):
            xx, tt, mm = args
            lf = self._head(p, xx).astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(lf, axis=-1)
            ll = jnp.take_along_axis(lf, tt[..., None].astype(jnp.int32), axis=-1)[..., 0]
            return jnp.sum((lse - ll) * mm), jnp.sum(mm)

        sums, ns = jax.lax.map(one, (xc, tc, mc))
        n = jnp.maximum(jnp.sum(ns), 1.0)
        return jnp.sum(sums) / n, n

    # -- serving ------------------------------------------------------------------

    def prefill(self, p, batch, ctx=None, *, last_pos=None) -> Tuple[jax.Array, dict]:
        """Fill the KV cache for a prompt; logits for the next-token position.

        ``last_pos`` (scalar int, optional) selects which position's logits
        to return; default is the final one. The serving engine uses this to
        prefill right-padded prompt buckets: the pad tokens fill cache slots
        beyond ``last_pos`` but are causally invisible to it, and decode
        masks them via ``valid_len`` before they are ever attended.
        """
        cfg = self.cfg
        enc_out = None
        if cfg.is_encdec:
            enc_out = self._encode(p, batch["frames"], ctx=ctx)
        x, prefix_len = self._input_states(p, batch)
        S = x.shape[1]
        x, caches, _ = stack_apply(
            cfg, p["layers"], self.plan, x, jnp.arange(S),
            mode="prefill", prefix_len=prefix_len, enc_out=enc_out, ctx=ctx,
            unroll=self.scan_unroll,
        )
        x = _norm(cfg, p["final_norm"], x)
        if last_pos is None:
            x_last = x[:, -1:]
        else:
            x_last = jax.lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)
        logits = self._head(p, x_last)
        return logits, caches

    def decode_step(self, p, tokens, caches, index, ctx=None) -> Tuple[jax.Array, dict]:
        """One new token given a cache. tokens: (B, 1); index: () int32."""
        cfg = self.cfg
        x = self._embed_tokens(p, tokens)
        if not cfg.use_rope:
            x = x + sinusoidal_emb(index[None], cfg.d_model).astype(self.dtype)[None]
        positions = index[None]
        x, caches_out, _ = stack_apply(
            cfg, p["layers"], self.plan, x, positions,
            mode="decode", caches=caches, cache_index=index, ctx=ctx,
            unroll=self.scan_unroll,
        )
        x = _norm(cfg, p["final_norm"], x)
        return self._head(p, x), caches_out

    # -- shapes for the dry-run -------------------------------------------------

    def cache_shapes(self, batch: int, seq: int) -> dict:
        cfg = self.cfg
        return stack_cache_shapes(
            cfg, self.plan, batch, seq, self.dtype,
            xdec_enc_seq=cfg.encoder_seq if cfg.is_encdec else None,
        )

    def input_specs(self, shape_name: str, spec: dict) -> dict:
        """ShapeDtypeStruct stand-ins for every model input of a shape cell."""
        cfg = self.cfg
        S, B = spec["seq_len"], spec["global_batch"]
        kind = spec["kind"]
        i32 = jnp.int32
        tok = lambda s: jax.ShapeDtypeStruct((B, s), i32)
        out: dict[str, Any] = {}
        S_text = S - (cfg.num_image_tokens if cfg.family == "vlm" else 0)
        if kind == "train":
            out["tokens"] = tok(S_text)
            out["targets"] = tok(S_text)
            if cfg.family == "vlm":
                out["patches"] = jax.ShapeDtypeStruct(
                    (B, cfg.num_image_tokens, cfg.vision_dim), self.dtype
                )
            if cfg.is_encdec:
                out["frames"] = jax.ShapeDtypeStruct((B, cfg.encoder_seq, cfg.d_model), self.dtype)
        elif kind == "prefill":
            out["tokens"] = tok(S_text)
            if cfg.family == "vlm":
                out["patches"] = jax.ShapeDtypeStruct(
                    (B, cfg.num_image_tokens, cfg.vision_dim), self.dtype
                )
            if cfg.is_encdec:
                out["frames"] = jax.ShapeDtypeStruct((B, cfg.encoder_seq, cfg.d_model), self.dtype)
        elif kind == "decode":
            out["tokens"] = tok(1)
            out["caches"] = self.cache_shapes(B, S)
            out["index"] = jax.ShapeDtypeStruct((), i32)
        else:
            raise ValueError(kind)
        return out


def _head_major(stored: dict) -> dict:
    """Every stack group's stored projections, head-major, in one program."""
    return {g: gqa_head_major(a) for g, a in stored.items()}


def build_model(cfg, scan_probe: Optional[int] = None, scan_unroll: bool = False) -> Model:
    return Model(cfg, scan_probe=scan_probe, scan_unroll=scan_unroll)
