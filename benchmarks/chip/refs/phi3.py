"""Plain reference of a Phi-3-family decoder (Phi-4-mini), and its seeded weights.

Everything here is straightforward ``jax.numpy`` and imports nothing of the
program under test. It holds three things:

* the weights, made from ``--seed``: every tensor is uniform with the
  variance of a fan-in init, keyed by (tensor, layer), so a single layer can
  be made again on its own, bit for bit, long after the program's copy is
  gone;
* ``program_params``: the same weights in the serving program's layout (the
  repo's dense parameter tree, heads zero-padded to ``kv_pad_to``), made in
  one jitted call in bf16, the type they are served in;
* ``logits_at``: the forward pass in float32 at the highest matmul precision,
  one layer at a time so that it fits beside nothing else, returning the
  logits at the positions asked for. ``quant="fp8"`` computes it with every
  weight matrix rounded to 8-bit floats (per-tensor scale): the control.

The architecture as run (see the configuration file): pre-norm RMSNorm
blocks, GQA with RoPE on the whole head (the program's departure from the
published partial rotary factor), SwiGLU MLP, tied embedding head.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST

# stable ids: a tensor's key is fold_in(fold_in(root, id), layer)
TENSOR_IDS = {
    "embed": 0, "wq": 1, "wk": 2, "wv": 3, "wo": 4, "w_gate": 5, "w_up": 6, "w_down": 7,
    "attn_norm": 8, "mlp_norm": 9, "final_norm": 10,
}
NORM_SPREAD = 0.1  # gammas are 1 + U(-0.1, 0.1), so the norms matter to the check


def root_key(seed: int) -> jax.Array:
    """A key from a seed of up to 64 bits."""
    k = jax.random.key(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _uniform(key, shape, std) -> jax.Array:
    """bf16 tensor, uniform with standard deviation ``std``."""
    u = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
    return (u * (std * 3.0**0.5)).astype(jnp.bfloat16)


def _shapes(dims) -> dict:
    d, h, kv, hd, f = dims.d, dims.heads, dims.kv_heads, dims.head_dim, dims.d_ff
    return {
        "wq": ((d, h, hd), d**-0.5),
        "wk": ((d, kv, hd), d**-0.5),
        "wv": ((d, kv, hd), d**-0.5),
        "wo": ((h, hd, d), (h * hd) ** -0.5),
        "w_gate": ((d, f), d**-0.5),
        "w_up": ((d, f), d**-0.5),
        "w_down": ((f, d), f**-0.5),
        "attn_norm": ((d,), NORM_SPREAD / 3.0**0.5),
        "mlp_norm": ((d,), NORM_SPREAD / 3.0**0.5),
    }


def _tensor_key(root, name: str, layer):
    return jax.random.fold_in(jax.random.fold_in(root, TENSOR_IDS[name]), layer)


def layer_weights(root, layer, dims) -> dict:
    """Layer ``layer``'s tensors in bf16 (norms as offsets from 1)."""
    return {
        name: _uniform(_tensor_key(root, name, layer), shape, std)
        for name, (shape, std) in _shapes(dims).items()
    }


def embed_weights(root, dims) -> jax.Array:
    return _uniform(_tensor_key(root, "embed", 0), (dims.vocab, dims.d), dims.d**-0.5)


def final_norm(root, dims) -> jax.Array:
    return _uniform(_tensor_key(root, "final_norm", 0), (dims.d,), NORM_SPREAD / 3.0**0.5)


# -- the program's layout --------------------------------------------------------


def program_params(seed: int, dims, kv_pad_to: int, expect=None, shardings=None) -> dict:
    """The weights as the repo's dense model holds them, in one jitted call.

    ``expect``: the program's abstract parameter tree; the result must match
    it leaf for leaf in shape and dtype, or this raises. ``shardings``: where
    each leaf is made (a tree like the result), so no chip holds them all.
    """
    kvp = max(kv_pad_to, dims.kv_heads)
    hp = kvp * (dims.heads // dims.kv_heads)

    def pad(a, axis, to):
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, to - a.shape[axis])
        return jnp.pad(a, widths)

    def make(lo, hi):
        root = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), lo), hi)
        layers = jax.vmap(lambda l: layer_weights(root, l, dims))(jnp.arange(dims.layers))
        return {
            "embed": embed_weights(root, dims),
            "layers": {
                "s0": {
                    "attn": {
                        "wq": pad(layers["wq"], 2, hp),
                        "wk": pad(layers["wk"], 2, kvp),
                        "wv": pad(layers["wv"], 2, kvp),
                        "wo": pad(layers["wo"], 1, hp),
                    },
                    "attn_norm": {"w": layers["attn_norm"]},
                    "mlp_norm": {"w": layers["mlp_norm"]},
                    "mlp": {k: layers[k] for k in ("w_gate", "w_up", "w_down")},
                }
            },
            "final_norm": {"w": final_norm(root, dims)},
        }

    out = jax.jit(make, out_shardings=shardings)(
        np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)
    )
    if expect is not None:
        got = jax.tree.map(lambda a: (a.shape, a.dtype), out)
        want = jax.tree.map(lambda a: (tuple(a.shape), a.dtype), expect)
        if got != want:
            raise ValueError(f"weight layout differs from the program's:\n{got}\n{want}")
    return out


# -- the forward pass --------------------------------------------------------------


def fp8_round(w: jax.Array) -> jax.Array:
    """Round to 8-bit floats (4 exponent bits, 3 mantissa bits) with one scale
    per tensor, back in float32. ``reduce_precision`` and not a cast to
    ``float8_e4m3fn`` and back: XLA may drop such a cast pair (excess
    precision), and the control then reads as the reference itself."""
    w = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / 240.0  # the format's largest normal
    return jax.lax.reduce_precision(w / scale, exponent_bits=4, mantissa_bits=3) * scale


def _f32(w, quant: Optional[str]):
    """float32 weights; with ``quant="fp8"`` rounded to fp8 in the forward
    pass, the gradient passed straight through."""
    w = w.astype(jnp.float32)
    if quant != "fp8":
        return w
    return w + jax.lax.stop_gradient(fp8_round(w) - w)


def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gamma


def _rope(x, theta):
    """x: (B, S, heads, Dh) at positions 0..S-1; rotates pairs (i, i + Dh/2)."""
    S, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : dh // 2], x[..., dh // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(x, w, *, eps, theta, quant, keep=None):
    """One decoder block on x: (B, S, d) float32. ``keep`` = (heads, d_ff):
    sum the row-parallel contractions (``wo`` over heads, ``w_down`` over
    d_ff) over that leading share only, as one chip of a tensor-parallel
    group would without the exchange."""
    B, S, _ = x.shape
    g = lambda n: 1.0 + w[n].astype(jnp.float32)
    mm = lambda n: _f32(w[n], quant)
    h = _rms(x, g("attn_norm"), eps)
    q = _rope(jnp.einsum("bsd,dhk->bshk", h, mm("wq"), precision=HI), theta)
    k = _rope(jnp.einsum("bsd,dhk->bshk", h, mm("wk"), precision=HI), theta)
    v = jnp.einsum("bsd,dhk->bshk", h, mm("wv"), precision=HI)
    H, KV, Dh = q.shape[2], k.shape[2], q.shape[3]
    qg = q.reshape(B, S, KV, H // KV, Dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, precision=HI) * Dh**-0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", a, v, precision=HI).reshape(B, S, H, Dh)
    if keep is not None:
        o = o * (jnp.arange(H) < keep[0])[:, None]
    x = x + jnp.einsum("bshk,hkd->bsd", o, mm("wo"), precision=HI)
    h = _rms(x, g("mlp_norm"), eps)
    gate = jnp.einsum("bsd,df->bsf", h, mm("w_gate"), precision=HI)
    up = jnp.einsum("bsd,df->bsf", h, mm("w_up"), precision=HI)
    hidden = jax.nn.silu(gate) * up
    if keep is not None:
        hidden = hidden * (jnp.arange(hidden.shape[-1]) < keep[1])
    return x + jnp.einsum("bsf,fd->bsd", hidden, mm("w_down"), precision=HI)


def logits_at(
    seed: int, dims, published: dict, tokens: np.ndarray, positions: np.ndarray,
    quant: Optional[str] = None,
) -> jax.Array:
    """float32 logits ``(B, P, V)`` of ``tokens`` ``(B, S)`` at ``positions`` ``(B, P)``.

    Rows are causal, so right-padding a row changes none of its earlier
    logits. Weights are made layer by layer from the seed and dropped after
    use.
    """
    eps, theta = float(published["rms_norm_eps"]), float(published["rope_theta"])
    root = root_key(seed)  # an argument of each program, so no program depends on the seed
    make_layer = jax.jit(lambda root, l: layer_weights(root, l, dims))
    step = jax.jit(lambda x, w: _layer(x, w, eps=eps, theta=theta, quant=quant))
    embed = jax.jit(lambda root: _f32(embed_weights(root, dims), quant))(root)
    x = jnp.take(embed, jnp.asarray(tokens), axis=0)
    for layer in range(dims.layers):
        x = step(x, make_layer(root, layer))

    @jax.jit
    def head(root, x, pos, embed):
        xs = jnp.take_along_axis(x, pos[..., None], axis=1)
        xs = _rms(xs, 1.0 + final_norm(root, dims).astype(jnp.float32), eps)
        return jnp.einsum("bpd,vd->bpv", xs, embed, precision=HI)

    return head(root, x, jnp.asarray(positions), embed)


# -- training ------------------------------------------------------------------------

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")  # AdamW decays these


def train_params(seed: int, dims) -> dict:
    """float32 weights: ``embed``, each layer tensor stacked over layers, and
    ``final_norm`` (norms as offsets from 1, as the serving layout keeps them)."""

    @jax.jit
    def make(root):
        layers = jax.vmap(lambda l: layer_weights(root, l, dims))(jnp.arange(dims.layers))
        out = {k: v.astype(jnp.float32) for k, v in layers.items()}
        out["embed"] = embed_weights(root, dims).astype(jnp.float32)
        out["final_norm"] = final_norm(root, dims).astype(jnp.float32)
        return out

    return make(root_key(seed))


def _loss(params, tokens, targets, *, eps, theta, quant, keep, chunk):
    """Mean next-token cross-entropy over every position, in float32."""
    embed = _f32(params["embed"], quant)
    x = jnp.take(embed, tokens, axis=0)
    layer = jax.checkpoint(lambda x, w: _layer(x, w, eps=eps, theta=theta, quant=quant, keep=keep))
    names = [k for k in params if k not in ("embed", "final_norm")]
    for i in range(params["wq"].shape[0]):
        x = layer(x, {k: params[k][i] for k in names})
    x = _rms(x, 1.0 + params["final_norm"], eps)
    B, S, d = x.shape

    @jax.checkpoint
    def ce(args):
        xc, tc = args
        lg = jnp.einsum("bsd,vd->bsv", xc, embed, precision=HI)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, tc[..., None], axis=-1)[..., 0])

    n = S // chunk
    xs = x.reshape(B, n, chunk, d).transpose(1, 0, 2, 3)
    ts = targets.reshape(B, n, chunk).transpose(1, 0, 2)
    return jnp.sum(jax.lax.map(ce, (xs, ts))) / (B * S)


def train_reference(seed, dims, published, batches, hp, *, quant=None, keep=None):
    """Three (or ``len(batches)``) AdamW steps from the seed's weights, in
    float32, spread over every chip of the host.

    ``hp``: the job's optimizer: ``lr`` (constant over these steps), ``b1``,
    ``b2``, ``eps``, ``weight_decay`` (on weight matrices and the embedding),
    ``grad_clip`` (on the global norm). Returns the loss of each step, each
    leaf's norm of the first clipped gradient, and each leaf's norm of the
    change of its weights over the steps.
    """
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as Ps

    eps, theta = float(published["rms_norm_eps"]), float(published["rope_theta"])
    devs = np.asarray(jax.devices())
    mesh = Mesh(devs, ("x",))
    n = len(devs)

    def spec(a):
        for ax in np.argsort(a.shape)[::-1]:
            if a.shape[ax] % n == 0:
                return NamedSharding(mesh, Ps(*[("x" if i == ax else None) for i in range(a.ndim)]))
        return NamedSharding(mesh, Ps())

    p0 = train_params(seed, dims)
    shard = jax.tree.map(spec, p0)
    p0 = jax.device_put(p0, shard)
    loss_fn = lambda p, t, y: _loss(  # noqa: E731
        p, t, y, eps=eps, theta=theta, quant=quant, keep=keep, chunk=min(256, t.shape[1])
    )
    b1, b2 = hp["b1"], hp["b2"]

    @jax.jit
    def step(p, m, v, count, tokens, targets):
        loss, g = jax.value_and_grad(loss_fn)(p, tokens, targets)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, hp["grad_clip"] / gn), g)
        count = count + 1
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        c1, c2 = 1 - b1**count, 1 - b2**count

        def upd(k, p, m, v):
            u = (m / c1) / (jnp.sqrt(v / c2) + hp["eps"])
            if k in MATRICES or k == "embed":
                u = u + hp["weight_decay"] * p
            return p - hp["lr"] * u

        p = {k: upd(k, p[k], m[k], v[k]) for k in p}
        return p, m, v, count, loss, {k: jnp.linalg.norm(x) for k, x in g.items()}

    data = NamedSharding(mesh, Ps("x", None)) if batches[0]["tokens"].shape[0] % n == 0 else None
    zeros = jax.tree.map(jnp.zeros_like, p0)
    p, m, v, count = p0, zeros, zeros, jnp.zeros((), jnp.float32)
    losses, first_grad = [], None
    for b in batches:
        t = jax.device_put(b["tokens"], data) if data else jnp.asarray(b["tokens"])
        y = jax.device_put(b["targets"], data) if data else jnp.asarray(b["targets"])
        p, m, v, count, loss, gnorms = step(p, m, v, count, t, y)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = {k: float(x) for k, x in gnorms.items()}
    change = jax.jit(lambda a, b: {k: jnp.linalg.norm(a[k] - b[k]) for k in a})(p, p0)
    return {
        "losses": losses,
        "grad_norms": first_grad,
        "change_norms": {k: float(x) for k, x in change.items()},
    }
