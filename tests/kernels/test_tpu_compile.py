"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Nothing runs: each kernel is lowered at the published widths of the model
that uses it and compiled by the TPU compiler for a v5e that is described,
not attached. That catches what interpret mode cannot (primitives Mosaic
does not lower, misaligned tiles, VMEM overflow) at no chip time. The
serving programs are compiled the same way, to read what XLA makes of the
weights they are given.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers all
import this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.ssd import ssd_bshp
from repro.models import build_model
from repro.models.attention import PagedKV
from repro.models.ssm import ssm_dims

SEQ = 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compiled_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("window", [None, 512])
def test_flash_attention_compiles_at_tinyllama_widths(one_chip, window):
    cfg = get_config("tinyllama-1.1b")
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    bf16 = jnp.bfloat16
    txt = _compiled_text(
        lambda q, k, v: flash_attention_bhsd(q, k, v, causal=True, window=window),
        [((1, H, SEQ, Dh), bf16), ((1, KV, SEQ, Dh), bf16), ((1, KV, SEQ, Dh), bf16)],
        one_chip,
    )
    assert "tpu_custom_call" in txt


def test_ssd_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-1.3b")
    d = ssm_dims(cfg)
    H, P, N = d["nheads"], d["headdim"], d["dstate"]
    assert (H, P, N, cfg.ssm_chunk) == (64, 64, 128, 256)
    bf16, f32 = jnp.bfloat16, jnp.float32
    txt = _compiled_text(
        lambda x, dt, A, Bm, Cm: ssd_bshp(x, dt, A, Bm, Cm, chunk=cfg.ssm_chunk),
        [((1, SEQ, H, P), bf16), ((1, SEQ, H), f32), ((H,), f32),
         ((1, SEQ, N), bf16), ((1, SEQ, N), bf16)],
        one_chip,
    )
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize(
    "arch,lanes,max_len",
    [("phi4-mini-3.8b", 6, 1024), ("tinyllama-1.1b", 4, 256)],
)
def test_paged_decode_attention_compiles_at_served_widths(one_chip, arch, lanes, max_len):
    """The decode tick's kernel as the engine calls it: every layer's pages
    in one pool, pages of 64 tokens (Phi-4-mini: 16 padded KV heads of 128,
    48 query heads; the chat cell's 6 lanes of 1,024 tokens)."""
    cfg = get_config(arch)
    KV, Dh = cfg.kv_heads_padded, cfg.head_dim
    G = cfg.heads_padded // KV
    page, L = 64, cfg.num_layers
    P = max_len // page
    pool = (L, 2 + lanes * P, KV, page, Dh)
    bf16, i32 = jnp.bfloat16, jnp.int32
    txt = _compiled_text(
        paged_decode_attention,
        [((lanes, KV, G, Dh), bf16), ((lanes, KV, Dh), bf16), ((lanes, KV, Dh), bf16),
         (pool, bf16), (pool, bf16), ((lanes, P), i32), ((lanes,), i32), ((), i32)],
        one_chip,
    )
    assert "tpu_custom_call" in txt


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")


def materialized(hlo: str, shapes: set) -> list:
    """Names of the fusions and copies that write an array whose trailing
    dims are one of ``shapes`` to memory: those outside the computations
    that fusions call (a slice fused into its consumer reads in place)."""
    called, found, comp = set(), [], None
    for line in hlo.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        ins = _INSTRUCTION.match(line)
        if ins is None or comp is None:
            continue
        name, dims, op = ins.groups()
        if op == "fusion":
            called.update(re.findall(r"calls=%([\w.\-]+)", line))
        shape = tuple(int(d) for d in dims.split(",") if d)
        if op in ("fusion", "copy") and shape[-3:] in shapes:
            found.append((comp, name))
    return [name for comp, name in found if comp not in called]


def test_serving_programs_read_the_projection_weights_in_place(one_chip):
    """The decode tick (``vmap(decode_step)`` over each lane's paged view, as
    the engine maps it) and a 256-token prefill, compiled at Phi-4-mini's
    widths (2 of its layers, the chat cell's 6 lanes of 1,024 tokens) with
    the serving form of the weights: no layer's Q/K/V slice is copied out
    (stored ``(d, H, Dh)``, XLA copies each into head-major order first)."""
    cfg = get_config("phi4-mini-3.8b").replace(num_layers=2)
    model = build_model(cfg)
    d, Dh, Hp, KVp = cfg.d_model, cfg.head_dim, cfg.heads_padded, cfg.kv_heads_padded
    slices = {(d, Hp, Dh), (d, KVp, Dh), (Hp, d, Dh), (KVp, d, Dh)}
    at = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(at, jax.eval_shape(model.serving_params, model.abstract_params()))
    lanes, page, pages = 6, 64, 1024 // 64
    pool = jax.ShapeDtypeStruct(
        (cfg.num_layers, 2 + lanes * pages, KVp, page, Dh), jnp.bfloat16, sharding=one_chip
    )
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731

    def tick(p, tok, k_pages, v_pages, tables, idx):
        def lane(t, table, i):
            view = {"s0": {"attn": PagedKV(k_pages, v_pages, table, i)}}
            return model.decode_step(p, t, view, i)

        return jax.vmap(lane)(tok, tables, idx)

    def prefill(p, tokens, last):
        return model.prefill(p, {"tokens": tokens}, last_pos=last)

    tick_hlo = jax.jit(tick).lower(
        params, i32(lanes, 1, 1), pool, pool, i32(lanes, pages), i32(lanes)
    ).compile().as_text()
    prefill_hlo = jax.jit(prefill).lower(params, i32(1, 256), i32()).compile().as_text()
    assert "tpu_custom_call" in tick_hlo  # the paged kernel, as on the chip
    assert materialized(tick_hlo, slices) == []
    assert materialized(prefill_hlo, slices) == []
