"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Nothing runs: each kernel is lowered at the published widths of the model
that uses it and compiled by the TPU compiler for a v5e that is described,
not attached. That catches what interpret mode cannot (primitives Mosaic
does not lower, misaligned tiles, VMEM overflow) at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers all
import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.ssd import ssd_bshp
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
