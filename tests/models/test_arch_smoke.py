"""Per-architecture smoke tests (assignment requirement).

Each assigned arch instantiates its REDUCED config (same family/topology,
small dims) and runs one forward + one train step on CPU, asserting output
shapes and the absence of NaNs. The FULL configs are exercised only via the
dry-run (ShapeDtypeStructs, no allocation).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_NAMES, get_config, get_reduced, param_count
from repro.models import build_model
from repro.optim import AdamWConfig, adamw_init, adamw_update


def make_batch(cfg, key, B=2, S=16):
    S_text = S - (cfg.num_image_tokens if cfg.family == "vlm" else 0)
    tokens = jax.random.randint(key, (B, S_text), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    if cfg.family == "vlm":
        batch["patches"] = jnp.ones((B, cfg.num_image_tokens, cfg.vision_dim), jnp.bfloat16)
    if cfg.is_encdec:
        batch["frames"] = jnp.ones((B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_reduced_forward_and_train_step(arch):
    cfg = get_reduced(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, jax.random.PRNGKey(1))

    @jax.jit
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(params, batch)
        new_params, new_opt, _ = adamw_update(ocfg, jnp.asarray(1e-3), params, grads, opt_state)
        return new_params, new_opt, loss, metrics

    ocfg = AdamWConfig(lr=1e-3)
    opt_state = adamw_init(ocfg, params)
    params2, opt2, loss, metrics = train_step(params, opt_state, batch)
    assert jnp.isfinite(loss), f"{arch}: non-finite loss {loss}"
    assert float(metrics["tokens"]) > 0
    # params actually changed and stayed finite
    changed = jax.tree.map(lambda a, b: jnp.any(a != b), params, params2)
    assert any(bool(x) for x in jax.tree.leaves(changed)), f"{arch}: no param updated"
    for leaf in jax.tree.leaves(params2):
        assert jnp.all(jnp.isfinite(leaf.astype(jnp.float32))), f"{arch}: NaN in params"


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_reduced_prefill_decode_shapes(arch):
    cfg = get_reduced(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    batch = make_batch(cfg, jax.random.PRNGKey(1), B, S)
    batch.pop("targets")
    logits, caches = jax.jit(model.prefill)(params, batch)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert jnp.all(jnp.isfinite(logits.astype(jnp.float32)))
    from repro.serve.kv import pad_caches_to

    caches = pad_caches_to(caches, 2)
    tok = jnp.zeros((B, 1), jnp.int32)
    logits2, caches2 = jax.jit(model.decode_step)(
        params, tok, caches, jnp.array(S, jnp.int32)
    )
    assert logits2.shape == (B, 1, cfg.vocab_size)
    assert jnp.all(jnp.isfinite(logits2.astype(jnp.float32)))


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact assigned hyperparameters."""
    spec = {
        "deepseek-coder-33b": (62, 7168, 56, 8, 19200, 32256),
        "phi4-mini-3.8b": (32, 3072, 24, 8, 8192, 200064),
        "tinyllama-1.1b": (22, 2048, 32, 4, 5632, 32000),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151936),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        "whisper-medium": (24, 1024, 16, 16, 4096, 51865),
        "paligemma-3b": (18, 2048, 8, 1, 16384, 257216),
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
        "deepseek-v2-236b": (60, 5120, 128, 128, 1536, 102400),
        "mamba2-1.3b": (48, 2048, 0, 0, 0, 50280),
    }[arch]
    cfg = get_config(arch)
    L, d, H, KV, ff, V = spec
    assert cfg.num_layers == L and cfg.d_model == d
    assert cfg.num_heads == H and cfg.num_kv_heads == KV
    ff_actual = cfg.moe_d_ff if cfg.is_moe else cfg.d_ff
    assert ff_actual == ff
    assert cfg.vocab_size == V


def test_param_counts_in_expected_range():
    """Analytic counts should land near the advertised model sizes."""
    expect = {
        "deepseek-coder-33b": (30e9, 36e9),
        "phi4-mini-3.8b": (3.0e9, 4.6e9),
        "tinyllama-1.1b": (0.9e9, 1.3e9),
        "qwen1.5-4b": (3.0e9, 5.0e9),
        "hymba-1.5b": (1.0e9, 2.0e9),
        "whisper-medium": (0.6e9, 1.0e9),
        "paligemma-3b": (2.0e9, 3.5e9),  # backbone only (frontend stubbed)
        "granite-moe-1b-a400m": (0.8e9, 1.6e9),
        "deepseek-v2-236b": (200e9, 260e9),
        "mamba2-1.3b": (1.0e9, 1.6e9),
    }
    for arch, (lo, hi) in expect.items():
        n = param_count(get_config(arch))["total"]
        assert lo <= n <= hi, f"{arch}: {n / 1e9:.2f}B outside [{lo / 1e9}, {hi / 1e9}]"


def test_shape_suite_skip_rules():
    """long_500k only for sub-quadratic archs (mamba2, hymba)."""
    for arch in ARCH_NAMES:
        cfg = get_config(arch)
        shapes = cfg.shapes()
        if arch in ("mamba2-1.3b", "hymba-1.5b"):
            assert "long_500k" in shapes
        else:
            assert "long_500k" not in shapes
        assert {"train_4k", "prefill_32k", "decode_32k"} <= set(shapes)
