"""The paged decode tick: attention reads the KV pages in place.

Against sequential decode, token for token, for a dense, an MoE, an MLA and
a hybrid configuration; one tick program for every occupancy and length; the
KV counter in ``stats()``; and the Pallas kernel itself (interpret mode) in
the tick in place of its oracle. The engine serves the Q/K/V projections
head-major: bit for bit what the stored tree computes, counted in
``stats()["params"]``, and the caller's tree is left usable.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.kernels import ops, ref
from repro.models import build_model
from repro.serve import ServeEngine
from repro.serve.kv import pad_caches_to

from decode_reference import sequential_decode

MAX_LEN, PAGE = 24, 4


def _model(arch):
    cfg = get_reduced(arch)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _requests(cfg, n=5, seed=0):
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=int(s)).astype(np.int32)
        for s in rng.integers(2, 11, size=n)
    ]
    return prompts, [int(b) for b in rng.integers(2, 10, size=n)]


def _serve(model, params, prompts, budgets):
    with ServeEngine(model, params, max_slots=3, max_len=MAX_LEN, page_size=PAGE) as engine:
        outs = engine.generate(prompts, budgets, timeout=300)
        return [list(map(int, o)) for o in outs], engine.stats()


@pytest.mark.parametrize(
    "arch,in_place,gathered",
    [
        ("tinyllama-1.1b", 2, 0),  # dense GQA: one scanned k/v pair
        ("granite-moe-1b-a400m", 2, 0),  # MoE, GQA attention
        ("deepseek-v2-236b", 0, 4),  # MLA latents (two stack groups) stay gathered
        ("hymba-1.5b", 6, 0),  # three global layers; rings and SSM state are slot leaves
    ],
)
def test_paged_tick_streams_sequential_decodes_tokens(arch, in_place, gathered):
    cfg, model, params = _model(arch)
    prompts, budgets = _requests(cfg)
    refs = [sequential_decode(model, params, p, b, MAX_LEN) for p, b in zip(prompts, budgets)]
    paged, stats = _serve(model, params, prompts, budgets)
    assert paged == refs
    assert (stats["kv"]["leaves_in_place"], stats["kv"]["leaves_gathered"]) == (in_place, gathered)


def test_one_tick_program_for_every_occupancy_and_length():
    cfg, model, params = _model("tinyllama-1.1b")
    compiles = []

    def listen(event, _secs, fun_name="", **_kw):
        if event == "/jax/core/compile/backend_compile_duration" and "_ptick" in fun_name:
            compiles.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        with ServeEngine(model, params, max_slots=3, max_len=MAX_LEN, page_size=PAGE) as engine:
            prompts, budgets = _requests(cfg, n=7, seed=3)
            first = engine.submit(prompts[0], 12)  # one lane, its length growing
            first.result(300)
            engine.generate(prompts[1:], budgets[1:], timeout=300)  # one to three lanes
            stats = engine.stats()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert stats["ticks"] > 12 and stats["completed"] == 7
    assert len(compiles) == 1, compiles


def test_the_pallas_kernel_in_the_tick_streams_the_oracles_tokens(monkeypatch):
    """The tick with the kernel (interpret mode) where the chip would run it:
    the lanes of the vmapped decode step join one kernel call per layer."""
    cfg, model, params = _model("phi4-mini-3.8b")  # padded heads: 48 / 16
    prompts, budgets = _requests(cfg, n=4, seed=1)
    oracle, _ = _serve(model, params, prompts, budgets)
    monkeypatch.setattr(
        ref, "paged_attention_ref", functools.partial(ops.paged_attention, interpret=True)
    )
    kernel, _ = _serve(model, params, prompts, budgets)
    assert kernel == oracle


def test_the_serving_form_computes_the_stored_forms_logits_bit_for_bit():
    cfg, model, params = _model("phi4-mini-3.8b")
    assert cfg.kv_heads_padded > cfg.num_kv_heads  # padded heads kept as they are
    served = model.serving_params(params)
    attn, held = params["layers"]["s0"]["attn"], served["layers"]["s0"]["attn"]
    assert sorted(held) == ["wk_hm", "wo", "wq_hm", "wv_hm"]
    assert held["wq_hm"].shape == (cfg.num_layers, cfg.heads_padded, cfg.d_model, cfg.head_dim)
    assert held["wo"] is attn["wo"] and served["embed"] is params["embed"]
    assert served["layers"]["s0"]["mlp"] is params["layers"]["s0"]["mlp"]
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 9), 0, cfg.vocab_size)
    prefill, step = jax.jit(model.prefill), jax.jit(model.decode_step)
    logits, caches = {}, {}
    for form, p in (("stored", params), ("serving", served)):
        logits[form], caches[form] = prefill(p, {"tokens": prompt})
    jax.tree.map(np.testing.assert_array_equal, caches["stored"], caches["serving"])
    np.testing.assert_array_equal(logits["stored"], logits["serving"])
    tok = jnp.argmax(logits["stored"][:, -1], -1)[:, None].astype(jnp.int32)
    cache, idx = pad_caches_to(caches["stored"], 4), jnp.asarray(9, jnp.int32)
    (ls, cs), (lh, ch) = step(params, tok, cache, idx), step(served, tok, cache, idx)
    np.testing.assert_array_equal(ls, lh)
    jax.tree.map(np.testing.assert_array_equal, cs, ch)


def test_the_tick_streams_the_stored_forms_tokens_and_leaves_the_callers_tree(monkeypatch):
    cfg, model, params = _model("phi4-mini-3.8b")
    prompts, budgets = _requests(cfg, n=5, seed=2)
    served, stats = _serve(model, params, prompts, budgets)
    monkeypatch.setattr(model, "serving_params", lambda p: p)
    stored, stats_stored = _serve(model, params, prompts, budgets)
    assert served == stored
    assert (stats["params"], stats_stored["params"]) == (3, 0)
    # the caller's arrays were not donated: still whole and usable
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(params))
    logits, _ = jax.jit(model.prefill)(params, {"tokens": jnp.asarray(prompts[0])[None]})
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize(
    "arch,head_major",
    [
        ("phi4-mini-3.8b", 3),  # one stack group
        ("granite-moe-1b-a400m", 3),
        ("hymba-1.5b", 15),  # five stack groups: three global layers, two scans
        ("deepseek-v2-236b", 0),  # MLA: the tree passes through as it is
        ("mamba2-1.3b", 0),  # no attention
    ],
)
def test_stats_count_the_head_major_projections(arch, head_major):
    cfg, model, params = _model(arch)
    if not head_major:
        assert model.serving_params(params) is params
    with ServeEngine(model, params, max_slots=2, max_len=MAX_LEN, page_size=PAGE) as engine:
        assert engine.stats()["params"] == head_major
