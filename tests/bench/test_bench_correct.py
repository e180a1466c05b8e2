"""What decides ``correct`` in the chat cell, at a size the CPU holds: a run
passes; with the timed path broken underneath, or with the fp8 control in the
program's place, it comes out false.

The cell is ``phi4mini.chat`` with its files as they are, cut to a tiny
width and depth and a short window here; the chip check is skipped and the
rest of a run is driven as ``run.py`` drives it.
"""
import json
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "benchmarks" / "chip"), str(ROOT / "src")]

import control  # noqa: E402
import harness  # noqa: E402

SEED = 4_000_000_017


def tiny_cell(seed: int = SEED):
    cell = harness.load_cell("phi4mini.chat")
    cell.config.update(
        hidden_size=128, intermediate_size=256, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=2, vocab_size=2048, head_dim=16,
    )
    mix = cell.traffic
    mix["rate_per_s"] = 20.0
    mix["engine"].update(max_slots=4, max_len=256, prefill_buckets=[32, 64, 128], page_size=16)
    mix["prompt_len"].update(min=8, max=100, median=40)
    mix["output_len"].update(min=4, max=24, median=8)
    cell.device = {"platform": "cpu", "kind": "cpu", "count": 1}
    cell.seed, cell.seconds, cell.trace, cell.t_start = seed, 2.0, False, time.perf_counter()
    return cell


def _run(cell):
    result, checks = cell.driver().run(cell)
    return result, {n: (v, lim) for n, v, lim in checks}


def test_a_sound_run_is_correct():
    result, checks = _run(tiny_cell())
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] == 40
    gap, limit = checks["max_logit_gap"]
    assert 0 <= gap <= limit
    assert set(result["metrics"]) == {
        "ttft_p50_ms", "itl_p99_ms", "serve_tokens_per_s", "setup_s"
    }


def test_a_token_altered_where_it_is_produced_is_caught(monkeypatch):
    """The decode tick's sampled tokens are shifted by one on the device."""
    cell = tiny_cell()
    serve = cell.driver()
    build = serve.build

    def broken_build(c):
        engine = build(c)
        tick = engine._tick_jit

        def altered(*args):
            toks, pools = tick(*args)
            return (toks + 1) % c.published["vocab_size"], pools

        engine._tick_jit = altered
        return engine

    monkeypatch.setattr(serve, "build", broken_build)
    monkeypatch.setattr(cell, "driver", lambda: serve)
    result, checks = _run(cell)
    assert not result["correct"]
    assert checks["max_logit_gap"][0] > checks["max_logit_gap"][1]


def test_the_fp8_control_fails_the_limit():
    """The reference computed with fp8 weights, put in the program's place:
    the tokens it puts first, read against the float32 reference."""
    cell = tiny_cell()
    serve = cell.driver()
    for seed in (SEED, SEED + 1, SEED + 2):
        cell.seed = seed
        r = control.readings(cell, serve, cell.seconds)
        limit = cell.config["check"]["max_logit_gap"]
        assert r["failed"] == 0 and r["tokens"] > 50
        assert r["program"] <= limit < r["control"], r


def test_gaps_read_the_served_token_against_the_best():
    logits = jnp.asarray([[[0.0, 2.0, 1.5], [3.0, 1.0, 0.0]]])
    gaps = np.asarray(harness.load_module(
        ROOT / "benchmarks" / "chip" / "drivers" / "serve.py"
    ).token_gaps(logits, np.asarray([[2, 0]])))
    np.testing.assert_allclose(gaps, [[0.5, 0.0]])


@pytest.mark.parametrize("layer", [0, 1])
def test_reference_layers_are_remade_bit_for_bit(layer):
    """A layer made alone equals its slice of the program's stacked weights."""
    cell = tiny_cell()
    ref = cell.reference()
    dims = cell.dims()
    prog = ref.program_params(SEED, dims, kv_pad_to=16)
    alone = ref.layer_weights(ref.root_key(SEED), layer, dims)
    stacked = prog["layers"]["s0"]
    np.testing.assert_array_equal(
        np.asarray(stacked["attn"]["wq"][layer, :, : dims.heads]), np.asarray(alone["wq"])
    )
    assert not np.asarray(stacked["attn"]["wq"][layer, :, dims.heads :]).any()
    np.testing.assert_array_equal(
        np.asarray(stacked["mlp"]["w_down"][layer]), np.asarray(alone["w_down"])
    )


# -- the train cell, on four virtual CPU devices ------------------------------------

TRAIN_SCRIPT = r"""
import json, sys, tempfile, time
root = sys.argv[1]
sys.path[:0] = [root + "/benchmarks/chip", root + "/src"]
import control, harness
cell = harness.load_cell("phi4mini.train_tp4")
# d 256: at d 64 the bf16 program's own rounding reads above the limits set on the chip
cell.config.update(
    hidden_size=256, intermediate_size=512, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, vocab_size=2048, head_dim=64)
cell.traffic.update(seq_len=64, global_batch=8)
cell.device = {"platform": "cpu", "kind": "cpu", "count": 4}
cell.seed, cell.seconds, cell.trace, cell.t_start = 4_000_000_017, 1.0, False, time.perf_counter()
train = cell.driver()
out = {}
result, checks = train.run(cell)
out["sound"] = {"correct": result["correct"], "checks": checks}
real = train.build
def frozen(c):
    trainer, step_fn, sh, params, opt, data = real(c)
    def unchanged(p, o, batch, step):  # a step that returns its state unchanged
        _p, _o, m = step_fn(p, o, batch, step)
        return p, o, m
    return trainer, unchanged, sh, params, opt, data
train.build = frozen
result, checks = train.run(cell)
out["unchanged"] = {"correct": result["correct"], "checks": checks}
train.build = real
with tempfile.TemporaryDirectory() as cell.scratch:
    out["readings"] = control.train_readings(cell, train)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def train_runs():
    import os
    import subprocess

    env = dict(
        os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4"
    )
    p = subprocess.run(
        [sys.executable, "-c", TRAIN_SCRIPT, str(ROOT)], env=env, capture_output=True,
        text=True, timeout=900,
    )
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_train_sound_run_is_correct(train_runs):
    assert train_runs["sound"]["correct"], train_runs["sound"]["checks"]


def test_train_step_that_returns_its_state_unchanged_is_caught(train_runs):
    """No leaf moves, so each leaf's change reads 1 against the reference."""
    checks = {n: (v, lim) for n, v, lim in train_runs["unchanged"]["checks"]}
    assert not train_runs["unchanged"]["correct"]
    assert checks["change_norm_gap"][0] == pytest.approx(1.0)


@pytest.mark.parametrize("fault", ["control", "half_batch", "no_exchange"])
def test_train_control_and_planted_faults_fail_a_limit(train_runs, fault):
    """The reference in the program's place: with fp8 weights, with half of
    each batch left out, with the tensor-parallel exchange left out."""
    limits = json.loads(
        (ROOT / "benchmarks" / "chip" / "configs" / "phi4-mini-3.8b-tp4.json").read_text()
    )["check"]
    got = train_runs["readings"][fault]
    assert any(got[k] > limits[k] for k in got), got
    assert all(v <= limits[k] for k, v in train_runs["readings"]["program"].items())
