"""Ahead-of-time compiles of each benchmark cell's programs, at the cell's
sizes, for a described TPU v5e; ``memory_analysis()`` checked against the chip.

Nothing runs. The chat cell's programs are the engine's prefill at each
bucket, the cache write at each bucket and the paged decode tick, compiled
from the engine itself. The topology is described inside a fixture, never at
import: one process at a time may load the TPU library, and every
pytest-xdist worker imports this file.
"""
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "benchmarks" / "chip"), str(ROOT / "src")]

import harness  # noqa: E402

CHIP_BYTES = 16e9  # one v5e (peaks.py)
HEADROOM = 1.5e9  # what the cell leaves free beyond the largest program


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _abstract(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def _bytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def test_chat_cell_fits_one_v5e(topo):
    from repro.models import build_model
    from repro.serve import ServeEngine

    cell = harness.load_cell("phi4mini.chat")
    eng_kw = cell.traffic["engine"]
    model = build_model(cell.model_config())
    one = SingleDeviceSharding(topo.devices[0])
    params = _abstract(model.abstract_params(), one)
    # the engine places its page pools on the host's CPU here; only their
    # shapes go to the described chip
    engine = ServeEngine(model, None, **eng_kw)
    kv = engine.kv
    try:
        pools = _abstract(kv.pools, one)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa: E731
        S = kv.max_slots
        tick = engine._tick_jit.lower(
            params, i32(S, 1, 1), pools, i32(S, kv.pages_per_seq), i32(S), i32(S)
        ).compile()
        tick_mem = tick.memory_analysis()
        weights, pool_bytes = _bytes(params), _bytes(pools)
        lookahead = eng_kw.get("prefill_lookahead", S)
        largest_cache = _bytes(_abstract(jax.eval_shape(
            lambda p, x: model.prefill(p, x)[1], params,
            {"tokens": i32(1, max(eng_kw["prefill_buckets"]))}), one))
        # the tick's pools are donated (its output aliases its input); the
        # joins admitted ahead wait beside it with their prefill caches
        tick_peak = (
            weights + pool_bytes + tick_mem.temp_size_in_bytes + lookahead * largest_cache
        )
        worst_prefill = 0
        for b in eng_kw["prefill_buckets"]:
            batch = {"tokens": i32(1, b)}
            pre = engine._prefill_jit.lower(params, batch, last_pos=i32()).compile()
            cache = jax.eval_shape(lambda p, x: model.prefill(p, x)[1], params, batch)
            cache = _abstract(cache, one)
            npg = kv.pages_for(b)
            kv._write_jit.lower(pools, cache, i32(npg), i32(), b).compile()
            pm = pre.memory_analysis()
            # while a prefill runs: weights, pools, the joins waiting with
            # their caches, and the prefill's own output and temporaries
            worst_prefill = max(
                worst_prefill,
                weights + pool_bytes + (lookahead + 1) * _bytes(cache)
                + pm.temp_size_in_bytes + pm.output_size_in_bytes,
            )
    finally:
        del engine, kv
    print(
        f"weights {weights} pools {pool_bytes} tick temp {tick_mem.temp_size_in_bytes} "
        f"tick peak {tick_peak} worst prefill peak {worst_prefill}"
    )
    assert max(tick_peak, worst_prefill) <= CHIP_BYTES - HEADROOM


def test_train_cell_step_fits_a_v5e_host(topo, tmp_path):
    """The train cell's step as ``Trainer`` builds it, on a (1 x 4) mesh of a
    described v5e:2x2: bytes per chip from ``memory_analysis()``."""
    from repro.launch.mesh import make_mesh
    from repro.optim.adamw import adamw_abstract_state
    from repro.runtime import Trainer, TrainerConfig

    cell = harness.load_cell("phi4mini.train_tp4")
    job = cell.traffic
    cfg = cell.model_config()
    mesh = make_mesh((1, job["model_parallel"]), ("data", "model"), devices=topo.devices)
    tcfg = TrainerConfig(
        checkpoint_every=0, seq_len=job["seq_len"], global_batch=job["global_batch"],
        lr=job["adamw"]["lr"], warmup=job["warmup"], num_steps=job["num_steps"],
    )
    trainer = Trainer(cfg, tcfg, str(tmp_path / "ckpt"), mesh=mesh)
    try:
        step, shardings = trainer._build_step()
        put = lambda tree, sh: jax.tree.map(  # noqa: E731
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, sh
        )
        params = trainer.model.abstract_params()
        state_p = put(params, shardings["params"])
        state_o = put(adamw_abstract_state(trainer.ocfg, params), shardings["opt"])
        batch = {
            k: jax.ShapeDtypeStruct((job["global_batch"], job["seq_len"]), jnp.int32)
            for k in ("tokens", "targets")
        }
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        compiled = step.lower(state_p, state_o, batch, scalar).compile()
    finally:
        trainer.close()
    mem = compiled.memory_analysis()
    per_chip = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    print(f"train step per chip: {per_chip} ({mem})")
    assert per_chip <= CHIP_BYTES - HEADROOM
    assert "all-reduce" in compiled.as_text()
