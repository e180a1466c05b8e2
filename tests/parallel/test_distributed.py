"""Multi-device correctness tests.

These run in SUBPROCESSES with ``--xla_force_host_platform_device_count=8``
so the main pytest process keeps its single-device jax (the dry-run is the
only place that touches 512 devices, per the assignment).
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_devices(code: str, n: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-4000:]}"
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """A (2 data x 4 model) sharded train step must match the single-device
    step numerically (same loss, same updated params)."""
    run_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.optim import AdamWConfig, adamw_init, adamw_update, cosine_schedule
    from repro.launch.mesh import make_host_mesh
    from repro.parallel.steps import build_train_step

    cfg = get_reduced("tinyllama-1.1b").replace(dtype="float32", remat="none")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 4, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1)}
    ocfg = AdamWConfig(lr=1e-3)
    opt = adamw_init(ocfg, params)
    lr_fn = cosine_schedule(1e-3, 10, 100)

    # single-device reference
    def ref_step(params, opt, batch, step):
        (loss, m), g = jax.value_and_grad(lambda p: model.loss(p, batch), has_aux=True)(params)
        p2, o2, _ = adamw_update(ocfg, lr_fn(step), params, g, opt)
        return p2, o2, loss
    rp, ro, rloss = jax.jit(ref_step)(params, opt, batch, jnp.asarray(0))

    mesh = make_host_mesh(model=4)
    spec = {"seq_len": S, "global_batch": B, "kind": "train"}
    step, shardings, abstract = build_train_step(
        model, mesh, ocfg, lr_fn, model.input_specs("train_4k", spec), donate=False)
    sp, so, metrics = step(params, opt, batch, jnp.asarray(0))
    np.testing.assert_allclose(float(metrics["loss"]), float(rloss), rtol=2e-5)
    flat_r = jax.tree.leaves(rp)
    flat_s = jax.tree.leaves(sp)
    for a, b in zip(flat_r, flat_s):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=2e-5, rtol=2e-4)
    print("TRAIN-STEP-MATCH-OK")
    """)


def test_moe_ep_matches_dense_oracle():
    """Expert-parallel shard_map MoE == dense-oracle MoE (fwd AND grads)."""
    run_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import ModelConfig
    from repro.models.common import Alloc
    from repro.models.moe import moe_params, moe_dense, moe_ep
    from repro.launch.mesh import make_host_mesh
    from repro.parallel.ctx import ParallelCtx

    cfg = ModelConfig(name="m", family="moe", num_layers=1, d_model=32, num_heads=2,
                      num_kv_heads=2, d_ff=0, vocab_size=64, num_experts=8,
                      experts_per_token=2, moe_d_ff=16, num_shared_experts=1,
                      capacity_factor=8.0,  # no drops -> exact equality
                      dtype="float32")
    a = Alloc("init", jax.random.PRNGKey(0), dtype=jnp.float32)
    p = moe_params(cfg, a)
    mesh = make_host_mesh(model=4)
    ctx = ParallelCtx(mesh, batch_axes=("data",))
    B, S, d = 4, 8, 32
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d), jnp.float32)

    def f_dense(p, x):
        y, aux = moe_dense(cfg, p, x)
        return jnp.sum(y * y) + aux
    def f_ep(p, x):
        y, aux = moe_ep(cfg, p, x, ctx)
        return jnp.sum(y * y) + aux

    yd, gd = jax.value_and_grad(f_dense)(p, x)
    ye, ge = jax.value_and_grad(f_ep)(p, x)
    np.testing.assert_allclose(float(yd), float(ye), rtol=1e-5)
    for ad, ae in zip(jax.tree.leaves(gd), jax.tree.leaves(ge)):
        np.testing.assert_allclose(np.asarray(ad), np.asarray(ae), atol=1e-4, rtol=1e-3)
    print("MOE-EP-MATCH-OK")
    """)


def test_elastic_checkpoint_restore_across_meshes():
    """Save params sharded on a (4,2) mesh, restore onto (2,4) and (8,1)."""
    run_devices("""
    import tempfile, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import CheckpointManager
    from repro.launch.mesh import make_host_mesh

    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            "b": jnp.ones((8,), jnp.bfloat16)}
    with tempfile.TemporaryDirectory() as d:
        mesh1 = make_host_mesh(model=2)
        t1 = jax.device_put(tree, {"w": NamedSharding(mesh1, P("data", "model")),
                                   "b": NamedSharding(mesh1, P("data"))})
        with CheckpointManager(d, keep=2) as cm:
            cm.save_async(5, t1, meta={"step": 5})
            cm.wait()
            mesh2 = make_host_mesh(model=4)
            shard2 = {"w": NamedSharding(mesh2, P("model", "data")), "b": None}
            restored, meta = cm.restore(tree, shardings=shard2)
            assert meta["step"] == 5
            np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))
            np.testing.assert_array_equal(
                np.asarray(restored["b"], np.float32), np.ones(8, np.float32))
    print("ELASTIC-OK")
    """)


def test_pipeline_parallel_matches_serial():
    """Task-graph-scheduled pipeline (4 stages over 'pod') == serial model."""
    run_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.parallel.pipeline import build_pipelined_loss, forward_tick_table

    S, M, W = 4, 8, 16  # stages, microbatches, width
    mesh = make_mesh((4,), ("pod",), devices=jax.devices()[:4])
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (S, W, W)) * 0.3,
              "b": jnp.zeros((S, W))}

    def stage_fn(p, x):  # residual MLP stage
        return x + jnp.tanh(x @ p["w"] + p["b"])

    def loss_fn(x, y):
        return jnp.mean((x - y) ** 2)

    x_mb = jax.random.normal(jax.random.PRNGKey(1), (M, 4, W))
    y_mb = jax.random.normal(jax.random.PRNGKey(2), (M, 4, W))

    # serial reference
    def serial_loss(params, x_mb, y_mb):
        def apply_all(x):
            for s in range(S):
                x = stage_fn(jax.tree.map(lambda l: l[s], params), x)
            return x
        losses = jax.vmap(lambda x, y: loss_fn(apply_all(x), y))(x_mb, y_mb)
        return jnp.mean(losses)

    ref, ref_grad = jax.value_and_grad(serial_loss)(params, x_mb, y_mb)

    pipe_loss, table = build_pipelined_loss(
        stage_fn, loss_fn, mesh, axis="pod", num_microbatches=M)
    got, got_grad = jax.value_and_grad(pipe_loss)(params, x_mb, y_mb)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(ref_grad), jax.tree.leaves(got_grad)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-3)
    # schedule sanity: the table came from the paper's scheduler
    assert table.shape[1] == S and (table >= -1).all()
    print("PIPELINE-OK")
    """)


def test_decode_step_sharded_matches_single_device():
    run_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.models.lm import extend_caches
    from repro.launch.mesh import make_host_mesh
    from repro.parallel.steps import build_decode_step

    cfg = get_reduced("granite-moe-1b-a400m").replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 4, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    logits0, caches = jax.jit(model.prefill)(params, {"tokens": tokens})
    caches = extend_caches(caches, 4)
    tok = jnp.zeros((B, 1), jnp.int32)
    ref_logits, _ = jax.jit(model.decode_step)(params, tok, caches, jnp.asarray(S))

    mesh = make_host_mesh(model=4)
    abstract = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                "caches": jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), caches),
                "index": jax.ShapeDtypeStruct((), jnp.int32)}
    step, shardings = build_decode_step(model, mesh, abstract)
    got_logits, _ = step(params, tok, caches, jnp.asarray(S))
    np.testing.assert_allclose(
        np.asarray(got_logits, np.float32), np.asarray(ref_logits, np.float32),
        atol=2e-4, rtol=2e-3)
    print("DECODE-MATCH-OK")
    """)
