"""Train driver: the repo's sharded train step, as ``Trainer`` builds it, on
the host's mesh.

Set-up builds one ``Trainer`` for the job (no checkpoints), takes its jitted
mesh step, makes the state under the step's shardings from the seed (the
benchmark's weights in the program's layout, the program's AdamW state), and
drives that one step object from the seed through its first steps, with the
``Prefetcher`` feeding it rows that all differ. Those steps compile the step
and give the readings that decide ``correct``: each step's loss, the norm of
each leaf of the first clipped gradient (from AdamW's first moment after one
step), and the norm of each leaf's change after the check steps. The window
then drives the same object on, for ``--seconds``, syncing with the host as
``Trainer.run`` does (on its log steps), and blocks on the last step.

After the window the state is freed and the plain float32 reference takes
the same steps on the same batches, spread over the host's chips.
"""
from __future__ import annotations

import gc
import sys
import tempfile
import time
from types import SimpleNamespace

import numpy as np

import harness
from peaks import peaks_for

# the traced part of a --trace 1 window, from its middle: about ten steps.
# Four chips' ops over ten seconds made a trace that took longer to write and
# read than a run may last.
TRACE_SPAN_S = 2.0

LEAVES = {  # the program's parameter path -> the reference's leaf
    ("embed",): "embed",
    ("final_norm", "w"): "final_norm",
    ("layers", "s0", "attn_norm", "w"): "attn_norm",
    ("layers", "s0", "mlp_norm", "w"): "mlp_norm",
    **{("layers", "s0", "attn", k): k for k in ("wq", "wk", "wv", "wo")},
    **{("layers", "s0", "mlp", k): k for k in ("w_gate", "w_up", "w_down")},
}


class Tokens:
    """Uniform token rows from the seed, a pure function of the step."""

    def __init__(self, vocab: int, seq_len: int, batch: int, seed: int) -> None:
        self.vocab, self.seq_len, self.rows, self.seed = vocab, seq_len, batch, seed

    def batch_at(self, step: int) -> dict:
        rng = np.random.default_rng((self.seed, 0x7472, step))
        x = rng.integers(0, self.vocab, (self.rows, self.seq_len + 1), dtype=np.int64)
        x = x.astype(np.int32)
        return {"tokens": x[:, :-1], "targets": x[:, 1:]}

    batch = batch_at  # the Prefetcher's source protocol: batch(step)


def leaf_norms(tree) -> dict:
    """Per-leaf float32 norms of a parameter-shaped tree, by reference leaf name."""
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.linalg.norm(x.astype(jnp.float32)) for x in xs])(
        [x for _, x in flat]
    )
    return {LEAVES[tuple(k.key for k in path)]: float(n) for (path, _), n in zip(flat, norms)}


def worst_leaf(prog: dict, ref: dict, floor_frac: float = 1e-3) -> tuple[float, str]:
    """The largest gap between the program's and the reference's norm of a
    leaf, over the larger of that leaf's reference norm and the median
    leaf's. Leaves whose reference norm is under ``floor_frac`` of the
    median's move by rounding alone and are left out."""
    med = float(np.median(list(ref.values())))
    worst, name = 0.0, ""
    for k, r in ref.items():
        if r < floor_frac * med:
            continue
        gap = abs(prog[k] - r) / max(r, med)
        if gap >= worst:
            worst, name = gap, k
    return worst, name


def build(cell):
    """The trainer, its jitted mesh step and the state made from the seed."""
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.optim import adamw_init
    from repro.runtime import Trainer, TrainerConfig

    job = cell.traffic
    cfg = cell.model_config()
    tcfg = TrainerConfig(
        num_steps=job["num_steps"], checkpoint_every=0, log_every=job["log_every"],
        seq_len=job["seq_len"], global_batch=job["global_batch"], lr=job["adamw"]["lr"],
        warmup=job["warmup"], seed=cell.seed,
    )
    data = Tokens(cfg.vocab_size, job["seq_len"], job["global_batch"], cell.seed)
    # no checkpoint is written (checkpoint_every=0); the manager still wants a directory
    trainer = Trainer(cfg, tcfg, cell.scratch, mesh=make_host_mesh(model=job["model_parallel"]),
                      data_source=data)
    for k, v in job["adamw"].items():
        if getattr(trainer.ocfg, k) != v:
            raise ValueError(f"the trainer's AdamW has {k}={getattr(trainer.ocfg, k)}, the job {v}")
    step_fn, shardings = trainer._build_step()
    params = cell.reference().program_params(
        cell.seed, cell.dims(), cfg.kv_pad_to, expect=trainer.model.abstract_params(),
        shardings=shardings["params"],
    )
    opt = jax.jit(lambda p: adamw_init(trainer.ocfg, p), out_shardings=shardings["opt"])(params)
    return trainer, step_fn, shardings, params, opt, data


def first_steps(cell, trainer, step_fn, shardings, params, opt, prefetch):
    """The check steps, through the one step object: returns the state after
    them and the program's readings (losses, first clipped gradient's leaf
    norms, each leaf's change)."""
    import jax
    import jax.numpy as jnp

    b1 = trainer.ocfg.b1
    losses = []
    for step in range(int(cell.traffic["check"]["steps"])):
        params, opt, m = step_fn(params, opt, prefetch.get(), jnp.asarray(step))
        losses.append(float(m["loss"]))
        if step == 0:
            grads = {k: v / (1 - b1) for k, v in leaf_norms(opt["m"]).items()}
    p0 = cell.reference().program_params(
        cell.seed, cell.dims(), trainer.model_cfg.kv_pad_to, shardings=shardings["params"]
    )
    change = leaf_norms(jax.tree.map(lambda a, b: a - b.astype(a.dtype), opt["master"], p0))
    return params, opt, {"losses": losses, "grad_norms": grads, "change_norms": change}


def reference(cell, data, **variant) -> dict:
    """The float32 reference over the check steps' batches; ``variant``:
    ``quant`` / ``keep`` of ``train_reference``, or ``half=True`` for the
    first half of each batch alone."""
    half = variant.pop("half", False)
    batches = [data.batch_at(s) for s in range(int(cell.traffic["check"]["steps"]))]
    if half:
        batches = [{k: v[: len(v) // 2] for k, v in b.items()} for b in batches]
    return cell.reference().train_reference(
        cell.seed, cell.dims(), cell.published, batches, cell.traffic["adamw"], **variant
    )


def gaps(prog: dict, ref: dict) -> dict:
    """The three numbers compared, each with where it was worst: the loss
    (relative, by step), the first gradient and the change (by leaf)."""
    loss = [abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])]
    return {
        "loss_rel_gap": (max(loss), f"step {int(np.argmax(loss))}"),
        "grad_norm_gap": worst_leaf(prog["grad_norms"], ref["grad_norms"]),
        "change_norm_gap": worst_leaf(prog["change_norms"], ref["change_norms"]),
    }


def run(cell):
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        cell.scratch = tmp
        return _run(cell)


def _run(cell):
    import jax
    import jax.numpy as jnp

    from repro.data import Prefetcher

    job = cell.traffic
    trainer, step_fn, shardings, params, opt, data = build(cell)
    prefetch = Prefetcher(data, pool=trainer.pool, depth=trainer.tcfg.prefetch_depth)
    try:
        params, opt, readings = first_steps(
            cell, trainer, step_fn, shardings, params, opt, prefetch
        )
        setup_s = time.perf_counter() - cell.t_start

        # -- the window: the same step object, on from the check steps; with
        # --trace 1 the profiler records TRACE_SPAN_S from its middle
        ta = max(0.0, cell.seconds / 2 - TRACE_SPAN_S / 2)
        tb = min(cell.seconds, ta + TRACE_SPAN_S)
        profiler = "waiting" if cell.trace else "off"
        t0 = time.perf_counter()
        step = len(readings["losses"])
        steps = 0
        while (now := time.perf_counter() - t0) < cell.seconds:
            if profiler == "waiting" and now >= ta:
                jax.profiler.start_trace(f"{cell.scratch}/xplane")
                profiler = "on"
            elif profiler == "on" and now >= tb:
                jax.profiler.stop_trace()
                profiler = "done"
            params, opt, m = step_fn(params, opt, prefetch.get(), jnp.asarray(step))
            step, steps = step + 1, steps + 1
            if step % trainer.tcfg.log_every == 0:
                float(m["loss"])  # Trainer.run's host sync on its log steps
        jax.block_until_ready((params, opt, m))
        t1 = time.perf_counter()
        if profiler == "on":
            jax.profiler.stop_trace()
        mem = harness.memory_peak_bytes()
    finally:
        prefetch.close()
        trainer.close()
    del params, opt, m
    gc.collect()

    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_kw: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None
    )
    t_ref = time.perf_counter()
    ref = reference(cell, data)
    ref_s = time.perf_counter() - t_ref
    got = gaps(readings, ref)
    checks = [(k, v, float(cell.config["check"][k])) for k, (v, _where) in got.items()]
    print(
        f"info: losses={readings['losses']} ref_losses={ref['losses']} "
        f"worst={ {k: w for k, (_v, w) in got.items()} } steps_in_window={steps} "
        f"reference_s={ref_s:.3f} reference_compile_s={sum(compile_s):.3f}",
        file=sys.stderr, flush=True,
    )
    view = SimpleNamespace(
        setup_s=setup_s, seconds=t1 - t0, steps=steps, dims=cell.dims(), chips=cell.chips,
        tokens_per_step=job["seq_len"] * job["global_batch"], seq_len=job["seq_len"],
        trace=None,
    )
    breakdown = None
    device = dict(cell.device, memory_peak_bytes=mem)
    if cell.trace:
        view.trace, breakdown = _reduce(f"{cell.scratch}/xplane")
        view.peaks = peaks_for(cell.device["kind"])
        device.update(busy_s=view.trace["busy_s"], window_s=view.trace["window_s"])
    metrics = harness.read_metrics(
        cell.per_layer if cell.trace else cell.end_to_end, view, required=not cell.trace
    )
    result = {
        "correct": all(v <= limit for _, v, limit in checks),
        "attempted": len(readings["losses"]) + steps, "failed": 0, "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    return result, checks


def _reduce(tdir):
    """Busy time averaged over the chips, chip 0's collective time and the
    breakdown, over the whole train steps of the trace on chip 0: the trace
    starts and stops inside a step, and records those two cut short."""
    import trace_reduce

    tr = trace_reduce.load(trace_reduce.find_xplane(tdir))
    if not tr.devices:
        raise RuntimeError("the trace holds no TPU device plane")
    dev0 = tr.devices[min(tr.devices)]
    steps = [p for p in dev0.programs if p.name == "jit_step_fn"][1:-1]
    if not steps:
        raise RuntimeError("the trace holds no whole train step")
    lo, hi = steps[0].start, steps[-1].end
    busy = [trace_reduce.busy_ns(d, lo, hi) for d in tr.devices.values()]
    coll, exposed = trace_reduce.collective_ns(dev0, lo, hi)
    merged = trace_reduce.merge(((o.start, o.end) for o in dev0.ops), lo, hi)
    idle = sorted(trace_reduce.gaps(merged, lo, hi), key=lambda g: g[0] - g[1])[:10]
    return {
        "busy_s": float(np.mean(busy)) / 1e9, "window_s": (hi - lo) / 1e9,
        "busy0_s": trace_reduce.total(merged) / 1e9, "collective_s": coll / 1e9,
        "exposed_collective_s": exposed / 1e9,
        "step_device_s": sum(p.dur for p in steps) / 1e9, "steps": len(steps),
    }, {
        "device_ops": trace_reduce.top_ops(dev0, lo, hi),
        "idle_gaps": [["between steps (host)", (e - s) / 1e9] for s, e in idle],
    }
