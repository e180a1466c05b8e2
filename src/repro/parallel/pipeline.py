"""Pipeline parallelism driven by the paper's task-graph scheduler.

The schedule COMES FROM the paper's machinery (DESIGN.md §2): the
(microbatch × stage) forward grid is a dependency-counted task graph;
``repro.core.schedule.simulate`` executes it with the paper's policy and
emits the tick table ``stage s works on microbatch (t - s) at tick t``.
The executor embeds that static table in a ``shard_map`` + ``ppermute``
stepper over a mesh axis (``pod`` on the production mesh):

  * every rank holds one stage's parameters (in_spec P('pod') on the
    stacked stage dim);
  * a lax.scan over ticks applies the stage function when the table says
    so (masked when idle — the pipeline bubble is real compute idleness);
  * activations move stage→stage with ``ppermute`` at each tick boundary;
  * the loss is computed on the last stage and psum'd.

Backward runs through jax.grad: the transpose of ppermute is the reverse
permute, so the generated backward is the mirrored pipeline schedule. With
remat on the stage function the activation footprint per stage is the
1F1B-style bound validated against ``peak_activation_buffers`` in tests.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core.schedule import simulate


def forward_tick_table(num_stages: int, num_microbatches: int) -> np.ndarray:
    """mb_for[tick, stage] = microbatch index or -1, derived by simulating
    the paper's scheduler on the forward grid."""
    from repro.core.schedule import PipelineOp, SimTask

    S, M = num_stages, num_microbatches
    tasks = []
    fid = {}
    for m in range(M):
        for s in range(S):
            fid[(m, s)] = len(tasks)
            tasks.append(
                SimTask(
                    name=f"F{m}.{s}", worker=s, priority=-float(m),
                    payload=PipelineOp("F", m, s),
                )
            )
    for m in range(M):
        for s in range(1, S):
            tasks[fid[(m, s - 1)]].successors.append(fid[(m, s)])
            tasks[fid[(m, s)]].num_predecessors += 1
    res = simulate(tasks, num_stages, allow_steal=False)
    ticks = int(round(res.makespan))
    table = -np.ones((ticks, num_stages), np.int32)
    for w, tl in enumerate(res.timelines):
        for tid, s0, _s1 in tl:
            op = tasks[tid].payload
            table[int(round(s0)), w] = op.microbatch
    return table


def build_pipelined_loss(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
    mesh: Mesh,
    *,
    axis: str = "pod",
    num_microbatches: int,
    remat: bool = True,
):
    """Returns loss(params_stacked, x_mb, y_mb) -> scalar.

    params_stacked: pytree with leading stage dim (sharded P(axis));
    x_mb, y_mb: (M, mb, ...) microbatched inputs/targets, replicated.
    stage_fn(stage_params, x) -> x; loss_fn(x_final, y) -> scalar mean.
    """
    S = mesh.shape[axis]
    table = forward_tick_table(S, num_microbatches)  # static schedule
    ticks = table.shape[0]
    mb_of = jnp.asarray(table)  # (ticks, S)
    fwd = jax.checkpoint(stage_fn) if remat else stage_fn

    def body(params_local, x_mb, y_mb):
        # params_local: this stage's params (leading dim 1 squeezed)
        params_local = jax.tree.map(lambda l: l[0], params_local)
        stage = jax.lax.axis_index(axis)
        mb_shape = x_mb.shape[1:]

        def tick(carry, t):
            buf, acc = carry  # buf: activation entering this stage
            mb = mb_of[t, stage]
            active = mb >= 0
            # stage 0 reads its microbatch from the input queue
            x_in = jnp.where(
                (stage == 0) & active,
                x_mb[jnp.clip(mb, 0, num_microbatches - 1)],
                buf,
            )
            out = fwd(params_local, x_in)
            out = jnp.where(active, out, buf)
            # last stage: loss for the finished microbatch
            contrib = jnp.where(
                (stage == S - 1) & active,
                loss_fn(out, y_mb[jnp.clip(mb, 0, num_microbatches - 1)]),
                0.0,
            )
            # hand activations downstream (ring; last->0 edge is ignored
            # because stage 0 always reads fresh input)
            nxt = jax.lax.ppermute(out, axis, [(i, (i + 1) % S) for i in range(S)])
            return (nxt, acc + contrib), None

        # the carry becomes device-varying after the first ppermute; mark the
        # initial values as varying so the scan carry types are stable
        buf0 = jax.lax.pcast(jnp.zeros(mb_shape, x_mb.dtype), (axis,), to="varying")
        acc0 = jax.lax.pcast(jnp.zeros((), jnp.float32), (axis,), to="varying")
        (buf, acc), _ = jax.lax.scan(tick, (buf0, acc0), jnp.arange(ticks))
        # mean over microbatches, summed across stages (only last contributes);
        # the psum makes the loss identical on every rank
        return jax.lax.psum(acc, axis) / num_microbatches

    def loss(params_stacked, x_mb, y_mb):
        return jax.shard_map(
            body, mesh=mesh, in_specs=(P(axis), P(), P()), out_specs=P()
        )(params_stacked, x_mb, y_mb)

    return loss, table
