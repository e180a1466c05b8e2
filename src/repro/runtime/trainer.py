"""Fault-tolerant training driver.

Composition of every substrate in the framework:
  * jitted train_step from parallel/steps.py (sharded params/opt/batch)
  * ThreadPool-prefetched data pipeline (repro.data)
  * async atomic checkpoints + resume (repro.checkpoint)
  * watchdog heartbeat + failure injection for fault-tolerance tests
  * elastic restore: a checkpoint from any mesh restores onto this mesh

Designed for the multi-controller pattern at scale: every host runs this
driver; the data source shards by host id; checkpoint writes are per-host
shards (here: single-host writes everything). The restart loop — crash,
re-exec, restore-latest, continue — is exactly what a 1000-node job does on
preemption; ``run_with_restarts`` simulates it in-process for tests.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.core import ThreadPool
from repro.data import Prefetcher, SyntheticTokens
from repro.models import build_model
from repro.optim import AdamWConfig, adamw_init, cosine_schedule
from repro.parallel.steps import build_train_step


@dataclass
class TrainerConfig:
    num_steps: int = 100
    checkpoint_every: int = 20  # 0: never save (a job with nothing to resume)
    log_every: int = 10
    seq_len: int = 128
    global_batch: int = 8
    lr: float = 3e-4
    warmup: int = 10
    keep_checkpoints: int = 3
    prefetch_depth: int = 2
    seed: int = 0
    # fault injection: raise at this step (once) to test restart/resume
    fail_at_step: Optional[int] = None
    heartbeat_timeout_s: float = 300.0


class Trainer:
    def __init__(
        self,
        model_cfg,
        tcfg: TrainerConfig,
        ckpt_dir: str,
        *,
        mesh=None,
        data_source=None,
    ) -> None:
        self.model_cfg = model_cfg
        self.tcfg = tcfg
        self.model = build_model(model_cfg)
        self.mesh = mesh
        self.pool = ThreadPool(4, name="trainer")
        self.ckpt = CheckpointManager(ckpt_dir, pool=self.pool, keep=tcfg.keep_checkpoints)
        self.ocfg = AdamWConfig(lr=tcfg.lr)
        self.lr_fn = cosine_schedule(tcfg.lr, tcfg.warmup, tcfg.num_steps)
        self.data = data_source or SyntheticTokens(
            model_cfg.vocab_size, tcfg.seq_len, tcfg.global_batch, seed=tcfg.seed
        )
        self._failed_once = False
        self.metrics_log: list[dict] = []
        self._heartbeat = time.monotonic()

    # -- state --------------------------------------------------------------------

    def init_state(self, shardings=None) -> dict:
        """Fresh params and AdamW state.

        ``shardings`` (a ``NamedSharding`` tree shaped like the state) builds
        every leaf in place on the mesh, so no single device ever holds the
        whole state — TinyLlama-1.1B's params plus AdamW state outgrow one
        16 GB chip.
        """

        def init(key):
            params = self.model.init(key)
            return {
                "params": params,
                "opt": adamw_init(self.ocfg, params),
                "step": jnp.zeros((), jnp.int32),
            }

        key = jax.random.PRNGKey(self.tcfg.seed)
        if shardings is None:
            return init(key)
        return jax.jit(init, out_shardings=shardings)(key)

    def _build_step(self):
        """Returns (jitted step, state shardings or None without a mesh)."""
        if self.mesh is not None:
            spec = {
                "seq_len": self.tcfg.seq_len,
                "global_batch": self.tcfg.global_batch,
                "kind": "train",
            }
            batch_abstract = self.model.input_specs("train", spec)
            step, specs, _ = build_train_step(
                self.model, self.mesh, self.ocfg, self.lr_fn, batch_abstract, donate=False
            )
            shardings = jax.tree.map(
                lambda sp: NamedSharding(self.mesh, sp),
                {"params": specs["params"], "opt": specs["opt"], "step": P()},
                is_leaf=lambda x: isinstance(x, P),
            )
            return step, shardings

        def step_fn(params, opt_state, batch, step):
            from repro.optim import adamw_update

            (loss, metrics), grads = jax.value_and_grad(
                lambda p: self.model.loss(p, batch), has_aux=True
            )(params)
            lr = self.lr_fn(step)
            new_params, new_opt, om = adamw_update(self.ocfg, lr, params, grads, opt_state)
            return new_params, new_opt, {"loss": loss, **metrics, **om}

        return jax.jit(step_fn), None

    # -- run -----------------------------------------------------------------------

    def run(self, *, resume: bool = True) -> dict:
        step_fn, shardings = self._build_step()
        state = self.init_state(shardings)
        start_step = 0
        if resume and self.ckpt.latest_step() is not None:
            state, meta = self.ckpt.restore(state, shardings=shardings)
            start_step = int(meta["step"])
        prefetch = Prefetcher(
            self.data, pool=self.pool, depth=self.tcfg.prefetch_depth, start_step=start_step
        )
        params, opt = state["params"], state["opt"]
        every = self.tcfg.checkpoint_every
        try:
            for step in range(start_step, self.tcfg.num_steps):
                self._check_heartbeat()
                if (
                    self.tcfg.fail_at_step is not None
                    and step == self.tcfg.fail_at_step
                    and not self._failed_once
                ):
                    self._failed_once = True
                    raise RuntimeError(f"injected failure at step {step}")
                batch = prefetch.get()
                params, opt, metrics = step_fn(params, opt, batch, jnp.asarray(step))
                self._heartbeat = time.monotonic()
                if step % self.tcfg.log_every == 0 or step == self.tcfg.num_steps - 1:
                    row = {k: float(v) for k, v in metrics.items()}
                    row["step"] = step
                    self.metrics_log.append(row)
                if every and (step + 1) % every == 0:
                    self.ckpt.save_async(
                        step + 1,
                        {"params": params, "opt": opt, "step": jnp.asarray(step + 1)},
                        meta={"step": step + 1, "cursor": prefetch.cursor},
                    )
            # final checkpoint (skip if the loop just saved this step)
            if every and self.tcfg.num_steps % every != 0:
                self.ckpt.save_async(
                    self.tcfg.num_steps,
                    {"params": params, "opt": opt, "step": jnp.asarray(self.tcfg.num_steps)},
                    meta={"step": self.tcfg.num_steps, "cursor": prefetch.cursor},
                )
            self.ckpt.wait()
            return {"params": params, "opt": opt, "metrics": self.metrics_log}
        finally:
            prefetch.close()

    def run_with_restarts(self, max_restarts: int = 3) -> dict:
        """The 1000-node preemption loop, in-process: crash -> restore ->
        continue. Used by the fault-tolerance tests and examples."""
        attempts = 0
        while True:
            try:
                return self.run(resume=True)
            except RuntimeError as e:
                attempts += 1
                if attempts > max_restarts:
                    raise
                self.ckpt.wait()
                print(f"[trainer] restart {attempts} after: {e}", flush=True)

    # -- watchdog ---------------------------------------------------------------------

    def _check_heartbeat(self) -> None:
        if time.monotonic() - self._heartbeat > self.tcfg.heartbeat_timeout_s:
            raise TimeoutError("watchdog: no step completed within heartbeat window")

    def close(self) -> None:
        try:
            self.ckpt.wait(60)
        finally:
            self.pool.close()

    def __enter__(self) -> "Trainer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
