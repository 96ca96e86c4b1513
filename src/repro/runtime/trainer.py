"""Fault-tolerant distributed training runtime.

Production behaviours implemented (and unit-tested on CPU):
  * jitted train_step with NamedSharding in/out + donated state (params and
    optimizer moments update in place — no per-step copies)
  * checkpoint/restart: atomic async checkpoints every `ckpt_every`;
    `run()` auto-resumes from the latest complete checkpoint, and any
    exception inside the step loop triggers restore-and-continue with
    bounded retries (node-failure recovery path)
  * elastic re-mesh: on (re)start the data mesh is rebuilt from the devices
    actually present; checkpoints are loaded with the *new* sharding, so a
    job restarted with a different pod slice resumes seamlessly
  * straggler detection: per-step wall-time EWMA + deviation; slow steps
    are logged with a z-score (the hook a real cluster uses to trigger
    hot-spare swaps)
  * deterministic data: the loader is keyed by (seed, host, step) — resume
    replays the exact batch stream
  * microbatch gradient accumulation (remat-ed scan) for global batches
    larger than device memory allows.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import manager as ckpt
from repro.configs.base import ArchConfig
from repro.data.pipeline import DataConfig, HostDataLoader
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.optim import optimizers as opt
from repro.parallel import sharding

log = logging.getLogger("repro.trainer")


@dataclass
class TrainerConfig:
    steps: int = 100
    seq_len: int = 128
    global_batch: int = 8
    microbatches: int = 1
    peak_lr: float = 3e-4
    warmup_steps: int = 10
    schedule: str = "cosine"            # cosine | wsd
    adamw: opt.AdamWConfig = field(default_factory=opt.AdamWConfig)
    accum_dtype: str = "float32"        # bf16 for the ~0.5T archs
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    ckpt_async: bool = True
    log_every: int = 10
    seed: int = 0
    max_restarts: int = 3
    straggler_ewma: float = 0.9
    straggler_zscore: float = 3.0


def make_data_mesh() -> Mesh:
    """Elastic 1-D data mesh over whatever devices are currently present."""
    return make_mesh((jax.device_count(), 1), ("data", "model"))


def make_schedule(tc: TrainerConfig) -> Callable:
    if tc.schedule == "wsd":
        stable = max(1, int(0.8 * tc.steps) - tc.warmup_steps)
        decay = max(1, tc.steps - tc.warmup_steps - stable)
        return opt.wsd_schedule(tc.peak_lr, tc.warmup_steps, stable, decay)
    return opt.cosine_schedule(tc.peak_lr, tc.warmup_steps, tc.steps)


def init_state(key, cfg: ArchConfig, tc: TrainerConfig):
    params = lm.init_lm(key, cfg)
    return {"params": params,
            "opt": opt.init_adamw(params, tc.adamw),
            "step": jnp.zeros((), jnp.int32)}


def build_train_step(cfg: ArchConfig, tc: TrainerConfig,
                     dp_axes: tuple = ("data",)):
    schedule = make_schedule(tc)
    dp = dp_axes if tc.global_batch % tc.microbatches == 0 else None

    def loss(params, batch):
        return lm.loss_fn(params, cfg, batch, dp_axes=dp)

    def train_step(state, batch):
        if tc.microbatches > 1:
            def resplit(x):
                x = x.reshape((tc.microbatches,
                               x.shape[0] // tc.microbatches) + x.shape[1:])
                # keep the *sequence* batch dim sharded on DP — without this
                # GSPMD moves the sharding to the microbatch (scan) axis and
                # every device materializes the full microbatch
                spec = P(None, dp_axes, *([None] * (x.ndim - 2)))
                return jax.lax.with_sharding_constraint(x, spec)
            mb = jax.tree.map(resplit, batch)

            adt = jnp.dtype(tc.accum_dtype)

            def acc(carry, b):
                (l, m), g = jax.value_and_grad(loss, has_aux=True)(
                    state["params"], b)
                carry = jax.tree.map(
                    lambda c, u: (c + u.astype(c.dtype)), carry, (l, g))
                return carry, m

            zero = (jnp.float32(0.0),
                    jax.tree.map(lambda p: jnp.zeros(p.shape, adt),
                                 state["params"]))
            (lsum, gsum), ms = jax.lax.scan(acc, zero, mb)
            l = lsum / tc.microbatches
            grads = jax.tree.map(lambda g: g / tc.microbatches, gsum)
            metrics = jax.tree.map(lambda x: jnp.mean(x), ms)
        else:
            (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(
                state["params"], batch)
        lr = schedule(state["step"])
        new_params, new_opt, gnorm = opt.adamw_update(
            grads, state["opt"], state["params"], lr, tc.adamw)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = dict(metrics, loss=l, lr=lr, grad_norm=gnorm)
        return new_state, metrics

    return train_step


class Trainer:
    def __init__(self, cfg: ArchConfig, tc: TrainerConfig,
                 mesh: Optional[Mesh] = None):
        self.cfg, self.tc = cfg, tc
        self.mesh = mesh or make_data_mesh()
        self.loader = HostDataLoader(DataConfig(
            vocab=cfg.vocab, seq_len=tc.seq_len,
            global_batch=tc.global_batch, seed=tc.seed))
        self.ckpt = (ckpt.CheckpointManager(tc.ckpt_dir)
                     if tc.ckpt_dir else None)
        self._compiled = None
        self.state = None
        self.step_times: list[float] = []
        self._ewma = None
        self._ewvar = 0.0
        self.restarts = 0

    # ------------------------------------------------------------------
    def _shardings(self, state):
        fsdp = sharding.needs_fsdp(self.cfg, self.mesh)
        pspecs = sharding.params_specs(
            self.cfg, jax.eval_shape(lambda s: s["params"], state), fsdp,
            self.mesh)
        state_specs = {"params": pspecs,
                       "opt": {"mu": opt_moment_specs(
                           jax.eval_shape(lambda s: s["opt"]["mu"], state),
                           pspecs),
                           "count": P()},
                       "step": P()}
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            state_specs,
                            is_leaf=lambda x: isinstance(x, P))

    def _batch_sharding(self, batch):
        specs = sharding.batch_specs(self.mesh, batch)
        return {k: NamedSharding(self.mesh, s) for k, s in specs.items()}

    def compile(self):
        key = jax.random.PRNGKey(self.tc.seed)
        with jax.default_device(jax.devices()[0]):
            state = init_state(key, self.cfg, self.tc)
        st_sh = self._shardings(state)
        self.state = jax.device_put(state, st_sh)
        step_fn = build_train_step(self.cfg, self.tc)
        _, b0 = self.loader.next()
        self.loader._cursor = 0
        b_sh = self._batch_sharding(b0)
        self._compiled = jax.jit(step_fn, in_shardings=(st_sh, b_sh),
                                 out_shardings=(st_sh, None),
                                 donate_argnums=(0,))
        self._batch_shardings = b_sh
        return self

    # ------------------------------------------------------------------
    def _record_step_time(self, dt: float, step: int):
        self.step_times.append(dt)
        if self._ewma is None:
            self._ewma = dt
            return
        a = self.tc.straggler_ewma
        dev = dt - self._ewma
        self._ewvar = a * self._ewvar + (1 - a) * dev * dev
        self._ewma = a * self._ewma + (1 - a) * dt
        z = dev / max(np.sqrt(self._ewvar), 1e-9)
        if z > self.tc.straggler_zscore and len(self.step_times) > 5:
            log.warning("straggler suspected at step %d: %.3fs (z=%.1f, "
                        "ewma %.3fs) — flagged for hot-spare rotation",
                        step, dt, z, self._ewma)

    def _maybe_restore(self):
        if self.ckpt is None:
            return 0
        restored, step = self.ckpt.restore_latest(
            jax.tree.map(np.asarray, self.state))
        if restored is None:
            return 0
        sh = self._shardings(restored)
        self.state = jax.device_put(restored, sh)
        log.info("restored checkpoint at step %s (mesh %s)", step,
                 dict(self.mesh.shape))
        return int(step)

    def run(self, fail_at: Optional[int] = None):
        """Train to tc.steps with restore-on-failure. `fail_at` injects a
        fault once (for tests / chaos drills)."""
        if self._compiled is None:
            self.compile()
        start = self._maybe_restore()
        step = start
        injected = False
        history = []
        while step < self.tc.steps:
            try:
                _, batch = self.loader._cursor, self.loader.batch_at(step)
                batch = jax.device_put(batch, self._batch_shardings)
                if fail_at is not None and step == fail_at and not injected:
                    injected = True
                    raise RuntimeError("injected node failure")
                t0 = time.perf_counter()
                with self.mesh:
                    self.state, metrics = self._compiled(self.state, batch)
                metrics["loss"].block_until_ready()
                self._record_step_time(time.perf_counter() - t0, step)
                step += 1
                if step % self.tc.log_every == 0 or step == self.tc.steps:
                    history.append((step, float(metrics["loss"])))
                    log.info("step %d loss %.4f lr %.2e", step,
                             float(metrics["loss"]),
                             float(metrics["lr"]))
                if self.ckpt and step % self.tc.ckpt_every == 0:
                    self.ckpt.save(self.state, step,
                                   blocking=not self.tc.ckpt_async)
            except Exception as e:  # noqa: BLE001 — node-failure recovery
                self.restarts += 1
                if self.restarts > self.tc.max_restarts:
                    raise
                log.warning("step %d failed (%s); restoring from latest "
                            "checkpoint (restart %d/%d)", step, e,
                            self.restarts, self.tc.max_restarts)
                restored = self._maybe_restore()
                step = restored
        if self.ckpt:
            self.ckpt.save(self.state, step, blocking=True)
        return history


def pspecs_for_opt(p: P) -> P:
    return p


def opt_moment_specs(mu_shape, pspecs):
    """Moments follow their parameter's spec; factored moments drop the
    reduced axis; error-feedback buffers follow the parameter."""
    def per_param(spec, st):
        out = {}
        for k, v in st.items():
            if k in ("m", "v", "ef"):
                out[k] = spec
            else:                        # v_row / v_col: one axis reduced
                out[k] = P(*list(spec)[: len(v.shape)])
        return out

    return jax.tree.map(per_param, pspecs, mu_shape,
                        is_leaf=lambda x: isinstance(x, P))
