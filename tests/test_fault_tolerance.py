"""Fault tolerance: heartbeats, re-mesh planning, stragglers, and the
end-to-end kill/restore/continue path."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as hst

from repro.configs import get_config
from repro.data import multimodal_batch_iter
from repro.distributed import checkpoint as ck
from repro.distributed.fault_tolerance import (HeartbeatMonitor, RemeshPlan,
                                               StragglerMitigator,
                                               plan_remesh)
from repro.launch.mesh import make_mesh
from repro.training.optimizer import OptConfig
from repro.training.train_loop import TrainConfig, fit


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_heartbeat_detects_dead_worker():
    clock = FakeClock()
    mon = HeartbeatMonitor(n_workers=4, timeout_s=10, clock=clock)
    clock.t = 5.0
    for w in (0, 1, 3):
        mon.beat(w)
    clock.t = 12.0
    assert mon.dead_workers() == [2]
    mon.evict(2)
    assert mon.alive() == [0, 1, 3]
    assert mon.dead_workers() == []


@given(n_fail=hst.integers(0, 20))
def test_remesh_preserves_model_axis(n_fail):
    alive = list(range(32 - n_fail))           # 32 workers x 16 devices
    if len(alive) * 16 < 16:
        return
    plan = plan_remesh(alive, devices_per_worker=16, model_axis=16)
    assert plan.shape[-1] == 16                # TP degree preserved
    assert plan.n_devices <= len(alive) * 16
    assert plan.n_devices % 16 == 0
    assert set(plan.dropped).isdisjoint(plan.workers)


def test_remesh_multipod_when_divisible():
    plan = plan_remesh(list(range(32)), 16, model_axis=16, pod_axis=2)
    assert plan.axes == ("pod", "data", "model")
    assert plan.shape == (2, 16, 16)


def test_straggler_detection():
    sm = StragglerMitigator(n_workers=4, min_samples=4, multiplier=2.0)
    for _ in range(8):
        for w in range(3):
            sm.record(w, 1.0)
        sm.record(3, 5.0)                      # persistent straggler
    assert sm.stragglers() == [3]
    assert sm.step_deadline() == pytest.approx(2.0, rel=0.5)


def test_kill_restore_continue_elastic():
    """Train, 'lose' the job, restore onto a different (null) topology via
    the topology-free checkpoint + deterministic data seek."""
    cfg = get_config("stablelm-1.6b").reduced(n_layers=2)
    with tempfile.TemporaryDirectory() as d:
        it = multimodal_batch_iter(cfg, global_batch=4, seq_len=64)
        fit(cfg, OptConfig(lr=1e-3),
            TrainConfig(steps=6, ckpt_dir=d, ckpt_every=3, log_every=100),
            it)
        assert ck.latest_step(d) == 6
        # "failure": fresh process state; re-mesh = (new) data iter + restore
        it2 = multimodal_batch_iter(cfg, global_batch=4, seq_len=64)
        res = fit(cfg, OptConfig(lr=1e-3),
                  TrainConfig(steps=9, ckpt_dir=d, ckpt_every=3,
                              log_every=100), it2)
        steps = [m["step"] for m in res.metrics_history]
        assert steps == [7, 8, 9]
        assert all(np.isfinite(m["loss"]) for m in res.metrics_history)


def test_restore_with_resharding(key):
    """restore() binds new shardings — the reshard-on-load contract."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh((1,), ("data",))
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    with tempfile.TemporaryDirectory() as d:
        ck.save(d, 1, tree)
        shard = {"w": NamedSharding(mesh, P("data"))}
        got, step, _ = ck.restore(d, tree, shardings=shard)
        assert got["w"].sharding == shard["w"]
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.asarray(tree["w"]))
