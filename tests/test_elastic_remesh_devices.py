"""Elastic re-mesh with REAL (placeholder) devices: train on a (2,4) mesh,
'lose a host', restore the topology-free checkpoint onto a (1,4) mesh and
keep training.  Runs in a subprocess (device count is locked at jax init).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import tempfile
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.data import multimodal_batch_iter
    from repro.distributed import checkpoint as ck
    from repro.distributed import sharding as sh
    from repro.distributed.fault_tolerance import plan_remesh
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import init_params
    from repro.training.optimizer import OptConfig, init_opt
    from repro.training.train_loop import build_accum_train_step

    cfg = get_config("stablelm-1.6b").reduced(n_layers=2)
    oc = OptConfig(lr=1e-3, warmup_steps=1)
    step_fn = jax.jit(build_accum_train_step(cfg, oc, 1),
                      donate_argnums=(0, 1))
    data = multimodal_batch_iter(cfg, global_batch=8, seq_len=64)

    # phase 1: 8 devices as (2 data, 4 model)
    mesh1 = make_mesh((2, 4), ("data", "model"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    pspecs = sh.tree_param_specs(mesh1, params)
    params = jax.device_put(params, sh.tree_shardings(mesh1, pspecs))
    opt = init_opt(params, oc)
    losses = []
    with mesh1:
        for _ in range(3):
            batch = jax.tree.map(jnp.asarray, next(data))
            params, opt, m = step_fn(params, opt, batch)
            losses.append(float(m["loss"]))
    d = tempfile.mkdtemp()
    ck.save(d, 3, {"params": params, "opt": opt})

    # phase 2: a host dies -> survivors host only 4 devices; the plan
    # preserves the model axis and shrinks DP
    plan = plan_remesh(alive_workers=[0], devices_per_worker=4, model_axis=4)
    assert plan.shape == (1, 4), plan.shape
    mesh2 = make_mesh(plan.shape, plan.axes)
    like = {"params": params, "opt": opt}
    shards = {"params": sh.tree_shardings(
                  mesh2, sh.tree_param_specs(mesh2, params)),
              "opt": sh.tree_shardings(
                  mesh2, sh.tree_param_specs(mesh2, opt))}
    state, step, _ = ck.restore(d, like, shardings=shards)
    params2, opt2 = state["params"], state["opt"]
    data.seek if hasattr(data, "seek") else None
    with mesh2:
        for _ in range(2):
            batch = jax.tree.map(jnp.asarray, next(data))
            params2, opt2, m = step_fn(params2, opt2, batch)
            losses.append(float(m["loss"]))
    assert all(l == l for l in losses)          # finite
    assert losses[-1] < losses[0] + 1.0         # no blow-up across re-mesh
    print("REMESH_OK", losses)
""")


@pytest.mark.slow
def test_elastic_remesh_across_topologies():
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT],
                          capture_output=True, text=True, timeout=600,
                          env=env,
                          cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "REMESH_OK" in proc.stdout
