"""Backend lowering matrix — the no-TPU gate for core/backends.

Lowers one reduced vlm BrickGraph through each requested backend
(HostBackend, DeviceBackend, and — given >= 2 placeholder devices — the
SubmeshBackend over a real submesh split), runs one forward per lowering,
and cross-checks the logits agree.  Wired into scripts/check.sh so no
backend path can rot without TPU hardware.

    PYTHONPATH=src python -m repro.launch.dryrun_backends \
        --arch llava-onevision-0.5b --backends host,device,submesh
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# pinned to the CPU's placeholder devices: never takes a TPU
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ both must run before any jax import — jax locks the platform and the
# device count at init

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, list_archs
from repro.core.bricks import decompose
from repro.core.plan import compile_plan
from repro.core.scheduler import make_virtual_accelerators
from repro.launch.mesh import make_mesh
from repro.launch.steps import init_params


def lower_and_run(cfg, graph, params, inputs, name: str):
    """Compile the graph under one backend lowering; return its logits."""
    if name == "submesh":
        mesh = make_mesh((1, jax.device_count()), ("data", "model"))
        accels = make_virtual_accelerators(mesh, fractions=(0.25, 0.75))
        enc, dec = accels
        assignment = {b.name: (enc.name if b.static_shape else dec.name)
                      for b in graph.bricks}
        plan = compile_plan(graph, params, placement=assignment,
                            accels=accels)
    else:
        plan = compile_plan(graph, params, backend=name)
    got = {s.backend.name for s in plan.steps}
    assert got == {name if name != "submesh" else "submesh"}, got
    out, _ = plan.run(inputs)
    print(f"  {name:8s} OK  logits{tuple(out.shape)}  "
          f"[{plan.describe()[:72]}...]")
    return np.asarray(out, np.float32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llava-onevision-0.5b",
                    choices=list_archs())
    ap.add_argument("--backends", default="host,device",
                    help="comma list of host|device|submesh")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch).reduced()
    if not cfg.vlm:
        raise SystemExit("dryrun_backends exercises the vlm chain "
                         "(vision -> projector -> decoder)")
    params = init_params(jax.random.PRNGKey(0), cfg)
    graph = decompose(cfg)
    rng = np.random.default_rng(0)
    inputs = {
        "tokens": jnp.asarray(rng.integers(3, 200, (1, 24)), jnp.int32),
        "vision_feats": jnp.asarray(
            rng.standard_normal(
                (1, cfg.vision_tokens, cfg.vision_feat_dim)) * 0.02,
            jnp.float32)}

    names = [b.strip() for b in args.backends.split(",") if b.strip()]
    if "submesh" in names and jax.device_count() < 2:
        print("  submesh  SKIP (needs >= 2 devices)")
        names.remove("submesh")
    print(f"backend matrix for {args.arch} on "
          f"{jax.device_count()} {jax.default_backend()} device(s): {names}")
    outs = {n: lower_and_run(cfg, graph, params, inputs, n) for n in names}

    ref_name, ref = next(iter(outs.items()))
    for n, out in outs.items():
        np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2,
                                   err_msg=f"{n} vs {ref_name}")
    print(f"OK: {len(outs)} backend lowerings agree ({', '.join(outs)})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
