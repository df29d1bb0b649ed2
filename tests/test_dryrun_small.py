"""Dry-run machinery integration test on a small multi-device mesh.

Runs in a SUBPROCESS because --xla_force_host_platform_device_count must be
set before jax initializes (and the rest of the suite needs 1 device).
Exercises: sharding rules binding, lower+compile of train/prefill/decode on
a (2,4) mesh, roofline extraction — the same path the 512-device production
dry-run takes.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.configs import get_config, SHAPES
    from repro.configs.base import ShapeCell
    from repro.launch import dryrun as dr
    import repro.launch.dryrun  # noqa
    from repro.analysis import roofline as rl

    from repro.launch.mesh import make_mesh
    cfg = get_config("{arch}").reduced()
    mesh = make_mesh((2, 4), ("data", "model"))
    cell = ShapeCell("t", "{kind}", {seq}, {batch})
    with mesh:
        lowered, compiled = dr.lower_cell(cfg, cell, mesh)
    extra = {{}}
    roof = rl.build("{arch}", cell.name, "2x4", 8, compiled, cfg, cell,
                    extra=extra)
    rec = roof.to_dict()
    rec["n_collectives"] = sum(rec["collective_count"].values())
    print("RESULT " + json.dumps(rec))
""")


def _run(arch, kind, seq, batch):
    code = SCRIPT.format(arch=arch, kind=kind, seq=seq, batch=batch)
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600, env=env,
                          cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stdout
    return json.loads(line[0][len("RESULT "):])


@pytest.mark.slow
def test_train_cell_compiles_on_mesh():
    rec = _run("stablelm-1.6b", "train", 256, 8)
    assert rec["flops_per_device"] > 0
    assert rec["n_collectives"] > 0           # FSDP/TP really communicates
    assert rec["bottleneck"] in ("compute", "memory", "collective")
    assert 0 < rec["useful_flops_ratio"] < 20


@pytest.mark.slow
def test_decode_cell_compiles_on_mesh():
    rec = _run("stablelm-1.6b", "decode", 512, 8)
    assert rec["flops_per_device"] > 0
    assert rec["model_flops"] > 0


@pytest.mark.slow
def test_moe_cell_compiles_on_mesh():
    rec = _run("deepseek-moe-16b", "train", 256, 8)
    # EP dispatch must show up as all-to-all or gather traffic
    assert rec["flops_per_device"] > 0
    assert sum(rec["collective_count"].values()) > 0


@pytest.mark.slow
def test_hybrid_decode_on_mesh():
    rec = _run("jamba-1.5-large-398b", "decode", 512, 8)
    assert rec["flops_per_device"] > 0
