import os
# a dry-run compiles for placeholder host devices only: pinned to the CPU
# so that on a machine with a TPU it never takes the chip
os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512"
                               ).strip()
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The lines above MUST run before any jax import — jax locks the device
count at first init.  512 placeholder host devices back both the 16x16
single-pod mesh and the 2x16x16 multi-pod mesh; unrelated pre-set
XLA_FLAGS are preserved, and a pre-set device count wins so ``--reduced``
CI runs can use 8 devices — see scripts/check.sh.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-67b \
        --shape train_4k [--multipod] [--out experiments/dryrun]

Succeeding here proves the distribution config is coherent: the sharding
rules satisfy the partitioner for every cell, and memory_analysis() shows it
fits.  cost_analysis() + the HLO collective parse feed §Roofline.
"""
import argparse
import json
import sys
import time
import traceback

import jax

from repro.analysis import roofline as rl
from repro.configs import SHAPES, cell_applicable, get_config, list_archs
from repro.distributed import sharding as sh
from repro.launch import steps as st
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.training.optimizer import OptConfig

from jax.sharding import NamedSharding, PartitionSpec as P


def opt_config_for(cfg) -> OptConfig:
    """bf16 adam moments for the >=100B archs (fits jamba-398B on one pod —
    DESIGN.md §6); fp32 otherwise."""
    big = cfg.n_params() > 60e9
    return OptConfig(state_dtype="bfloat16" if big else "float32")


def lower_cell(cfg, cell, mesh, *, verbose=True, quant=None):
    """Returns (lowered, compiled, aux) for one cell on one mesh.

    quant: quantization profile name (e.g. "nanomind-default") — the
    paper's W4A16 serving path: packed-int weights lower as model inputs
    and dequantize in-register inside the layer scan."""
    batch_sds = st.input_specs(cfg, cell)
    bspecs = sh.tree_batch_specs(mesh, batch_sds)
    batch_in = sh.with_specs(batch_sds, bspecs, mesh)

    params_sds = st.abstract_params(cfg, quant_policy=quant)
    pspecs = sh.tree_param_specs(mesh, params_sds)
    params_in = sh.with_specs(params_sds, pspecs, mesh)
    pshard = sh.tree_shardings(mesh, pspecs)

    if cell.kind == "train":
        opt_cfg = opt_config_for(cfg)
        opt_sds = st.abstract_opt(cfg, opt_cfg, params_sds)
        ospecs = sh.tree_param_specs(mesh, opt_sds)
        opt_in = sh.with_specs(opt_sds, ospecs, mesh)
        oshard = sh.tree_shardings(mesh, ospecs)
        fn = st.build_train_step(cfg, opt_cfg)
        jitted = jax.jit(fn, donate_argnums=(0, 1),
                         out_shardings=(pshard, oshard, None))
        lowered = jitted.lower(params_in, opt_in, batch_in)
    elif cell.kind == "prefill":
        cache_sds = st.abstract_cache(cfg, cell.global_batch, cell.seq_len)
        cspecs = sh.tree_cache_specs(mesh, cache_sds)
        cshard = sh.tree_shardings(mesh, cspecs)
        logits_shard = NamedSharding(
            mesh, sh.batch_spec(mesh, "logits",
                                (cell.global_batch, cfg.padded_vocab)))
        fn = st.build_prefill_step(cfg, cell.seq_len)
        jitted = jax.jit(fn, out_shardings=(logits_shard, cshard))
        lowered = jitted.lower(params_in, batch_in)
    else:  # decode / serve
        cache_sds = st.abstract_cache(cfg, cell.global_batch, cell.seq_len)
        cspecs = sh.tree_cache_specs(mesh, cache_sds)
        cache_in = sh.with_specs(cache_sds, cspecs, mesh)
        cshard = sh.tree_shardings(mesh, cspecs)
        logits_shard = NamedSharding(
            mesh, sh.batch_spec(mesh, "logits",
                                (cell.global_batch, cfg.padded_vocab)))
        fn = st.build_serve_step(cfg)
        jitted = jax.jit(fn, donate_argnums=(2,),
                         out_shardings=(logits_shard, cshard))
        lowered = jitted.lower(params_in, batch_in["tokens"], cache_in)
    compiled = lowered.compile()
    return lowered, compiled


def mem_per_device(compiled, n_devices):
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None, None
    if ma is None:
        return None, None
    fields = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, f, None)
        if v is not None:
            fields[f] = int(v)
    total = (fields.get("argument_size_in_bytes", 0)
             + fields.get("temp_size_in_bytes", 0)
             + fields.get("output_size_in_bytes", 0)
             - fields.get("alias_size_in_bytes", 0))
    return total, fields


def pick_mode(cfg, cell, requested: str = "auto") -> str:
    """Sharding mode per cell (see distributed/sharding.py).

    auto: "serve" for decode cells when the model-parallel-only weights fit
    (<12 GB/dev) — replicating over "data" kills the per-token FSDP
    regather; "tp" otherwise (the paper-faithful baseline layout)."""
    if requested != "auto":
        return requested
    if cell.kind == "decode" and cfg.n_params() * 2 / 16 < 12e9:
        return "serve"
    return "tp"


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir=None,
             verbose=True, mode: str = "tp", overrides=None, quant=None,
             reduced=False):
    import dataclasses
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    cell = SHAPES[shape]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    if reduced:
        # CI-sized cell: same lower+compile+roofline path, 8 host devices
        cfg = cfg.reduced()
        cell = dataclasses.replace(cell, name=cell.name + "-reduced",
                                   seq_len=min(cell.seq_len, 256),
                                   global_batch=min(cell.global_batch, 8))
        mesh_name = "2x4"
    ok, why = cell_applicable(cfg, cell)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "mode": mode,
           "quant": quant}
    sh.set_mode(mode)
    if not ok:
        rec.update(status="skipped", reason=why)
        if verbose:
            print(f"[{arch} x {shape} x {mesh_name}] SKIP: {why}")
        return rec

    if reduced:
        mesh = make_mesh((2, 4), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    t0 = time.time()
    try:
        with mesh:
            lowered, compiled = lower_cell(cfg, cell, mesh, verbose=verbose,
                                           quant=quant)
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[{arch} x {shape} x {mesh_name}] FAIL {type(e).__name__}: {e}")
        return rec
    t_compile = time.time() - t0

    mem_total, mem_fields = mem_per_device(compiled, n_dev)
    extra = {}
    roof = rl.build(arch, shape, mesh_name, n_dev, compiled, cfg, cell,
                    mem_per_device=mem_total, extra=extra)
    rec.update(status="ok", compile_s=round(t_compile, 1),
               memory_fields=mem_fields, **roof.to_dict(), **extra)
    if out_dir:
        # cache the partitioned HLO so analysis iterations skip recompiles
        import gzip
        hlo_dir = os.path.join(os.path.dirname(out_dir.rstrip("/")), "hlo")
        os.makedirs(hlo_dir, exist_ok=True)
        with gzip.open(os.path.join(
                hlo_dir, f"{arch}__{shape}__{mesh_name}.hlo.gz"), "wt") as f:
            f.write(compiled.as_text())
    if verbose:
        print(f"compile={t_compile:.0f}s mem/dev="
              f"{(mem_total or 0)/1e9:.2f}GB " + roof.summary())
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fn = os.path.join(out_dir, f"{arch}__{shape}__{mesh_name}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--mode", default="tp",
                    choices=["tp", "fsdp", "serve", "auto"])
    ap.add_argument("--override", default=None,
                    help="comma-separated cfg overrides, e.g. n_heads=32")
    ap.add_argument("--quant", default=None,
                    help="quant profile for serving cells, e.g. "
                         "nanomind-default (the paper's W4A16)")
    ap.add_argument("--print-hlo", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="CI mode: reduced config + shrunken cell on a 2x4 "
                         "mesh (set XLA_FLAGS device_count=8 in the env)")
    args = ap.parse_args(argv)
    mode = pick_mode(get_config(args.arch), SHAPES[args.shape], args.mode)
    overrides = {}
    if args.override:
        import dataclasses as _dc
        for kv in args.override.split(","):
            k, v = kv.split("=")
            overrides[k] = type(getattr(get_config(args.arch), k))(v) \
                if not isinstance(getattr(get_config(args.arch), k), bool) \
                else v.lower() == "true"
    rec = run_cell(args.arch, args.shape, args.multipod, args.out, mode=mode,
                   overrides=overrides, quant=args.quant,
                   reduced=args.reduced)
    if rec.get("status") == "error":
        print(rec.get("traceback", ""), file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
