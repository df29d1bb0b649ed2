"""Serving launcher: continuous-batching engine + battery-aware policy.

    PYTHONPATH=src python -m repro.launch.serve --arch llava-onevision-0.5b \
        --requests 16 --battery 0.9

Submits synthetic prompts (+ stub vision features for vlm archs), runs the
engine to completion, prints the paper's metrics (tokens/s, end-to-end
latency, memory, modeled watts/hours).  Exits nonzero when any request
fails or goes unserved.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from repro.analysis.energy import EDGE_GPU, hours_on_battery, watts
from repro.configs import get_config, list_archs
from repro.core.power import BatteryAwareExecutor, PMU
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import init_params
from repro.serving.engine import Request, ServingEngine
from repro.telemetry.calibration import CostCalibration


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llava-onevision-0.5b",
                    choices=list_archs())
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=None,
                    help="per-slot KV length (default 512, or 2048 with "
                         "--full so a 729-patch image plus prompt fits)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--battery", type=float, default=1.0)
    ap.add_argument("--quantize", default=None,
                    choices=[None, "nanomind-default", "all-q4", "dec-q2"])
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--calibration", default=None, metavar="PATH",
                    help="persist wall-clock cost calibration across "
                         "restarts: load PATH if it exists, feed it to "
                         "the engine's energy governor, and atomically "
                         "re-save the measured table on shutdown")
    args = ap.parse_args(argv)
    if args.max_len is None:
        args.max_len = 2048 if args.full else 512
    enable_compile_cache()

    cfg = get_config(args.arch)
    if cfg.encdec:
        raise SystemExit("serve: decoder-only archs (enc-dec via examples/)")
    if not args.full:
        cfg = cfg.reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    if args.quantize:
        from repro.core.quantize import PROFILES, quantize_tree, \
            dequantize_tree
        params = dequantize_tree(quantize_tree(params,
                                               PROFILES[args.quantize]))

    executor = BatteryAwareExecutor(PMU())
    executor.pmu.level = args.battery
    calibration = None
    if args.calibration and os.path.exists(args.calibration):
        calibration = CostCalibration.load(args.calibration)
        print(f"[serve] loaded calibration from {args.calibration} "
              f"({len(calibration)} entries)")
    eng = ServingEngine(cfg, params, n_slots=args.slots,
                        max_len=args.max_len, executor=executor,
                        calibration=calibration)

    rng = np.random.default_rng(0)
    for i in range(args.requests):
        tokens = rng.integers(3, cfg.vocab_size - 1, int(rng.integers(8, 64)))
        if cfg.vlm:
            # one placeholder per image patch ahead of the text: the
            # projected patches replace their embeddings (model._embed)
            tokens = np.concatenate([np.zeros(cfg.vision_tokens), tokens])
        req = Request(rid=i, tokens=tokens.astype(np.int32),
                      max_new_tokens=args.max_new)
        if cfg.vlm:
            req.vision_feats = rng.standard_normal(
                (1, cfg.vision_tokens, cfg.vision_feat_dim)
            ).astype(np.float32) * 0.02
        eng.submit(req)

    t0 = time.time()
    done = eng.run()
    wall = time.time() - t0
    lat = [r.e2e_latency for r in done if r.e2e_latency]
    mem = eng.memory_bytes()
    state, knobs, objective = executor.current()
    print(f"[serve] {args.arch} battery={args.battery:.0%} state={state.value}"
          f" objective={objective}")
    print(f"  finished={len(done)}/{args.requests} wall={wall:.1f}s "
          f"throughput={eng.stats.decoded_tokens / wall:.1f} tok/s")
    if lat:
        print(f"  e2e latency: mean={np.mean(lat):.2f}s p95="
              f"{np.percentile(lat, 95):.2f}s")
    print(f"  memory: weights={mem['weights']/1e6:.1f}MB "
          f"kv={mem['kv_pool']/1e6:.1f}MB tabm={mem['tabm']/1e6:.2f}MB")
    if eng.tabm is not None:
        # every vision hand-off really went through the ring: writes ==
        # reads == served vlm requests, stalls = producer backpressure
        print(f"  tabm ring: {eng.tabm.stats}")
    if args.calibration:
        # fold this run's wall-clock probes on top of whatever table we
        # loaded, so the file converges across restarts (save is atomic:
        # tmp + os.replace)
        table = eng.measured_calibration()
        if calibration is not None:
            for key, s in table.to_dict()["table"].items():
                brick, _, prof = key.rpartition("@")
                calibration.observe(brick, prof or None, s["seconds"],
                                    s["tokens"], s["joules"], n=s["n"])
            table = calibration
        table.save(args.calibration)
        print(f"  calibration: saved {len(table)} entries to "
              f"{args.calibration}")
    failed = [r for r in done if r.error is not None]
    if failed or len(done) != args.requests:
        raise SystemExit(
            f"serve: {len(failed)} failed, {args.requests - len(done)} "
            f"unserved of {args.requests} requests"
            + (f"; first error: {failed[0].error}" if failed else ""))


if __name__ == "__main__":
    main()
