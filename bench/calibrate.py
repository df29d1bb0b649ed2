#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/calibrate.py --workload llava-chat-mixedres \
        --seconds 10 --seeds 101 102 103

For each seed, in one process: one whole run of the cell (set-up,
warm-up, a window of ``--seconds`` at the cell's own load), then the
widest logit gap of the served tokens against the float32 reference (the
program's reading, the number ``run.py`` compares) and the same gap of
the control: the reference itself with its weights in a lower precision
(``int8`` and ``fp8`` e4m3, one scale per output channel) put in the
program's place, read at the same positions of the same prompts and
tokens.  The limit in ``bench/checks/<cell>.json`` lies between the
program's largest reading and the control's smallest (see PERF.md).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import registry, run  # noqa: E402

MODES = ("f32", "int8", "fp8")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = registry.find_cell(args.workload)
    try:
        run._import_program()
        run.use_compile_cache()
        devices, peaks = run.check_device(cell)
        t = T_START
        for seed in args.seeds:
            res = run.run_cell(cell, seed, args.seconds, False, peaks,
                               devices, modes=MODES, t_start=t)
            w = res["window"]
            print(json.dumps({
                "workload": args.workload, "seed": seed,
                "correct": res["correct"],
                "checked_tokens": w["checked_tokens"],
                **{f"max_gap_{m}": max(w[f"gap_{m}"]) if w.get(f"gap_{m}")
                   else None for m in MODES},
                **{f"gap_{m}": w.get(f"gap_{m}") for m in MODES},
                "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                "compiles_in_window": w["compiles_in_window"],
                "memory_peak_bytes": res["device"]["memory_peak_bytes"],
            }), flush=True)
            t = time.monotonic()
    except run.BenchError as e:
        print(f"bench.calibrate: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
