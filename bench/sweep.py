#!/usr/bin/env python3
"""Find the highest open-loop rate an open-loop cell sustains, on the chip.

    python3 bench/sweep.py --workload llava-chat-mixedres --seed 1 \
        --seconds 20 --rates 4 6 8 12 16

One process sets up and warms the cell once, then runs one window per
rate (the cell's traffic file with ``rate_rps`` replaced), draining the
engine between windows.  Per rate it prints the time to first token, the
gap between tokens, how many requests were still queued at the window's
end, and how the mean time to first token of the window's second half
compares with its first half: a backlog that grows shows as a ratio well
above 1.  The cell's traffic file records the rate chosen from this.
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
from bench.record import pct  # noqa: E402
from bench.traffic import generators as gen  # noqa: E402


def sweep(cell, seed: int, seconds: float, rates) -> list:
    import jax
    h = run.Harness(cell, seed, seconds)
    jax.monitoring.register_event_duration_secs_listener(h.on_compile)
    h.warm_up()
    buckets = h.cfg.vision_token_buckets or (h.cfg.vision_tokens,)
    out = []
    for k, rate in enumerate(rates):
        traffic = dict(cell.traffic, rate_rps=float(rate))
        h.plan = gen.generate(traffic, buckets, h.cfg.vocab_size, seed + k,
                              seconds)
        h.feats = {}
        for i in range(len(h.plan.requests)):
            h.features(i)
        h.tracked, h.active, h.steps, h.spans, h.lateness = [], [], [], [], []
        compiles = h.compiles
        rec = h.window()
        ttft = rec.ttft_s()
        due = rec.due_in_window()
        half = rec.w0 + 0.5 * rec.window_s
        first = [t for r, t in zip(due, ttft) if r.due < half]
        second = [t for r, t in zip(due, ttft) if r.due >= half]
        row = {
            "rate_rps": rate, "requests": len(due),
            "ttft_p50_ms": 1e3 * (pct(ttft, 50) or 0),
            "ttft_p95_ms": 1e3 * (pct(ttft, 95) or 0),
            "itl_p95_ms": 1e3 * (pct(rec.itl_s(), 95) or 0),
            "queued_at_end": len(h.eng.queue),
            "live_at_end": len(h.eng.live),
            "ttft_growth": (sum(second) / max(1, len(second)))
            / max(1e-9, sum(first) / max(1, len(first))),
            "compiles_in_window": h.compiles - compiles,
        }
        print(json.dumps(row), flush=True)
        out.append(row)
        h._drain()
    h.eng.shutdown()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = registry.find_cell(args.workload)
    if cell.traffic["kind"] != "open_poisson":
        print("bench.sweep: only an open-loop cell has a rate",
              file=sys.stderr)
        return 2
    try:
        run._import_program()
        run.use_compile_cache()
        run.check_device(cell)
        sweep(cell, args.seed, args.seconds, args.rates)
    except run.BenchError as e:
        print(f"bench.sweep: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
