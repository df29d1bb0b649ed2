import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
"""Disaggregated two-fleet serving: prefill fleet -> Transport -> decode
fleet.

The fleet-scale topology ("Cost-Efficient Multimodal LLM Inference via
Cross-Tier GPU Heterogeneity", PAPERS.md): a
:class:`~repro.serving.disagg.PrefillWorker` stages vision encode ->
projector -> grouped batched prefill on a compute-rich fleet and streams
each request — committed TABM slab + the *written* KV blocks + block
grant, never a whole ``max_len`` lane — over a serialized
:class:`~repro.core.transport.Transport` to a
:class:`~repro.serving.disagg.DecodeWorker` that admits straight into
its own paged pool and cohort-decodes.  Both fleets are ordinary
``ServingEngine`` instances on per-ordinal device backends
(``device:0`` / ``device:1`` — ``core/backends.device_backend``), so a
multi-device box is the degenerate single-host case; the scheduler's
split pricing (``core/scheduler.schedule_split``) is printed for the
chosen transport.

Every run asserts the acceptance bar:

* greedy decode tokens are **bit-identical** to a fresh single-process
  ``ServingEngine`` oracle, per request, across >= 2 slot classes;
* the paged KV bytes that crossed the wire are **less** than shipping
  whole ``max_len`` lanes (``PagedKVCache.slot_lane_bytes``).

    PYTHONPATH=src python -m repro.launch.serve_disagg \
        --transport {inproc,pipe,socket} --requests 4

``--transport pipe`` / ``socket`` spawn the decode fleet as a real
subprocess (``--role decode`` plus fd / port plumbing below) that
re-initializes identical params from the same seed — nothing but frames
crosses the boundary.  A TPU chip belongs to one process, and both
fleets need a device, so on a TPU these two refuse to start: use
``--transport inproc`` there (both fleets in one process, on the device
ordinals ``core/scheduler.fleet_accelerators`` picks from the visible
device count).
"""
import argparse
import subprocess
import sys
import time

import jax
import numpy as np

from repro.configs import get_config, list_archs
from repro.core.bricks import decompose
from repro.core.scheduler import (fleet_accelerators, populate_brick_bytes,
                                  schedule_split)
from repro.core.transport import PipeTransport, SocketTransport
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import init_params
from repro.serving.disagg import DecodeWorker, PrefillWorker, \
    serve_disagg_inproc
from repro.serving.engine import Request, ServingEngine

ENGINE_KW = dict(n_slots=4, max_len=256, block_size=32)


def make_requests(cfg, n: int, max_new: int):
    """>= 2 slot classes: thumbnails (1 image) interleaved with 4-image
    full-resolution requests, varying prompt lengths."""
    reqs = []
    for i in range(n):
        rng = np.random.default_rng(i)
        hi = i % 2 == 1
        plen = 6 + (i % 3)
        reqs.append(Request(
            rid=i, tokens=(np.arange(plen) % 50 + 3).astype(np.int32),
            n_images=4 if hi else 1,
            max_new_tokens=max_new + (i % 2),
            vision_feats=rng.standard_normal(
                (1, 32 if hi else 8, cfg.vision_feat_dim)
            ).astype(np.float32) * 0.02))
    return reqs


def oracle_tokens(cfg, params, reqs):
    """The single-process baseline: same engine geometry, no wire."""
    with ServingEngine(cfg, params, **ENGINE_KW) as eng:
        for r in reqs:
            eng.submit(r)
        done = eng.run()
    assert all(r.error is None for r in done), \
        [(r.rid, r.error) for r in done]
    return {r.rid: list(r.out_tokens) for r in done}


def run_decode_fleet(args):
    """The decode-fleet subprocess (``--role decode``): identical params
    re-initialized from the shared seed; only frames cross the wire."""
    cfg = get_config(args.arch).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    if args.transport == "pipe":
        tr = PipeTransport(args.recv_fd, args.send_fd)
    elif args.transport == "socket":
        tr = SocketTransport.connect("127.0.0.1", args.port)
    else:
        raise SystemExit("--role decode needs --transport pipe|socket")
    worker = DecodeWorker(cfg, params, tr, **ENGINE_KW)
    results = worker.run()
    ok = sum(1 for r in results.values() if r.error is None)
    print(f"[decode-fleet] served {ok}/{len(results)} requests, "
          f"{worker.engine.stats.decoded_tokens} decode tokens")
    tr.close()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llava-onevision-0.5b",
                    choices=list_archs())
    ap.add_argument("--transport", default="inproc",
                    choices=["inproc", "pipe", "socket"])
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=4)
    # decode-fleet subprocess plumbing (not for direct use)
    ap.add_argument("--role", default="prefill",
                    choices=["prefill", "decode"], help=argparse.SUPPRESS)
    ap.add_argument("--recv-fd", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--send-fd", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--port", type=int, default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.transport != "inproc" and jax.default_backend() == "tpu":
        raise SystemExit(
            f"serve_disagg: --transport {args.transport} runs the decode "
            "fleet in a second process, and a TPU chip belongs to one "
            "process; use --transport inproc on TPU")

    if args.role == "decode":
        run_decode_fleet(args)
        return

    if args.requests < 3:
        raise SystemExit("--requests must be >= 3 (the smoke's floor)")
    cfg = get_config(args.arch).reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)

    # the scheduler's split pricing for this wire: the same chain DP as
    # any placement, over the two fleet rows priced at the transport's
    # link_bw — a fast wire pulls the static bricks onto the prefill
    # fleet, a slow one keeps them co-located
    graph = decompose(cfg)
    populate_brick_bytes(graph, params)
    split = schedule_split(graph, args.transport,
                           n_tokens=cfg.vision_tokens)
    print(f"[schedule_split @ {args.transport}] {split}")

    reqs = make_requests(cfg, args.requests, args.max_new)
    oracle = oracle_tokens(cfg, params, make_requests(
        cfg, args.requests, args.max_new))

    t0 = time.time()
    child = None
    if args.transport == "inproc":
        # degenerate single-host case: each fleet's engine on its OWN
        # device ordinal when there are two (device:0 / device:1), both
        # on device:0 when one device is visible
        pre_acc, dec_acc = fleet_accelerators(
            args.transport, n_devices=jax.device_count())
        results, stats = serve_disagg_inproc(
            cfg, params, reqs,
            prefill_kwargs=dict(backend=pre_acc.backend, **ENGINE_KW),
            decode_kwargs=dict(backend=dec_acc.backend, **ENGINE_KW))
    else:
        base_cmd = [sys.executable, "-m", "repro.launch.serve_disagg",
                    "--role", "decode", "--transport", args.transport,
                    "--arch", args.arch]
        if args.transport == "pipe":
            a2b_r, a2b_w = os.pipe()
            b2a_r, b2a_w = os.pipe()
            child = subprocess.Popen(
                base_cmd + ["--recv-fd", str(a2b_r),
                            "--send-fd", str(b2a_w)],
                pass_fds=(a2b_r, b2a_w))
            os.close(a2b_r)
            os.close(b2a_w)
            tr = PipeTransport(b2a_r, a2b_w)
        else:
            srv, port = SocketTransport.listen()
            child = subprocess.Popen(base_cmd + ["--port", str(port)])
            tr = SocketTransport.accept(srv, timeout=120.0)
            srv.close()
        pre = PrefillWorker(cfg, params, tr, **ENGINE_KW)
        for r in reqs:
            pre.submit(r)
        stats = pre.run()
        results = pre.collect(len(reqs))
        stats.wire_seconds = tr.send_seconds
        stats.transport = tr.name
        pre.engine.shutdown()
        tr.close()
    wall = time.time() - t0
    if child is not None:
        assert child.wait(timeout=300) == 0, "decode fleet exited nonzero"

    # acceptance: bit-identical greedy tokens, across >= 2 slot classes
    classes = {r.slot_class for r in reqs}
    assert len(classes) >= 2, f"need >= 2 slot classes, got {classes}"
    for r in reqs:
        got = results.get(r.rid)
        assert got is not None and got.error is None, \
            f"request {r.rid} failed: {got and got.error}"
        assert got.tokens == oracle[r.rid], (
            f"request {r.rid} tokens diverged over {args.transport}: "
            f"{got.tokens} != oracle {oracle[r.rid]}")
    # acceptance: only granted/written blocks crossed, never whole lanes
    lane_total = stats.sent * stats.lane_bytes_baseline
    assert stats.kv_wire_bytes < lane_total, (
        f"wire shipped {stats.kv_wire_bytes}B of KV, whole lanes would "
        f"be {lane_total}B — paged export is not saving bytes")
    print(f"[prefill-fleet] {stats.sent} prefills shipped, "
          f"{stats.wire_bytes}B on the wire "
          f"({stats.kv_wire_bytes}B paged KV vs {lane_total}B whole-lane "
          f"baseline), {len(classes)} slot classes, {wall:.1f}s")
    # feedback edge: reprice the split from what the frames actually
    # clocked (measured bytes/s over the static transport class row)
    if stats.wire_seconds > 0 and stats.wire_bytes > 0:
        from repro.telemetry.calibration import CostCalibration
        cal = CostCalibration()
        cal.observe_link(stats.transport, stats.wire_bytes,
                         stats.wire_seconds, n=max(1, stats.sent))
        mbw = stats.wire_bytes / stats.wire_seconds
        split2 = schedule_split(graph, args.transport,
                                n_tokens=cfg.vision_tokens,
                                calibration=cal)
        print(f"[schedule_split recalibrated @ {mbw / 1e6:.0f} MB/s "
              f"measured] {split2}")
    print(f"OK: disaggregated prefill/decode fleets over "
          f"{args.transport}: {len(reqs)} requests bit-identical to the "
          f"single-process oracle")


if __name__ == "__main__":
    main()
