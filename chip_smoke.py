#!/usr/bin/env python3
"""Bring-up smoke of the served path on a TPU.

    python chip_smoke.py [--seed 0]          # one chip
    python chip_smoke.py --four-chips        # the two paths that span chips

Default (one chip): llava-onevision-0.5b at its published widths (24
layers, d_model 896, vocab 151,936), random weights from ``--seed`` and
stub vision features at full width (1152-d), served through
``ServingEngine`` as ``launch/serve.py`` builds it:

1. four requests, two thumbnails (196 patch tokens) and two
   full-resolution images (729), so both TABM slot classes, staging,
   grouped prefill and the cohort decode step all run; every request must
   finish without error, with every token inside the vocab, and the cohort
   step must have resolved to the fused Pallas path, compiled (not
   interpret mode);
2. one cohort decode step on the served KV pool, fused against composed
   (``kernels/fused_decode.cohort_step``) and against a float32 reference
   (the composed step on float32 weights and pool, matmuls at "highest"
   precision).  Both bf16 paths round, at different points, so their
   logits differ by about as much as each differs from the reference
   (over 24 layers, a few percent of the largest logit).  The stated
   tolerance: the fused step may be no further from the reference than
   ``REF_MARGIN`` times the composed step's own distance to it.  A step
   that computed in a lower precision, or wrongly, would fail it.

``--four-chips`` runs only the two paths that exist across devices, each
against its single-device oracle at the same widths: bricks placed on
separate submeshes (``core/scheduler.make_virtual_accelerators``) and the
two-fleet in-process disaggregation (prefill on ``device:0``, decode on
``device:1``).  A decoder on a submesh of several devices takes the
composed cohort step (Mosaic kernels are not partitioned), so its oracle
is the single-device engine on that same step.  Greedy tokens must be
equal.  Where a submesh run's tokens differ (its sharded reductions sum
in another order than one device does, and with random weights the top
logit can flip on rounding), its first-step logits must pass the same
test against a float32 forward pass (``models/model.lm_forward``) as the
fused step does above, with the single-device plan as the baseline.

Timings printed here are host wall-clock smoke readings, not measurements.
The last line of standard output is one JSON object naming the device;
without a TPU the script exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ARCH = "llava-onevision-0.5b"
# the fused / submesh path may be this much further from the float32
# reference than the single-device composed path is
REF_MARGIN = 1.5
ENGINE_KW = dict(n_slots=4, max_len=2048, block_size=64)


def _import_repro():
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit("chip_smoke: src/repro is not next to this script")
    sys.path.insert(0, src)


def make_requests(cfg, seed: int, n_thumb: int = 2, n_full: int = 2,
                  max_new: int = 16, rid0: int = 0):
    """Thumbnail and full-resolution single-image requests with stub
    vision features at the config's width.  A prompt holds one
    placeholder token per image patch (their embeddings are replaced by
    the projected patches, models/model._embed), then the text."""
    import numpy as np
    from repro.serving.engine import Request
    thumb, full = cfg.vision_token_buckets[0], cfg.vision_token_buckets[-1]
    rng = np.random.default_rng(seed)
    reqs = []
    for i, n_tok in enumerate([thumb] * n_thumb + [full] * n_full):
        text = rng.integers(3, cfg.vocab_size - 1, int(rng.integers(8, 40)))
        reqs.append(Request(
            rid=rid0 + i,
            tokens=np.concatenate([np.zeros(n_tok), text]).astype(np.int32),
            n_images=1, max_new_tokens=max_new,
            vision_feats=(rng.standard_normal(
                (1, n_tok, cfg.vision_feat_dim)) * 0.02).astype(np.float32)))
    return reqs


def check(ok: bool, what: str) -> None:
    """Fail the smoke (exit 1, no result line) unless ``ok``."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def check_served(cfg, reqs, done):
    """Every request finished, without error, with in-vocab tokens."""
    by_rid = {r.rid: r for r in done}
    check(sorted(by_rid) == sorted(r.rid for r in reqs),
          f"served {sorted(by_rid)}, submitted {[r.rid for r in reqs]}")
    for r in reqs:
        got = by_rid[r.rid]
        check(got.error is None, f"request {r.rid} failed: {got.error!r}")
        check(len(got.out_tokens) == r.max_new_tokens,
              f"request {r.rid}: {len(got.out_tokens)} tokens, want "
              f"{r.max_new_tokens}")
        check(all(0 <= t < cfg.vocab_size for t in got.out_tokens),
              f"request {r.rid}: token outside the vocab {got.out_tokens}")


def serve_phase(cfg, params, seed: int):
    """Serve the request mix twice on one engine: the first pass compiles,
    the second gives the smoke tokens/s reading.  Returns the engine's
    resolved cohort path, the pool and the readings."""
    from repro.serving.engine import ServingEngine
    out = {}
    with ServingEngine(cfg, params, **ENGINE_KW) as eng:
        for label, rid0 in (("cold", 0), ("warm", 100)):
            reqs = make_requests(cfg, seed + rid0, rid0=rid0)
            before, tok0 = len(eng.done), eng.stats.decoded_tokens
            t0 = time.perf_counter()
            for r in reqs:
                eng.submit(r)
            done = eng.run()[before:]
            out[label + "_s"] = time.perf_counter() - t0
            out[label + "_tokens"] = eng.stats.decoded_tokens - tok0
            check_served(cfg, reqs, done)
            out["classes"] = sorted({r.slot_class for r in done})
        out["cohort_path"] = eng.cohort_path
        out["pool"] = eng.slots.pool
        out["blocks_per_slot"] = eng.slots.blocks_per_slot
    return out


def compare_phase(cfg, params, pool, blocks_per_slot: int, seed: int):
    """One cohort decode step on ``pool``: fused, composed, and composed
    in float32.  Returns the three logits arrays and bc."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.fused_decode import cohort_step
    from repro.serving.kv_cache import paged_positions

    bs = ENGINE_KW["block_size"]
    W = blocks_per_slot
    bc = ENGINE_KW["n_slots"]
    rng = np.random.default_rng(seed)
    tables = jnp.arange(bc * W, dtype=jnp.int32).reshape(bc, W)
    lengths = jnp.asarray(rng.integers(1, W * bs - 1, bc), jnp.int32)
    tokens = jnp.asarray(rng.integers(3, cfg.vocab_size - 1, (bc, 1)),
                         jnp.int32)
    slot_ids = jnp.arange(bc, dtype=jnp.int32)
    paged = paged_positions(cfg)

    def step(c, p, pl, use_fused):
        fn = jax.jit(lambda p, *a: cohort_step(
            p, c, *a, block_size=bs, paged=paged, use_fused=use_fused,
            interpret=False if use_fused else None))
        logits, _ = fn(p, tokens, lengths, slot_ids, tables, pl)
        return vocab_logits(cfg, logits)

    composed = step(cfg, params, pool, False)
    fused = step(cfg, params, pool, True)
    with jax.default_matmul_precision("highest"):
        ref = step(dataclasses.replace(cfg, dtype="float32"),
                   to_f32(params), to_f32(pool), False)
    return fused, composed, ref, bc


def vocab_logits(cfg, logits):
    """Host float32 logits without the padded vocab columns (those hold
    the -1e30 mask)."""
    import numpy as np
    out = np.asarray(logits, np.float32)[..., :cfg.vocab_size]
    check(np.isfinite(out).all(), "non-finite logits")
    return out


def to_f32(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def check_against_reference(what: str, got, base, ref) -> None:
    """``got`` and ``base`` are two bf16 computations of ``ref``; ``got``
    may be no further from it than ``REF_MARGIN`` times ``base`` is."""
    import numpy as np
    e_got = float(np.max(np.abs(got - ref)))
    e_base = float(np.max(np.abs(base - ref)))
    diff = float(np.max(np.abs(got - base)))
    agree = int(np.sum(got.argmax(-1) == base.argmax(-1)))
    print(f"[{what}] max|diff| {diff:.6g} against the baseline; against "
          f"the float32 reference: {e_got:.6g} (baseline {e_base:.6g}, "
          f"bound {REF_MARGIN * e_base:.6g}); max|logit| "
          f"{float(np.max(np.abs(ref))):.6g}; argmax agrees "
          f"{agree}/{len(got)}")
    check(e_got <= REF_MARGIN * e_base,
          f"{what}: further from the float32 reference than the bound")


def build(seed: int):
    import jax
    from repro.configs import get_config
    from repro.launch.steps import init_params
    cfg = get_config(ARCH)
    # one jitted program: eager init dispatches one op at a time
    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    return cfg, params


def one_chip(seed: int, compile_s: list) -> None:
    from repro.kernels.dispatch import resolve_interpret

    t0 = time.perf_counter()
    cfg, params = build(seed)
    print(f"[setup] {ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size}, params from seed {seed} in "
          f"{time.perf_counter() - t0:.1f}s")

    res = serve_phase(cfg, params, seed)
    check(res["cohort_path"] == ("fused", False),
          f"cohort step resolved to {res['cohort_path']}, want the fused "
          "Pallas path compiled")
    check(resolve_interpret() is False, "Pallas would run in interpret mode")
    check(len(res["classes"]) >= 2, f"slot classes {res['classes']}")
    print(f"[serve] 4 requests x 2 passes OK, slot classes "
          f"{res['classes']}, cohort path {res['cohort_path']}")
    print(f"[serve] cold pass (compiles included) {res['cold_s']:.2f}s; "
          f"backend compile time so far {sum(compile_s):.2f}s")
    print(f"[serve] smoke reading, host wall clock, not a measurement: "
          f"{res['warm_tokens'] / res['warm_s']:.1f} decode tokens/s "
          f"({res['warm_tokens']} tokens in {res['warm_s']:.2f}s warm)")

    fused, composed, ref, bc = compare_phase(
        cfg, params, res["pool"], res["blocks_per_slot"], seed)
    check_against_reference(f"compare fused vs composed, bc={bc}",
                            fused, composed, ref)


def first_step_logits(cfg, params, reqs, **plan_kw):
    """(n_requests, vocab) logits of each request's first generated
    token: through one plan lowering, or, with no ``plan_kw``, through the
    float32 forward pass (``lm_forward``) at "highest" precision."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.bricks import decompose
    from repro.core.plan import compile_plan
    from repro.models.model import lm_forward

    if plan_kw:
        run = compile_plan(decompose(cfg), params, **plan_kw).run
    else:
        c32, p32 = dataclasses.replace(cfg, dtype="float32"), to_f32(params)

        def run(batch):
            with jax.default_matmul_precision("highest"):
                return lm_forward(p32, c32, batch["tokens"],
                                  vision_feats=batch["vision_feats"])
    out = []
    for r in reqs:
        # right-pad to a power of two, as the engine's prefill buckets do
        # (chunked attention wants whole chunks); causal attention keeps
        # the logits at the true end unchanged
        n = len(r.tokens)
        tokens = np.zeros((1, max(128, 1 << (n - 1).bit_length())), np.int32)
        tokens[0, :n] = r.tokens
        logits, _ = run({
            "tokens": jnp.asarray(tokens),
            "vision_feats": jnp.asarray(r.vision_feats, jnp.float32)})
        out.append(vocab_logits(cfg, logits[0, n - 1]))
    return np.stack(out)


def four_chips(seed: int) -> None:
    import jax
    from repro.core.bricks import decompose
    from repro.core.scheduler import make_virtual_accelerators
    from repro.launch.mesh import make_mesh
    from repro.serving.disagg import serve_disagg_inproc
    from repro.serving.engine import ServingEngine

    n = jax.device_count()
    check(n >= 2, f"--four-chips needs several devices, found {n}")
    cfg, params = build(seed)

    def serve(**kw):
        reqs = make_requests(cfg, seed, n_full=1, max_new=8)
        with ServingEngine(cfg, params, **ENGINE_KW, **kw) as eng:
            for r in reqs:
                eng.submit(r)
            done = eng.run()
            path = eng.cohort_path
        check_served(cfg, reqs, done)
        return reqs, {r.rid: list(r.out_tokens) for r in done}, path

    reqs, oracle, path = serve()
    print(f"[oracle] single device: {len(oracle)} requests served, cohort "
          f"path {path}")

    mesh = make_mesh((1, n), ("data", "model"))
    enc, dec = make_virtual_accelerators(mesh, fractions=(0.25, 0.75))
    placement = {b.name: (enc.name if b.static_shape else dec.name)
                 for b in decompose(cfg).bricks}
    _, sub, sub_path = serve(placement=placement, accels=[enc, dec])
    # a decoder over several devices takes the composed step (the fused
    # kernels are not partitioned): its oracle is the composed step on
    # one device, so only the placement differs
    _, base, _ = serve(use_fused=sub_path[0] == "fused")
    if sub == base:
        print(f"[submesh] greedy tokens equal the single-device oracle "
              f"({len(sub)} requests; encoder on "
              f"{enc.mesh.devices.size}, decoder on "
              f"{dec.mesh.devices.size} devices; cohort path {sub_path})")
    else:
        print(f"[submesh] greedy tokens differ from the oracle: {sub} "
              f"against {base}; comparing first-step logits")
        check_against_reference(
            "submesh first step vs single device",
            first_step_logits(cfg, params, reqs, placement=placement,
                              accels=[enc, dec]),
            first_step_logits(cfg, params, reqs, backend="device"),
            first_step_logits(cfg, params, reqs))

    results, stats = serve_disagg_inproc(
        cfg, params, make_requests(cfg, seed, n_full=1, max_new=8),
        prefill_kwargs=dict(backend="device:0", **ENGINE_KW),
        decode_kwargs=dict(backend="device:1", **ENGINE_KW))
    for rid, want in oracle.items():
        got = results.get(rid)
        check(got is not None and got.error is None,
              f"disagg request {rid}: {got}")
        check(got.tokens == want,
              f"disagg request {rid}: {got.tokens} != oracle {want}")
    print(f"[disagg] prefill device:0 -> decode device:1: greedy tokens "
          f"equal the oracle ({len(results)} requests, "
          f"{stats.kv_wire_bytes} B of paged KV crossed)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the submesh and two-fleet paths, each "
                         "against its single-device oracle")
    args = ap.parse_args(argv)

    _import_repro()
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    print(f"[device] platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devs)}; compile cache {cache}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {dev.platform}); nothing "
              "runs off the chip", file=sys.stderr)
        return 1

    compile_s: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: compile_s.append(secs)
        if name.endswith("backend_compile_duration") else None)

    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed, compile_s)
    print(f"[done] {time.perf_counter() - t0:.1f}s wall, backend compile "
          f"{sum(compile_s):.1f}s over {len(compile_s)} programs")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
