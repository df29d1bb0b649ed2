"""Mixed-class TABM engine smoke — part of the no-TPU gate (make check).

Default mode drives one high-resolution and one thumbnail request through
a reduced ``ServingEngine`` on placeholder devices, so the
class-partitioned slot pool path (core/slot_classes +
core/tabm.SlotClassPool) is exercised by CI: classification at submit,
per-class staging threads, class-sized ring commits, per-class
release/drain.  Exits non-zero on any violation.

``--stage-batch K`` (K > 1) runs the *batched staging* smoke instead:
eight queued same-class requests through the microbatching pipeline, and
asserts the acceptance evidence — at least one multi-request strided slab
commit (``slab_commit`` trace event + ring ``slab_commits`` stat) and at
least one batch>1 grouped prefill (``prefill_batch``), with greedy tokens
identical to the synchronous one-by-one oracle.

``--decode-cohort`` runs the *continuous-batching decode* smoke: five
mixed-class requests against a 2-slot paged KV pool, so the engine must
retire and admit mid-flight while the survivors keep decoding in the
same batched cohort step.  Asserts the acceptance evidence — a
``decode_cohort`` trace of size > 1, at least one retirement before a
later admission, >= 2 slot classes — and that every request's greedy
tokens equal the request decoded alone in its own engine.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m repro.launch.smoke_classes [--stage-batch 4 \
                                             | --decode-cohort]
"""
from __future__ import annotations

import argparse
import sys

import numpy as np


def _mixed_class_smoke(cfg, params) -> int:
    from repro.core.slot_classes import resolution_buckets
    from repro.serving.engine import Request, ServingEngine

    buckets = resolution_buckets(cfg)
    thumb_tokens, full_tokens = buckets[0], buckets[-1]
    rng = np.random.default_rng(0)

    def feats(n_tokens):
        return rng.standard_normal(
            (1, n_tokens, cfg.vision_feat_dim)).astype(np.float32) * 0.02

    with ServingEngine(cfg, params, n_slots=2, max_len=128) as eng:
        print("slot classes (rings materialize lazily on first use):")
        for name, c in eng.tabm.classes.items():
            print(f"  {name:>12}: {c.n_images} img x {c.tokens_per_image} "
                  f"tok -> slab {c.max_tokens} tok, {c.n_slots} slots, "
                  f"{eng.tabm.class_nbytes(name)} B")
        assert not eng.tabm.rings              # nothing allocated yet
        hi = Request(rid=0, tokens=np.arange(8) + 3, max_new_tokens=4,
                     vision_feats=feats(full_tokens))
        thumb = Request(rid=1, tokens=np.arange(6) + 3, max_new_tokens=4,
                        vision_feats=feats(thumb_tokens))
        eng.submit(hi)
        eng.submit(thumb)
        done = eng.run()

        assert len(done) == 2, f"expected 2 finished requests, got {done}"
        for r in done:
            assert r.error is None, f"request {r.rid} failed: {r.error!r}"
            assert len(r.out_tokens) >= 4, f"request {r.rid} undergenerated"
        assert hi.slot_class != thumb.slot_class, (
            f"hi-res and thumbnail landed in one class "
            f"({hi.slot_class}) — partitioning is broken")
        hi_ring = eng.tabm.ring(hi.slot_class)
        th_ring = eng.tabm.ring(thumb.slot_class)
        assert hi_ring.max_tokens >= full_tokens > th_ring.max_tokens, (
            "thumbnail slab is not smaller than the full-resolution slab")
        assert hi_ring.stats["writes"] == th_ring.stats["writes"] == 1, (
            f"each class ring should carry exactly its own request: "
            f"hi={hi_ring.stats} thumb={th_ring.stats}")
        assert set(eng.tabm.rings) == {hi.slot_class, thumb.slot_class}, (
            f"only the classes traffic touched should have allocated "
            f"pools, got {list(eng.tabm.rings)}")
        print(f"classes used: hi-res={hi.slot_class} "
              f"thumbnail={thumb.slot_class}")
        print(f"per-class stats: hi={hi_ring.stats} thumb={th_ring.stats}")
        print(f"tokens: hi={hi.out_tokens} thumb={thumb.out_tokens}")
    print("OK: mixed-class engine smoke passed")
    return 0


def _batched_staging_smoke(cfg, params, stage_batch: int) -> int:
    from repro.serving.engine import Request, ServingEngine

    n_reqs = 8

    def reqs():
        rng = np.random.default_rng(1)         # same feats in both runs
        return [Request(rid=i, tokens=np.arange(8) + 3, max_new_tokens=4,
                        vision_feats=rng.standard_normal(
                            (1, cfg.vision_tokens, cfg.vision_feat_dim)
                        ).astype(np.float32) * 0.02)
                for i in range(n_reqs)]

    batch = reqs()
    with ServingEngine(cfg, params, n_slots=4, max_len=128,
                       stage_batch=stage_batch) as eng:
        for r in batch:
            eng.submit(r)
        done = eng.run()
        assert len(done) == n_reqs and all(r.error is None for r in done)
        classes = {r.slot_class for r in batch}
        assert len(classes) == 1, f"expected one class, got {classes}"
        events = [(e, k) for e, k, _ in eng.trace]
        slabs = [k for e, k in events if e == "slab_commit"]
        prefills = [k for e, k in events if e == "prefill_batch"]
        ring = eng.tabm.ring(batch[0].slot_class)
        assert slabs and max(slabs) > 1, (
            f"no multi-request slab commit in the trace: {events}")
        assert ring.stats["slab_commits"] >= 1, ring.stats
        assert prefills and max(prefills) > 1, (
            f"no batch>1 prefill call in the trace: {events}")
        print(f"slab commits (K): {slabs}  grouped prefills (B): {prefills}")
        print(f"ring stats: {ring.stats}")
        batched_tokens = {r.rid: r.out_tokens for r in done}

    # the one-by-one oracle: sync staging (K=1) + batch-1 prefill groups
    oracle = reqs()
    with ServingEngine(cfg, params, n_slots=4, max_len=128,
                       async_staging=False, stage_batch=1) as eng:
        eng.executor.policy.full_batch = 1     # one admission per step
        for r in oracle:
            eng.submit(r)
        done = eng.run()
        assert all(r.error is None for r in done)
        oracle_tokens = {r.rid: r.out_tokens for r in done}
    assert batched_tokens == oracle_tokens, (
        f"batched staging changed greedy tokens:\n"
        f"  batched: {batched_tokens}\n  oracle:  {oracle_tokens}")
    print("OK: batched staging smoke passed (tokens == one-by-one oracle)")
    return 0


def _decode_cohort_smoke(cfg, params) -> int:
    from repro.serving.engine import Request, ServingEngine

    def reqs():
        out = []
        for rid, (n_tok, n_img, n_new, plen) in enumerate(
                [(8, 1, 6, 7), (2, 1, 3, 6), (32, 4, 5, 9),
                 (2, 1, 4, 8), (8, 1, 3, 6)]):
            rng = np.random.default_rng(rid)
            out.append(Request(
                rid=rid, tokens=(np.arange(plen) % 50 + 3).astype(np.int32),
                n_images=n_img, max_new_tokens=n_new,
                vision_feats=rng.standard_normal(
                    (1, n_tok, cfg.vision_feat_dim)
                ).astype(np.float32) * 0.02))
        return out

    batch = reqs()
    with ServingEngine(cfg, params, n_slots=2, max_len=128,
                       block_size=32) as eng:
        for r in batch:
            eng.submit(r)
        done = eng.run()
        assert len(done) == 5, f"expected 5 finished, got {len(done)}"
        for r in done:
            assert r.error is None, f"request {r.rid} failed: {r.error!r}"
        classes = {r.slot_class for r in batch}
        assert len(classes) >= 2, f"expected >=2 classes, got {classes}"
        events = [(e, k) for e, k, _ in eng.trace]
        cohorts = [k for e, k in events if e == "decode_cohort"]
        assert max(cohorts) > 1, f"never decoded a cohort >1: {cohorts}"
        first_finish = next(i for i, (e, _) in enumerate(events)
                            if e == "finish")
        assert any(e == "prefill" and i > first_finish
                   for i, (e, _) in enumerate(events)), (
            "no mid-flight admission after the first retirement")
        eng.slots.check_block_invariants()
        cohort_tokens = {r.rid: r.out_tokens for r in done}
        print(f"classes: {sorted(classes)}  cohort sizes: {sorted(set(cohorts))}")
        print(f"paged pool: {eng.slots.n_blocks} blocks x "
              f"{eng.slots.block_size} tok, all free again")

    for ref in reqs():                         # the per-request oracle
        with ServingEngine(cfg, params, n_slots=2, max_len=128,
                           block_size=32) as eng:
            eng.submit(ref)
            eng.run()
            assert ref.error is None
            assert cohort_tokens[ref.rid] == ref.out_tokens, (
                f"request {ref.rid}: cohort decode changed greedy tokens\n"
                f"  cohort: {cohort_tokens[ref.rid]}\n"
                f"  alone:  {ref.out_tokens}")
    print("OK: decode-cohort smoke passed (tokens == per-request oracle, "
          "mid-flight admit/retire observed)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="class-partitioned TABM smoke")
    ap.add_argument("--stage-batch", type=int, default=1,
                    help="staging microbatch; >1 runs the batched-staging "
                         "smoke (strided slab commit + grouped prefill)")
    ap.add_argument("--decode-cohort", action="store_true",
                    help="run the continuous-batching decode smoke "
                         "(paged KV, mid-flight admit/retire, per-request "
                         "oracle equivalence)")
    args = ap.parse_args(argv)

    import jax
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.steps import init_params

    enable_compile_cache()
    cfg = get_config("llava-onevision-0.5b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    if args.decode_cohort:
        return _decode_cohort_smoke(cfg, params)
    if args.stage_batch > 1:
        return _batched_staging_smoke(cfg, params, args.stage_batch)
    return _mixed_class_smoke(cfg, params)


if __name__ == "__main__":
    sys.exit(main())
