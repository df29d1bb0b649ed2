#!/usr/bin/env python3
"""The benchmark's one command: run one cell of ``BENCHMARK.json`` on the
chip this process finds, and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (counted in ``setup_s``, from process start to the window): the
persistent compile cache at ``.jax_cache/`` in the checkout, the cell's
configuration with weights made on the device from ``--seed`` in one
jitted call, ``ServingEngine`` with the configuration's engine settings
and its own default cohort path, then a warm-up that compiles exactly the
shapes this cell's traffic reaches (staging microbatches, prefill bucket x
group sizes, cohort buckets).  Backlog cells also fill the cohort.

The window: the harness submits each request when it is due and calls
``ServingEngine.step`` otherwise, stamping every request's new tokens
after each step.  Latency counts from when a request was due.  With
``--trace 1`` a profiler trace of the window's last seconds is taken and
the per-layer metrics are read instead of the end-to-end ones.

After the window the peak device memory is read, the engine shut down,
and a sample of the finished requests (drawn from the seed, the longest
one always in it) is compared with the float32 reference
(``bench/references``): the widest gap by which a served token's logit
lies below the reference's best must stay within the cell's limit
(``bench/checks/<cell>.json``).  The numbers compared are printed beside
their limits as the last lines on standard error and, under ``checks``,
last in the result line.

Without a TPU, with fewer chips than the cell asks for, or on a device
kind missing from ``bench/peaks.json``, it exits nonzero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import registry  # noqa: E402
from bench.record import RunRecord, Span, Step, Tracked  # noqa: E402
from bench.traffic import generators as gen  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
WAIT_AFTER_S = 60.0        # how long a staging hand-off may take in warm-up


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, bad config, stuck)."""


def use_compile_cache():
    """JAX's persistent compile cache at a fixed path in the checkout,
    given to the program's own switch (``enable_compile_cache``) through
    the variable it reads."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # no eviction: the cell's programs must all stay cached, or every run
    # compiles again (a size cap in the environment evicts them)
    jax.config.update("jax_compilation_cache_max_size", -1)
    # every program, the quick ones too: a run after the first compiles
    # nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def _import_program():
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"the system under test is not at {src}/repro")
    if src not in sys.path:
        sys.path.insert(0, src)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def check_device(cell: registry.Cell):
    """The devices this run uses; fails off a TPU, with fewer chips than
    the cell asks for, or on a device kind the peak table lacks."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX sees {devs[0].platform}; the "
                         "benchmark measures only on the chip")
    need = int(cell.entry["chips"])
    if len(devs) < need:
        raise BenchError(f"cell {cell.name} needs {need} chips, found "
                         f"{len(devs)}")
    try:
        peaks = registry.peaks(devs[0].device_kind, cell.bench_dir)
    except registry.UnknownName as e:
        raise BenchError(str(e)) from e
    return devs[:need], peaks


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def seed_key(seed: int):
    """A PRNG key from any whole number, wider than 32 bits included."""
    import jax
    s = int(seed) & 0xFFFFFFFFFFFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def build_model(cell: registry.Cell, seed: int):
    """The repo's model config with the file's overrides, and weights made
    by the reference's rule from ``seed`` in one jitted call, in the tree
    layout and dtypes the program serves."""
    import dataclasses

    import jax
    from repro.configs import get_config
    from repro.launch.steps import init_params

    spec = cell.config
    overrides = {k: tuple(v) if isinstance(v, list) else v
                 for k, v in spec.get("overrides", {}).items()}
    cfg = dataclasses.replace(get_config(spec["arch"]), **overrides)
    ref = cell.reference()
    bad = ref.check_sizes(spec["config"], dataclasses.asdict(cfg))
    if bad:
        raise BenchError(f"config {spec['name']}: sizes disagree: {bad}")
    template = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                  cfg))
    params = jax.jit(lambda k: ref.make_params(k, template))(seed_key(seed))
    jax.block_until_ready(params)
    return cfg, params, ref


def make_engine(cfg, params, engine: dict, seed: int):
    from repro.core.power import PMU, BatteryAwareExecutor, PowerPolicy
    from repro.serving.engine import ServingEngine
    policy = PowerPolicy(full_batch=int(engine["max_batch"]))
    return ServingEngine(
        cfg, params, n_slots=int(engine["n_slots"]),
        max_len=int(engine["max_len"]),
        block_size=int(engine["block_size"]),
        executor=BatteryAwareExecutor(PMU(), policy),
        rng_seed=int(seed) & 0x7FFFFFFF)


def _annotate(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Harness:
    """One engine under one traffic plan: warm-up, window, record."""

    def __init__(self, cell: registry.Cell, seed: int, seconds: float):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        t = time.monotonic()
        self.cfg, self.params, self.ref = build_model(cell, seed)
        self.timing = {"weights_s": time.monotonic() - t}
        self.engine_cfg = dict(cell.config["engine"])
        t = time.monotonic()
        self.eng = make_engine(self.cfg, self.params, self.engine_cfg, seed)
        self.timing["engine_s"] = time.monotonic() - t
        buckets = self.cfg.vision_token_buckets or (self.cfg.vision_tokens,)
        self.plan = gen.generate(cell.traffic, buckets, self.cfg.vocab_size,
                                 seed, seconds, stream=0)
        self.warm_plan = gen.generate(cell.traffic, buckets,
                                      self.cfg.vocab_size, seed,
                                      max(seconds, 8.0), stream=1)
        # an open loop's arrivals are known: make their image features in
        # set-up; a closed loop's next request is made while its client
        # thinks, a backlog's as it is queued
        self.feats = {}
        if self.plan.kind == "open_poisson":
            for i in range(len(self.plan.requests)):
                self.features(i)
        self.tracked: List[Tracked] = []
        self.active: List[Tracked] = []
        self.steps: List[Step] = []
        self.spans: List[Span] = []
        self.compiles = 0
        self.compiles_total = 0
        self._warm_j = 0                          # warm-up request counter
        self.compiled: List[str] = []            # programs compiled in window
        self.counting = False
        self.next_rid = 0
        self.lateness: List[float] = []
        self.clock_offset = time.time() - time.monotonic()

    # -- requests -----------------------------------------------------------
    def _request(self, spec, feats, max_new: Optional[int] = None):
        import numpy as np
        from repro.serving.engine import Request
        rid = self.next_rid
        self.next_rid += 1
        tokens = np.concatenate([np.zeros(spec.n_patches, np.int32),
                                 spec.text]).astype(np.int32)
        return Request(rid=rid, tokens=tokens, vision_feats=feats,
                       n_images=1,
                       max_new_tokens=spec.max_new if max_new is None
                       else max_new,
                       temperature=spec.temperature)

    def features(self, i: int):
        if i not in self.feats:
            self.feats[i] = gen.vision_features(self.plan.requests[i],
                                                self.cfg.vision_feat_dim)
        return self.feats[i]

    def submit(self, i: int, due: float, now: float) -> Tracked:
        spec = self.plan.requests[i]
        req = self._request(spec, self.features(i))
        self.feats.pop(i, None)
        tr = Tracked(idx=i, prompt_len=spec.prompt_len,
                     n_patches=spec.n_patches, max_new=spec.max_new,
                     due=due, req=req)
        self.eng.submit(req)
        self.tracked.append(tr)
        self.active.append(tr)
        self.lateness.append(now - due)
        return tr

    def _group_key(self, spec):
        from repro.serving.kv_cache import bucket_length
        return (bucket_length(spec.prompt_len, buckets=self.eng._buckets()),
                spec.n_patches)

    # -- warm-up -------------------------------------------------------------
    def _concurrency(self) -> int:
        n_slots = int(self.engine_cfg["n_slots"])
        if self.plan.kind == "closed":
            return min(n_slots, int(self.cell.traffic["clients"]))
        return n_slots

    def _warm_request(self, spec, j: int, max_new: int):
        import numpy as np
        rng = np.random.default_rng([self.seed & 0xFFFFFFFFFFFFFFFF, 3, j])
        feats = rng.standard_normal((1, spec.n_patches,
                                     self.cfg.vision_feat_dim),
                                    dtype=np.float32)
        return self._request(spec, feats, max_new=max_new)

    def _drain(self, deadline_s: float = 600.0):
        t_end = time.monotonic() + deadline_s
        while self.eng.queue or self.eng.live:
            self.eng.step()
            if time.monotonic() > t_end:
                raise BenchError("warm-up did not drain")

    def warm_up(self):
        """Compile every shape this cell's traffic reaches, and no other:
        each staging microbatch size of each image class it sends, each
        prefill (bucket, image class) it sends at every group size it can
        form, and every cohort bucket up to its concurrency.  Twice: some
        programs compile again once their inputs come from the engine's
        own earlier outputs, and the second pass, otherwise cheap, meets
        them as the window will."""
        self._warm_pass()
        n = self.compiles_total
        self._warm_pass()
        print(f"bench: warm-up second pass compiled "
              f"{self.compiles_total - n} programs", file=sys.stderr)
        if self.plan.kind == "backlog":
            self.fill_backlog()

    def _warm_pass(self):
        import jax.numpy as jnp
        import numpy as np

        eng = self.eng
        conc = self._concurrency()
        keys = {}
        for s in self.plan.requests + self.warm_plan.requests:
            keys.setdefault(self._group_key(s), s)
        classes = sorted({k[1] for k in keys})
        max_stage = min(self.cfg.max_stage_batch, eng.executor.policy
                        .full_stage_batch, conc)
        j = self._warm_j
        for n_patches in classes:                   # staging microbatches
            cls = eng.tabm.classify(n_patches, 1)
            for k in range(1, min(max_stage, eng.tabm.classes[cls].n_slots)
                           + 1):
                batch = []
                for _ in range(k):
                    rng = np.random.default_rng([self.seed & 0xFFFFFFFF, 4,
                                                 j])
                    j += 1
                    batch.append({"vision_feats": jnp.asarray(
                        rng.standard_normal(
                            (1, n_patches, self.cfg.vision_feat_dim),
                            dtype=np.float32))})
                slots = eng.plan.produce_many(batch, slot_class=cls,
                                              block=True)
                for _ in slots:
                    got = eng.plan.consume(slot_class=cls)
                    eng.plan.release(got[0], slot_class=cls)
        # cohort buckets first: every later insert into the pool then
        # meets it as the cohort step leaves it, as in the window
        bc = 1
        while True:
            bucket = eng._cohort_bucket(bc)
            tokens = np.zeros((bucket, 1), np.int32)
            lengths = np.zeros((bucket,), np.int32)
            slot_ids = np.full((bucket,), eng.slots.n_slots, np.int32)
            tables = np.full((bucket, eng.slots.blocks_per_slot),
                             eng.slots.n_blocks, np.int32)
            logits, eng.slots.pool = eng._cohort_fn(bucket)(
                eng.params, jnp.asarray(tokens), jnp.asarray(lengths),
                jnp.asarray(slot_ids), jnp.asarray(tables), eng.slots.pool)
            int(jnp.argmax(logits[0:1], axis=-1).astype(jnp.int32)[0])
            if bucket >= eng._cohort_bucket(conc):
                break
            bc = bucket + 1
        max_group = min(int(self.engine_cfg["max_batch"]), conc)
        for key, spec in sorted(keys.items()):       # prefill groups
            for B in range(1, max_group + 1):
                reqs = [self._warm_request(spec, j + b, max_new=1)
                        for b in range(B)]
                j += B
                for r in reqs:
                    eng.submit(r)
                t_end = time.monotonic() + WAIT_AFTER_S
                while not all(r.staged for r in reqs):
                    eng._feed_staging()
                    time.sleep(0.002)
                    if time.monotonic() > t_end:
                        raise BenchError("warm-up staging stalled")
                before = eng.stats.prefills
                eng.step()
                if eng.stats.prefills - before != B:
                    raise BenchError(f"warm-up group {key} x {B} admitted "
                                     f"{eng.stats.prefills - before}")
                self._drain()
        self._warm_j = j

    def _depth(self) -> int:
        return int(self.cell.traffic["depth_slots"]) \
            * int(self.engine_cfg["n_slots"])

    def _top_up(self, now: float):
        while len(self.eng.queue) < self._depth():
            if len(self.tracked) >= len(self.plan.requests):
                raise BenchError("backlog ran out of requests: raise "
                                 "max_requests in the traffic file")
            self.submit(len(self.tracked), now, now)

    def fill_backlog(self, limit_s: float = 300.0):
        """Fill the cohort before the window: keep the queue at depth and
        step until every slot decodes."""
        t_end = time.monotonic() + limit_s
        n_slots = int(self.engine_cfg["n_slots"])
        while len(self.eng.live) < n_slots:
            self._top_up(time.monotonic())
            self.eng.step()
            self.stamp(time.monotonic(), record=False)
            if time.monotonic() > t_end:
                raise BenchError("backlog did not fill the cohort")
        self._top_up(time.monotonic())

    # -- window --------------------------------------------------------------
    def on_compile(self, name: str, secs: float, fun_name: str = "?", **_):
        if name.endswith("backend_compile_duration"):
            self.compiles_total += 1
            if self.counting:
                self.compiles += 1
                self.compiled.append(fun_name)

    def stamp(self, now: float, record: bool = True):
        """Stamp the tokens that the last step produced, and fold the
        engine's new probe spans into the record."""
        probe = self.eng.probe._samples
        new = [probe[i] for i in range(self._probe_seen, len(probe))] \
            if record else []
        self._probe_seen = len(probe)
        prefills = [s for s in new if s.phase == "prefill"]
        context = 0
        still = []
        for tr in self.active:
            req = tr.req
            n = len(req.out_tokens)
            had = len(tr.stamps)
            if n > had:
                first = had == 0
                if first and prefills:
                    t_ft = req.first_token_t - self.clock_offset
                    for s in prefills:
                        if s.t - s.dt - 1e-3 <= t_ft <= s.t + 1e-3:
                            tr.prefill_start = s.t - s.dt
                            break
                for k in range(had, n):
                    tr.stamps.append(now)
                    if k >= 1:
                        context += tr.prompt_len + k
            if req.error is not None:
                tr.failed = True
                tr.done = now
            elif req.finish_t is not None:
                tr.done = now
            else:
                still.append(tr)
        self.active = still
        if not record:
            return
        for s in new:
            if s.phase == "decode":
                self.steps.append(Step(
                    t=s.t, rows=int(s.tokens),
                    bucket=int(self.eng._cohort_bucket(int(s.tokens))),
                    context=context, dt=s.dt))
            self.spans.append(Span(phase=s.phase, brick=s.brick, t=s.t,
                                   dt=s.dt, tokens=int(s.tokens)))

    def window(self, tracer=None) -> RunRecord:
        eng = self.eng
        kind = self.plan.kind
        self._probe_seen = len(eng.probe._samples)
        writes0 = eng.tabm.stats["writes"]
        traced = tracer is not None
        self.counting = True
        w0 = time.monotonic()
        deadline = w0 + self.seconds
        nxt = 0                                   # open loop: next request
        due = None                                # closed loop: next due
        think = 0
        client: Optional[Tracked] = None
        if kind == "closed":
            due = w0
        while True:
            now = time.monotonic()
            if now >= deadline:
                break
            if tracer is not None:
                tracer.tick(now)
            with _annotate("bench.submit", traced):
                if kind == "open_poisson":
                    while nxt < len(self.plan.requests) and \
                            w0 + self.plan.due_s[nxt] <= now:
                        self.submit(nxt, w0 + float(self.plan.due_s[nxt]),
                                    now)
                        nxt += 1
                elif kind == "backlog":
                    self._top_up(now)
                elif due is not None and due <= now:
                    if len(self.tracked) >= len(self.plan.requests):
                        raise BenchError("closed loop ran out of requests")
                    client = self.submit(len(self.tracked), due, now)
                    due = None
            if eng.queue or eng.live:
                with _annotate("bench.step", traced):
                    if tracer is not None:
                        tracer.mark_step(time.monotonic())
                    eng.step()
                with _annotate("bench.stamp", traced):
                    self.stamp(time.monotonic())
                if client is not None and client.done is not None:
                    due = client.done + float(self.plan.think_s[think])
                    think += 1
                    client = None
                    if len(self.tracked) < len(self.plan.requests):
                        self.features(len(self.tracked))
            else:
                if kind == "open_poisson":
                    wake = (w0 + float(self.plan.due_s[nxt])
                            if nxt < len(self.plan.requests) else deadline)
                else:
                    wake = due if due is not None else deadline
                wake = min(wake, deadline)
                if tracer is not None:
                    wake = min(wake, tracer.next_event(now))
                with _annotate("bench.wait", traced):
                    time.sleep(max(0.0, wake - time.monotonic()))
        w1 = time.monotonic()
        self.counting = False
        if tracer is not None:
            tracer.finish()
        if len(eng.probe._samples) >= eng.probe._samples.maxlen:
            raise BenchError("engine probe overflowed inside the window")
        return RunRecord(
            cell=self.cell.name, sizes=self.cell.config["config"],
            peaks={}, setup_s=0.0,
            w0=w0, w1=w1, requests=list(self.tracked), steps=self.steps,
            spans=self.spans,
            tabm_writes=eng.tabm.stats["writes"] - writes0,
            kv_read_positions=eng.slots.blocks_per_slot
            * eng.slots.block_size)


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def sample_served(record: RunRecord, n: int, seed: int) -> List[Tracked]:
    """Up to ``n`` requests the window served without error: the one with
    the most served tokens, and the rest drawn from the seed.  Finished
    requests are taken first; where fewer than ``n`` finished (a backlog
    whose answers outlast the window), requests still streaming at the
    window's end fill the sample, each judged on every token it was
    served."""
    import numpy as np
    done = [r for r in record.requests
            if r.done is not None and not r.failed and r.done <= record.w1]
    if len(done) < n:
        done += [r for r in record.requests
                 if r.done is None and not r.failed and r.stamps
                 and r.stamps[-1] >= record.w0]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.stamps), -r.idx))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, 2])
    pick = rng.permutation(len(rest))[: max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def reference_gaps(harness: Harness, sample: List[Tracked],
                   modes=("f32",)) -> dict:
    """For each weight mode, the per-request widest logit gap.

    ``f32``: max over served tokens of (reference's best logit minus the
    served token's reference logit).  Any other mode is the control: at
    the same positions, the reference's gap of the token that the control
    puts first."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref, sizes = harness.ref, harness.cell.config["config"]
    pad_s = int(harness.cell.checks["pad_positions"])
    pad_out = int(harness.cell.checks["pad_outputs"])
    fns = {}

    def fn(mode, n_vis):
        if (mode, n_vis) not in fns:
            fns[(mode, n_vis)] = jax.jit(
                lambda p, t, f, pos, st: ref.forward(
                    p, sizes, t, f, pos, st, n_out=pad_out, mode=mode))
        return fns[(mode, n_vis)]

    out = {m: [] for m in modes}
    out["tokens"] = 0
    for tr in sample:
        req = tr.req
        served = np.asarray(req.out_tokens, np.int64)
        n = len(served)
        seq = np.concatenate([np.asarray(req.tokens), served[:-1]])
        if len(seq) > pad_s or n > pad_out:
            raise BenchError(f"request {tr.idx}: {len(seq)} positions / "
                             f"{n} outputs exceed the check's padding "
                             f"{pad_s} / {pad_out}")
        toks = np.zeros(pad_s, np.int32)
        toks[:len(seq)] = seq
        args = (harness.params, jnp.asarray(toks),
                jnp.asarray(req.vision_feats[0]), ref.positions(pad_s, sizes),
                jnp.int32(tr.prompt_len - 1))
        base = np.asarray(fn("f32", tr.n_patches)(*args))[:n]
        best = base.max(axis=-1)
        out["tokens"] += n
        for mode in modes:
            if mode == "f32":
                pick = served
            else:
                ctrl = np.asarray(fn(mode, tr.n_patches)(*args))[:n]
                pick = ctrl.argmax(axis=-1)
            gap = best - base[np.arange(n), pick]
            out[mode].append(float(gap.max()))
    return out


def check(harness: Harness, record: RunRecord, seed: int,
          modes=("f32",)) -> dict:
    """The numbers compared, each with its limit, and the control's
    readings when ``modes`` asks for them."""
    limits = harness.cell.checks["limits"]
    sample = sample_served(record, int(harness.cell.checks["requests"]),
                             seed)
    failed = sum(1 for r in record.requests if r.failed)
    vocab = int(harness.cell.config["config"]["vocab_size"])
    out_of_vocab = sum(1 for r in record.requests
                       for t in r.req.out_tokens if not 0 <= t < vocab)
    gaps = reference_gaps(harness, sample, modes) if sample else None
    checks = {
        "failed_requests": {"value": failed, "limit": 0},
        "out_of_vocab_tokens": {"value": out_of_vocab, "limit": 0},
        "checked_requests": {"value": len(sample),
                             "limit": int(limits["min_checked_requests"])},
        "max_logit_gap": {
            "value": max(gaps["f32"]) if gaps else None,
            "limit": float(limits["max_logit_gap"])},
    }
    ok = (failed == 0 and out_of_vocab == 0
          and len(sample) >= int(limits["min_checked_requests"])
          and gaps is not None
          and max(gaps["f32"]) <= float(limits["max_logit_gap"]))
    extra = {"checked_tokens": gaps["tokens"] if gaps else 0}
    if gaps:
        for m in modes:
            extra[f"gap_{m}"] = gaps[m]
    return {"correct": bool(ok), "checks": checks, "extra": extra}


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def read_metrics(entries: List[dict], record: RunRecord,
                 bench_dir: str) -> dict:
    """Each metric's reader, by name; one that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in entries:
        value = registry.metric_reader(m["name"], bench_dir)(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(cell: registry.Cell, seed: int, seconds: float, trace: bool,
             peaks: dict, devices, modes=("f32",),
             t_start: float = T_START) -> dict:
    """Set up, warm up, run the window, read the metrics and check the
    served tokens.  Returns the result object (last key ``checks``)."""
    import jax

    t0 = time.monotonic()
    harness = Harness(cell, seed, seconds)
    t1 = time.monotonic()
    print(f"bench: set-up parts: {harness.timing}", file=sys.stderr)
    jax.monitoring.register_event_duration_secs_listener(harness.on_compile)
    try:
        harness.warm_up()
        print(f"bench: set-up {t0 - t_start:.3f} s to import and reach the "
              f"chip, {t1 - t0:.3f} s weights, engine and traffic, "
              f"{time.monotonic() - t1:.3f} s warm-up", file=sys.stderr)
        tracer = None
        if trace:
            from bench import tracing
            tracer = tracing.Tracer(seconds)
        setup_s = time.monotonic() - t_start
        record = harness.window(tracer)
    finally:
        jax.monitoring.unregister_event_duration_listener(
            harness.on_compile)
    record.setup_s = setup_s
    record.peaks = peaks
    dev = devices[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    if tracer is not None:
        record.trace = tracer.reduce(record)
        device["busy_s"] = record.trace["busy_s"]
        device["window_s"] = record.trace["window_s"]
    queued = len(harness.eng.queue)
    harness.eng.shutdown()
    harness.eng.slots.pool = None
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = read_metrics(entries, record, cell.bench_dir)
    verdict = check(harness, record, seed, modes)
    lat = sorted(harness.lateness)
    result = {
        "correct": verdict["correct"],
        "attempted": len(record.requests),
        "failed": sum(1 for r in record.requests if r.failed),
        "metrics": metrics,
        "device": device,
    }
    if tracer is not None:
        result["breakdown"] = record.trace["breakdown"]
    result["window"] = {
        "seconds": record.window_s,
        "compiles_in_window": harness.compiles,
        "compiled_in_window": harness.compiled[:20],
        "generator_late_p50_ms": 1e3 * lat[len(lat) // 2] if lat else 0.0,
        "generator_late_max_ms": 1e3 * lat[-1] if lat else 0.0,
        "requests_due": len(record.due_in_window()),
        "tokens": record.tokens_in_window(),
        "queued_at_end": queued,
        **verdict["extra"],
    }
    result["checks"] = verdict["checks"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = registry.find_cell(args.workload)
        _import_program()
        use_compile_cache()
        devices, peaks = check_device(cell)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          peaks, devices)
    except (BenchError, registry.UnknownName, OSError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    w = result["window"]
    print(f"bench: {args.workload} seed {args.seed}: window "
          f"{w['seconds']:.3f} s, {w['requests_due']} requests due, "
          f"{w['tokens']} tokens, {w['compiles_in_window']} compiles in "
          f"the window {w['compiled_in_window']}, generator late p50 "
          f"{w['generator_late_p50_ms']:.3f}"
          f" ms max {w['generator_late_max_ms']:.3f} ms", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
