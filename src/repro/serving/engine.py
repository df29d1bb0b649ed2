"""Continuous-batching serving engine — a three-stage async pipeline over
a class-partitioned TABM pool, batched at every stage:

    producer threads (StagingWorker,         consumer (step loop)
    one per slot class)                      ---------------------
    ------------------------------           plan.consume (per-slot,
    microbatch: vision encode +              per-class ready wait) ->
    projector as ONE jit call ->             grouped batch-B prefill ->
    plan.produce_many -> ONE strided         KVCache.insert_many (one
    class-slab ring commit (blocks on        strided scatter) ->
    class FULL = per-class backpressure)     batched decode

Batching knobs: a class's staging microbatch is
``min(ModelConfig.max_stage_batch, Knobs.max_stage_batch, ring
capacity)`` — THROTTLED shrinks the batch before any class sheds depth —
and ``_admit`` groups *consecutive* bucket-matched staged requests (same
prompt bucket + same class/slab width) into one compiled batch-B prefill
call.  Cross-class aging (``aging_steps``) reserves a KV slot for a
request skipped too many admission rounds, so a thumbnail flood cannot
starve a stalled hi-res head forever.

The vision path is not reimplemented here: the engine compiles the
BrickGraph into an :class:`repro.core.plan.ExecutionPlan` and drives the
plan's TABM edge as a real producer/consumer pair —

* **slot classes**: every vision request is classified at submit (image
  count × resolution bucket, from the arch config — core/slot_classes)
  and staged through its own class-sized ring of the
  :class:`~repro.core.tabm.SlotClassPool`.  A 1-image thumbnail no longer
  pads into a 4-image full-resolution slab, and a FULL high-resolution
  ring stalls only that class's producer thread — thumbnails keep
  staging and admitting (class isolation).
* **producer** (:class:`StagingWorker`): one thread per slot class pulls
  admitted requests from its class's hand-off queue and runs
  ``plan.produce`` (vision encode -> projector -> ring commit) *off the
  step loop*, so request k+1's vision encode overlaps request k's decode
  — the paper's TABM smoothing made actually concurrent.  A FULL class
  ring blocks that class's thread inside ``acquire_write`` (backpressure,
  never a silent bypass); admission charges each request's class against
  its own staged-ahead depth budget
  (core/scheduler.class_staging_budgets), scaled by the battery knob
  ``class_depth_scale`` — THROTTLED shrinks the high-resolution classes'
  depth first, so expensive staging is the first load shed.
* **consumer** (``_bind_vision``): at admission the request's committed
  slot is bound as the prefill's vision input after a per-slot ready wait
  on its class ring (``wait_ready``; zero-copy via donation, see
  core/tabm.py) and released once the prefill has consumed it —
  validated by the ring's seqlock generation.

Lifecycle: ``shutdown()`` (or the context manager) stops the worker —
closing the ring wakes a producer stalled on FULL — joins the thread,
drains staged-but-unconsumed slots back to EMPTY, and resolves every
outstanding request (queued or live mid-decode) as failed with
:class:`EngineClosed`; an engine dropped without shutdown is reaped by a
finalizer so the producer thread never leaks.  A staging error (e.g. the projector
raising) aborts the ring write inside ``plan.produce`` and surfaces on the
originating request's ``error`` field; the request finishes failed instead
of wedging the pipeline.  ``async_staging=False`` keeps the old inline
single-threaded staging — bit-identical tokens, used as the equivalence
oracle in tests/test_engine_async.py.

Other paper mechanisms wired in:
* **module-level offloading** — the same plan compiles against submesh
  accelerators (core/scheduler.make_virtual_accelerators) for the pod-mode
  NPU/GPU split; see launch/serve_disagg.py.
* **battery-aware execution** — admission/batch knobs come from the
  three-state policy; CRITICAL switches to cascade one-shot inference.
* **static shapes** — prompts bucket-pad (kv_cache.bucket_length): one
  compiled prefill per bucket, one compiled decode step, never recompiled.

Decode is a **cohort step** over a **paged KV pool**
(kv_cache.PagedKVCache): every in-flight request joins one batched jit
decode call — padded to a small set of cohort-size buckets (powers of
two, one compile each) — that gathers each row's context through its
block table and scatters the new K/V back into its granted blocks.
Admission *grants* each request the KV blocks its lifetime needs,
charged per slot class (core/scheduler.kv_block_budgets) exactly like
staged-ahead depth, and the battery knob ``class_kv_scale`` sheds the
high-resolution classes' block share first under THROTTLED.  A
finishing request's blocks return to the free pool the same step
(continuous batching: the next staged request can admit mid-flight,
while everyone else's rows decode on undisturbed).

Staged TABM slots are **shared**: two requests submitting identical
vision bytes (same class, same content hash) stage ONCE — the second
takes a refcounted read view of the first's READY slot
(core/tabm.addref/shared_view) and the slab frees only when the last
holder releases.

Metrics mirror the paper's evaluation: tokens/s, end-to-end latency
(submit -> finish), modeled energy, memory (pool + weights).  ``trace``
records the producer/consumer interleaving ((event, rid, t) tuples) —
the overlap evidence the async tests assert on.  ``probe`` holds the
program's spans, each also a profiler annotation of the same name:
``serve.submit``, ``serve.admit`` (one admission round), ``serve.park``
(an idle wait in it), ``serve.prefill`` (one group), ``serve.decode``
split into ``.launch`` / ``.wait`` / ``.sample``, ``tabm.wait_ready``,
the plan's ``tabm.acquire`` / ``tabm.stage.<brick>`` / ``tabm.commit``,
and ``jit.trace`` / ``jit.compile`` (see telemetry/probes.py).  Each
request carries its lifecycle on ``time.monotonic()``: ``submit_t``,
``staged_t``, ``admit_t``, ``first_token_mt``, ``finish_mt``.
"""
from __future__ import annotations

import hashlib
import queue
import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.bricks import decompose
from repro.core.plan import compile_plan
from repro.core.power import BatteryAwareExecutor, PMU, PowerState
from repro.core.scheduler import class_staging_budgets, kv_block_budgets
from repro.core.tabm import SlotClassPool, TABMError
from repro.models import model as M
from repro.serving.kv_cache import PagedKVCache, bucket_length
from repro.serving.sampling import greedy, sample_rows
from repro.telemetry.calibration import CostCalibration
from repro.telemetry.ledger import Ledger
from repro.telemetry.probes import WallProbe, jit_counts, watch_jit

EOS_ID = 1
# wall clock minus monotonic clock, read once: maps the monotonic
# lifecycle stamps onto calendar time (Request.first_token_t, finish_t)
_WALL_MINUS_MONO = time.time() - time.monotonic()


class TraceEvent(NamedTuple):
    """One engine lifecycle event, stamped with ``time.monotonic()`` at
    record time — monotonic so producer-thread and step-loop events
    interleave in true order (the telemetry ledger's wall-time probes
    anchor to the same clock).  Tuple-compatible: existing consumers
    unpack ``(event, rid, t)``."""

    event: str
    rid: int
    t: float


class EngineClosed(RuntimeError):
    """The engine shut down before this request could complete."""


@dataclass
class Request:
    rid: int
    tokens: np.ndarray                     # prompt token ids
    vision_feats: Optional[np.ndarray] = None
    n_images: int = 1                      # images the vision feats cover
    max_new_tokens: int = 32
    temperature: float = 0.0
    # lifecycle stamps, all time.monotonic(): handed to the engine
    # (submit), slab committed or nothing to stage (staged), its prefill
    # group began (admit), first token picked, finished or failed
    submit_t: Optional[float] = None
    staged_t: Optional[float] = None
    admit_t: Optional[float] = None
    first_token_mt: Optional[float] = None
    finish_mt: Optional[float] = None
    out_tokens: List[int] = field(default_factory=list)
    slot: Optional[int] = None                 # KV-cache slot once admitted
    tabm_slot: Optional[int] = None            # class-ring slot once staged
    slot_class: Optional[str] = None           # TABM class, set at submit
    stage_submitted: bool = False              # handed to the StagingWorker
    aging: int = 0                             # admission rounds spent queued
                                               # (cross-class KV reservation
                                               # once >= engine.aging_steps)
    error: Optional[BaseException] = None      # staging/engine failure
    # committed TABM slab, trimmed to its true token count — captured at
    # vision bind when the engine runs capture_slab=True (the prefill
    # fleet: the slab rides the wire so the hand-off is self-contained)
    slab: Optional[np.ndarray] = field(default=None, repr=False)
    # staged-slab sharing: identical vision bytes stage once.  share_of
    # points at the request that owns the staging; the owner's sharers
    # list is granted refcounted views of its slot at bind time
    share_of: Optional["Request"] = None
    sharers: List["Request"] = field(default_factory=list, repr=False)
    _share_key: Optional[tuple] = None
    _tabm_gen: Optional[int] = None            # seqlock gen at consume
    _staged_ev: threading.Event = field(default_factory=threading.Event,
                                        repr=False)

    @property
    def staged(self) -> bool:
        """Producer half already ran (committed or failed).  Derived from
        the event so the admission check and the idle park can never
        desynchronize."""
        return self._staged_ev.is_set()

    def _mark_staged(self):
        if self.staged_t is None:
            self.staged_t = time.monotonic()
        self._staged_ev.set()

    @property
    def first_token_t(self) -> Optional[float]:
        """Wall-clock (``time.time()``) time of the first token."""
        return None if self.first_token_mt is None \
            else self.first_token_mt + _WALL_MINUS_MONO

    @property
    def finish_t(self) -> Optional[float]:
        """Wall-clock (``time.time()``) time the request finished."""
        return None if self.finish_mt is None \
            else self.finish_mt + _WALL_MINUS_MONO

    @property
    def e2e_latency(self) -> Optional[float]:
        if self.finish_mt is None or self.submit_t is None:
            return None
        return self.finish_mt - self.submit_t


@dataclass
class EngineStats:
    decoded_tokens: int = 0
    prefills: int = 0
    steps: int = 0
    finished: int = 0
    failed: int = 0
    # device-to-host reads made to sample decode tokens: one per cohort
    # step, so decoded_tokens / sample_reads is the mean cohort rows
    sample_reads: int = 0
    start_t: Optional[float] = None      # first decode step (monotonic)

    def tokens_per_s(self) -> float:
        """Decoded tokens per second since the first decode step."""
        if self.start_t is None:
            return 0.0
        dt = time.monotonic() - self.start_t
        return self.decoded_tokens / dt if dt > 0 else 0.0


_STOP = object()


class StagingWorker:
    """The pipeline's producer stage: one thread *per slot class*, each
    draining its class's hand-off queue into **microbatches** through
    ``plan.produce_many`` — one batched vision-encode+projector call and
    one strided slab commit per drain, up to ``stage_batch(cls)`` requests
    (the battery-scaled ``Knobs.max_stage_batch`` × the arch's
    ``max_stage_batch``, clamped to the class ring's capacity).

    The worker owns the ring-write side of the TABM contract, per class:
    a class thread blocks *inside* ``acquire_write_many`` on its own FULL
    ring (so backpressure stalls exactly that class's producer — never
    the decode loop, never another class's staging), aborts the whole
    slab if a brick raises — then **isolates** the failure by restaging
    the microbatch one request at a time, so one request's bad input
    fails only its owner, never its batchmates — and attaches any
    failure to the originating request before flagging it staged.
    ``shutdown`` closes the pool first — waking every stalled class
    thread — then joins them all; requests still queued at that point
    are cancelled with :class:`EngineClosed`.

    ``classes=(None,)`` (the default) degenerates to the single-ring,
    single-thread pipeline; ``stage_batch=None`` to K=1 staging."""

    def __init__(self, plan, trace, classes=(None,), stage_batch=None):
        self.plan = plan
        self._trace = trace                     # (event, rid) -> None
        self._classes = tuple(classes)
        self._stage_batch = stage_batch         # (slot_class) -> int | None
        self._qs: Dict[Optional[str], "queue.Queue"] = {
            c: queue.Queue() for c in self._classes}
        self._stop = threading.Event()
        self._lock = threading.Lock()
        # handed over, not yet staged — charged per class at hand-off
        self._in_flight: Dict[Optional[str], int] = {
            c: 0 for c in self._classes}
        self._threads: Dict[Optional[str], threading.Thread] = {}

    def in_flight(self, slot_class: Optional[str] = None) -> int:
        with self._lock:
            return self._in_flight[slot_class]

    def in_flight_by_class(self) -> Dict[Optional[str], int]:
        with self._lock:
            return dict(self._in_flight)

    def start(self, slot_class: Optional[str] = None):
        if slot_class not in self._threads:
            name = "tabm-staging" if slot_class is None \
                else f"tabm-staging[{slot_class}]"
            t = threading.Thread(target=self._run, args=(slot_class,),
                                 name=name, daemon=True)
            self._threads[slot_class] = t
            t.start()

    def submit(self, reqs):
        """Hand one request — or one list of same-class requests, the
        admission round's microbatch — to the owning class thread."""
        batch = reqs if isinstance(reqs, list) else [reqs]
        if not batch:
            return
        if self._stop.is_set():
            raise EngineClosed("staging worker already shut down")
        cls = batch[0].slot_class
        if any(r.slot_class != cls for r in batch):
            raise EngineClosed("a staging microbatch must be one class")
        if cls not in self._qs:
            raise EngineClosed(f"no staging queue for slot class {cls!r}")
        self.start(cls)
        with self._lock:
            self._in_flight[cls] += len(batch)
        self._qs[cls].put(batch)

    def _cap(self, slot_class: Optional[str]) -> int:
        if self._stage_batch is None:
            return 1
        return max(1, int(self._stage_batch(slot_class)))

    def _run(self, slot_class: Optional[str]):
        q = self._qs[slot_class]
        pending: "deque[Request]" = deque()
        stop_seen = False
        while True:
            if not pending:
                item = q.get()
                if item is _STOP:
                    break
                pending.extend(item if isinstance(item, list) else [item])
            while True:                        # opportunistic drain, no block
                try:
                    nxt = q.get_nowait()
                except queue.Empty:
                    break
                if nxt is _STOP:
                    stop_seen = True
                    break
                pending.extend(nxt if isinstance(nxt, list) else [nxt])
            cap = self._cap(slot_class)        # battery-scaled, per drain
            batch = [pending.popleft()
                     for _ in range(min(cap, len(pending)))]
            self._stage_batch_now(slot_class, batch)
            if stop_seen and not pending:
                break

    def _stage_batch_now(self, slot_class: Optional[str],
                         batch: List[Request]):
        """One microbatch through produce_many: K FIFO slots, one batched
        projector call, one strided slab commit; per-request commit
        events so consumers see the same per-slot signals as K=1."""
        try:
            if self._stop.is_set():
                raise EngineClosed("engine shut down before staging")
            for req in batch:
                self._trace("stage_start", req.rid)
            slots = self.plan.produce_many(
                [{"vision_feats": jnp.asarray(r.vision_feats)}
                 for r in batch],
                slot_class=slot_class, block=True)
            if slots is None:                  # ring closed mid-stall
                raise EngineClosed("ring closed while staging stalled")
            for req, slot in zip(batch, slots):
                req.tabm_slot = slot
                self._trace("stage_commit", req.rid)
            if len(batch) > 1:                 # the acceptance evidence
                self._trace("slab_commit", len(batch))
        except BaseException as e:
            if len(batch) > 1 and not isinstance(e, EngineClosed):
                # the slab was aborted whole (abort-all-on-failure);
                # isolate the bad request by restaging one at a time so
                # the error lands only on its owner
                self._restage_isolated(slot_class, batch)
            else:
                for req in batch:              # propagate to the request(s)
                    req.error = e
                    self._trace("stage_error", req.rid)
        finally:
            with self._lock:
                self._in_flight[slot_class] -= len(batch)
            for req in batch:
                req._mark_staged()

    def _restage_isolated(self, slot_class: Optional[str],
                          batch: List[Request]):
        for req in batch:
            try:
                if self._stop.is_set():
                    raise EngineClosed("engine shut down before staging")
                slot = self.plan.produce(
                    {"vision_feats": jnp.asarray(req.vision_feats)},
                    slot_class=slot_class, block=True)
                if slot is None:
                    raise EngineClosed("ring closed while staging stalled")
                req.tabm_slot = slot
                self._trace("stage_commit", req.rid)
            except BaseException as e:
                req.error = e
                self._trace("stage_error", req.rid)

    def shutdown(self, timeout: float = 10.0) -> bool:
        """Stop accepting, cancel in-flight staging, join every class
        thread.  Returns True when all threads are fully dead (no daemon
        leak)."""
        self._stop.set()
        if self.plan.tabm is not None:
            self.plan.tabm.close()        # wakes every class's FULL stall
        threads = list(self._threads.items())
        for cls, _ in threads:
            self._qs[cls].put(_STOP)
        deadline = time.monotonic() + timeout
        alive = False
        for _, t in threads:
            t.join(max(0.0, deadline - time.monotonic()))
            alive = alive or t.is_alive()
        return not alive


class ServingEngine:
    """Decoder-only (dense/moe/ssm/hybrid/vlm) continuous-batching engine."""

    def __init__(self, cfg: ModelConfig, params, *, n_slots: int = 8,
                 max_len: int = 2048, executor: Optional[
                     BatteryAwareExecutor] = None,
                 rng_seed: int = 0, async_staging: bool = True,
                 placement=None, accels=None, backend=None,
                 stage_batch: Optional[int] = None,
                 aging_steps: int = 32, block_size: int = 64,
                 kv_blocks: Optional[int] = None,
                 share_staged: bool = True,
                 calibration: Optional[CostCalibration] = None,
                 capture_slab: bool = False,
                 use_fused: Optional[bool] = None):
        assert not cfg.encdec, "engine serves decoder-only archs"
        self.cfg = cfg
        self.params = params
        # fused cohort-decode step (kernels/fused_decode): None resolves
        # per the dispatch convention — compiled Pallas on real TPU only
        # (off-TPU it would run in interpret mode).  The two paths agree
        # within a stated tolerance, not bit for bit; which is faster on
        # the chip has not been measured.  True forces the fused step
        # (tests/bench).  The resolution lands in cohort_path at the
        # first cohort build: ("fused" | "composed", interpret?)
        self.use_fused = use_fused
        self.cohort_path: Optional[Tuple[str, bool]] = None
        # paged decode pool: kv_blocks < n_slots*blocks_per_slot
        # oversubscribes slots against KV memory; admission grants per
        # request, per class (kv_block_budgets)
        self.slots = PagedKVCache(cfg, n_slots, max_len,
                                  block_size=block_size,
                                  total_blocks=kv_blocks)
        self.max_len = max_len
        self.executor = executor or BatteryAwareExecutor(PMU())
        # staging microbatch override; None = min(arch max_stage_batch,
        # battery Knobs.max_stage_batch), always clamped to ring capacity
        self._stage_batch_override = stage_batch
        # cross-class aging: a vision request skipped at admission this
        # many rounds reserves a KV slot against newer other-class
        # requests (anti-starvation under thumbnail floods)
        self.aging_steps = aging_steps
        self.queue: List[Request] = []
        self.live: Dict[int, Request] = {}      # slot -> request
        self.done: List[Request] = []
        self.stats = EngineStats()
        self.key = jax.random.PRNGKey(rng_seed)
        # producer/consumer interleaving evidence: TraceEvent(event, rid,
        # t=monotonic); bounded so a long-running server doesn't grow it
        # without limit
        self.trace: "deque[TraceEvent]" = deque(maxlen=4096)
        # wall-time probe feeding the telemetry ledger: per-brick staging
        # spans (via the plan) + the engine's prefill/decode spans, all
        # host clocks — no device syncs beyond the ones the loop already
        # pays.  `calibration` (optional, e.g. from a previous run's
        # measured ledger) lets admission price KV budgets from
        # observation (see _kv_energy_pressure)
        self.probe = WallProbe()
        # jit traces, compiles and cache loads, counted for the process
        # and recorded as jit.* spans into this probe (jit_counts())
        watch_jit(self.probe)
        self._jit_base = jit_counts()
        self.calibration = calibration
        self._kv_pressure: Optional[float] = None
        # class-partitioned TABM pool between encoder and decoder bricks
        # (vlm archs): one class-sized ring per image-count x resolution
        # bucket (core/slot_classes), so a thumbnail request neither pads
        # into nor queues behind a multi-image full-resolution slab
        self.tabm = SlotClassPool.from_config(
            cfg, dim=cfg.d_model,
            slots_per_class=max(2, n_slots // 2)) if cfg.vlm else None
        # the one brick runtime: vision staging routes through the plan's
        # projector brick and TABM edge (no inline reimplementation).
        # placement/accels/backend pick the lowering substrate per brick
        # (core/backends) — the engine's step loop is identical on all of
        # them, the paper's "same graph, swappable compute unit"
        self.plan = compile_plan(decompose(cfg), params, tabm=self.tabm,
                                 placement=placement, accels=accels,
                                 backend=backend, probe=self.probe)
        # remembered so the battery policy's demotion can be undone when
        # charge recovers (plan.relower back to the compiled substrate)
        self._lowered_backends = {s.brick.name: s.backend
                                  for s in self.plan.steps}
        self._demoted_to: Optional[str] = None
        # producer stage: own thread unless the caller opts back into the
        # synchronous single-threaded pipeline (the equivalence oracle)
        self.async_staging = bool(async_staging and self.tabm is not None)
        self._worker = None
        if self.async_staging:
            # the worker must reference the engine only weakly (the live
            # thread roots the worker), or a dropped engine could never be
            # collected and its producer thread would leak; the finalizer
            # joins the thread for callers that skip shutdown()
            wself = weakref.ref(self)

            def _trace(event, rid):
                eng = wself()
                if eng is not None:
                    eng._trace_event(event, rid)

            def _stage_cap(slot_class):
                eng = wself()
                return 1 if eng is None else eng._class_stage_batch(
                    slot_class)

            self._worker = StagingWorker(
                self.plan, _trace, classes=tuple(self.tabm.names()),
                stage_batch=_stage_cap)
            self._finalizer = weakref.finalize(
                self, StagingWorker.shutdown, self._worker, 1.0)
        self._closed = False

        self._prefill_cache: Dict[int, Any] = {}
        # one compiled cohort decode step per cohort-size bucket
        self._cohort_cache: Dict[int, Any] = {}
        # compiled greedy picks by logits (shape, dtype, sharding): the
        # cohort buckets' are built with their step (_cohort_fn), so a
        # serving window never compiles one
        self._picks: Dict[tuple, Any] = {}
        # staged-slab dedup registry: share key -> owning request
        self.share_staged = bool(share_staged and self.tabm is not None)
        self._stage_keys: Dict[tuple, Request] = {}
        # prefill-fleet mode: keep each request's committed slab (host
        # copy, trimmed) at vision bind, so export_remote can ship it
        self.capture_slab = bool(capture_slab)

    # -- public api ----------------------------------------------------------
    def submit(self, req: Request):
        if self._closed:
            raise EngineClosed("engine already shut down")
        with self.probe.span("serve.submit", "engine", "submit"):
            req.submit_t = time.monotonic()
            self._submit(req)

    def _submit(self, req: Request):
        if self.tabm is None or req.vision_feats is None:
            req._mark_staged()             # text-only: nothing to commit
        elif req.slot_class is None:
            # classify from the vision spec (token count x image count) —
            # the request is charged against exactly this class's ring and
            # admission depth; an unservable spec fails fast, at submit
            req.slot_class = self.tabm.classify(
                int(np.asarray(req.vision_feats).shape[1]), req.n_images)
        else:
            self.tabm.ring(req.slot_class)     # unknown class fails fast
        if self.share_staged and req.vision_feats is not None:
            # staged-slab dedup: identical vision bytes (class + shape +
            # content hash) stage once; later twins take refcounted read
            # views of the owner's slot at bind time (_grant_shares)
            key = self._stage_key(req)
            req._share_key = key
            owner = self._stage_keys.get(key)
            if (owner is not None and owner.error is None
                    and owner.finish_mt is None):
                req.share_of = owner
                owner.sharers.append(req)
            else:
                self._stage_keys[key] = req
        self.queue.append(req)

    def run(self, max_steps: int = 10_000) -> List[Request]:
        while (self.queue or self.live) and self.stats.steps < max_steps:
            self.step()
        return self.done

    def shutdown(self, timeout: float = 10.0) -> bool:
        """Tear the pipeline down: stop+join the producer thread (a FULL
        stall is woken via ring close), drain staged-but-unconsumed slots
        back to EMPTY, and resolve every outstanding request — live
        mid-decode ones keep their partial tokens — as failed with
        EngineClosed.  Idempotent; returns True when no worker thread is
        left alive."""
        self._closed = True
        joined = True
        if self._worker is not None:
            joined = self._worker.shutdown(timeout)
            if joined:
                # torn down manually; a thread that outlived the join
                # timeout keeps its finalizer as the reaping safety net
                self._finalizer.detach()
        elif self.tabm is not None:
            self.tabm.close()
        if self.tabm is not None and joined:
            self.tabm.drain()              # READY/CONSUMED leftovers -> EMPTY
        for slot, req in list(self.live.items()):
            if req.error is None:
                req.error = EngineClosed("engine shut down mid-decode")
            self.slots.release(slot)
            self._fail(req)                # partial out_tokens are kept
        self.live.clear()
        while self.queue:
            req = self.queue.pop(0)
            if req.error is None:
                req.error = EngineClosed("engine shut down before admission")
            self._fail(req)
        return joined

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False

    # -- internals -----------------------------------------------------------
    def _trace_event(self, event: str, rid: int):
        self.trace.append(TraceEvent(event, rid, time.monotonic()))

    def _stage_key(self, req: Request) -> tuple:
        """Dedup identity of a request's staged vision: class + slab
        shape + dtype + content hash — equal keys would commit
        byte-identical slabs, so one commit can serve all of them."""
        feats = np.asarray(req.vision_feats)
        return (req.slot_class, feats.shape, str(feats.dtype),
                hashlib.sha1(feats.tobytes()).hexdigest())

    def _prefill_fn(self, bucket: int):
        if bucket not in self._prefill_cache:
            cfg = self.cfg
            # prefilled caches are written straight into granted KV
            # blocks (insert_many), so the cache width must be
            # block-aligned — the prompt bucket rounded up, NOT max_len:
            # a short prompt's prefill touches only the blocks its
            # grant actually covers
            bs = self.slots.block_size
            decode_len = -(-bucket // bs) * bs

            @jax.named_scope("serve_prefill")
            def fn(p, tokens, vision_embeds, last_idx):
                """Right-padded bucket prefill; logits read at the true
                prompt end (last_idx-1); pad positions stay in the cache
                but decode's per-slot length mask never attends them."""
                B, S = tokens.shape
                from repro.models.common import (default_mrope_positions,
                                                 default_positions)
                positions = default_positions(B, S)
                mrope = (default_mrope_positions(B, S)
                         if cfg.rope == "mrope" else None)
                rope_fn = M.make_rope_fn(cfg, positions, mrope)
                x = p["embed"][tokens]
                if vision_embeds is not None:
                    x = jnp.concatenate(
                        [vision_embeds.astype(x.dtype),
                         x[:, vision_embeds.shape[1]:]], axis=1)
                from repro.models import decoder as dec
                x, caches, _ = dec.stack_forward(
                    p["layers"], cfg, x, rope_fn, causal=True,
                    want_cache=True, decode_len=decode_len, remat=False)
                x_last = jnp.take_along_axis(
                    x, (last_idx - 1)[:, None, None].astype(jnp.int32), 1)
                logits = M._head(p, cfg, x_last)
                return logits[:, 0], {"layers": caches}

            # the compiled module is named after the function (jit_<name>)
            fn.__name__ = fn.__qualname__ = f"serve_prefill_b{bucket}"
            self._prefill_cache[bucket] = jax.jit(fn)
        return self._prefill_cache[bucket]

    def _cohort_bucket(self, n: int) -> int:
        """Pad the cohort to the next power of two (capped at n_slots):
        a handful of compiled step sizes instead of one per live count."""
        return min(1 << max(0, n - 1).bit_length(), self.slots.n_slots)

    def _cohort_slots(self) -> List[int]:
        """The slots decoding this step: every live slot — ONE batched
        call serves the whole fleet."""
        return sorted(self.live)

    def _cohort_fn(self, bc: int):
        """The compiled cohort decode step for cohort-size bucket `bc`
        (kernels/fused_decode.cohort_step).  The composed body gathers
        each row's context from the paged pool through its block table —
        (bc, W) ids -> (G, bc, W*block_size, KV, hd) — runs ONE
        ``lm_decode_step`` over the cohort (per-row lengths as the
        index vector; rows are independent, so each decodes exactly as
        it would alone), then scatters the one new K/V position back
        into each row's current block and the updated slot state back
        by slot id.  Padded rows carry sentinel ids: gathers fill
        zeros (masked by length 0), scatters drop — padding costs no
        host branching and writes nothing.

        ``use_fused`` (engine flag) swaps the body for the fused
        Pallas step: in-VMEM weight unpack + QKV/MLP GEMMs +
        single-position KV scatter, within a stated tolerance of the
        composed body.  Both flags (fused?, interpret?) resolve HERE, at
        build time, outside the jit — the dispatch rule of
        kernels/dispatch — and are recorded in
        ``cohort_path``.  Building a bucket also compiles the greedy pick
        over its logits (:meth:`_jit_cohort`)."""
        if bc not in self._cohort_cache:
            cfg = self.cfg
            paged = self.slots.paged
            bs = self.slots.block_size

            from repro.kernels.dispatch import resolve_interpret
            from repro.kernels.fused_decode import (cohort_step,
                                                    fused_supported)
            use_fused = self.use_fused
            if use_fused is None:
                # default: fused only where compiled Pallas actually runs
                # (real TPU, no force_ref override) on ONE device: Mosaic
                # kernels are not partitioned automatically, and a
                # decoder placed on a submesh of several devices runs its
                # step on all of them.  Off-TPU, interpret mode would be
                # far slower than the composed XLA path
                use_fused = (fused_supported(cfg) and not resolve_interpret()
                             and len(self._pool_devices()) == 1)
            interp = bool(use_fused) and resolve_interpret(None)
            self.cohort_path = ("fused" if use_fused else "composed", interp)
            name = f"serve_cohort_b{bc}"       # jit_<name> in the trace

            @jax.named_scope("serve_decode")
            def fn(p, tokens, lengths, slot_ids, tables, pool):
                return cohort_step(
                    p, cfg, tokens, lengths, slot_ids, tables, pool,
                    block_size=bs, paged=paged, use_fused=use_fused,
                    interpret=interp)

            fn.__name__ = fn.__qualname__ = name
            return self._jit_cohort(bc, fn)
        return self._cohort_cache[bc]

    def _pool_devices(self) -> set:
        return {d for leaf in jax.tree.leaves(self.slots.pool)
                for d in leaf.sharding.device_set}

    def _jit_cohort(self, bc: int, fn):
        """Jit cohort step ``fn`` as bucket ``bc``'s, and compile the
        greedy pick over its (bc, V) logits now, so that the first step
        at this bucket compiles nothing more.  The logits' shape comes
        from an abstract trace that the step's first call reuses.  On a
        pool spread over several devices the logits' placement is known
        only from a real step, so the pick compiles at the first one."""
        step = self._cohort_cache[bc] = jax.jit(fn, donate_argnums=(5,))
        args = [jax.ShapeDtypeStruct(shape, jnp.int32) for shape in
                ((bc, 1), (bc,), (bc,), (bc, self.slots.blocks_per_slot))]
        logits, _ = step.eval_shape(self.params, *args, self.slots.pool)
        devices = self._pool_devices()
        if len(devices) == 1:
            self._greedy_pick(logits.shape, logits.dtype,
                              jax.sharding.SingleDeviceSharding(
                                  devices.pop()))
        return step

    def _greedy_pick(self, shape, dtype, sharding):
        """The compiled argmax for logits of this shape, dtype and
        placement, compiled on first use."""
        key = (tuple(shape), np.dtype(dtype), sharding)
        if key not in self._picks:
            self._picks[key] = jax.jit(greedy).lower(jax.ShapeDtypeStruct(
                shape, dtype, sharding=sharding)).compile()
        return self._picks[key]

    def _stage(self, depth_scale: float = 1.0):
        """Synchronous fallback producer (``async_staging=False``): run the
        plan's frontend/projector stages inline for queued vlm requests,
        class by class.  A FULL class ring stalls *that class* — its
        requests keep their FIFO positions and retry next step — while
        later requests of other classes continue staging (per-class
        backpressure, never a bypass, never cross-class head-of-line
        blocking).  The battery knob gates classes exactly like the async
        hand-off: a class whose scaled depth is already met stages
        nothing this step (high-resolution classes shed first)."""
        if self.tabm is None:
            return
        table = self.tabm.admission_table(depth_scale)
        stalled: set = set()                   # classes FULL this pass
        for req in self.queue:
            if req.staged or req.vision_feats is None \
                    or req.share_of is not None:
                continue
            if req.slot_class in stalled:      # keep FIFO within the class
                continue
            ring, cap = table[req.slot_class]
            staged_now = ring.staged_ahead() if ring is not None else 0
            if cap < self.tabm.max_ahead(req.slot_class) \
                    and staged_now >= cap:
                # the *throttle* binds (scaled depth met) — skip the class
                # without touching the ring; plain FULL still goes through
                # produce below so backpressure stalls are observable
                stalled.add(req.slot_class)
                continue
            if not req.stage_submitted:    # one stage_start per request,
                req.stage_submitted = True  # even across FULL-stall retries
                self._trace_event("stage_start", req.rid)
            try:
                slot = self.plan.produce(
                    {"vision_feats": jnp.asarray(req.vision_feats)},
                    slot_class=req.slot_class)
            except Exception as e:             # surface on the owning request
                req.error = e
                req._mark_staged()
                self._trace_event("stage_error", req.rid)
                continue
            if slot is None:                   # class FULL -> stall the class
                stalled.add(req.slot_class)
                continue
            req.tabm_slot = slot
            req._mark_staged()
            self._trace_event("stage_commit", req.rid)

    def _class_stage_batch(self, slot_class: Optional[str]) -> int:
        """The effective staging microbatch for one class *right now*:
        the engine override, else min(arch ``max_stage_batch``, battery
        ``Knobs.max_stage_batch``) — THROTTLED shrinks the batch before
        any depth sheds — clamped to the class ring's capacity (a slab
        larger than the ring could never commit)."""
        if self._stage_batch_override is not None:
            cap = self._stage_batch_override
        else:
            _, knobs, _ = self.executor.current()
            cap = min(self.cfg.max_stage_batch, knobs.max_stage_batch)
        if self.tabm is not None and slot_class is not None:
            cap = min(cap, self.tabm.classes[slot_class].n_slots)
        return max(1, cap)

    def _feed_staging(self, knobs=None):
        """Admission's producer hand-off, charged per class *and per
        microbatch*: each round, every class collects its eligible queued
        requests — up to its staged-ahead depth budget
        (core/scheduler.class_staging_budgets), itself capped at one
        staging microbatch — and hands them to its class thread as ONE
        list, which the worker commits as one strided slab
        (``produce_many``).  The depth cap is each class's own
        ``max_ahead`` — by default the class ring's capacity, so the
        hand-off queue is bounded by the ring and shutdown cancellation
        stays cheap — scaled by the battery knob ``class_depth_scale``
        (high-resolution classes shrink first; the microbatch shrinks
        before that).  A class with no budget (FULL, throttled, or
        saturated hand-off) is simply skipped; later requests of other
        classes still hand off — the class isolation the single FIFO cap
        could not give."""
        if knobs is None:
            _, knobs, _ = self.executor.current()
        # the battery knobs are constant within one admission round: read
        # them once (the caller's copy), clamp per class against the
        # static ring capacities — never re-poll the executor per request
        if self._stage_batch_override is not None:
            global_cap = max(1, self._stage_batch_override)
        else:
            global_cap = max(1, min(self.cfg.max_stage_batch,
                                    knobs.max_stage_batch))
        budgets = class_staging_budgets(
            self.tabm, self._worker.in_flight_by_class(),
            knobs.class_depth_scale, stage_batch=global_cap)
        groups: Dict[str, List[Request]] = {}
        for req in self.queue:
            if req.staged or req.stage_submitted \
                    or req.vision_feats is None or req.share_of is not None:
                continue
            # budgets are already microbatch- and ring-capacity-capped
            if len(groups.get(req.slot_class, ())) >= \
                    budgets.get(req.slot_class, 0):
                continue                       # class exhausted; others go on
            req.stage_submitted = True
            groups.setdefault(req.slot_class, []).append(req)
        for batch in groups.values():          # one hand-off = one microbatch
            self._worker.submit(batch)

    def _ring_of(self, req: Request):
        """The class ring holding this request's staged embeds."""
        return self.tabm.ring(req.slot_class)

    def _bind_vision(self, req: Request) -> Optional[jnp.ndarray]:
        """Consumer half: per-slot ready wait on the request's class ring,
        then bind that ring's oldest READY slot as the prefill's vision
        input.  FIFO commit order == FIFO admission order *within a
        class*, so the bound slot is this request's; the seqlock
        generation is captured so release can assert the zero-copy view
        stayed valid across the prefill."""
        if req.tabm_slot is None:
            return None
        if req.share_of is not None:
            # refcounted read view of the owner's consumed slot — the
            # slab was staged once, this request never touched the ring
            got = self.plan.shared_view(req.tabm_slot, req._tabm_gen,
                                        slot_class=req.slot_class)
            if got is None:
                raise TABMError(
                    f"shared slot {req.tabm_slot} ({req.slot_class}) "
                    f"recycled before request {req.rid} bound its view")
            view, n = got
            if self.capture_slab:
                req.slab = np.array(view[:n])      # host copy, trimmed
            return view[None, :n]
        # normally immediate — admission only runs once `staged` is set,
        # which the worker sets strictly after commit — but this is the
        # formal consumer-side gate (and the blocking point if admission
        # ever runs ahead of the staged flag)
        with self.probe.span("tabm.wait_ready", "tabm", "wait_ready"):
            ready = self.plan.wait_ready(req.tabm_slot, timeout=30.0,
                                         slot_class=req.slot_class)
        if not ready:
            raise TABMError(
                f"slot {req.tabm_slot} ({req.slot_class}) did not become "
                f"READY (aborted, ring closed, or timed out)")
        got = self.plan.consume(slot_class=req.slot_class)
        if got is None or got[0] != req.tabm_slot:
            # enforced with a real raise (not assert): this is the
            # per-class FIFO contract the zero-copy hand-off stands on
            raise TABMError(
                f"consume returned {got and got[0]}, expected request "
                f"{req.rid}'s slot {req.tabm_slot} of class "
                f"{req.slot_class} (per-class FIFO order broken)")
        slot, view, n = got
        req._tabm_gen = self._ring_of(req).slot_generation(slot)
        self._grant_shares(req, slot)
        if self.capture_slab:
            req.slab = np.array(view[:n])          # host copy, trimmed
        return view[None, :n]

    def _grant_shares(self, owner: Request, slot: int):
        """The owner's slab just got consumed: grant every waiting twin
        a refcounted view of the same slot (tabm.addref) so they admit
        without ever staging.  A twin the addref misses (slot already
        on its way out) falls back to staging privately."""
        if owner._share_key is not None and \
                self._stage_keys.get(owner._share_key) is owner:
            self._stage_keys.pop(owner._share_key)
        for s in owner.sharers:
            if (s.error is not None or s.finish_mt is not None
                    or s.share_of is not owner):
                continue
            if self.plan.addref(slot, owner._tabm_gen,
                                slot_class=owner.slot_class):
                s.tabm_slot = slot
                s._tabm_gen = owner._tabm_gen
                s._mark_staged()           # admissible, no staging needed
                self._trace_event("stage_share", s.rid)
            else:
                s.share_of = None          # stage privately instead
        owner.sharers = []

    def _unshare(self, req: Request):
        """A request leaves the dedup registry (failed or shut down):
        sharers not yet granted a view go back to staging privately."""
        if req._share_key is not None and \
                self._stage_keys.get(req._share_key) is req:
            self._stage_keys.pop(req._share_key)
        for s in req.sharers:
            if s.share_of is req and s.tabm_slot is None:
                s.share_of = None
        req.sharers = []

    def _fail(self, req: Request):
        self._unshare(req)
        if req.finish_mt is None:
            req.finish_mt = time.monotonic()
        self.stats.failed += 1
        self._trace_event("failed", req.rid)
        self.done.append(req)

    def _apply_backend_knobs(self, knobs):
        """The PowerPolicy re-lowering hook: demote the static-shape
        (encoder-side) bricks to the knob's cheaper backend under deep
        THROTTLED, and restore the compiled substrate when charge
        recovers.  plan.relower swaps each step atomically, so the
        staging thread's in-flight produce is never torn."""
        target = knobs.backend_demotion
        if target == self._demoted_to:
            return
        for s in list(self.plan.steps):
            if not s.brick.static_shape:
                continue
            self.plan.relower(
                s.brick.name,
                target if target is not None
                else self._lowered_backends[s.brick.name])
        self._demoted_to = target
        self._trace_event(f"relower:{target or 'restore'}", -1)

    def _group_key(self, req: Request):
        """Bucket-match key for grouped prefill: requests sharing a
        prompt bucket and an identical vision spec (class + staged token
        count — one slab shape, one compiled prefill signature) may
        prefill as one batch.  Text-only requests group by bucket."""
        bucket = bucket_length(len(req.tokens), buckets=self._buckets())
        vis = None
        if self.tabm is not None and req.vision_feats is not None:
            vis = (req.slot_class,
                   int(np.asarray(req.vision_feats).shape[1]))
        return (bucket, vis)

    def _admissible(self, req: Request) -> bool:
        return not (self.tabm is not None and req.vision_feats is not None
                    and not req.staged)

    def _block_need(self, req: Request) -> int:
        """KV blocks this request's lifetime needs: the block-aligned
        prompt bucket (the prefill writes that many), grown to cover
        max_new_tokens of decode, capped at a full slot's worth."""
        bs = self.slots.block_size
        bucket = bucket_length(len(req.tokens), buckets=self._buckets())
        aligned = -(-bucket // bs) * bs
        want = max(aligned,
                   min(self.max_len, len(req.tokens) + req.max_new_tokens))
        return min(self.slots.blocks_per_slot, -(-want // bs))

    def _collect_group(self, i: int, max_n: int,
                       kv_budget: Optional[int] = None) -> List[Request]:
        """Pop the maximal run of *consecutive* bucket-matched admissible
        requests starting at queue position i (consecutive, so per-class
        ring-FIFO consume order and overall admission FIFO both hold).
        The run also stops where its cumulative KV-block need would
        outrun the free pool (or the class's battery-scaled block
        budget) — the caller admits what fits, the rest keeps FIFO."""
        key = self._group_key(self.queue[i])
        blocks_left = self.slots.free_block_count
        if kv_budget is not None:
            blocks_left = min(blocks_left, kv_budget)
        blocks_left -= self._block_need(self.queue[i])
        j = i + 1
        while j < len(self.queue) and j - i < max_n:
            nxt = self.queue[j]
            if (nxt.error is not None or not self._admissible(nxt)
                    or self._group_key(nxt) != key):
                break
            need = self._block_need(nxt)
            if need > blocks_left:
                break
            blocks_left -= need
            j += 1
        group = self.queue[i:j]
        del self.queue[i:j]
        return group

    def _admit_group(self, group: List[Request]):
        """One batch-B prefill call for a bucket-matched group: bind each
        request's staged slab view (class-FIFO consume order == group
        order), run the compiled bucket prefill once over the stacked
        batch, then write all B prefilled caches into B KV slots in a
        single strided ``insert_many``.  On any failure the whole group
        fails: every KV slot and every consumed ring slot is released —
        nothing leaks, the engine keeps serving.  Unlike the staging
        side there is no one-by-one retry: the ring slots were already
        consumed, so releasing them destroys the staged vision (a retry
        would need a full restage), and a prefill-time failure is
        batch-level in practice — the per-request inputs (bucketed int
        tokens, validated slab views) cannot individually fail a
        compiled call."""
        # the group's prefill span; a failed group is not measured
        span = self.probe.span("serve.prefill", "decoder", "prefill").start()
        for req in group:
            req.admit_t = span.t0
        taken: List[int] = []
        try:
            for req in group:
                slot = self.slots.take_slot()
                if slot is None:               # sized by the caller; defensive
                    raise RuntimeError("KV slots exhausted mid-group")
                taken.append(slot)
                # the lifetime block grant, charged to the class — the
                # caller (_collect_group) sized the group to fit
                self.slots.grant_blocks(slot, self._block_need(req),
                                        slot_class=req.slot_class)
            B = len(group)
            bucket = self._group_key(group[0])[0]
            padded = np.zeros((B, bucket), np.int32)
            lens = np.zeros((B,), np.int32)
            for b, req in enumerate(group):
                prompt = np.asarray(req.tokens, np.int32)
                padded[b, :len(prompt)] = prompt   # right-pad into the bucket
                lens[b] = len(prompt)
            views = [v for v in (self._bind_vision(r) for r in group)
                     if v is not None]
            vision = jnp.concatenate(views, axis=0) if views else None
            logits, cache = self._prefill_fn(bucket)(
                self.params, jnp.asarray(padded), vision,
                jnp.asarray(lens))
            for req in group:                  # prefill consumed the views
                if req.tabm_slot is not None:
                    if not self._ring_of(req).view_valid(req.tabm_slot,
                                                         req._tabm_gen):
                        raise TABMError(
                            f"slot {req.tabm_slot} recycled under request "
                            f"{req.rid}'s zero-copy view (seqlock "
                            f"violation)")
                    self.plan.release(req.tabm_slot,
                                      slot_class=req.slot_class)
        except Exception as e:
            # neither a KV slot nor a ring slot may leak, and every
            # request must still be accounted for (e.g. the ring closed
            # under a concurrent shutdown mid-admission): fail the group,
            # keep serving
            for req in group:
                if req.tabm_slot is None:
                    pass
                elif (req._tabm_gen is not None
                        and self._ring_of(req).view_valid(req.tabm_slot,
                                                          req._tabm_gen)):
                    self.plan.release(req.tabm_slot,   # consumed, unreleased
                                      slot_class=req.slot_class)
                elif req._tabm_gen is None:
                    # staged but never consumed (a bind earlier in the
                    # group raised): its committed slot is the class
                    # ring's oldest READY — pull it out and release, or
                    # an ownerless slot would wedge every later same-
                    # class consume (per-class FIFO).  A closed ring
                    # (consume -> None) is drained at shutdown instead.
                    got = self.plan.consume(slot_class=req.slot_class)
                    if got is not None and got[0] == req.tabm_slot:
                        self.plan.release(got[0], slot_class=req.slot_class)
                req.error = e
                self._fail(req)
            for slot in taken:
                self.slots.release(slot)
            span.end(keep=False)
            return
        self.slots.insert_many(taken, cache, [int(n) for n in lens])
        for b, (slot, req) in enumerate(zip(taken, group)):
            req.slot = slot
            self.live[slot] = req
            self.stats.prefills += 1
            self._trace_event("prefill", req.rid)
            # first token from this request's row of the prefill logits
            tok = self._pick(logits[b:b + 1], [req])
            req.out_tokens.append(int(tok[0]))
            req.first_token_mt = time.monotonic()
        if len(group) > 1:                     # the acceptance evidence
            self._trace_event("prefill_batch", len(group))
        # measured prefill span: ends past insert_many and the first-token
        # reads, so device work is complete — true wall time of the group
        span.tokens = int(lens.sum())
        span.end()

    def _admit(self):
        """One admission round (the ``serve.admit`` span)."""
        with self.probe.span("serve.admit", "engine", "admit"):
            self._admit_round()

    def _admit_round(self):
        state, knobs, _ = self.executor.current()
        self._apply_backend_knobs(knobs)
        power_ok = (knobs.admission_rate > 0
                    or state is PowerState.UNCONSTRAINED)
        if power_ok:
            if self._worker is not None:
                # producer threads run ahead, charged per class and scaled
                # by the battery knob (batch shrinks first, then high-res
                # classes shed depth)
                self._feed_staging(knobs)
            else:
                # sync fallback: inline, same per-class battery gating —
                # the equivalence oracle throttles like the async path
                self._stage(knobs.class_depth_scale)
        budget = min(len(self.slots.free), knobs.max_batch)
        if not power_ok:
            budget = 0
        # per-class KV *block* budgets, battery-scaled exactly like the
        # staging depth (shed_scales): under THROTTLED the hi-res
        # classes' share of the paged pool shrinks first, so expensive
        # long-context grants are shed while thumbnails keep admitting
        kv_budgets = None
        if self.tabm is not None:
            kv_budgets = kv_block_budgets(
                self.tabm, self.slots.n_blocks, self.slots.used_blocks,
                knobs.class_kv_scale,
                energy_pressure=self._kv_energy_pressure())
        # cross-class aging: classes of requests that have waited out
        # aging_steps admission rounds while skipped (class stalled or
        # slow); each holds one KV-slot reservation that newer requests
        # of OTHER classes may not take — a thumbnail flood can no longer
        # absorb every freed slot while a hi-res head waits.  A class the
        # battery policy deliberately shed (depth gated to zero) earns no
        # reservation: fairness must not undo the power policy's choice
        # to keep cheap classes flowing.
        shed: set = set()
        if self.tabm is not None:
            shed = {name for name, (_, cap) in self.tabm.admission_table(
                knobs.class_depth_scale).items() if cap <= 0}
        # ONE reservation per aged class, not per aged request: a class
        # admits FIFO, so one held slot guarantees its aged head makes
        # progress, while a deeply-backlogged class can never reserve the
        # whole KV pool away from everyone else
        aged_classes: set = set()
        # classes with a request skipped earlier in THIS pass: later
        # classmates must be skipped too, even if their staged flag reads
        # True by now — admission samples `staged` at different times per
        # request, and admitting a younger classmate whose older sibling
        # was mid-staging a moment ago would consume the sibling's ring
        # slot (per-class FIFO violation)
        stalled: set = set()
        i = 0
        while i < len(self.queue) and budget > 0:
            req = self.queue[i]
            if not self._admissible(req) or (
                    req.vision_feats is not None
                    and req.slot_class in stalled):
                # this request's class producer is stalled (FULL ring,
                # throttled depth, or an earlier classmate this pass) —
                # skip it, keep its FIFO position, and let staged
                # requests of *other* classes admit behind it: a stalled
                # high-res class never blocks thumbnails
                stalled.add(req.slot_class)
                req.aging += 1                 # a real skip, not residency
                if req.aging >= self.aging_steps \
                        and req.slot_class not in shed:
                    aged_classes.add(req.slot_class)
                i += 1
                continue
            # error is read only after the staged flag: the worker stores
            # error before staged=True, so a failed request can never slip
            # through as staged-with-no-slot and prefill without vision
            if req.error is not None:          # staging failed: finish failed
                self.queue.pop(i)
                self._fail(req)
                continue
            # KV slots reserved by aged classes other than this request's
            # stay free for them (their class may stage any round now)
            reserved = sum(1 for c in aged_classes if c != req.slot_class)
            avail = len(self.slots.free) - reserved
            if avail <= 0:
                if req.vision_feats is not None:
                    stalled.add(req.slot_class)    # keep class FIFO
                req.aging += 1
                i += 1                         # reserved: skip, keep position
                continue
            # paged-KV admission: the head's lifetime block need must fit
            # the class's battery-scaled share (hi-res classes shed
            # first) AND the free pool; a gated head keeps its FIFO
            # position — blocks freed by any finishing request are
            # grantable the very next round (continuous batching)
            need = self._block_need(req)
            kv_cap = (kv_budgets.get(req.slot_class)
                      if kv_budgets is not None
                      and req.vision_feats is not None else None)
            if kv_cap is not None and need > kv_cap:
                stalled.add(req.slot_class)    # keep class FIFO
                req.aging += 1
                self._trace_event("kv_gated", req.rid)
                i += 1
                continue
            if need > self.slots.free_block_count:
                if req.vision_feats is not None:
                    stalled.add(req.slot_class)
                req.aging += 1
                i += 1
                continue
            group = self._collect_group(i, min(budget, avail),
                                        kv_budget=kv_cap)
            budget -= len(group)
            self._admit_group(group)
            # queue shrank at position i: the next candidate is at i again
        if not self.live and self.queue:
            waiter = None
            if self._worker is not None:
                # idle consumer waiting on the producer: park briefly on
                # the first pending staged event instead of hot-spinning
                # the loop (only stage_submitted requests qualify — the
                # worker WILL stage those; gated heads won't set it)
                waiter = next((r for r in self.queue
                               if r.error is None and r.stage_submitted
                               and not r.staged), None)
            if waiter is not None:
                with self.probe.span("serve.park", "engine", "park"):
                    waiter._staged_ev.wait(0.05)
            elif not any(r.staged and r.error is None for r in self.queue):
                # nothing live, nothing admissible, nothing being staged —
                # every queued request is power- or class-depth-gated.
                # Breathe instead of hot-spinning the step loop at full
                # CPU (which would burn the very battery the throttle is
                # conserving) until charge recovers.
                with self.probe.span("serve.park", "engine", "park"):
                    time.sleep(0.005)

    def _pick(self, logits, reqs: List[Request]):
        """The next token of every row of ``logits`` (n, V), in one device
        call: an (n,) int32 device array.  Row b is ``reqs[b]``'s; rows
        past ``len(reqs)`` are cohort padding, picked greedily and
        ignored.  When every request is greedy the rows take the
        compiled argmax (ties to the lowest index); otherwise all go
        through ``sample_rows`` with one key split for the call."""
        if all(r.temperature == 0.0 for r in reqs):
            return self._greedy_pick(logits.shape, logits.dtype,
                                     logits.sharding)(logits)
        temps = np.zeros((logits.shape[0],), np.float32)
        temps[:len(reqs)] = [r.temperature for r in reqs]
        self.key, k = jax.random.split(self.key)
        return sample_rows(logits, k, jnp.asarray(temps))

    def _buckets(self):
        caps = [b for b in (128, 256, 512, 1024, 2048, 4096)
                if b <= self.max_len - 1]
        return tuple(caps) or (self.max_len - 1,)

    def step(self):
        self._admit()
        if not self.live:
            self.stats.steps += 1
            return
        # cohort decode: every in-flight request rides ONE batched jit
        # step, padded to a power-of-two cohort bucket (sentinel rows:
        # gathers fill, scatters drop).  Rows are independent, so a
        # request admitted or retired between steps never perturbs the
        # others' tokens — mid-flight continuous batching
        cohort = self._cohort_slots()
        bc = self._cohort_bucket(len(cohort))
        tokens = np.zeros((bc, 1), np.int32)
        lengths = np.zeros((bc,), np.int32)
        slot_ids = np.full((bc,), self.slots.n_slots, np.int32)
        tables = np.full((bc, self.slots.blocks_per_slot),
                         self.slots.n_blocks, np.int32)
        tables[:len(cohort)] = self.slots.gather_tables(cohort)
        for b, slot in enumerate(cohort):
            req = self.live[slot]
            tokens[b, 0] = req.out_tokens[-1]
            lengths[b] = self.slots.lengths[slot]
            slot_ids[b] = slot
        # measured decode span for the telemetry ledger, in three parts
        # that tile it: launch (dispatch of the cohort step), wait (the
        # pick's dispatch and its one read, which returns once the device
        # step is done) and sample (the host bookkeeping of every row).
        # The read syncs, so the span is true wall time of one cohort
        # step (host clocks only — replint-clean)
        span = self.probe.span("serve.decode", "decoder", "decode",
                               tokens=len(cohort)).start()
        span.part("serve.decode.launch", "decode.launch")
        if self.stats.start_t is None:
            self.stats.start_t = span.t0
        logits, self.slots.pool = self._cohort_fn(bc)(
            self.params, jnp.asarray(tokens), jnp.asarray(lengths),
            jnp.asarray(slot_ids), jnp.asarray(tables), self.slots.pool)
        self.stats.steps += 1
        self._trace_event("decode_cohort", len(cohort))
        span.part("serve.decode.wait", "decode.wait")
        # every row, padding included, in one pick: one compiled pick per
        # bucket, where slicing off the live rows would compile one per
        # live count
        picked = self._pick(logits, [self.live[s] for s in cohort])
        # deliberate per-step sampling read: the ids feed the next step's
        # host-side token buffer and EOS checks
        toks = np.asarray(picked).tolist()  # replint: disable=host-sync
        self.stats.sample_reads += 1
        span.part("serve.decode.sample", "decode.sample")

        finished = []
        for b, slot in enumerate(cohort):
            req = self.live[slot]
            t = toks[b]
            req.out_tokens.append(t)
            self.slots.bump(slot)
            self.stats.decoded_tokens += 1
            over_len = self.slots.lengths[slot] + 1 >= self.max_len
            if (t == EOS_ID or len(req.out_tokens) >= req.max_new_tokens
                    or over_len):
                req.finish_mt = time.monotonic()
                finished.append(slot)
        span.end()
        for slot in finished:
            req = self.live.pop(slot)
            self.done.append(req)
            # the retiring request's KV blocks return to the free pool
            # NOW — grantable to the next admission round, mid-flight
            self.slots.release(slot)
            self.stats.finished += 1
            self._trace_event("finish", req.rid)

    # -- disaggregated fleets (serving/disagg.py) ----------------------------
    def prefill_step(self) -> List[Request]:
        """One admission round without decoding — the prefill fleet's
        step: staging hand-off + grouped batched prefill exactly as
        :meth:`step` would run them, but the newly admitted requests
        (prefilled cache landed, first token picked from the prefill
        logits) are *returned* instead of decoded, ready for
        :meth:`export_remote`.  Requests whose staging failed land in
        ``done`` as usual."""
        before = set(self.live)
        self._admit()
        self.stats.steps += 1
        return [self.live[s] for s in sorted(set(self.live) - before)]

    def export_remote(self, req: Request):
        """Hand a just-prefilled request off the engine as a
        :class:`~repro.core.transport.RemotePrefill`: export the
        *written* KV blocks (the block-aligned prompt bucket — never the
        whole grant, never a whole lane), pop the request from the live
        set, and release its slot and blocks — this engine is done with
        it; the decode fleet owns it now.  Must run before any decode
        step touches the slot (the prefill fleet never decodes, so the
        per-slot length still equals the prompt length)."""
        from repro.core.transport import RemotePrefill
        slot = req.slot
        if slot is None or self.live.get(slot) is not req:
            raise RuntimeError(
                f"request {req.rid} is not live on this engine")
        bs = self.slots.block_size
        bucket = bucket_length(len(req.tokens), buckets=self._buckets())
        nb_written = -(-bucket // bs)
        granted = len(self.slots.block_tables[slot])
        rp = RemotePrefill(
            rid=req.rid,
            prompt=np.asarray(req.tokens, np.int32),
            first_token=int(req.out_tokens[0]),
            max_new_tokens=int(req.max_new_tokens),
            blocks_granted=granted,
            paged=self.slots.paged,
            kv=self.slots.export_blocks(slot, nb_written),
            slot_class=req.slot_class,
            slab=req.slab,
            prompt_len=int(self.slots.lengths[slot]))
        del self.live[slot]
        self.slots.release(slot)
        req.slot = None
        self._trace_event("export_remote", req.rid)
        return rp

    def admit_remote(self, msg) -> bool:
        """Admit a :class:`~repro.core.transport.RemotePrefill` streamed
        from a prefill fleet straight into the paged pool: take a slot,
        grant the request's full block count, land the shipped written
        blocks (:meth:`PagedKVCache.import_blocks`), and enter the
        request live with its first token — from here :meth:`step`
        decodes it exactly like a locally prefilled request (same cohort
        step, same EOS/max-new semantics: bit-identical tokens).

        Returns False — admit nothing, change nothing — when no slot or
        too few free blocks are available; the caller decodes a step to
        retire capacity and retries (continuous batching across the
        fleet boundary)."""
        if self._closed:
            raise EngineClosed("engine already shut down")
        if tuple(msg.paged) != tuple(self.slots.paged):
            raise RuntimeError(
                f"remote prefill paged layout {tuple(msg.paged)} does not "
                f"match this pool's {tuple(self.slots.paged)} (fleet "
                f"config mismatch)")
        if int(msg.blocks_granted) > self.slots.free_block_count:
            return False
        slot = self.slots.take_slot()
        if slot is None:
            return False
        self.slots.grant_blocks(slot, int(msg.blocks_granted),
                                slot_class=msg.slot_class)
        self.slots.import_blocks(slot, msg.kv)
        self.slots.lengths[slot] = int(msg.prompt_len)
        req = Request(rid=int(msg.rid),
                      tokens=np.asarray(msg.prompt, np.int32),
                      max_new_tokens=int(msg.max_new_tokens),
                      slot_class=msg.slot_class)
        req.slot = slot
        req.out_tokens.append(int(msg.first_token))
        # its life on this engine begins here, with its first token
        req.submit_t = req.staged_t = req.admit_t = req.first_token_mt = \
            time.monotonic()
        req._staged_ev.set()
        self.live[slot] = req
        self.stats.prefills += 1
        self._trace_event("admit_remote", req.rid)
        return True

    # -- reporting / telemetry ----------------------------------------------
    def memory_bytes(self) -> Dict[str, int]:
        from repro.core.quantize import tree_bytes
        return {"weights": tree_bytes(self.params),
                "kv_pool": self.slots.nbytes,
                "tabm": self.tabm.nbytes if self.tabm else 0}

    def _kv_energy_pressure(self) -> float:
        """Measured-over-modeled decode J/token ratio for kv_block_budgets
        (cached: one scheduler lookup, not one per admission round).
        1.0 — i.e. no tightening — without a calibration table, without
        an energy observation, or when the plan carries no accelerator
        identities to price the model against."""
        if self.calibration is None:
            return 1.0
        if self._kv_pressure is None:
            from repro.core.scheduler import brick_cost
            press = 1.0
            for s in self.plan.steps:
                if s.brick.kind == "decoder" and s.accel is not None:
                    modeled = brick_cost(s.brick, s.accel, 1)
                    press = self.calibration.energy_pressure(
                        s.brick.name, s.accel.profile.name,
                        modeled.energy_j)
                    break
            self._kv_pressure = press
        return self._kv_pressure

    def jit_counts(self) -> Dict[str, int]:
        """jaxpr traces, backend compiles and persistent-cache loads in
        this process since the engine was built (``jit.*`` spans in
        :attr:`probe`)."""
        now = jit_counts()
        return {k: now[k] - self._jit_base[k] for k in now}

    def measured_ledger(self) -> Ledger:
        """The dynamic (probe-fed) telemetry ledger of this engine run:
        per-brick staging spans recorded by the plan plus the engine's
        prefill/decode spans, folded per (brick, phase)."""
        return self.probe.to_ledger(meta={"collector": "serving-engine"})

    def measured_calibration(self, prior: int = 4) -> CostCalibration:
        """A scheduler-consumable calibration table from this run's
        measured ledger — the feedback loop closed in one call:
        ``schedule(graph, accels, n, calibration=eng.measured_calibration())``
        prices the next placement from what this engine observed."""
        return CostCalibration.from_ledger(self.measured_ledger(),
                                           prior=prior)
