"""ExecutionPlan — the one brick runtime (paper §3.1–3.2 made executable).

This module compiles ``(BrickGraph, Placement, TABM ring, SubmeshPipes)``
into *bound, jit-cached per-brick callables* with typed input/output ports.
It is the single execution path behind the serving engine, the cascade
runner, and the scheduler — the three previously divergent interpreters of
a BrickGraph.

Paper-term → API mapping:

* **Model decomposition (§3.1)** — the :class:`~repro.core.bricks.BrickGraph`
  chain with per-brick :class:`~repro.core.bricks.Port` declarations.  The
  plan validates the wiring at compile time (every required input port is
  either produced upstream or named an external input) and type-checks port
  values (int tokens vs float features) when they bind.
* **Module-level offloading (§3.2)** — a ``Placement`` from
  :func:`repro.core.scheduler.schedule` binds each brick to an
  :class:`~repro.core.scheduler.Accelerator`, and each accelerator names a
  :class:`~repro.core.backends.Backend` — the substrate the brick lowers
  to.  ``compile_plan`` consults the backend table (never ``accel.mesh``
  branches): ``SubmeshBackend`` device_puts weights onto the submesh and
  wires :class:`~repro.core.scheduler.SubmeshPipe` edges (ICI, never the
  host); ``DeviceBackend`` commits weights to one device;
  ``HostBackend`` keeps them host-side and loads per execution.  The same
  Placement therefore executes identically on any substrate, and
  :meth:`ExecutionPlan.relower` moves one brick to a cheaper backend at
  runtime (the battery policy's THROTTLED hook).
* **Embeddings zero-copy transfer / TABM (§3.2)** — the edge whose producer
  emits ``vision_embeds`` routes through a
  :class:`~repro.core.tabm.RingBuffer` (or a class-partitioned
  :class:`~repro.core.tabm.SlotClassPool`, one class-sized ring per
  image-count × resolution bucket): :meth:`ExecutionPlan.produce` runs
  the upstream (encoder-side) stages and commits into a slot (donation =
  the TPU zero-copy), :meth:`ExecutionPlan.consume` binds the oldest READY
  slot for the decoder side, and a full ring stalls the producer — the
  backpressure signal the engine's admission loop obeys, per class, so a
  FULL high-resolution class never blocks thumbnail staging.
* **On-demand cascade (§3.2, Fig. 2)** — ``residency="one-brick"`` lowers
  every brick through the transient ``HostBackend``: params host-side,
  each brick load → execute → release, recording a :class:`PlanTrace`
  that proves peak memory is max(brick) not sum(bricks).
  ``residency="resident"`` (default) binds all brick params once for
  serving.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backends import Backend, BACKENDS, resolve_backend
from repro.core.bricks import Brick, BrickGraph, Port
from repro.core.tabm import SlotClassPool


class PlanError(RuntimeError):
    pass


def _nbytes(tree) -> int:
    total = 0
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
        elif hasattr(leaf, "size"):
            total += int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
    return total


# ---------------------------------------------------------------------------
# trace (the cascade's residency evidence; cheap enough to always record)
# ---------------------------------------------------------------------------

@dataclass
class PlanEvent:
    brick: str
    phase: str                 # load | execute | release
    t: float
    resident_bytes: int


@dataclass
class PlanTrace:
    events: List[PlanEvent] = field(default_factory=list)
    peak_bytes: int = 0
    sum_bytes: int = 0         # what a monolithic load would have held

    def record(self, brick, phase, resident):
        self.events.append(PlanEvent(brick, phase, time.time(), resident))
        self.peak_bytes = max(self.peak_bytes, resident)


# ---------------------------------------------------------------------------
# compiled steps
# ---------------------------------------------------------------------------

@dataclass
class PlanStep:
    """One brick bound to its backend, accelerator, params, and callable."""

    brick: Brick
    fn: Callable                       # jitted (params, ctx) -> out
    params: Any                        # backend-bound tree (device | host)
    backend: Backend                   # the lowering substrate
    accel: Optional[object] = None     # scheduler.Accelerator or None
    inbound: Dict[str, Callable] = field(default_factory=dict)
    # inbound: port name -> transfer fn applied when the value was produced
    # on a different accelerator (backend.make_edge: SubmeshPipe.transfer,
    # committed device_put, or host pull)


class ExecutionPlan:
    """Bound, executable form of a BrickGraph.

    Built by :func:`compile_plan`; see the module docstring for the paper
    mapping.  The three consumers:

    * ``plan.run(inputs)`` — one full forward pass (logits), used by tests,
      examples, and the cascade runner.
    * ``plan.produce / consume / release`` — the TABM edge split into its
      producer/consumer halves, used by the serving engine so vision
      encoding and decoder admission decouple through the ring.
    * ``plan.brick_params(name)`` — the placement-bound weights, used by
      launchers that keep specialized compiled fns (cached prefill/decode).
    """

    def __init__(self, graph: BrickGraph, steps: List[PlanStep], *,
                 residency: str, tabm=None, tabm_producer: Optional[int] = None,
                 tabm_transfer: Optional[Callable] = None,
                 input_ports: Tuple[Port, ...] = (), probe=None):
        self.graph = graph
        self.cfg = graph.cfg
        self.steps = steps
        self.residency = residency
        self.tabm = tabm
        self._tabm_producer = tabm_producer
        self._tabm_transfer = tabm_transfer
        self.input_ports = input_ports
        # optional telemetry WallProbe: per-brick wall-time spans recorded
        # by run()/produce_many() (host clocks only — on async resident
        # backends a span measures dispatch, a calibrated lower bound;
        # transient backends sync, so theirs is true wall time)
        self.probe = probe
        self._params = None            # full tree, kept for relower()
        # "what a monolithic load would have held": each top-level param
        # entry once — tied-embedding archs share "embed" between the
        # embedding and head bricks and must not count it twice
        merged: Dict[str, Any] = {}
        for s in steps:
            merged.update(s.params)
        self._sum_bytes = _nbytes(merged)
        self._resident_bytes = self._resident_baseline()

    def _resident_baseline(self) -> int:
        """Bytes held by resident-backend steps between executions (tied
        params counted once); transient (host) steps contribute zero.
        Cached as ``_resident_bytes``; recomputed only by relower()."""
        merged: Dict[str, Any] = {}
        for s in self.steps:
            if s.backend.resident:
                merged.update(s.params)
        return _nbytes(merged)

    # -- introspection ------------------------------------------------------
    def brick_params(self, name: str) -> Any:
        for s in self.steps:
            if s.brick.name == name:
                return s.params
        raise KeyError(name)

    def backend_of(self, name: str) -> Backend:
        for s in self.steps:
            if s.brick.name == name:
                return s.backend
        raise KeyError(name)

    def describe(self) -> str:
        rows = []
        for s in self.steps:
            ins = ",".join(p.name + ("?" if p.optional else "")
                           for p in s.brick.in_ports)
            acc = s.accel.name if s.accel is not None else "-"
            rows.append(f"{s.brick.name}({ins})->{s.brick.out_port.name}"
                        f"@{acc}/{s.backend.name}")
        return " | ".join(rows)

    # -- re-lowering (the battery policy's THROTTLED hook) ------------------
    def relower(self, brick_name: str, backend) -> PlanStep:
        """Re-lower one brick to a different backend at runtime: re-bind
        its params and swap in the (shared, jit-cached) executable for
        that substrate.  The step is replaced atomically, so a concurrent
        ``produce`` on the staging thread sees either the old or the new
        step, never a half-built one.  Routing (accel identity, inbound
        transfers) is preserved — re-lowering changes where the brick's
        *weights and compute* live, not the graph wiring."""
        be = resolve_backend(backend)
        for i, s in enumerate(self.steps):
            if s.brick.name != brick_name:
                continue
            if s.backend is be:
                return s
            if self._params is None:
                raise PlanError("plan kept no full param tree; relower "
                                "is only available on compile_plan output")
            new = PlanStep(
                brick=s.brick, fn=be.compile_fn(s.brick, self.cfg),
                params=be.bind_params(s.brick, self._params, s.accel),
                backend=be, accel=s.accel, inbound=s.inbound)
            self.steps[i] = new        # atomic swap under the GIL
            self._resident_bytes = self._resident_baseline()
            return new
        raise KeyError(brick_name)

    # -- execution ----------------------------------------------------------
    @staticmethod
    def _check_port(port: Port, value):
        kind = jnp.asarray(value).dtype.kind if not hasattr(value, "dtype") \
            else jnp.dtype(value.dtype).kind
        want = "iu" if port.dtype_kind == "int" else "fV"
        if kind not in want + ("b" if port.dtype_kind == "int" else ""):
            raise PlanError(f"port {port.name!r} expects {port.dtype_kind} "
                            f"values, got dtype kind {kind!r}")

    def _gather(self, step: PlanStep, env, env_src):
        ctx = {}
        for port in step.brick.in_ports:
            if port.name not in env or env[port.name] is None:
                if port.optional:
                    continue
                raise PlanError(f"brick {step.brick.name!r} missing required "
                                f"input port {port.name!r}")
            v = env[port.name]
            self._check_port(port, v)
            src = env_src.get(port.name)
            if src is not step.accel and port.name in step.inbound:
                v = step.inbound[port.name](v)
            ctx[port.name] = v
        return ctx

    def _load(self, step: PlanStep):
        return step.backend.load(step.brick, step.params)

    def run(self, inputs: Dict[str, Any],
            trace: Optional[PlanTrace] = None) -> Tuple[Any, PlanTrace]:
        """One full inference pass through every brick.  Returns the final
        brick's output (logits) and the residency trace.  When a TABM ring
        is attached, the vision_embeds edge really goes through a slot
        (commit -> bind -> release), so the ring lifecycle is exercised on
        every pass."""
        trace = trace if trace is not None else PlanTrace()
        trace.sum_bytes = max(trace.sum_bytes, self._sum_bytes)
        resident = self._resident_bytes
        env: Dict[str, Any] = dict(inputs)
        env_src: Dict[str, Any] = {k: None for k in env}
        out = None
        ring_slot = None
        for i, step in enumerate(self.steps):
            transient = not step.backend.resident
            dev_params = self._load(step)
            if transient:
                resident += _nbytes(dev_params)
            trace.record(step.brick.name, "load", resident)

            t0 = time.perf_counter()
            ctx = self._gather(step, env, env_src)
            out = step.fn(dev_params, ctx)
            if transient:
                # deliberate residency trace point: the sync makes the
                # brick's device-memory high-water mark observable
                out = jax.block_until_ready(out)  # replint: disable=host-sync
            trace.record(step.brick.name, "execute", resident)
            if self.probe is not None:
                # a full pass is a prefill; bricks up to the TABM edge
                # are the staging side of it
                phase = ("stage" if self._tabm_producer is not None
                         and i <= self._tabm_producer else "prefill")
                ntok = (int(out.shape[1]) if getattr(out, "ndim", 0) >= 2
                        else 0)
                self.probe.record(step.brick.name, phase,
                                  time.perf_counter() - t0, tokens=ntok)

            if self.tabm is not None and i == self._tabm_producer:
                out, ring, slot = self._through_ring(out)
                ring_slot = (ring, slot)
            env[step.brick.out_port.name] = out
            env_src[step.brick.out_port.name] = step.accel

            if transient:
                # release: only `out` survives to the next stage
                step.backend.unload(dev_params)
                resident -= _nbytes(dev_params)
            trace.record(step.brick.name, "release", resident)
            del dev_params
        if ring_slot is not None:
            ring_slot[0].release(ring_slot[1])
        return out, trace

    def _through_ring(self, out):
        """Synchronous TABM crossing inside run(): commit the producer's
        output to a slot, immediately bind it back as the consumer view.
        With a class-partitioned pool the slab is picked by the embeds'
        token count (the request's class), so run() exercises the same
        class-sized ring the engine would.  A failed commit aborts the
        write — the slot must never be left in STAGING (same contract as
        produce())."""
        if out.shape[0] != 1:
            raise PlanError("TABM slots hold one request's embeds (batch 1)")
        if isinstance(self.tabm, SlotClassPool):
            ring = self.tabm.ring(self.tabm.classify_total(out.shape[1]))
        else:
            ring = self.tabm
        slot = ring.acquire_write()
        if slot is None:
            raise PlanError("TABM ring full inside a synchronous run(); "
                            "a prior consumer never released its slot")
        try:
            v = out if self._tabm_transfer is None \
                else self._tabm_transfer(out)
            ring.commit_write(slot, v[0])
        except Exception:
            ring.abort_write(slot)
            raise
        got = ring.acquire_read()
        assert got is not None
        s, view, n = got
        return view[None, :n], ring, s

    # -- TABM edge, split for the engine's producer/consumer decoupling -----
    def _span(self, name: str, brick: str, phase: str, tokens: int = 0):
        """A probe span (a profiler annotation too), or nothing without
        a probe."""
        if self.probe is None:
            return contextlib.nullcontext()
        return self.probe.span(name, brick, phase, tokens)

    def _tabm_ring(self, slot_class: Optional[str]):
        """Resolve the ring a TABM operation targets: the single ring, or
        the named class ring of a class-partitioned pool."""
        if self.tabm is None:
            raise PlanError("plan compiled without a TABM ring")
        if isinstance(self.tabm, SlotClassPool):
            if slot_class is None:
                raise PlanError("class-partitioned TABM pool: pass "
                                "slot_class= (see core/slot_classes)")
            return self.tabm.ring(slot_class)
        if slot_class is not None:
            raise PlanError(f"slot_class={slot_class!r} given but the "
                            f"plan's TABM is a single ring")
        return self.tabm

    def tabm_capacity(self, slot_class: Optional[str] = None) -> int:
        """Slot capacity of the targeted ring — the hard ceiling on one
        microbatch (``produce_many`` of more slots can never fit)."""
        return self._tabm_ring(slot_class).n_slots

    def produce(self, inputs: Dict[str, Any], *,
                slot_class: Optional[str] = None, block: bool = False,
                timeout: Optional[float] = None) -> Optional[int]:
        """Producer half: acquire a ring slot, run the stages upstream of
        the TABM edge (vision encode -> projector), commit.  Returns the
        slot id, or None when the ring is FULL — the caller must stall and
        retry (backpressure), never bypass the ring.

        This is the K=1 case of :meth:`produce_many` — same slab padding,
        same abort-on-error contract, one slot."""
        slots = self.produce_many([inputs], slot_class=slot_class,
                                  block=block, timeout=timeout)
        return None if slots is None else slots[0]

    def produce_many(self, batch_of_inputs: List[Dict[str, Any]], *,
                     slot_class: Optional[str] = None, block: bool = False,
                     timeout: Optional[float] = None
                     ) -> Optional[List[int]]:
        """Batched producer half: acquire K FIFO-contiguous ring slots,
        run the upstream stages (vision encode -> projector) as ONE
        batched jit call over the whole microbatch, and commit a single
        strided slab covering all K slots.  Returns the slot ids in
        request order, or None when the ring cannot hold the microbatch
        (the caller stalls — all-or-nothing backpressure, never a partial
        commit).

        Each element of ``batch_of_inputs`` is one request's
        ``{"vision_feats": (1, t_i, f)}``; requests are padded to the
        target ring's slab width (``max_tokens`` — all K must share a
        slot class), so one compiled executable serves every microbatch
        of the class, and each slot's true length rides in the ring's
        per-slot token counts (the consumer binds ``view[:n]``, so pad
        rows are never read — the per-request mask).  The upstream bricks
        are token-wise (frontend stub, projector), so padded rows cannot
        perturb real rows and K=1 produces bit-identical embeds to the
        unbatched path.

        With a class-partitioned pool, ``slot_class`` names the class
        ring (the engine passes the class it grouped the microbatch by);
        left None, it is inferred from the largest vision_feats token
        count in the batch.  ``block=True`` parks the calling thread
        until K slots free from the ring head — where the engine's
        per-class StagingWorker stalls, off the decode loop.

        Error contract: if any upstream brick raises, ALL K acquired
        slots are aborted back to EMPTY (``abort_many`` — abort-all-on-
        failure, the write pointer rewinds past the whole run) before the
        exception propagates; the caller owns surfacing the error on the
        originating requests."""
        if self.tabm is None:
            raise PlanError("plan compiled without a TABM ring")
        if not batch_of_inputs:
            raise PlanError("produce_many needs at least one request")
        feats = []
        for inputs in batch_of_inputs:
            extra = set(inputs) - {"vision_feats"}
            if extra:
                raise PlanError(f"produce_many batches the vision_feats "
                                f"port only; got extra inputs {sorted(extra)}")
            f = inputs.get("vision_feats")
            if f is None:
                raise PlanError("produce_many needs vision_feats for "
                                "every request in the microbatch")
            if f.shape[0] != 1:
                raise PlanError("TABM slots hold one request's embeds "
                                "(batch 1 per request)")
            feats.append(f)
        if slot_class is None and isinstance(self.tabm, SlotClassPool):
            slot_class = self.tabm.classify_total(
                max(int(f.shape[1]) for f in feats))
        ring = self._tabm_ring(slot_class)
        lengths = [int(f.shape[1]) for f in feats]
        for n in lengths:
            if n > ring.max_tokens:
                raise PlanError(f"{n} vision tokens > slot capacity "
                                f"{ring.max_tokens} of the target ring")
        with self._span("tabm.acquire", "tabm", "acquire"):
            slots = ring.acquire_write_many(len(feats), block=block,
                                            timeout=timeout)
        if slots is None:
            return None
        try:
            # pad every request into the class slab and stack: one
            # (K, slab, f) batch through encoder+projector, one jit call
            slab = ring.max_tokens
            stacked = np.zeros((len(feats), slab, feats[0].shape[-1]),
                               feats[0].dtype)
            for b, f in enumerate(feats):
                # deliberate host-side slab packing: requests arrive as
                # host arrays; one device upload follows (jnp.asarray)
                stacked[b, : lengths[b]] = np.asarray(f[0])  # replint: disable=host-sync
            env: Dict[str, Any] = {"vision_feats": jnp.asarray(stacked)}
            env_src: Dict[str, Any] = {k: None for k in env}
            out = None
            for step in self.steps[: self._tabm_producer + 1]:
                transient = not step.backend.resident
                dev_params = self._load(step)
                with self._span(f"tabm.stage.{step.brick.name}",
                                step.brick.name, "stage",
                                tokens=len(feats) * slab):
                    ctx = self._gather(step, env, env_src)
                    out = step.fn(dev_params, ctx)
                    if transient:
                        # deliberate residency trace point (see run())
                        out = jax.block_until_ready(out)  # replint: disable=host-sync
                        step.backend.unload(dev_params)
                env[step.brick.out_port.name] = out
                env_src[step.brick.out_port.name] = step.accel
            if out.shape[0] != len(feats):
                raise PlanError(f"projector returned batch {out.shape[0]} "
                                f"for a {len(feats)}-request microbatch")
            if out.shape[1] != slab:
                # the committed per-slot lengths are the INPUT token
                # counts — valid only while the upstream bricks are
                # token-count-preserving; a resampling projector must
                # fail loudly here, not stage misaligned views
                raise PlanError(
                    f"upstream bricks changed the token count "
                    f"({slab} -> {out.shape[1]}); produce_many requires "
                    f"token-count-preserving staging bricks")
            v = out if self._tabm_transfer is None else self._tabm_transfer(out)
            with self._span("tabm.commit", "tabm", "commit"):
                ring.commit_many(slots, v, lengths)
        except Exception:
            ring.abort_many(slots)
            raise
        return slots

    def consume(self, *, slot_class: Optional[str] = None,
                block: bool = False, timeout: Optional[float] = None):
        """Consumer half: bind the oldest READY slot (of ``slot_class``'s
        ring when the pool is class-partitioned).  Returns
        (slot, view, n_tokens) or None when nothing is ready (with
        ``block=True``: only on timeout or a closed ring)."""
        return self._tabm_ring(slot_class).acquire_read(block=block,
                                                        timeout=timeout)

    def wait_ready(self, slot: int, timeout: Optional[float] = None, *,
                   slot_class: Optional[str] = None) -> bool:
        """Block until `slot` is committed — the decode loop's per-slot
        (and per-class) ready wait, replacing inline staging."""
        return self._tabm_ring(slot_class).wait_ready(slot, timeout)

    def addref(self, slot: int, gen: int, *,
               slot_class: Optional[str] = None) -> bool:
        """Pin an already-consumed TABM slot for one more bucket-matched
        consumer (refcounted READY-slot sharing; see
        :meth:`repro.core.tabm.RingBuffer.addref`).  False = the slot was
        recycled, the caller must stage its own copy."""
        return self._tabm_ring(slot_class).addref(slot, gen)

    def shared_view(self, slot: int, gen: int, *,
                    slot_class: Optional[str] = None):
        """(view, n_tokens) of a shared consumed slot, seqlock-validated
        against ``gen`` — None when the slot moved on."""
        return self._tabm_ring(slot_class).shared_view(slot, gen)

    def release(self, slot: int, *, slot_class: Optional[str] = None):
        self._tabm_ring(slot_class).release(slot)


# ---------------------------------------------------------------------------
# compiler
# ---------------------------------------------------------------------------

def _backend_for(brick_name: str, accel, *, override, placement_backends,
                 residency: str) -> Backend:
    """The backend table lookup, in priority order: an explicit
    compile_plan ``backend=`` override (global or per-brick dict) >
    ``residency="one-brick"`` (every brick through the transient
    HostBackend) > the Placement's carried backend name > the
    accelerator's profile / the default (see backends.resolve_backend)."""
    if override is not None:
        spec = override.get(brick_name) if isinstance(override, dict) \
            else override
        if spec is not None:
            be = resolve_backend(spec, accel)
            if residency == "one-brick" and be.resident:
                raise PlanError(
                    f"residency='one-brick' needs a transient backend, "
                    f"but brick {brick_name!r} was overridden to the "
                    f"resident {be.name!r} backend")
            return be
    if residency == "one-brick":
        return BACKENDS["host"]
    if placement_backends and brick_name in placement_backends:
        return resolve_backend(placement_backends[brick_name], accel)
    return resolve_backend(None, accel)


def compile_plan(graph: BrickGraph, params, *, placement=None, accels=None,
                 tabm=None, residency: str = "resident",
                 backend=None, probe=None, transport=None) -> ExecutionPlan:
    """Compile a BrickGraph (+ optional Placement and TABM ring) into an
    :class:`ExecutionPlan`.

    placement: a :class:`~repro.core.scheduler.Placement` or a raw
        ``{brick_name: accel_name}`` dict; requires ``accels``.  A
        Placement's ``backends`` map (filled by ``schedule()`` from each
        accelerator's ``backend`` profile field) picks each brick's
        lowering substrate.
    accels: the accelerator list the placement names refer to.
    tabm: a :class:`~repro.core.tabm.RingBuffer` or class-partitioned
        :class:`~repro.core.tabm.SlotClassPool` for the vision_embeds
        edge (the paper's zero-copy hand-off).
    residency: "resident" (serving: params bound once) | "one-brick"
        (cascade: every brick lowered through the transient HostBackend —
        load -> execute -> release, host-side between events).
    backend: override the backend table — a registry name
        (``"submesh" | "device" | "host"``), a
        :class:`~repro.core.backends.Backend` instance, or a per-brick
        ``{brick_name: spec}`` dict.  The same graph + placement lowers
        to any substrate; see docs/ARCHITECTURE.md "Backend lowering".
    probe: a :class:`~repro.telemetry.probes.WallProbe` that run() /
        produce_many() record per-brick wall-time spans into (the
        telemetry ledger's dynamic population path); None = no probing.
    transport: a :class:`~repro.core.transport.Transport` instance the
        plan's cross-accelerator edges are bound to.  None (default) =
        direct backend edges, exactly the pre-transport behavior; a
        serializing transport routes every such edge through its wire
        codec (``Transport.make_edge``), proving the format transparent
        to plan dataflow — the disaggregated drivers pass their live
        fleet connection here.
    """
    if residency not in ("resident", "one-brick"):
        raise PlanError(f"unknown residency {residency!r}")
    assignment = getattr(placement, "assignment", placement)
    placement_backends = getattr(placement, "backends", None)
    by_name = {a.name: a for a in (accels or [])}
    if assignment:
        missing = [b.name for b in graph.bricks if b.name not in assignment]
        if missing:
            raise PlanError(f"placement misses bricks: {missing}")
        unknown = sorted(set(assignment.values()) - set(by_name))
        if unknown:
            raise PlanError(f"placement names unknown accelerators: {unknown}")

    # wiring validation + external input discovery
    produced: Dict[str, Brick] = {}
    externals: List[Port] = []
    for b in graph.bricks:
        for p in b.in_ports:
            if p.name not in produced and not p.optional \
                    and all(e.name != p.name for e in externals):
                externals.append(p)
        produced[b.out_port.name] = b

    steps: List[PlanStep] = []
    src_accel: Dict[str, Any] = {}                 # port -> producing accel
    edges: Dict[Tuple[str, str, str], Any] = {}    # (src, dst, backend) -> fn
    for b in graph.bricks:
        accel = by_name[assignment[b.name]] if assignment else None
        be = _backend_for(b.name, accel, override=backend,
                          placement_backends=placement_backends,
                          residency=residency)
        inbound: Dict[str, Callable] = {}
        if accel is not None:
            for p in b.in_ports:
                src = src_accel.get(p.name)
                if src is accel:
                    continue
                # keyed on the backend *instance*: two distinct instances
                # sharing a registry name (e.g. DeviceBackends pinned to
                # different devices) must not reuse each other's transfer
                key = (src.name if src is not None else "-",
                       accel.name, id(be))
                if key not in edges:
                    edges[key] = (be.make_edge(src, accel)
                                  if transport is None
                                  else transport.make_edge(src, accel, be))
                if edges[key] is not None:
                    inbound[p.name] = edges[key]
        steps.append(PlanStep(
            brick=b, fn=be.compile_fn(b, graph.cfg),
            params=be.bind_params(b, params, accel),
            backend=be, accel=accel, inbound=inbound))
        src_accel[b.out_port.name] = accel

    # the TABM edge: the brick producing vision_embeds hands off through the
    # ring; the transfer (if the consumer sits on another submesh/device)
    # happens producer-side so the pool can live consumer-side
    tabm_producer = tabm_transfer = None
    if tabm is not None:
        for i, s in enumerate(steps):
            if s.brick.out_port.name == "vision_embeds":
                tabm_producer = i
                break
        if tabm_producer is None:
            raise PlanError("tabm ring given but no brick produces "
                            "'vision_embeds'")
        nxt = steps[tabm_producer + 1] if tabm_producer + 1 < len(steps) \
            else None
        if nxt is not None and "vision_embeds" in nxt.inbound:
            tabm_transfer = nxt.inbound.pop("vision_embeds")

    plan = ExecutionPlan(graph, steps, residency=residency, tabm=tabm,
                         tabm_producer=tabm_producer,
                         tabm_transfer=tabm_transfer,
                         input_ports=tuple(externals), probe=probe)
    plan.pipes = edges
    plan._params = params
    return plan
