"""Cross-accelerator, module-level scheduler (paper §3.2).

NANOMIND's central mechanism: map each brick to the compute unit whose
characteristics match it — "NPUs excel at low-bit tensor ops but are
inefficient for floating-point workloads; GPUs are far better at large-scale
parallel floating-point".  Two instantiations share one cost model:

* **Edge profile** (the paper's RK3566): NPU / GPU / CPU accelerators with
  the paper's constraints — the NPU only takes *static-shape* bricks
  (§NPU: recompiling on shape change is impractical) and prefers low-bit;
  the CPU is the fallback.  Used by the fleet simulator's modeled
  profile (``launch/fleet_sim.py``) and the quickstart example.

* **Pod profile** (this repo's target): a TPU pod is silicon-homogeneous,
  so accelerator heterogeneity becomes *profile heterogeneity* —
  :func:`make_virtual_accelerators` slices the pod's "model" axis into
  submeshes (encoder slice ≙ NPU, decoder slice ≙ GPU) each with its own
  quantization/static-shape profile.  Hand-off between submeshes is a
  sharding-preserving device_put (pure ICI; never through the host) —
  the TABM edge at pod scale.

Placement is exact chain dynamic programming over the BrickGraph (the
pipelines are chains): dp[i][acc] = best cost of placing brick i on acc,
including the edge-transfer term.  The objective (latency | energy) comes
from the battery policy (core/power.py).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.analysis.energy import (EDGE_CPU, EDGE_GPU, EDGE_NPU,
                                   EnergyProfile, TPU_V5E, step_energy,
                                   step_time)
from repro.core.backends import bit_efficiency, substrate_backend
from repro.core.bricks import Brick, BrickGraph
from repro.telemetry.calibration import CostCalibration


@dataclass(frozen=True)
class Accelerator:
    """A compute unit the scheduler can place a brick on.

    Both the cost model (:meth:`throughput_scale`) and backend resolution
    (:meth:`backend_name`) read the shared substrate table in
    ``core/backends.py`` — one row per energy profile ties the unit's
    per-bit-width throughput to the backend (and thus kernel mode) it
    lowers through, so the scheduler can never price a unit the lowering
    contradicts."""

    name: str
    profile: EnergyProfile
    static_only: bool = False          # paper §NPU: static graphs only
    dynamic_ok: bool = True
    mesh: Optional[object] = None      # submesh (pod mode)
    width: float = 1.0                 # fraction of a full unit
    backend: Optional[str] = None      # lowering substrate (core/backends
                                       # registry name); None = from the
                                       # substrate table / inferred

    def throughput_scale(self, quant_label: str) -> float:
        return bit_efficiency(self.profile.name, quant_label) * self.width

    def backend_name(self) -> str:
        """The backend this accelerator lowers bricks through: its
        explicit profile field, else the shared substrate table row of
        its energy profile, else submesh when it carries a mesh, else
        host (the paper's edge units are emulated on a pinned CPU
        thread — see core/backends.py)."""
        if self.backend:
            return self.backend
        sub = substrate_backend(self.profile.name)
        if sub is not None and not (sub == "submesh" and self.mesh is None):
            return sub
        return "submesh" if self.mesh is not None else "host"


def edge_accelerators() -> List[Accelerator]:
    """The paper's RK3566: NPU (static, low-bit), Mali GPU, Cortex CPU.

    Backends come from the shared substrate table (core/backends.py): the
    NPU and CPU lower through the thread-pinned HostBackend (the
    container has no such silicon; host threads emulate it, reference
    kernels only); the GPU lowers through the DeviceBackend (committed
    default-device streams)."""
    return [
        Accelerator("npu", EDGE_NPU, static_only=True, dynamic_ok=False),
        Accelerator("gpu", EDGE_GPU),
        Accelerator("cpu", EDGE_CPU),
    ]


def make_virtual_accelerators(mesh, fractions=(0.25, 0.75)
                              ) -> List[Accelerator]:
    """Slice the pod's "model" axis into profile-heterogeneous submeshes.

    fractions: (encoder_frac, decoder_frac) of the model axis.  The encoder
    slice runs static-shape low-bit bricks (≙ NPU); the decoder slice runs
    the W4A16 TP decode (≙ GPU)."""
    from jax.sharding import Mesh
    axis = mesh.axis_names.index("model")
    n = mesh.devices.shape[axis]
    cut = max(1, int(round(n * fractions[0])))
    sl_enc = [slice(None)] * mesh.devices.ndim
    sl_dec = [slice(None)] * mesh.devices.ndim
    sl_enc[axis] = slice(0, cut)
    sl_dec[axis] = slice(cut, n)
    enc_mesh = Mesh(mesh.devices[tuple(sl_enc)], mesh.axis_names,
                    axis_types=mesh.axis_types)
    dec_mesh = Mesh(mesh.devices[tuple(sl_dec)], mesh.axis_names,
                    axis_types=mesh.axis_types)
    scale = lambda f: dataclasses.replace(
        TPU_V5E, peak_flops=TPU_V5E.peak_flops * f,
        hbm_bw=TPU_V5E.hbm_bw * f)
    return [
        Accelerator("enc-submesh", scale(cut / n), static_only=True,
                    dynamic_ok=False, mesh=enc_mesh, width=cut / n),
        Accelerator("dec-submesh", scale((n - cut) / n), mesh=dec_mesh,
                    width=(n - cut) / n),
    ]


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

@dataclass
class BrickCost:
    latency_s: float
    energy_j: float
    feasible: bool = True


def brick_cost(brick: Brick, acc: Accelerator, n_tokens: int,
               mem_clock_scale: float = 1.0, batch: int = 1,
               calibration: Optional[CostCalibration] = None) -> BrickCost:
    """Roofline latency + modeled energy of ONE call over a microbatch of
    ``batch`` requests (``n_tokens`` each) on one unit.

    Batch-awareness is the staging pipeline's amortization: compute
    scales with the microbatch (``batch * n_tokens`` tokens) but the
    brick's weight traffic is charged ONCE per call — ``batch``
    independent calls would pay the weight stream ``batch`` times, so
    for memory-bound bricks (exactly the projector/prefill side the TABM
    slab batches) ``brick_cost(..., batch=K).latency_s`` is well below
    ``K * brick_cost(...).latency_s``.

    ``calibration`` is the measured-not-modeled feedback edge
    (telemetry/calibration.py): when the table holds a sample for this
    (brick, profile) — falling back to the brick's profile-agnostic
    key — the measured per-token seconds (and joules, when observed)
    override the model with sample-count weight ``n / (n + prior)``:
    empty table -> pure model, a well-observed brick -> pure
    measurement.  Infeasible stays infeasible regardless — no
    observation can put a dynamic brick on a static-only unit."""
    if not brick.static_shape and acc.static_only:
        return BrickCost(float("inf"), float("inf"), feasible=False)
    flops = brick.flops_per_token * n_tokens * max(1, batch)
    wbytes = max(brick.param_bytes, 1)
    scale = acc.throughput_scale(brick.quant_label)
    p = acc.profile
    eff = dataclasses.replace(
        p, peak_flops=p.peak_flops * max(scale, 1e-9),
        hbm_bw=p.hbm_bw * mem_clock_scale)
    t = step_time(eff, flops, wbytes)
    e = step_energy(eff, flops, wbytes, 0.0, wall_s=t)
    if calibration is not None:
        s = calibration.sample(brick.name, p.name)
        if s is not None and s.tokens > 0:
            w = calibration.weight(s.n)
            units = n_tokens * max(1, batch)
            t = (1.0 - w) * t + w * s.seconds_per_token * units
            if s.joules > 0:
                e = (1.0 - w) * e + w * s.joules_per_token * units
    return BrickCost(t, e)


def transfer_cost(bytes_moved: int, src: Accelerator, dst: Accelerator
                  ) -> Tuple[float, float]:
    """Edge hand-off: zero when staying put (TABM zero-copy); ICI/DMA
    otherwise."""
    if src.name == dst.name:
        return 0.0, 0.0
    bw = min(src.profile.link_bw, dst.profile.link_bw)
    t = bytes_moved / bw
    e = bytes_moved * (src.profile.e_link + dst.profile.e_link) / 2
    return t, e


# ---------------------------------------------------------------------------
# placement (exact chain DP)
# ---------------------------------------------------------------------------

@dataclass
class Placement:
    assignment: Dict[str, str]
    latency_s: float
    energy_j: float
    per_brick: Dict[str, BrickCost] = field(default_factory=dict)
    # brick -> backend registry name (core/backends), carried from each
    # accelerator's profile so compile_plan lowers through the same
    # substrate the cost model priced
    backends: Dict[str, str] = field(default_factory=dict)

    def __str__(self):
        cells = " | ".join(f"{b}->{a}" for b, a in self.assignment.items())
        return (f"Placement[{cells}] lat={self.latency_s*1e3:.2f}ms "
                f"E={self.energy_j:.3f}J")


def edge_bytes(graph: BrickGraph, n_tokens: int) -> int:
    """Activation bytes crossing a brick edge: (tokens, d_model) bf16."""
    return n_tokens * graph.cfg.d_model * 2


def schedule(graph: BrickGraph, accels: List[Accelerator], n_tokens: int,
             objective: str = "latency", mem_clock_scale: float = 1.0,
             batch: int = 1,
             calibration: Optional[CostCalibration] = None) -> Placement:
    """Exact DP over the brick chain.

    dp[i][a] = best objective of bricks[0..i] with brick i on accel a.
    ``batch`` prices every brick (and edge) for a microbatch of that many
    requests — the staging pipeline's unit of work — so a placement can
    be optimized for the batched regime, where weight traffic amortizes
    (``brick_cost``) and the latency/energy balance between units shifts
    toward the compute-bound ones.  ``calibration`` threads measured
    per-brick costs into every cell (see :func:`brick_cost`), so the DP
    places from observation when samples exist — a brick the table
    shows slower-than-modeled on one unit migrates off it."""
    bricks = graph.bricks
    nA = len(accels)
    costs = [[brick_cost(b, a, n_tokens, mem_clock_scale, batch=batch,
                         calibration=calibration)
              for a in accels] for b in bricks]
    xfer = edge_bytes(graph, n_tokens) * max(1, batch)

    def metric(c: BrickCost, t_extra: float, e_extra: float) -> float:
        if objective == "energy":
            return c.energy_j + e_extra
        return c.latency_s + t_extra

    INF = float("inf")
    dp = [[INF] * nA for _ in bricks]
    back: List[List[int]] = [[-1] * nA for _ in bricks]
    for a in range(nA):
        if costs[0][a].feasible:
            dp[0][a] = metric(costs[0][a], 0.0, 0.0)
    for i in range(1, len(bricks)):
        for a in range(nA):
            if not costs[i][a].feasible:
                continue
            for pa in range(nA):
                if dp[i - 1][pa] == INF:
                    continue
                tt, te = transfer_cost(xfer, accels[pa], accels[a])
                cand = dp[i - 1][pa] + metric(costs[i][a], tt, te)
                if cand < dp[i][a]:
                    dp[i][a] = cand
                    back[i][a] = pa

    last = int(np.argmin(dp[-1]))
    if dp[-1][last] == INF:
        raise RuntimeError("no feasible placement")
    order = [last]
    for i in range(len(bricks) - 1, 0, -1):
        order.append(back[i][order[-1]])
    order.reverse()

    assignment = {b.name: accels[a].name for b, a in zip(bricks, order)}
    backends = {b.name: accels[a].backend_name()
                for b, a in zip(bricks, order)}
    lat = e = 0.0
    per = {}
    prev = None
    for i, (b, a) in enumerate(zip(bricks, order)):
        c = costs[i][a]
        per[b.name] = c
        lat += c.latency_s
        e += c.energy_j
        if prev is not None and prev != a:
            tt, te = transfer_cost(xfer, accels[prev], accels[a])
            lat, e = lat + tt, e + te
        prev = a
    return Placement(assignment, lat, e, per, backends=backends)


def populate_brick_bytes(graph: BrickGraph, params) -> None:
    """Fill Brick.param_bytes from real (possibly quantized) params."""
    from repro.core.bricks import brick_param_bytes
    sizes = brick_param_bytes(graph, params)
    graph.bricks = [dataclasses.replace(b, param_bytes=sizes[b.name])
                    for b in graph.bricks]


# ---------------------------------------------------------------------------
# admission-depth hook (the async TABM producer/consumer pipeline)
# ---------------------------------------------------------------------------

def staged_ahead_depth(ring) -> int:
    """How far the producer has run ahead of the consumer: slots STAGING or
    READY in the TABM ring.  Distinct from ``ring.occupancy`` — a CONSUMED
    slot still occupies the ring but is *behind* the consumer, so it says
    nothing about how much staged work the decoder has banked."""
    return ring.staged_ahead()


def staging_budget(ring, in_flight: int, max_ahead: Optional[int] = None
                   ) -> int:
    """How many more requests the engine may hand to the staging worker.

    ``in_flight``: requests already handed over but not yet committed (the
    worker's queue + the one it is staging).  ``max_ahead`` caps total
    staged-ahead depth; default = ring size (the producer would block on
    FULL beyond that anyway, and a bounded hand-off queue keeps shutdown
    cancellation cheap).  This is the admission check the async engine
    uses instead of raw ring occupancy; the class-partitioned pool
    applies it per class via :func:`class_staging_budgets`."""
    cap = ring.n_slots if max_ahead is None else max_ahead
    return max(0, cap - staged_ahead_depth(ring) - in_flight)


def class_staging_budgets(pool, in_flight: Dict[str, int],
                          depth_scale: float = 1.0,
                          stage_batch: Optional[int] = None
                          ) -> Dict[str, int]:
    """Per-class admission budgets over a class-partitioned TABM pool.

    ``staging_budget`` grown into a table: the pool's
    ``admission_table(depth_scale)`` yields ``{slot_class: (ring,
    max_ahead)}`` — each class's own ring and its battery-scaled depth
    (``core/power.Knobs.class_depth_scale`` shrinks the high-resolution
    classes first) — and each class is charged its own budget, so a FULL
    or throttled high-resolution class never starves thumbnail admission.
    ``in_flight``: per-class hand-over counts from the engine's staging
    worker.  A class whose ring has not materialized yet (lazy pool:
    no request of that class has ever staged) has zero staged-ahead
    depth by definition.

    ``stage_batch`` makes the charge *microbatch-aware*: the engine hands
    each class's round of requests to its producer thread as ONE
    microbatch (one strided slab commit, one batched projector call), so
    a round's budget is capped at one microbatch — the class is charged a
    microbatch per round, not ``K`` independent admissions, and the
    hand-off can never outrun what one ``produce_many`` commits.
    ``Knobs.max_stage_batch`` scales it down under battery throttling
    (batch shrinks before depth sheds)."""
    budgets = {}
    for name, (ring, cap) in pool.admission_table(depth_scale).items():
        flight = in_flight.get(name, 0)
        if ring is None:                       # unmaterialized: EMPTY ring
            budget = max(0, cap - flight)
        else:
            budget = staging_budget(ring, flight, max_ahead=cap)
        if stage_batch is not None and stage_batch > 0:
            budget = min(budget, stage_batch)
        budgets[name] = budget
    return budgets


def kv_block_budgets(pool, total_blocks: int,
                     used: Dict[Optional[str], int],
                     kv_scale: float = 1.0,
                     energy_pressure: float = 1.0) -> Dict[str, int]:
    """Per-class paged-KV *block* budgets — staged-ahead depth charging
    applied to decode memory.

    The engine's :class:`~repro.serving.kv_cache.PagedKVCache` grants
    each admitted request a run of fixed-size KV blocks; this table says
    how many MORE blocks each slot class may be granted right now.  Each
    class's cap is its share of the whole block pool under
    ``core/power.Knobs.class_kv_scale``, shed high-resolution-first in
    exactly the staged-ahead order (``core/slot_classes.shed_scales``):
    at scale 1.0 every class may use the full pool (free-block count is
    the only bound), under THROTTLED the largest class's cap shrinks
    fully by the scale while the thumbnail class keeps the whole pool —
    so long-context hi-res KV grants are the first decode-side load
    shed, mirroring how ``class_staging_budgets`` sheds staging depth.

    ``used``: blocks currently granted per class
    (``PagedKVCache.used_blocks``); classes absent from it hold none.

    ``energy_pressure`` is the telemetry feedback
    (``CostCalibration.energy_pressure``): the measured-over-modeled
    decode J/token ratio.  Decode running hotter than the model priced
    (> 1) tightens the effective scale, so hi-res KV grants shed EARLIER
    than the battery knob alone would — the paged pool reacts to
    observed energy, not just predicted charge."""
    from repro.core.slot_classes import shed_scales
    eff_scale = kv_scale / max(1.0, energy_pressure)
    budgets = {}
    for name, eff in shed_scales(pool.classes, eff_scale).items():
        cap = max(0, min(total_blocks, int(total_blocks * eff)))
        budgets[name] = max(0, cap - used.get(name, 0))
    return budgets


# ---------------------------------------------------------------------------
# pod-mode hand-off (the TABM edge between submeshes)
# ---------------------------------------------------------------------------

# SubmeshPipe moved to core/transport.py (it is the degenerate — same
# process, nothing serialized — member of the Transport family);
# re-exported here because SubmeshBackend.make_edge and older callers
# import it from the scheduler.
from repro.core.transport import SubmeshPipe  # noqa: E402,F401


# ---------------------------------------------------------------------------
# disaggregated fleets (prefill fleet + decode fleet over a Transport)
# ---------------------------------------------------------------------------

def fleet_accelerators(transport, n_devices: int = 2,
                       calibration: Optional[CostCalibration] = None
                       ) -> List[Accelerator]:
    """The two-fleet disaggregated topology as scheduler rows.

    "Cost-Efficient Multimodal LLM Inference via Cross-Tier GPU
    Heterogeneity" (PAPERS.md): vision encode + batched prefill are
    compute-bound, decode is memory-bound — opposite ideal hardware, so
    each side gets its own pool.  The prefill fleet is compute-rich and
    ``static_only`` (it takes the static-shape vision/projector/prefill
    bricks; the dynamic decode bricks *cannot* land there, so the cut is
    guaranteed); the decode fleet keeps full memory bandwidth but a
    fraction of the FLOPs (cheap decode workers).  Both rows' profiles
    carry ``link_bw = transport.link_bw`` so every cross-fleet edge the
    chain DP prices is a real serialized wire crossing — the placement
    responds to the transport (``core/transport.TRANSPORTS``), not to an
    assumed ICI.  When ``calibration`` holds a link observation for this
    transport (``CostCalibration.observe_link``, fed from
    ``Transport.measured_link_bw``) the measured bytes/s blends over the
    static class row — a wire that clocks slower than its class pushes
    the split toward fewer crossings.

    The fleets lower through per-ordinal device backends
    (``"device:0"`` / ``"device:1"``) — a multi-GPU box is the
    degenerate single-host two-fleet case; with one visible device both
    fleets share ordinal 0."""
    bw = float(getattr(transport, "link_bw", 8e9))
    if calibration is not None:
        bw = calibration.link_bw(getattr(transport, "name", None), bw)
    wire = lambda p: dataclasses.replace(p, link_bw=min(p.link_bw, bw))
    # prefill fleet: a full unit (compute-rich); decode fleet: cheap
    # workers at a quarter of the FLOPs but the full memory bandwidth
    # decode's weight streaming wants
    prefill_p = TPU_V5E
    decode_p = dataclasses.replace(TPU_V5E,
                                   peak_flops=TPU_V5E.peak_flops * 0.25)
    dec_dev = "device:1" if n_devices > 1 else "device:0"
    return [
        Accelerator("prefill-fleet", wire(prefill_p), static_only=True,
                    dynamic_ok=False, backend="device:0"),
        Accelerator("decode-fleet", wire(decode_p), backend=dec_dev),
    ]


def schedule_split(graph: BrickGraph, transport, n_tokens: int,
                   objective: str = "latency", batch: int = 1,
                   calibration: Optional[CostCalibration] = None
                   ) -> Placement:
    """Price the prefill/decode split over a serialized transport.

    Runs the same exact chain DP as :func:`schedule`, but over the two
    fleet rows of :func:`fleet_accelerators` — ``transfer_cost`` then
    prices every cross-fleet edge at the transport's wire bandwidth, so
    the scheduler decides what crosses the wire per substrate table AND
    per transport: a slow socket pushes compute toward fewer crossings,
    a fast in-process channel frees the DP to cut where the roofline
    prefers.  ``transport`` may be a Transport class, instance, or
    registry name (``core/transport.resolve_transport``).

    ``calibration`` feeds BOTH blending edges: per-brick measured
    seconds into ``brick_cost`` (as in :func:`schedule`) and measured
    wire bandwidth into the fleet rows' ``link_bw``
    (``CostCalibration.observe_link`` -> :func:`fleet_accelerators`) —
    the split is repriced from what the frames actually clocked, not
    the transport's static class row."""
    if isinstance(transport, str):
        from repro.core.transport import resolve_transport
        transport = resolve_transport(transport)
    return schedule(graph,
                    fleet_accelerators(transport, calibration=calibration),
                    n_tokens, objective, batch=batch,
                    calibration=calibration)
