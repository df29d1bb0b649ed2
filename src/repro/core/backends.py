"""Backend lowering — one Placement, many substrates (paper §3.2).

The paper's core claim is that each LMM brick runs on its *best-suited*
compute unit (NPU / GPU / DSP).  A :class:`Backend` owns the four
substrate-specific decisions :func:`repro.core.plan.compile_plan` used to
hardcode behind ``if accel.mesh is not None`` branches:

* ``bind_params(brick, params, accel)`` — where a brick's weights live
  between executions (submesh-sharded, committed to one device, or
  host-side numpy);
* ``compile_fn(brick, cfg)`` — the brick's executable, drawn from one
  module-level jit cache (keyed ``(brick, cfg, kernel-mode)``) so the
  engine, cascade, and scheduler paths share compiled executables, and
  consulting :mod:`repro.kernels.dispatch` for the Pallas-vs-reference
  kernel decision;
* ``make_edge(src_accel, dst_accel)`` — the inbound-transfer factory for
  values produced on a different accelerator (SubmeshPipe over ICI,
  committed device_put, or a host pull);
* ``load / unload`` — one-brick residency: a *transient* backend
  (``resident = False``) materializes params load -> execute -> release,
  the paper's On-Demand Cascade policy.

Concrete backends and the paper's hardware they stand in for:

=============== ======================= ================================
backend          paper unit              lowering
=============== ======================= ================================
SubmeshBackend   pod-scale "NPU"/"GPU"   NamedSharding onto the accel's
                 submesh slices          submesh + SubmeshPipe edges
DeviceBackend    single GPU/TPU          committed default-device
                                         placement, device_put edges
HostBackend      NPU/DSP emulated on     host-side numpy params,
                 a pinned CPU thread     load->execute->release,
                                         reference kernels (force_ref)
=============== ======================= ================================

``Accelerator.backend`` names a row of this table; ``schedule()`` carries
it into ``Placement.backends``; ``compile_plan`` resolves each brick
through :func:`resolve_backend` — the same graph lowers to any substrate,
and :meth:`repro.core.plan.ExecutionPlan.relower` re-lowers a single
brick (the ``PowerPolicy.knobs`` THROTTLED demotion hook).
"""
from __future__ import annotations

import re
import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bricks import Brick
from repro.kernels import dispatch


class BackendError(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# shared executable cache — one jit per (brick, cfg, kernel-mode), so every
# compile_plan call (engine, cascade, scheduler, re-lowering) reuses the
# same compiled callables instead of minting a fresh jax.jit per plan
# ---------------------------------------------------------------------------

_JIT_CACHE: Dict[Tuple[Any, Any, str], Callable] = {}
_JIT_CACHE_LOCK = threading.Lock()


def brick_executable(brick: Brick, cfg, mode: str = "auto") -> Callable:
    """The brick's jitted ``(params, ctx) -> out`` callable.

    ``mode`` is the kernel-dispatch mode baked into the trace:
    ``"auto"`` (Pallas on TPU, interpret elsewhere) or ``"ref"`` (the
    reference/interpret path always — every call runs under
    ``dispatch.force_ref()`` so retraces can never escape it).

    Brick and ModelConfig are frozen dataclasses, so two ``decompose(cfg)``
    calls over equal configs produce equal keys and hit the same entry —
    the cache works *across* plans, which is what lets the engine,
    cascade, and scheduler paths share compiled executables."""
    key = (brick, cfg, mode)
    with _JIT_CACHE_LOCK:
        fn = _JIT_CACHE.get(key)
        if fn is not None:
            return fn
        def apply(p, ctx, _b=brick):
            return _b.apply(p, cfg, ctx)

        # a stable program name in the trace: jit_brick_<name>
        apply.__name__ = apply.__qualname__ = f"brick_{brick.name}"
        jitted = jax.jit(apply)
        if mode == "ref":
            def fn(p, ctx, _j=jitted):
                with dispatch.force_ref():
                    return _j(p, ctx)
        else:
            # an "auto" executable must never trace while a reference
            # override is in effect — jit would bake interpret=True into
            # the shared cache entry for every later caller.  Route such
            # calls to the "ref" variant instead (per call, so toggling
            # REPRO_FORCE_REF or a force_ref() scope always takes effect).
            def fn(p, ctx, _j=jitted, _b=brick):
                if dispatch.force_ref_active():
                    return brick_executable(_b, cfg, "ref")(p, ctx)
                return _j(p, ctx)
        _JIT_CACHE[key] = fn
        return fn


def jit_cache_len() -> int:
    """Number of cached brick executables (test hook for cache hits)."""
    return len(_JIT_CACHE)


# ---------------------------------------------------------------------------
# the Backend protocol
# ---------------------------------------------------------------------------

class Backend:
    """The four substrate-specific decisions of plan lowering.

    Subclasses override the hooks; the base class is the protocol
    documentation (and deliberately not instantiable into a plan —
    ``resolve_backend`` only hands out registered concrete backends)."""

    name: str = "base"
    #: params stay bound between executions; False = load->execute->release
    resident: bool = True
    #: kernels/dispatch mode baked into this backend's executables
    kernel_mode: str = "auto"

    def bind_params(self, brick: Brick, params, accel=None):
        """Placement-time binding of the brick's param slice."""
        raise NotImplementedError

    def compile_fn(self, brick: Brick, cfg) -> Callable:
        """The brick's executable, from the shared jit cache."""
        return brick_executable(brick, cfg, self.kernel_mode)

    def make_edge(self, src_accel, dst_accel) -> Optional[Callable]:
        """Inbound transfer for values produced on a different accelerator
        (``src_accel`` may be None: an external input or host producer).
        None = no transfer needed."""
        return None

    def load(self, brick: Brick, bound):
        """Materialize params for one execution (transient backends)."""
        return bound

    def unload(self, dev_params) -> None:
        """Release what :meth:`load` materialized (transient backends)."""


class SubmeshBackend(Backend):
    """Today's pod path, behavior-preserving: brick weights device_put onto
    the accelerator's submesh (replicated NamedSharding) and every
    cross-accelerator edge a sharding-preserving device_put over ICI
    (:class:`repro.core.scheduler.SubmeshPipe`) — never through the host."""

    name = "submesh"

    def bind_params(self, brick, params, accel=None):
        if accel is None or getattr(accel, "mesh", None) is None:
            raise BackendError(
                f"submesh backend needs an accelerator with a mesh to "
                f"lower brick {brick.name!r}")
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(brick.params_of(params),
                              NamedSharding(accel.mesh, P()))

    def make_edge(self, src_accel, dst_accel):
        from jax.sharding import NamedSharding, PartitionSpec as P
        if getattr(dst_accel, "mesh", None) is None:
            raise BackendError("submesh edge needs a destination mesh")
        if src_accel is not None and getattr(src_accel, "mesh", None) \
                is not None:
            from repro.core.scheduler import SubmeshPipe
            return SubmeshPipe(src_accel, dst_accel, P()).transfer
        dst = NamedSharding(dst_accel.mesh, P())
        return lambda v, _s=dst: jax.device_put(v, _s)


class DeviceBackend(Backend):
    """Single-GPU/TPU lowering: brick weights committed to one device
    (default: ``jax.devices()[0]``), inbound edges a committed device_put
    onto that device's stream, no submeshes anywhere."""

    name = "device"

    def __init__(self, device=None):
        self._device = device

    @property
    def device(self):
        return self._device if self._device is not None else jax.devices()[0]

    def bind_params(self, brick, params, accel=None):
        return jax.device_put(brick.params_of(params), self.device)

    def make_edge(self, src_accel, dst_accel):
        return lambda v, _d=self.device: jax.device_put(v, _d)


class HostBackend(Backend):
    """Thread-pinned CPU execution emulating the paper's NPU/DSP bricks.

    * params are bound host-side (numpy) and materialized per execution —
      ``load -> execute -> release`` — which is exactly the On-Demand
      Cascade residency policy (``residency="one-brick"`` lowers every
      brick through this backend);
    * executables are traced under ``dispatch.force_ref()``: host bricks
      always take the reference/interpret kernels, like the paper's units
      that never run the MXU Pallas path;
    * execution is pinned to one dedicated thread per backend instance —
      the emulated compute unit — so host bricks serialize against each
      other the way a real offload target would, whichever engine/worker
      thread drives the plan."""

    name = "host"
    resident = False
    kernel_mode = "ref"

    def __init__(self, pin_thread: bool = True):
        self._pin = pin_thread
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pool_lock = threading.Lock()
        self._pool_tids: set = set()

    def _executor(self) -> ThreadPoolExecutor:
        with self._pool_lock:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="host-backend",
                    initializer=lambda: self._pool_tids.add(
                        threading.get_ident()))
            return self._pool

    def bind_params(self, brick, params, accel=None):
        return jax.tree.map(np.asarray, brick.params_of(params))

    def compile_fn(self, brick, cfg):
        fn = brick_executable(brick, cfg, self.kernel_mode)
        if not self._pin:
            return fn

        def pinned(p, ctx, _fn=fn):
            if threading.get_ident() in self._pool_tids:
                return _fn(p, ctx)          # already on the pinned thread
            return self._executor().submit(_fn, p, ctx).result()

        return pinned

    def make_edge(self, src_accel, dst_accel):
        # jax.devices("cpu") is the right probe: the CPU platform is
        # registered even when the default backend is TPU/GPU, while
        # local_devices() only lists the default backend's devices
        try:
            cpu = jax.devices("cpu")[0]
        except RuntimeError:
            cpu = None
        if cpu is not None:
            return lambda v, _d=cpu: jax.device_put(v, _d)
        return lambda v: jnp.asarray(np.asarray(v))

    def load(self, brick, bound):
        return jax.tree.map(jnp.asarray, bound)

    def unload(self, dev_params):
        for leaf in jax.tree.leaves(dev_params):
            if hasattr(leaf, "delete"):
                try:
                    leaf.delete()
                except Exception:
                    pass


# ---------------------------------------------------------------------------
# registry — the backend table compile_plan consults
# ---------------------------------------------------------------------------

BACKENDS: Dict[str, Backend] = {
    "submesh": SubmeshBackend(),
    "device": DeviceBackend(),
    "host": HostBackend(),
}


def register_backend(backend: Backend) -> Backend:
    """Add a custom substrate to the lowering table."""
    BACKENDS[backend.name] = backend
    return backend


# per-ordinal DeviceBackends ("device:N" specs) — cached so two plans
# naming the same ordinal share one backend instance, and thus one edge
# identity in compile_plan's edge cache
_DEVICE_BACKENDS: Dict[int, DeviceBackend] = {}
_DEVICE_BACKENDS_LOCK = threading.Lock()


def device_backend(ordinal: int) -> DeviceBackend:
    """The committed DeviceBackend for one device ordinal.

    ``resolve_backend("device:N")`` lands here: each accelerator gets
    its OWN device/stream — weights committed to ``jax.devices()[N]``,
    inbound edges device_put onto it — so a multi-GPU (or
    ``--xla_force_host_platform_device_count``) box is the degenerate
    single-host two-fleet case: prefill fleet on ``device:0``, decode
    fleet on ``device:1`` (``core/scheduler.fleet_accelerators``)."""
    with _DEVICE_BACKENDS_LOCK:
        be = _DEVICE_BACKENDS.get(ordinal)
        if be is None:
            devs = jax.devices()
            if not 0 <= ordinal < len(devs):
                raise BackendError(
                    f"device ordinal {ordinal} out of range "
                    f"({len(devs)} visible device(s))")
            be = DeviceBackend(devs[ordinal])
            be.name = f"device:{ordinal}"
            _DEVICE_BACKENDS[ordinal] = be
        return be


# ---------------------------------------------------------------------------
# substrate table — ONE source of truth tying each energy profile (the
# scheduler's cost-model unit) to the backend it lowers through and the
# relative matmul efficiency per quant label.  Before this table the
# scheduler's _BIT_EFFICIENCY and the backend kernel modes agreed only by
# convention; now ``core/scheduler.brick_cost`` (via
# ``Accelerator.throughput_scale`` -> :func:`bit_efficiency`) and backend
# resolution (:func:`substrate_backend`, consulted by ``resolve_backend``
# and ``Accelerator.backend_name``) read the same rows — a unit priced as
# reference-kernel-slow at fp cannot silently lower through the Pallas
# path, and vice versa.
# ---------------------------------------------------------------------------

_SPARSE_RE = re.compile(r"^(?P<base>.+?)-sp(?P<pct>\d{1,2})$")
_GROUP_RE = re.compile(r"^(?P<base>.+?)-g\d+$")


@dataclass(frozen=True)
class Substrate:
    """One compute-unit row: lowering backend + per-quant-label relative
    matmul throughput (fraction of the unit's peak at its preferred
    width).  ``kernel_mode`` is derived from the backend row, never
    stated twice.

    ``sparse_gain`` is the fraction of activation-aware-pruned MACs the
    unit actually skips (EdgeMM-style structured sparsity): a composite
    label like ``q4f16-g32-sp50`` prices as the base row sped up by
    ``1 / (1 - sparsity * sparse_gain)``.  Units whose kernels cannot
    skip zeros (reference host path) keep gain 0 — pruning buys them
    nothing, and ``schedule()`` can therefore flip a sparse brick to a
    sparsity-capable unit even when the dense costs tie."""

    backend: str                            # BACKENDS registry name
    bit_efficiency: Tuple[Tuple[str, float], ...]
    sparse_gain: float = 0.0

    @property
    def kernel_mode(self) -> str:
        return BACKENDS[self.backend].kernel_mode

    def efficiency(self, quant_label: str, default: float = 1.0) -> float:
        table = dict(self.bit_efficiency)
        if quant_label in table:
            return table[quant_label]
        sparsity = 0.0
        m = _SPARSE_RE.match(quant_label)
        if m:
            sparsity = int(m.group("pct")) / 100.0
            quant_label = m.group("base")
        g = _GROUP_RE.match(quant_label)     # "q4f16-g32" -> "q4f16" row
        if g:
            quant_label = g.group("base")
        base = table.get(quant_label, default)
        if sparsity <= 0.0:
            return base
        return base / max(1.0 - sparsity * self.sparse_gain, 1e-6)


SUBSTRATES: Dict[str, Substrate] = {
    # NPU fp16 at 0.6: the RKNN static-graph driver keeps fp16 encoders
    # "substantially faster on the NPU" (paper §NPU) even though its
    # native width is int8 — the paper's Sec. 4 observation that NPUs
    # consistently win encoder inference must emerge from the cost model.
    # The npu/cpu rows lower through the host backend (reference kernels
    # on a pinned thread — hence the fp penalty); the gpu row through the
    # committed device backend; the pod profile through submeshes.
    # sparse_gain: the NPU's structured-sparse MAC arrays skip most
    # pruned products; the GPU recovers about half; the reference host
    # kernels and the MXU (dense systolic array) skip none.
    "rk-npu": Substrate("host", (("q8f16", 1.0), ("q4f16", 1.0),
                                 ("q2f16", 1.0), ("fp16", 0.6),
                                 ("bf16", 0.6)), sparse_gain=0.9),
    "rk-gpu": Substrate("device", (("q8f16", 0.9), ("q4f16", 0.9),
                                   ("q2f16", 0.9), ("fp16", 1.0),
                                   ("bf16", 1.0)), sparse_gain=0.5),
    "rk-cpu": Substrate("host", (("q8f16", 0.8), ("q4f16", 0.6),
                                 ("q2f16", 0.5), ("fp16", 0.3),
                                 ("bf16", 0.3))),
    "tpu-v5e": Substrate("submesh", (("q8f16", 1.0), ("q4f16", 1.0),
                                     ("q2f16", 1.0), ("fp16", 1.0),
                                     ("bf16", 1.0))),
}


def bit_efficiency(profile_name: str, quant_label: str,
                   default: float = 1.0) -> float:
    """The cost model's throughput scale for one unit at one quant width,
    from the shared substrate table (1.0 for unknown units/labels)."""
    sub = SUBSTRATES.get(profile_name)
    return default if sub is None else sub.efficiency(quant_label, default)


def substrate_backend(profile_name: str) -> Optional[str]:
    """The backend registry name a unit's profile lowers through, or None
    for profiles the table does not know."""
    sub = SUBSTRATES.get(profile_name)
    return None if sub is None else sub.backend


def resolve_backend(spec: Union[str, Backend, None],
                    accel=None) -> Backend:
    """Resolve a backend spec to a concrete Backend.

    Priority: explicit ``spec`` (Backend instance, registry name, or a
    ``"device:N"`` ordinal — the per-device committed backend of
    :func:`device_backend`) > the accelerator's ``backend`` profile
    field > the shared :data:`SUBSTRATES` row of the accelerator's
    energy profile (the same row the scheduler's cost model prices
    with) > inferred from the accelerator (mesh -> submesh, mesh-less ->
    host: the paper's edge units are emulated host-side) > ``device``
    (default-device placement when nothing was specified)."""
    if isinstance(spec, Backend):
        return spec
    if spec is not None:
        if isinstance(spec, str) and spec.startswith("device:"):
            tail = spec.split(":", 1)[1]
            if not tail.isdigit():
                raise BackendError(
                    f"bad device ordinal in backend spec {spec!r} "
                    f"(want 'device:<int>')")
            return device_backend(int(tail))
        try:
            return BACKENDS[spec]
        except KeyError:
            raise BackendError(
                f"unknown backend {spec!r}; registered: "
                f"{sorted(BACKENDS)}") from None
    if accel is not None:
        name = getattr(accel, "backend", None)
        if name:
            return resolve_backend(name)
        profile = getattr(accel, "profile", None)
        sub = substrate_backend(getattr(profile, "name", ""))
        mesh = getattr(accel, "mesh", None)
        # the table row binds unless it is physically impossible (a
        # submesh lowering needs a mesh to exist on this accelerator)
        if sub is not None and not (sub == "submesh" and mesh is None):
            return BACKENDS[sub]
        if mesh is not None:
            return BACKENDS["submesh"]
        return BACKENDS["host"]
    return BACKENDS["device"]
