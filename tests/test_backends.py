"""Backend lowering API: one Placement, many substrates (core/backends).

Covers the api_redesign acceptance criteria:
* the same BrickGraph + Placement lowered via SubmeshBackend,
  DeviceBackend, and HostBackend produces identical greedy tokens through
  ServingEngine (and identical plan.run logits);
* cascade max-not-sum residency holds on the HostBackend lowering;
* the module-level jit cache is shared across compile_plan calls — the
  engine/cascade/scheduler paths reuse compiled executables (the old
  per-plan ``_make_fn`` lambda bug);
* kernels/dispatch: one TPU check, REPRO_FORCE_REF override, force_ref
  scope, and HostBackend executables pinned to the reference path;
* Accelerator.backend -> schedule() -> Placement.backends carry-through;
* plan.relower + PowerPolicy.knobs.backend_demotion (the THROTTLED
  re-lowering hook) change the substrate without changing the numbers.
"""
import os
import subprocess
import sys
import textwrap
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.energy import TPU_V5E
from repro.configs import get_config
from repro.core import backends as B
from repro.core.backends import (BACKENDS, BackendError, HostBackend,
                                 jit_cache_len, resolve_backend)
from repro.core.bricks import decompose
from repro.core.plan import compile_plan
from repro.core.power import PowerPolicy
from repro.core.scheduler import (Accelerator, edge_accelerators,
                                  populate_brick_bytes, schedule)
from repro.kernels import dispatch
from repro.launch.mesh import make_mesh
from repro.launch.steps import init_params
from repro.models.model import lm_forward
from repro.serving.engine import Request, ServingEngine


@pytest.fixture(scope="module")
def vlm():
    cfg = get_config("llava-onevision-0.5b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _submesh_accels():
    """Two submesh accelerators over the test container's single device —
    enough to drive the SubmeshBackend lowering (NamedSharding binds +
    SubmeshPipe edges); the 8-device split runs in scripts/check.sh."""
    mesh = make_mesh((1,), ("model",))
    return [
        Accelerator("enc", TPU_V5E, static_only=True, dynamic_ok=False,
                    mesh=mesh, backend="submesh"),
        Accelerator("dec", TPU_V5E, mesh=mesh, backend="submesh"),
    ]


def _static_assignment(cfg):
    return {b.name: ("enc" if b.static_shape else "dec")
            for b in decompose(cfg).bricks}


def _reqs(cfg, n=3, n_new=5):
    rng = np.random.default_rng(0)
    return [Request(
        rid=i, tokens=(np.arange(6 + i) % 50 + 3).astype(np.int32),
        max_new_tokens=n_new,
        vision_feats=rng.standard_normal(
            (1, cfg.vision_tokens, cfg.vision_feat_dim)
        ).astype(np.float32) * 0.02) for i in range(n)]


# ---------------------------------------------------------------------------
# the tentpole: same graph, swappable substrate
# ---------------------------------------------------------------------------

def test_plan_logits_identical_across_backends(vlm):
    """One BrickGraph lowered through all three backends returns the
    monolithic forward's logits."""
    cfg, params = vlm
    rng = np.random.default_rng(0)
    inputs = {"tokens": jnp.asarray(rng.integers(3, 200, (1, 24)),
                                    jnp.int32),
              "vision_feats": jnp.asarray(
                  rng.standard_normal(
                      (1, cfg.vision_tokens, cfg.vision_feat_dim)) * 0.02,
                  jnp.float32)}
    mono, _ = lm_forward(params, cfg, inputs["tokens"],
                         vision_feats=inputs["vision_feats"])
    mono = np.asarray(mono, np.float32)

    lowerings = {
        "device": dict(backend="device"),
        "host": dict(backend="host"),
        "submesh": dict(placement=_static_assignment(cfg),
                        accels=_submesh_accels()),
    }
    for name, kw in lowerings.items():
        plan = compile_plan(decompose(cfg), params, **kw)
        assert all(s.backend.name == name for s in plan.steps), name
        out, _ = plan.run(inputs)
        np.testing.assert_allclose(np.asarray(out, np.float32), mono,
                                   rtol=2e-2, atol=2e-2, err_msg=name)


def test_engine_greedy_tokens_identical_across_backends(vlm):
    """The issue's equivalence criterion: identical greedy tokens through
    ServingEngine whichever substrate the plan lowered to."""
    cfg, params = vlm
    results = {}
    for name, kw in [("device", dict(backend="device")),
                     ("host", dict(backend="host")),
                     ("submesh", dict(placement=_static_assignment(cfg),
                                      accels=_submesh_accels()))]:
        with ServingEngine(cfg, params, n_slots=2, max_len=128,
                           **kw) as eng:
            for r in _reqs(cfg):
                eng.submit(r)
            done = eng.run()
            assert all(r.error is None for r in done), name
            results[name] = {r.rid: tuple(r.out_tokens) for r in done}
    assert results["device"] == results["host"] == results["submesh"]
    assert all(results["device"][i] for i in range(3))


def test_cascade_max_not_sum_on_host_backend(vlm):
    """HostBackend is the cascade policy: load -> execute -> release per
    brick on the pinned host thread; peak residency stays max-not-sum and
    returns to zero."""
    cfg, params = vlm
    plan = compile_plan(decompose(cfg), params, backend="host")
    assert all(not s.backend.resident for s in plan.steps)
    rng = np.random.default_rng(0)
    _, trace = plan.run({
        "tokens": jnp.asarray(rng.integers(3, 200, (1, 16)), jnp.int32),
        "vision_feats": jnp.asarray(
            rng.standard_normal(
                (1, cfg.vision_tokens, cfg.vision_feat_dim)) * 0.02,
            jnp.float32)})
    for b in plan.graph.names():
        phases = [(e.brick, e.phase) for e in trace.events]
        assert (b, "load") in phases and (b, "release") in phases
    assert trace.events[-1].resident_bytes == 0
    assert 0 < trace.peak_bytes < trace.sum_bytes
    # execution really went through the backend's pinned thread
    host = BACKENDS["host"]
    assert host._pool is not None and host._pool_tids
    assert any(t.name.startswith("host-backend")
               for t in threading.enumerate())


# ---------------------------------------------------------------------------
# satellite: shared jit cache (the old per-plan _make_fn lambda bug)
# ---------------------------------------------------------------------------

def test_jit_cache_shared_across_compile_plan_calls(vlm):
    """Two compile_plan calls over equal (brick, cfg) keys must reuse the
    cached executables — no fresh jax.jit per plan, so engine, cascade,
    and scheduler plans share compiled functions."""
    cfg, params = vlm
    plan_a = compile_plan(decompose(cfg), params, backend="device")
    n_after_first = jit_cache_len()
    plan_b = compile_plan(decompose(cfg), params, backend="device")
    assert jit_cache_len() == n_after_first          # pure cache hits
    for sa, sb in zip(plan_a.steps, plan_b.steps):
        assert sa.fn is sb.fn, sa.brick.name         # the same executable
    # a different kernel mode is a different executable (host = ref path),
    # but re-lowering to host twice is again pure cache hits
    plan_h = compile_plan(decompose(cfg), params, backend="host")
    n_after_host = jit_cache_len()
    plan_h2 = compile_plan(decompose(cfg), params, backend="host")
    assert jit_cache_len() == n_after_host
    assert B.brick_executable(plan_h.steps[0].brick, cfg, "ref") \
        is B.brick_executable(plan_h2.steps[0].brick, cfg, "ref")


# ---------------------------------------------------------------------------
# satellite: one kernel dispatch helper
# ---------------------------------------------------------------------------

def test_kernel_dispatch_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_FORCE_REF", raising=False)
    on_tpu = dispatch.on_tpu()
    # explicit caller choice always wins
    assert dispatch.resolve_interpret(True) is True
    assert dispatch.resolve_interpret(False) is False
    # default: interpret off-TPU, compiled on TPU
    assert dispatch.resolve_interpret(None) is (not on_tpu)
    # env var forces the reference path everywhere
    monkeypatch.setenv("REPRO_FORCE_REF", "1")
    assert dispatch.resolve_interpret(None) is True
    monkeypatch.setenv("REPRO_FORCE_REF", "0")
    assert dispatch.resolve_interpret(None) is (not on_tpu)
    # the scoped (thread-local, re-entrant) override HostBackend uses
    with dispatch.force_ref():
        assert dispatch.resolve_interpret(None) is True
        with dispatch.force_ref():
            assert dispatch.resolve_interpret(None) is True
        assert dispatch.resolve_interpret(None) is True
    assert dispatch.resolve_interpret(None) is (not on_tpu)


def test_ops_share_the_dispatch_helper():
    """No kernel wrapper keeps a private jax.default_backend() check."""
    import inspect
    import repro.kernels.cache_update.ops as c
    import repro.kernels.dequant_gemm.ops as d
    import repro.kernels.flash_attention.ops as f
    import repro.kernels.linear_attention.ops as l
    import repro.kernels.ssd.ops as s
    for mod in (c, d, f, l, s):
        src = inspect.getsource(mod)
        assert "default_backend" not in src, mod.__name__
        assert "resolve_interpret" in src, mod.__name__


# ---------------------------------------------------------------------------
# carry-through: Accelerator.backend -> schedule() -> Placement.backends
# ---------------------------------------------------------------------------

def test_accelerator_backend_profile_carries_into_placement(vlm):
    cfg, params = vlm
    accels = edge_accelerators()
    assert {a.name: a.backend_name() for a in accels} == {
        "npu": "host", "gpu": "device", "cpu": "host"}
    graph = decompose(cfg)
    populate_brick_bytes(graph, params)
    pl = schedule(graph, accels, n_tokens=24)
    assert set(pl.backends) == set(pl.assignment) == set(graph.names())
    by_name = {a.name: a for a in accels}
    for brick, acc in pl.assignment.items():
        assert pl.backends[brick] == by_name[acc].backend_name()
    # and compile_plan lowers each brick through the carried backend
    plan = compile_plan(graph, params, placement=pl, accels=accels)
    for s in plan.steps:
        assert s.backend.name == pl.backends[s.brick.name]


def test_one_brick_rejects_resident_override(vlm):
    """residency='one-brick' promises max-not-sum memory; a resident
    backend override would silently break that, so it must be an error."""
    from repro.core.plan import PlanError
    cfg, params = vlm
    with pytest.raises(PlanError):
        compile_plan(decompose(cfg), params, backend="device",
                     residency="one-brick")
    # a transient override is the same lowering the alias picks
    plan = compile_plan(decompose(cfg), params, backend="host",
                        residency="one-brick")
    assert all(not s.backend.resident for s in plan.steps)


def test_resolve_backend_priorities():
    assert resolve_backend("host") is BACKENDS["host"]
    assert resolve_backend(BACKENDS["device"]) is BACKENDS["device"]
    with pytest.raises(BackendError):
        resolve_backend("no-such-substrate")
    # accelerator profile field beats inference
    acc = Accelerator("x", TPU_V5E, backend="device")
    assert resolve_backend(None, acc) is BACKENDS["device"]
    # mesh-less accelerator with no profile -> host emulation
    assert resolve_backend(None, Accelerator("y", TPU_V5E)) \
        is BACKENDS["host"]
    # nothing at all -> default-device placement
    assert resolve_backend(None) is BACKENDS["device"]


# ---------------------------------------------------------------------------
# the THROTTLED re-lowering hook
# ---------------------------------------------------------------------------

def test_power_policy_backend_demotion_knob():
    pol = PowerPolicy(t_high=0.6, t_low=0.2)
    assert pol.knobs(0.9).backend_demotion is None       # UNCONSTRAINED
    assert pol.knobs(0.55).backend_demotion is None      # mild THROTTLED
    assert pol.knobs(0.25).backend_demotion == "host"    # deep THROTTLED
    assert pol.knobs(0.1).backend_demotion == "host"     # CRITICAL


def test_relower_changes_substrate_not_numbers(vlm):
    cfg, params = vlm
    rng = np.random.default_rng(0)
    inputs = {"tokens": jnp.asarray(rng.integers(3, 200, (1, 16)),
                                    jnp.int32),
              "vision_feats": jnp.asarray(
                  rng.standard_normal(
                      (1, cfg.vision_tokens, cfg.vision_feat_dim)) * 0.02,
                  jnp.float32)}
    plan = compile_plan(decompose(cfg), params)          # default: device
    out_dev, _ = plan.run(inputs)
    step = plan.relower("projector", "host")
    assert step.backend.name == "host"
    assert plan.backend_of("projector").name == "host"
    assert plan.backend_of("decoder").name == "device"   # others untouched
    out_mixed, _ = plan.run(inputs)
    np.testing.assert_allclose(np.asarray(out_mixed, np.float32),
                               np.asarray(out_dev, np.float32),
                               rtol=2e-2, atol=2e-2)
    plan.relower("projector", "device")                  # restore
    assert plan.backend_of("projector").name == "device"


def test_engine_applies_demotion_and_restores(vlm):
    """The battery hook end to end: a deep-THROTTLED PMU makes the engine
    relower its static (encoder-side) bricks to the host backend; a
    recovered battery restores the compiled substrate."""
    from repro.core.power import BatteryAwareExecutor, PMU
    cfg, params = vlm
    ex = BatteryAwareExecutor(PMU())
    ex.pmu.level = 0.25                                  # deep THROTTLED
    with ServingEngine(cfg, params, n_slots=2, max_len=128,
                       executor=ex) as eng:
        assert eng.plan.backend_of("projector").name == "device"
        eng.step()                                       # applies knobs
        assert eng.plan.backend_of("projector").name == "host"
        assert eng.plan.backend_of("decoder").name == "device"
        # demoted lowering still serves correctly
        eng.submit(_reqs(cfg, n=1, n_new=3)[0])
        done = eng.run()
        assert done[0].error is None and len(done[0].out_tokens) >= 3
        ex.pmu.level = 1.0                               # charge recovers
        eng.step()
        assert eng.plan.backend_of("projector").name == "device"


SPLIT_DECODER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.core.bricks import decompose
    from repro.core.scheduler import make_virtual_accelerators
    from repro.kernels import dispatch
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import init_params
    from repro.serving.engine import Request, ServingEngine

    cfg = get_config("llava-onevision-0.5b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    enc, dec = make_virtual_accelerators(make_mesh((1, 4), ("data", "model")),
                                         fractions=(0.25, 0.75))
    placement = {b.name: (enc.name if b.static_shape else dec.name)
                 for b in decompose(cfg).bricks}
    dispatch.on_tpu = lambda: True     # the default resolution a TPU sees
    with ServingEngine(cfg, params, n_slots=2, max_len=128,
                       placement=placement, accels=[enc, dec]) as eng:
        eng.submit(Request(rid=0, tokens=np.arange(12, dtype=np.int32) + 3,
                           max_new_tokens=3,
                           vision_feats=np.zeros((1, cfg.vision_tokens,
                                                  cfg.vision_feat_dim),
                                                 np.float32)))
        done = eng.run()
        assert done[0].error is None, done[0].error
        assert eng.cohort_path == ("composed", False), eng.cohort_path
    print("SPLIT_OK")
""")


def test_decoder_on_several_devices_takes_composed_step():
    """A decoder brick placed on a submesh of 3 devices decodes over all
    of them, where a Mosaic kernel cannot be partitioned: the engine's
    default resolution must pick the composed step there, even on a TPU
    (on_tpu forced; the composed step then runs on the CPU as is)."""
    proc = subprocess.run([sys.executable, "-c", SPLIT_DECODER],
                          capture_output=True, text=True, timeout=600,
                          env=dict(os.environ, PYTHONPATH="src"),
                          cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SPLIT_OK" in proc.stdout
