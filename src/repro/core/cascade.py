"""On-Demand Cascade Inference (paper §3.2, Fig. 2).

In Critical Conservation mode the system becomes event-triggered and
strictly sequential: each brick follows "load -> execute -> release" — it is
loaded, performs its task, is released, and passes only the *minimal* output
(a text string or an embedding vector) to the next stage: "a lightweight,
domino-like chain" whose peak memory is max(brick) instead of sum(bricks).

The cascade is now a *backend strategy*, not an interpreter: it compiles
the BrickGraph with :func:`repro.core.plan.compile_plan` lowering every
brick through the transient ``HostBackend`` (``residency="one-brick"`` is
the same lowering) — brick params live host-side (numpy) and every
``run_once`` loads one brick, applies it through the same jit-cached
callable the serving engine uses, then deletes the device buffers before
the next brick loads.  There is no per-kind dispatch here; the dataflow is
the bricks' declared ports.  The high-water-mark trace proves the
max-not-sum claim (tests/test_cascade.py).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from repro.core.bricks import BrickGraph
from repro.core.plan import PlanEvent, PlanTrace, compile_plan

# historical names, still the public API of this module
CascadeEvent = PlanEvent
CascadeTrace = PlanTrace


class CascadeRunner:
    """Event-triggered sequential pipeline over a BrickGraph: a thin
    HostBackend (transient, load->execute->release) lowering of the
    shared ExecutionPlan."""

    def __init__(self, graph: BrickGraph, host_params: Dict[str, Any]):
        """host_params: the full param pytree — held HOST-side (numpy) by
        the plan; cascade mode keeps nothing resident between events."""
        self.graph = graph
        self.cfg = graph.cfg
        self.plan = compile_plan(graph, host_params, backend="host",
                                 residency="one-brick")

    def run_once(self, inputs: Dict[str, Any],
                 trace: Optional[CascadeTrace] = None):
        """One event-triggered inference pass through every brick.
        Returns (final logits, residency trace)."""
        return self.plan.run(inputs, trace=trace)
