"""Telemetry: measured-not-modeled feedback for the scheduler + benches.

Three coupled layers (ISSUE 8 / ROADMAP "measured energy/roofline
ledger"):

* :mod:`repro.telemetry.ledger` — a ``FlopCount``-style accumulating
  record of flops / bytes / link-bytes / tokens / joules / seconds per
  brick per phase (stage | prefill | decode), JSON-persisted; populated
  statically from the roofline+energy cost model at compile time and
  dynamically from wall-time probes.
* :mod:`repro.telemetry.probes` — timestamped per-brick wall-time
  samples recorded by ``ExecutionPlan`` / ``ServingEngine`` outside jit
  regions (host clocks only, replint-clean).
* :mod:`repro.telemetry.calibration` — measured per-brick
  seconds/joules tables that ``core/scheduler.brick_cost`` consults, so
  placement prices come from observation when samples exist.
* :mod:`repro.telemetry.fleet` — a RAPS-``FLOPSManager``-style
  simulator stepping hundreds of battery devices (own PMU/PowerPolicy
  each) through request traces, reporting fleet tokens/s, J/token and
  survival-hours histograms.
"""
from repro.telemetry.calibration import CostCalibration
from repro.telemetry.ledger import Ledger, PhaseRecord
from repro.telemetry.probes import WallProbe

__all__ = ["CostCalibration", "Ledger", "PhaseRecord", "WallProbe"]
