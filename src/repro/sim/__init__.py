"""Discrete-event timed I/O engine over the functional ZapRAID simulator.

Layers (see DESIGN.md §8):

* :mod:`repro.sim.engine`   -- virtual clock + event heap;
* :mod:`repro.sim.device`   -- ``TimedDrive``: per-zone command queues with
  perfmodel-sampled service times over ``SimZnsDrive``;
* :mod:`repro.sim.workload` -- MSR-style trace parsing + synthetic and
  multi-tenant generators;
* :mod:`repro.sim.stats`    -- per-request latency recording and
  percentiles.

The timed request pipeline itself lives in :mod:`repro.core.handlers`
(``HandlerPipeline`` with an engine attached); this package holds the
engine-side primitives it schedules on.
"""
from repro.sim.device import ServiceModel, TimedDrive, make_timed_drives, plan_group_appends
from repro.sim.engine import Engine
from repro.sim.faults import FaultEvent, FaultInjector, FaultPlan
from repro.sim.stats import LatencyRecorder
from repro.sim.workload import Request, TenantSpec, multi_tenant, parse_msr_trace, synthetic

__all__ = [
    "Engine",
    "FaultEvent",
    "FaultInjector",
    "FaultPlan",
    "LatencyRecorder",
    "Request",
    "ServiceModel",
    "TenantSpec",
    "TimedDrive",
    "make_timed_drives",
    "multi_tenant",
    "parse_msr_trace",
    "plan_group_appends",
    "synthetic",
]
