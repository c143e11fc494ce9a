"""repro.dist — distribution substrate: sharding rules, elastic restore,
fault tolerance.

  compat    — the one mesh / abstract-mesh / shard_map constructor set
  sharding  — logical-axis rulebook (make_resolver / resolve_axes / batch_axes)
  elastic   — elastic_restore: checkpoint restore onto a *different* mesh
  fault     — Heartbeat, StragglerMonitor, retry_step
  chaos     — Fault / FaultSchedule / ChaosInjector: seeded, scripted fault
              injection for the fleet supervisor (timing moves, bits don't)
"""
from repro.dist.chaos import (
    ChaosInjector,
    ChaosTransientError,
    Fault,
    FaultSchedule,
    WorkerKilled,
)
from repro.dist.compat import abstract_mesh, make_compat_mesh, shard_map_compat
from repro.dist.elastic import elastic_restore, target_shardings
from repro.dist.fault import Heartbeat, StragglerMonitor, retry_step
from repro.dist.sharding import batch_axes, make_resolver, resolve_axes

__all__ = [
    "abstract_mesh",
    "make_compat_mesh",
    "shard_map_compat",
    "elastic_restore",
    "target_shardings",
    "ChaosInjector",
    "ChaosTransientError",
    "Fault",
    "FaultSchedule",
    "WorkerKilled",
    "Heartbeat",
    "StragglerMonitor",
    "retry_step",
    "batch_axes",
    "make_resolver",
    "resolve_axes",
]
