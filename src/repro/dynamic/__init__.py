"""Dynamic partitioning subsystem: keep a graph AND its partition resident
on device while absorbing streams of edge/node updates.

Three layers (ISSUE 4):

* :mod:`repro.dynamic.store` — a mutable device-resident graph: base CSR
  (:class:`~repro.graph.csr.GraphDev`) plus a bounded COO *delta overlay*,
  merged back into CSR by a bucketed device compaction.
* :mod:`repro.dynamic.repair` — the incremental repair kernels: h-hop
  affected-region expansion on device, region-masked gain/balance rounds.
  The size-constrained LP sweep itself is dispatched by
  :meth:`repro.core.engine.LPEngine.repair` over a *region pack*.
* :mod:`repro.dynamic.session` — :class:`PartitionSession`, the serving
  loop: batched update requests in, repaired device-resident labels out,
  with a cut/imbalance quality guard that escalates to a full multilevel
  ``partition()`` when local repair can no longer hold quality.
* :mod:`repro.dynamic.group` — :class:`SessionGroup`, the multi-tenant
  throughput layer (ISSUE 8): vmapped repair over a bucketed batch of
  independent sessions, serving a merged update stream with per-tenant
  solo bit-parity.
"""

from .group import GroupStats, SessionGroup
from .session import PartitionSession, SessionConfig, UpdateResult
from .store import (
    DynamicGraphStore,
    GraphUpdate,
    UpdateValidationError,
    churn_updates,
)

__all__ = [
    "DynamicGraphStore",
    "GraphUpdate",
    "GroupStats",
    "PartitionSession",
    "SessionConfig",
    "SessionGroup",
    "UpdateResult",
    "UpdateValidationError",
    "churn_updates",
]
