"""Multi-tree search service: handles, global scheduler, arena pools.

  SearchClient / SearchHandle    (client.py)   submit() -> opaque handle
      with done()/result()/cancel()/moves() streaming, poll()/run_until()
      progress — callers never touch pools or arenas.
  SchedulerCore / SchedulePolicy (scheduler_core.py)   global admission
      across config buckets (round-robin | weighted-queue-depth |
      deadline-aware), deadline eviction, cold-pool retirement, and the
      cross-pool fused SimulationBackend.evaluate batch.
  ArenaPool                      (pool.py)     one bucket's G-slot arena,
      StateTables, queue, and the BSP superstep body (split at the
      Simulation boundary for cross-pool fusion).

Executors are imported from repro.core.executor.
"""

from repro.service.client import SearchClient, SearchHandle
from repro.service.scheduler_core import (
    POLICY_NAMES, DeadlineAwarePolicy, RoundRobinPolicy, SchedulePolicy,
    SchedulerCore, WeightedQueueDepthPolicy, make_policy,
)
from repro.service.pool import (
    ArenaPool, MoveEvent, SearchRequest, SearchResult, ServiceStats,
)

__all__ = [
    "SearchClient", "SearchHandle",
    "SchedulerCore", "SchedulePolicy", "POLICY_NAMES", "make_policy",
    "RoundRobinPolicy", "WeightedQueueDepthPolicy", "DeadlineAwarePolicy",
    "ArenaPool", "MoveEvent", "SearchRequest", "SearchResult",
    "ServiceStats",
]
