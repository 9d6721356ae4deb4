"""First-class scheduling layer: pluggable policies + per-shape plan cache.

``SchedulePolicy.resolve(phase, seq_bucket, batch_per_device,
occupancy=..., skew=...) -> Plan`` is the single interface through which
the engine, the DEP executor and the examples obtain schedules;
``PlanCache`` memoizes resolved plans per shape (prefill buckets) or per
``OccupancySummary`` (decode solved on the real live-slot composition) so
steady-state decode pays ~zero solver cost.
"""
from repro.sched.cache import EntryMeta, PlanCache, PlanCacheStats, PlanKey
from repro.sched.occupancy import (DEFAULT_BUCKETS, OccupancySummary,
                                   bucket_length)
from repro.sched.policy import (EPSPipelinePolicy, FinDEPPolicy, POLICIES,
                                SchedulePolicy, SequentialDEPPolicy,
                                StaticPolicy, make_policy)

__all__ = [
    "PlanCache", "PlanCacheStats", "PlanKey", "EntryMeta", "SchedulePolicy",
    "FinDEPPolicy", "StaticPolicy", "SequentialDEPPolicy",
    "EPSPipelinePolicy", "POLICIES", "make_policy",
    "OccupancySummary", "DEFAULT_BUCKETS", "bucket_length",
]
