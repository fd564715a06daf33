"""Serving-path runtime: the adaptive micro-batching query scheduler, its
plan cache (≙ the amortize-per-query-cost discipline of the
reference's server-side scans, applied to concurrent request traffic), the
query-lifecycle resilience layer (deadlines, admission control, circuit
breaking, graceful degradation — serve/resilience/), and the fleet-facing
ReplicaRouter (health/lag-aware read balancing + failover —
serve/router.py)."""

from geomesa_tpu.serve.resilience import (ApproximateCount,  # noqa: F401
                                          CircuitOpenError, Deadline,
                                          DeadlineExceeded, ShedError)
from geomesa_tpu.serve.router import (HttpEndpoint,  # noqa: F401
                                      LocalEndpoint, NoEndpointAvailable,
                                      ReplicaRouter)
from geomesa_tpu.serve.scheduler import (PlannerBinding,  # noqa: F401
                                         QueryScheduler, SchedulerCrashed,
                                         SchedulerShutdown, StoreBinding)
