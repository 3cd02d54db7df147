from repro.serving.engine import DecodeEngine, GenerationResult
from repro.serving.kvpool import (PagedDecodeStream, PagePool, PoolExhausted,
                                  RadixCache)
from repro.serving.observe import (NULL_TRACER, Counter, Gauge, Histogram,
                                   MetricsRegistry, NullTracer, Tracer)
from repro.serving.request import ServeRequest, ServeResult
from repro.serving.resilience import (CircuitBreaker, FaultInjector,
                                      FaultSpec, HeadFault, LogicalClock,
                                      StreamWatchdog)
from repro.serving.scheduler import (AdmissionRejected, BudgetAdmission,
                                     ContinuousScheduler, SchedulerStalled,
                                     ServerStats)
from repro.serving.router import (DEFAULT_ACCURACY, CostAwarePolicy,
                                  RoutingPolicy, StaticPolicy, TierPolicy,
                                  route_requests)
from repro.serving.spec import (DraftLenController, SpecDecodeStream,
                                SpecPolicy, spec_step_flops)

__all__ = ["DecodeEngine", "GenerationResult",
           "PagePool", "PagedDecodeStream", "PoolExhausted", "RadixCache",
           "ServeRequest", "ServeResult",
           "RoutingPolicy", "StaticPolicy", "TierPolicy", "CostAwarePolicy",
           "DEFAULT_ACCURACY", "route_requests",
           "ContinuousScheduler", "SchedulerStalled", "ServerStats",
           "BudgetAdmission", "AdmissionRejected",
           "SpecPolicy", "SpecDecodeStream", "DraftLenController",
           "spec_step_flops",
           "FaultInjector", "FaultSpec", "HeadFault", "LogicalClock",
           "CircuitBreaker", "StreamWatchdog",
           "Tracer", "NullTracer", "NULL_TRACER",
           "Counter", "Gauge", "Histogram", "MetricsRegistry"]
