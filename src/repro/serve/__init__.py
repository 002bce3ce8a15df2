from repro.serve.engine import ServingEngine            # noqa: F401
from repro.serve.bcnn_engine import BCNNEngine, drive_poisson  # noqa: F401
from repro.serve.slots import (Request, SlotScheduler, Span,  # noqa: F401
                               SpanLog, latency_stats)
from repro.serve.replica import EngineReplica, SwapTicket      # noqa: F401
from repro.serve.autoscale import (AutoscaleConfig,     # noqa: F401
                                   FleetAutoscaler, ScaleEvent)
from repro.serve.router import (BULK, DEFAULT_CLASSES,  # noqa: F401
                                ONLINE, RequestClass, Router,
                                RouterOverload, RouterRequest,
                                RouterShutdown, drive_mixed_poisson)
