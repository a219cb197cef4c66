"""serve/ — continuous-batching LM inference on the training stack.

The ROADMAP's "serves heavy traffic" leg: an Orca-style engine that
runs many concurrent, independently-arriving requests through ONE
accelerator with iteration-level scheduling — a slot-pooled, paged and
prefix-shared KV cache of fixed shapes, with a refcounted block pool and
a radix index (``pages``; what its programs share: ``cache``), an
admission scheduler with bounded
queue + priorities + per-request deadlines (``scheduler``), the engine
loop and threaded front door (``engine``), and per-request SLO metrics
(``metrics``). Architecture and failure grammar: docs/serving.md.
"""

from ..nn.paged import BlockGenerationUnsupported  # noqa: F401
from .cache import CompileCounts  # noqa: F401
from .disagg import DisaggConfig, DisaggEngine  # noqa: F401
from .engine import EngineConfig, InferenceEngine  # noqa: F401
from .fleet import (FleetAutoscaler, FleetConfig, FleetHandle,  # noqa: F401
                    FleetRouter, ReplicaFailed)
from .metrics import aggregate, percentile, request_record  # noqa: F401
from .pages import PagedSlotPool, PagePool, PrefixIndex  # noqa: F401
from .scheduler import AdmissionScheduler  # noqa: F401
from .spec import SpecConfig, SpecState  # noqa: F401
from .types import (AdmissionRejected, EngineStopped,  # noqa: F401
                    HandoffCorrupt, HandoffError, HandoffTimeout,
                    PagePoolExhausted, PrefillEngineDied, Request,
                    RequestDeadlineExceeded, RequestHandle,
                    SamplingParams, ServeError, SpecDecodeError)

__all__ = [
    "AdmissionRejected", "AdmissionScheduler",
    "BlockGenerationUnsupported", "CompileCounts",
    "DisaggConfig", "DisaggEngine", "EngineConfig", "EngineStopped",
    "FleetAutoscaler", "FleetConfig", "FleetHandle", "FleetRouter",
    "HandoffCorrupt", "HandoffError", "HandoffTimeout",
    "InferenceEngine", "PagePool", "PagePoolExhausted", "PagedSlotPool",
    "PrefillEngineDied", "PrefixIndex", "ReplicaFailed", "Request",
    "RequestDeadlineExceeded", "RequestHandle", "SamplingParams",
    "ServeError", "SpecConfig", "SpecDecodeError",
    "SpecState", "aggregate", "percentile", "request_record",
]
