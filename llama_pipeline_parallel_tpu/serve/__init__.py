"""Continuous-batching serving subsystem (docs/SERVING.md).

The second workload next to training: the decode stack generalized from
one-shot batches to a long-lived service — one KV store (pages.py: slots
over fixed-size pages + a slot->page table, so HBM tracks tokens actually
generated; optional int8 pages, optional prefix sharing), admission
scheduler with continuous batching and chunked batched prefill
(engine.py), SLO telemetry (telemetry.py), per-request distributed
tracing (reqtrace.py), and a stdlib HTTP front-end (frontend.py).
`tools/serve.py` wraps it into a supervised process;
`tools/serving_report.py` summarizes its telemetry offline;
`tools/request_report.py` renders per-request waterfalls;
`tools/serve_traffic.py` generates synthetic Poisson traffic against it.
"""

from llama_pipeline_parallel_tpu.serve.engine import (
    EngineShutdown,
    RequestHandle,
    RequestRejected,
    ServeConfig,
    ServeEngine,
    ServeLoop,
    ServeOverloaded,
    ServePagesExhausted,
    ServeRequest,
)
from llama_pipeline_parallel_tpu.serve.pages import PagedKVCache
from llama_pipeline_parallel_tpu.serve.reqtrace import (
    RequestTraceRecorder,
    TraceContext,
)
from llama_pipeline_parallel_tpu.serve.telemetry import SLOStats

__all__ = [
    "EngineShutdown", "PagedKVCache", "RequestHandle", "RequestRejected",
    "RequestTraceRecorder", "ServeConfig", "ServeEngine", "ServeLoop",
    "ServeOverloaded", "ServePagesExhausted", "ServeRequest",
    "SLOStats", "TraceContext",
]
