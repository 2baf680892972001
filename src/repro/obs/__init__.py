"""Observability: metrics sinks, phase timers, the host watch, the layer
scopes of the train step, and the comm flight recorder
(docs/observability.md).

Everything here is host-side bookkeeping or HLO metadata — enabling a
sink never adds collectives or device ops to a traced program, so the
HLO budget checks hold with instrumentation on or off.
"""

from repro.obs.flight_recorder import CompileSnapshot, FlightRecorder
from repro.obs.host import HostWatch
from repro.obs.metrics import (Fence, Histogram, InMemorySink, JsonlSink,
                               Metrics, MetricsSink, NullSink, PhaseTimer,
                               as_sink, block_until_ready, read_jsonl,
                               render_step, scoped_timer)

__all__ = [
    "CompileSnapshot", "FlightRecorder", "Fence", "Histogram", "HostWatch",
    "InMemorySink", "JsonlSink", "Metrics", "MetricsSink", "NullSink",
    "PhaseTimer", "as_sink", "block_until_ready", "read_jsonl",
    "render_step", "scoped_timer",
]
