"""paxtrace: end-to-end causal tracing + crash flight recorder.

Three pieces, all host-side (never inside ``ops/`` kernels -- paxlint
TPU209 enforces that):

  * ``trace`` -- a trace context (trace_id, span_id, sampling bit)
    propagated at the transport FRAME layer (the wire tag space 1..127
    is fully allocated, so the context rides the frame header, not the
    message codecs) plus the Tracer that emits receive/timer/drain
    spans with stage sub-spans (decode, handler, drain, fan-out,
    wal-fsync, send-release, ...), and the per-thread stage
    accounting behind ``fpx_runtime_drain_stage_seconds``.
  * ``flight`` -- a fixed-size per-role flight recorder ring buffer
    over an mmap'd file: the OS keeps the dirty pages when the process
    is SIGKILL'd, so a crashed role still leaves a record of its last
    actions for the chaos driver's post-mortem.
  * ``perfetto`` -- span records -> Chrome-trace-event JSON (loads in
    Perfetto / chrome://tracing), per-command critical paths, and the
    drain-stage latency-breakdown table.
  * ``telemetry`` -- the paxpulse HOST side: one batched D2H collect
    per reporting interval of the device counters that ride inside the
    jitted pipeline as arrays (ops/telemetry.py -- counters are data,
    not hooks, so TPU209 stays satisfied), publishing
    ``fpx_pipeline_*`` RuntimeMetrics and Perfetto counter tracks.

Docs: docs/OBSERVABILITY.md.
"""

from frankenpaxos_tpu.obs.flight import FlightRecorder
from frankenpaxos_tpu.obs.perfetto import (
    latency_breakdown,
    load_jsonl,
    to_chrome_trace,
    trace_tree,
)
from frankenpaxos_tpu.obs.telemetry import (
    collect,
    TelemetryReporter,
    TelemetrySnapshot,
)
from frankenpaxos_tpu.obs.trace import (
    RuntimeMetrics,
    SpanRecord,
    TraceContext,
    Tracer,
    VirtualClock,
)

__all__ = [
    "FlightRecorder",
    "RuntimeMetrics",
    "SpanRecord",
    "TelemetryReporter",
    "TelemetrySnapshot",
    "TraceContext",
    "Tracer",
    "VirtualClock",
    "collect",
    "latency_breakdown",
    "load_jsonl",
    "to_chrome_trace",
    "trace_tree",
]
