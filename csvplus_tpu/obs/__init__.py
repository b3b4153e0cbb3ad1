"""First-class observability subsystem (docs/OBSERVABILITY.md).

What grew out of ``utils/observe.py``'s 211-line helper once every hard
diagnosis (r05 warm join, r06 mesh RSS) turned out to need it:

* :mod:`~csvplus_tpu.obs.span` — hierarchical per-query spans with
  ``contextvars`` trace isolation (:data:`tracer`), and beside the
  traces the process journal of once-per-object work
  (``tracer.milestone``, ``tracer.journal``);
* :mod:`~csvplus_tpu.obs.export` — Chrome-trace/Perfetto JSON +
  span JSON-lines exporters and the Chrome-trace schema validator;
* :mod:`~csvplus_tpu.obs.recompile` — jit-lowering accounting for the
  registered module-level kernels (:class:`RecompileWatch`);
* :mod:`~csvplus_tpu.obs.memory` — RSS and device-memory probes, plus
  the bench-artifact host header;
* :mod:`~csvplus_tpu.obs.metrics` — the production telemetry plane
  (ISSUE 13): typed metric registry, Prometheus text exposition +
  optional HTTP endpoint, the JSONL metrics pump, tail-sampled request
  tracing, and the :class:`TelemetryPlane` bundle the serving tier
  carries;
* :mod:`~csvplus_tpu.obs.flight` — the crash flight recorder: a
  bounded process-global event ring dumped atomically on terminal
  failure paths;
* :mod:`~csvplus_tpu.obs.sketch` — the Space-Saving top-K heavy-hitter
  sketch behind ``python -m csvplus_tpu.obs skew``.

The legacy ``telemetry`` singleton keeps its API and feeds the same
machinery: ``telemetry.stage()`` opens a span whenever a trace is
active in the calling context.
"""

from .flight import FlightRecorder, recorder
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    MetricsPump,
    PromHttpEndpoint,
    TailSampler,
    TelemetryPlane,
)
from .sketch import SpaceSaving, skew_report
from .export import (
    SpanJsonlSink,
    chrome_trace_events,
    export_chrome_trace,
    spans_to_json,
    validate_chrome_trace,
    write_chrome_trace,
    write_spans_jsonl,
)
from .memory import (
    device_memory_stats,
    host_header,
    peak_rss_mb,
    rss_mb,
)
from .recompile import (
    RecompileWatch,
    compile_counts,
    register_kernel,
    registered_kernels,
)
from .span import Span, Trace, Tracer, tracer

__all__ = [
    "Span",
    "Trace",
    "Tracer",
    "tracer",
    "SpanJsonlSink",
    "chrome_trace_events",
    "export_chrome_trace",
    "spans_to_json",
    "validate_chrome_trace",
    "write_chrome_trace",
    "write_spans_jsonl",
    "device_memory_stats",
    "host_header",
    "peak_rss_mb",
    "rss_mb",
    "RecompileWatch",
    "compile_counts",
    "register_kernel",
    "registered_kernels",
    "FlightRecorder",
    "recorder",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricRegistry",
    "MetricsPump",
    "PromHttpEndpoint",
    "TailSampler",
    "TelemetryPlane",
    "SpaceSaving",
    "skew_report",
]
