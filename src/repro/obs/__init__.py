"""Phase-level tracing & metrics (spans, counters, exporters, profiler
hooks) — see trace.py for the core, export.py for artifact formats,
profile.py for the XLA-level bracket."""
from repro.obs.export import chrome_trace, export, export_chrome, export_jsonl
from repro.obs.profile import jax_profile
from repro.obs.trace import (NOOP_SPAN, EventRecord, SpanRecord, Tracer,
                             count, disable, enable, event, gauge, get_tracer,
                             record_span, set_tracer, span)

__all__ = ["NOOP_SPAN", "EventRecord", "SpanRecord", "Tracer", "chrome_trace",
           "count", "disable", "enable", "event", "export", "export_chrome",
           "export_jsonl", "gauge", "get_tracer", "jax_profile",
           "record_span", "set_tracer", "span"]
