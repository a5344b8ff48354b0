"""graftwatch — tracing, flight recorder, SLOs, and backend status.

v1 ("graftscope") was single-process: `trace` holds the span/tracer
core (context-local spans, trace-id propagation, Chrome trace-event
export) and `device` the cached backend view /healthz serves. v2
("graftwatch") makes it fleet-wide:

  recorder  the always-on flight recorder: every finished span and
            log record lands in a bounded lock-free ring; slow/error/
            incident traces are pinned past churn; breaker openings
            and failpoint-injected faults auto-capture timestamped
            incident files (/debug/incidents).
  slo       declared objectives (scan latency p99, error rate,
            device-serving ratio) over sliding windows with
            multi-window burn-rate gauges; shed-aware (admission
            429s are load, not errors).
  collect   cross-process trace assembly: pulls /debug/traces
            fragments from the router + every replica and stitches
            one Chrome/Perfetto document via forwarded parent-span
            ids (X-Trivy-Parent-Span).
  check     offline validator for incident files, trace dumps, and
            profile manifests (`python -m trivy_tpu.obs.check`),
            wired into tier-1.

v3 ("graftprof") adds the device-performance layer:

  perf      the dispatch ledger (per-shape padded-vs-real rows,
            compile wall time, device→host bytes, hit-buffer fill),
            HBM/resident-memory telemetry, and the live jax.profiler
            capture behind /debug/profile (operator-requested or SLO
            burn-triggered).
  perfcheck the noise-aware bench-tail regression gate
            (`python -m trivy_tpu.obs.perfcheck OLD.json NEW.json`).

Metrics live in `trivy_tpu.metrics` (the registry predates this
package and is imported everywhere). See ARCHITECTURE.md "Fleet
observability (graftwatch)" for the span taxonomy, retention policy,
and SLO definitions.
"""

from .device import device_status, note_dispatch
from .perf import LEDGER, PROF
from .recorder import RECORDER
from .slo import SLO
from .trace import (COLLECTOR, add_attr, chrome_trace, current_span_id,
                    current_trace_id, ensure_trace, new_trace, span,
                    write_chrome_trace)

__all__ = [
    "COLLECTOR", "LEDGER", "PROF", "RECORDER", "SLO", "add_attr",
    "chrome_trace", "current_span_id", "current_trace_id",
    "device_status", "ensure_trace", "new_trace", "note_dispatch",
    "span", "write_chrome_trace",
]
