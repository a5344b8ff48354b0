"""graftscope: context-local pipeline tracing.

The reference's observability is logs, because its unit of work is one
CLI run; a batched RPC service needs to answer "where did this 400ms
scan go — walker, host prep, XLA compile, device execute, or hit
assembly?" per request. This module provides the span primitive the
whole pipeline is instrumented with:

    with span("detect.prepare", queries=len(qs)) as sp:
        ...
        sp.attrs["n_pairs"] = prep.n_pairs

Spans carry a trace id (stamped per scan / per RPC, propagated from
client to server via the X-Trivy-Trace-Id header), a span id, their
parent span id (contextvar nesting — correct across server handler
threads; a remote parent forwarded via the X-Trivy-Parent-Span header
links fragments across processes), wall time and the CPU time of the
thread that ran them, and free-form attributes.

Self time is a span's own share: its wall and CPU time less what its
children on the SAME thread cover. A child on another thread (a
detectd round under a request's context, an assemble-pool task)
subtracts nothing — it runs beside its parent, not inside it. So the
self CPU of every span a thread runs sums to that thread's CPU inside
spans, and a process's CPU splits into per-span-name shares plus what
no span covers.

While a profiler session is active (the benchmark's `--trace 1`,
/debug/profile, `--profile-dir`), each span also enters a
`jax.profiler.TraceAnnotation` under its own name, so it lands on the
profile's host plane on the device trace's clock.

Three sinks receive finished spans (graftwatch):

  * the span aggregate in METRICS — per span name, the count, self
    seconds and self CPU seconds (`trivy_tpu_spans_total`,
    `trivy_tpu_span_self_seconds_total`,
    `trivy_tpu_span_self_cpu_seconds_total`), one lock per span;
  * the always-on flight recorder (obs/recorder.py) — a bounded
    lock-free ring every span lands in, serving /debug/traces and
    incident capture; its per-span cost is one counter bump and one
    slot store;
  * the COLLECTOR, only while recording is enabled (`--trace FILE` on
    the CLI, the server's --trace flag, or a per-phase breakdown) —
    the opt-in complete-trace dump.

Export is Chrome trace-event JSON ("X" complete events, microsecond
timestamps), loadable in Perfetto / chrome://tracing.

Instrumentation never goes INSIDE device code — under jit tracing a
span would time the trace, not the device, and a clock read lowers to
nothing. graftlint rule TPU107 enforces this.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import os
import sys
import threading
import time
import uuid

from ..metrics import METRICS
from .recorder import RECORDER

# active span (for parent linkage) and active trace id; contextvars so
# each server handler thread / asyncio task nests independently
_SPAN: contextvars.ContextVar = contextvars.ContextVar(
    "trivy_tpu_span", default=None)
_TRACE: contextvars.ContextVar = contextvars.ContextVar(
    "trivy_tpu_trace", default="")
# remote parent span id (X-Trivy-Parent-Span): adopted by the first
# span opened under it with no LOCAL parent, so a server fragment's
# root span links to the router/client span that forwarded the RPC
_REMOTE_PARENT: contextvars.ContextVar = contextvars.ContextVar(
    "trivy_tpu_remote_parent", default="")


def _new_id(nbytes: int = 8) -> str:
    return uuid.uuid4().hex[:2 * nbytes]


# span ids: a random per-process prefix plus a counter, 16 hex
# characters — unique across the processes obs.collect stitches, at a
# fraction of a uuid4's cost. A forked child draws a prefix of its own.
_ID_PREFIX = ""
_ID_COUNTER = itertools.count()


def _reseed_ids() -> None:
    global _ID_PREFIX, _ID_COUNTER
    _ID_PREFIX = os.urandom(4).hex()
    _ID_COUNTER = itertools.count()


_reseed_ids()
os.register_at_fork(after_in_child=_reseed_ids)


def _span_id() -> str:
    return f"{_ID_PREFIX}{next(_ID_COUNTER) & 0xFFFFFFFF:08x}"


class Span:
    """One finished (or in-flight) timed region."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "attrs",
                 "wall_start", "start", "dur", "cpu", "thread_id",
                 "child_dur", "child_cpu", "_parent", "_tok", "_ann")

    def __init__(self, name: str, trace_id: str, parent_id: str,
                 attrs: dict):
        self.name = name
        self.trace_id = trace_id
        self.span_id = _span_id()
        self.parent_id = parent_id
        self.attrs = attrs
        self.wall_start = 0.0   # time.time() at enter
        self.start = 0.0        # perf_counter at enter
        self.dur = 0.0          # perf_counter seconds
        self.cpu = 0.0          # thread_time seconds
        self.thread_id = 0
        # wall and CPU seconds of finished children on this thread
        self.child_dur = 0.0
        self.child_cpu = 0.0
        # while open: the enclosing span, the context token, the
        # profiler annotation
        self._parent = self._tok = self._ann = None

    def __enter__(self) -> "Span":
        parent = self._parent = _SPAN.get()
        self.trace_id = _TRACE.get()
        self.parent_id = (parent.span_id if parent is not None
                          else _REMOTE_PARENT.get())
        self.thread_id = threading.get_ident()
        ann = _annotation()
        if ann is not None and ann.is_enabled():
            self._ann = ann(self.name)
            self._ann.__enter__()
        self.wall_start = time.time()
        self.cpu = time.thread_time()
        self.start = time.perf_counter()
        self._tok = _SPAN.set(self)
        return self

    def __exit__(self, *exc) -> bool:
        self.dur = time.perf_counter() - self.start
        self.cpu = time.thread_time() - self.cpu
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        _SPAN.reset(self._tok)
        parent = self._parent
        # a finished span in the ring keeps no chain of parents alive
        self._parent = self._tok = self._ann = None
        if parent is not None and parent.thread_id == self.thread_id:
            parent.child_dur += self.dur
            parent.child_cpu += self.cpu
        METRICS.inc_many(((SPANS_TOTAL, 1.0),
                          (SPAN_SELF, self.dur - self.child_dur),
                          (SPAN_SELF_CPU, self.cpu - self.child_cpu)),
                         span=self.name)
        RECORDER.record_span(self)
        COLLECTOR.record(self)
        return False

    @property
    def self_dur(self) -> float:
        return self.dur - self.child_dur

    @property
    def self_cpu(self) -> float:
        return self.cpu - self.child_cpu


class Collector:
    """Process-wide sink for finished spans (bounded, thread-safe).

    Shared across server handler threads — every mutation of the span
    buffer happens under the lock (graftlint TPU106 covers this
    module)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._spans: list[Span] = []
        self._enabled = False
        self._limit = 200_000
        self._dropped = 0
        self._base: dict = {}   # span_totals() when recording began

    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self, limit: int | None = None) -> None:
        base = span_totals()
        with self._lock:
            self._spans = []
            self._dropped = 0
            self._base = base
            if limit is not None:
                self._limit = limit
            self._enabled = True

    def disable(self) -> None:
        with self._lock:
            self._enabled = False

    def record(self, s: Span) -> None:
        if not self._enabled:
            return
        with self._lock:
            if len(self._spans) >= self._limit:
                self._dropped += 1
                return
            self._spans.append(s)

    @property
    def dropped(self) -> int:
        return self._dropped

    def snapshot(self) -> list[Span]:
        with self._lock:
            return list(self._spans)

    def drain(self) -> list[Span]:
        with self._lock:
            out = self._spans
            self._spans = []
            return out

    def phase_totals(self) -> dict[str, dict]:
        """The span aggregate since recording began, by name →
        {count, self_ms, self_cpu_ms} (the per-phase breakdown)."""
        with self._lock:
            base = self._base
        out: dict[str, dict] = {}
        for name, (n, wall, cpu) in span_totals().items():
            b = base.get(name, (0, 0.0, 0.0))
            if n > b[0]:
                out[name] = {"count": int(n - b[0]),
                             "self_ms": round((wall - b[1]) * 1e3, 3),
                             "self_cpu_ms": round((cpu - b[2]) * 1e3, 3)}
        return out


COLLECTOR = Collector()

SPANS_TOTAL = "trivy_tpu_spans_total"
SPAN_SELF = "trivy_tpu_span_self_seconds_total"
SPAN_SELF_CPU = "trivy_tpu_span_self_cpu_seconds_total"


def span_totals() -> dict[str, tuple]:
    """→ {span name: (count, self seconds, self CPU seconds)} since the
    process started — the span aggregate /metrics exports."""
    count = METRICS.family(SPANS_TOTAL)
    wall = METRICS.family(SPAN_SELF)
    cpu = METRICS.family(SPAN_SELF_CPU)
    return {dict(labels)["span"]: (n, wall.get(labels, 0.0),
                                   cpu.get(labels, 0.0))
            for labels, n in count.items()}


# jax.profiler.TraceAnnotation, looked up once jax is loaded (no
# profiler session can be active before): None until then
_ANNOTATION = None


def _annotation():
    global _ANNOTATION
    if _ANNOTATION is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


def current_trace_id() -> str:
    return _TRACE.get()


def current_span_id() -> str:
    """Span id of the innermost active span ('' outside any span) —
    what the client/router forward as X-Trivy-Parent-Span."""
    s = _SPAN.get()
    return s.span_id if s is not None else ""


@contextlib.contextmanager
def new_trace(trace_id: str | None = None,
              parent_id: str | None = None):
    """Set a fresh trace id for the enclosed work (per-RPC stamp).
    `parent_id` installs a REMOTE parent span id: the first span
    opened inside (with no local parent) adopts it, stitching this
    process's fragment under the caller's forwarding span."""
    tid = trace_id or _new_id(16)
    tok = _TRACE.set(tid)
    ptok = _REMOTE_PARENT.set(parent_id) if parent_id else None
    try:
        yield tid
    finally:
        if ptok is not None:
            _REMOTE_PARENT.reset(ptok)
        _TRACE.reset(tok)


@contextlib.contextmanager
def ensure_trace(trace_id: str | None = None):
    """Reuse the active trace id, or start one if none is active —
    the per-scan stamp (scanner.scan_many) that must not clobber a
    server-stamped per-RPC id."""
    cur = _TRACE.get()
    if cur and trace_id is None:
        yield cur
        return
    with new_trace(trace_id) as tid:
        yield tid


def span(name: str, **attrs) -> Span:
    """Time a region: `with span("layer.phase", **attrs) as sp:`; nests
    via contextvars, and `sp.attrs[...] = x` attaches attributes
    discovered mid-flight. Every finished span lands in the always-on
    flight recorder's ring (graftwatch); the COLLECTOR additionally
    keeps it only while recording is enabled. A span with no local
    parent adopts the remote parent id installed by
    new_trace(parent_id=...). Its count, self time and self CPU go to
    the span aggregate; under an active profiler session it is also a
    TraceAnnotation."""
    return Span(name, "", "", attrs)


def add_attr(**attrs) -> None:
    """Attach attributes to the innermost active span (no-op outside
    any span)."""
    s = _SPAN.get()
    if s is not None:
        s.attrs.update(attrs)


# ---------------------------------------------------------------------------
# Chrome trace-event export (Perfetto / chrome://tracing)

def chrome_trace(spans: list[Span] | None = None,
                 dropped: int | None = None) -> dict:
    """→ the Chrome trace-event JSON document for `spans` (default: the
    COLLECTOR's current buffer). "X" complete events; ts/dur in
    microseconds relative to the earliest span; span/trace/parent ids
    and attributes ride in `args`. Truncation is never silent: spans
    dropped at the collector's limit surface as a trailing instant
    event ("graftscope.dropped_spans")."""
    if spans is None:
        spans = COLLECTOR.snapshot()
    if dropped is None:
        dropped = COLLECTOR.dropped
    base = min((s.start for s in spans), default=0.0)
    pid = os.getpid()
    events = []
    for s in spans:
        events.append({
            "name": s.name,
            "cat": "graftscope",
            "ph": "X",
            "ts": round((s.start - base) * 1e6, 3),
            "dur": round(s.dur * 1e6, 3),
            "pid": pid,
            "tid": s.thread_id,
            "args": {
                "trace_id": s.trace_id,
                "span_id": s.span_id,
                "parent_id": s.parent_id,
                "cpu_ms": round(s.cpu * 1e3, 3),
                "self_cpu_ms": round(s.self_cpu * 1e3, 3),
                **s.attrs,
            },
        })
    if dropped:
        end = max((e["ts"] + e["dur"] for e in events), default=0.0)
        events.append({
            "name": "graftscope.dropped_spans", "cat": "graftscope",
            "ph": "i", "s": "g", "ts": end, "pid": pid, "tid": 0,
            "args": {"dropped": dropped},
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write_chrome_trace(path: str,
                       spans: list[Span] | None = None) -> None:
    doc = chrome_trace(spans)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
