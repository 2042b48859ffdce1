"""Distributed span tracing: one trace tree per submission, end to end.

PR 1 gave every submission a flat correlation ID; this module grows it
into Dapper-style spans (PAPERS.md) so `kfx trace <job>` can answer
"where did the wall clock go". The model:

  * a **trace** is one submission, identified by the 16-hex ID minted at
    admission (``ControlPlane.apply``) and stored under the
    ``kubeflow.org/trace-id`` annotation;
  * a **span** is one timed unit of work inside it — span_id, parent_id,
    wall-clock start, duration, ok/error status and free-form string
    attributes;
  * spans nest per thread (a span started while another is open parents
    to it), and cross **process** boundaries via ``KFX_SPAN_ID`` in a
    child's environment (gang members inherit the spawn span) or the
    ``X-Kfx-Span-Id`` HTTP header (router -> model server);
  * finished spans append to a per-process JSONL file under
    ``<KFX_WORKDIR>/spans/`` (``<component>-<pid>.jsonl``): the control
    plane writes ``<home>/spans/``, each gang replica writes its gang
    workdir, the model server its revision workdir. ``obs.timeline``
    merges them back into one tree and computes the critical path.

The old flat-ID helpers (current_trace_id / ensure_trace / ...) are
unchanged; ``span(...)`` keeps its PR-1 signature (trace scoping +
optional histogram observation) and now records real spans.

A process that owns an accelerator also puts its spans on the
**profiler's clock**: it registers an annotation factory once
(``set_annotation_factory(jax.profiler.TraceAnnotation)`` — this module
never imports jax, the plane imports it) and from then on every
``span()`` / ``start_span()`` opens that annotation beside the span it
records, and ``annotate(name, **attrs)`` gives the annotation alone,
for phases too fine to be worth a JSONL line. The spans then sit in any
xplane trace of the process next to the device's operations, with no
clock matching on the reader's side. With no profiler session active
an annotation is a flag test; with no factory registered ``annotate``
returns one shared null context.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
import uuid
from typing import Dict, Iterator, List, Optional

TRACE_ENV = "KFX_TRACE_ID"
TRACE_ANNOTATION = "kubeflow.org/trace-id"
TRACE_HEADER = "X-Kfx-Trace-Id"

SPAN_ENV = "KFX_SPAN_ID"
SPAN_ANNOTATION = "kubeflow.org/span-id"
SPAN_HEADER = "X-Kfx-Span-Id"
COMPONENT_ENV = "KFX_COMPONENT"
SPANS_DIRNAME = "spans"

_tls = threading.local()

# The bridge to the profiler's clock: a callable ``(name, **attrs) ->
# context manager`` (jax.profiler.TraceAnnotation), set once by a
# process that holds a device. None: spans go to the JSONL log only.
_annotation_factory = None
_NO_ANNOTATION = contextlib.nullcontext()


def set_annotation_factory(factory) -> None:
    """Register (None: clear) the factory that ``span`` / ``start_span``
    / ``annotate`` open beside what they record. Called by the code
    that already imports jax (the decode engine, the LM train loop,
    jax_runner); this module stays free of it."""
    global _annotation_factory
    _annotation_factory = factory


def annotate(name: str, **attrs):
    """A context manager that marks ``name`` (with ``attrs``) on the
    calling thread in the profiler's trace, and nothing else: no span
    record, no trace scoping. The shared null context when no factory
    is registered."""
    factory = _annotation_factory
    if factory is None:
        return _NO_ANNOTATION
    return factory(name, **attrs)


def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]


def set_trace_id(trace_id: str) -> None:
    """Set the calling thread's current trace ID ("" clears it)."""
    _tls.trace_id = trace_id or ""


def current_trace_id() -> str:
    """The calling thread's trace ID, falling back to the process env
    (gang members inherit KFX_TRACE_ID from the operator)."""
    return getattr(_tls, "trace_id", "") or os.environ.get(TRACE_ENV, "")


def current_span_id() -> str:
    """The innermost open span on this thread, falling back to the
    process env (gang members inherit the spawn span as KFX_SPAN_ID) —
    what a child span or a cross-process export should parent to."""
    stack = getattr(_tls, "span_stack", None)
    if stack:
        return stack[-1].span_id
    return os.environ.get(SPAN_ENV, "")


def trace_of(obj) -> str:
    """The trace ID stored on a resource's metadata, or ""."""
    if obj is None:
        return ""
    return obj.metadata.annotations.get(TRACE_ANNOTATION, "")


def span_of(obj) -> str:
    """The admission span ID stored on a resource's metadata, or "" —
    what reconcile spans parent to."""
    if obj is None:
        return ""
    return obj.metadata.annotations.get(SPAN_ANNOTATION, "")


def ensure_trace(obj, trace_id: Optional[str] = None) -> str:
    """Make sure a resource carries a trace annotation (minting one if
    absent); returns the effective ID."""
    existing = trace_of(obj)
    if existing:
        return existing
    tid = trace_id or new_trace_id()
    obj.metadata.annotations[TRACE_ANNOTATION] = tid
    return tid


class Span:
    """One timed unit of work under a trace ID."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "start",
                 "duration", "status", "attrs", "started", "elapsed",
                 "_prev_trace", "_annotation")

    def __init__(self, name: str, trace_id: str, parent_id: str = "",
                 ts: Optional[float] = None,
                 attrs: Optional[Dict[str, str]] = None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = new_span_id()
        self.parent_id = parent_id
        self.start = time.time() if ts is None else ts
        self.duration = 0.0
        self.status = "ok"
        self.attrs: Dict[str, str] = dict(attrs or {})
        # perf_counter pair for the sub-ms elapsed the PR-1 histogram
        # contract reports; wall-clock start/duration are what the
        # cross-process timeline aligns on.
        self.started = time.perf_counter()
        self.elapsed = 0.0
        self._prev_trace = ""
        self._annotation = None  # the open profiler annotation, if any

    def to_record(self) -> Dict:
        rec = {"name": self.name, "trace": self.trace_id,
               "span": self.span_id, "parent": self.parent_id,
               "ts": self.start, "dur": self.duration,
               "status": self.status}
        if self.attrs:
            rec["attrs"] = self.attrs
        return rec


# -- the per-process span sink ------------------------------------------------

class _SpanSink:
    """Appends finished spans to ``<dir>/<component>-<pid>.jsonl``.

    One open handle, line-buffered JSON — a span is durable the moment
    finish_span returns, so a worker that os._exit()s at a chaos crash
    still leaves its timeline behind. When the file passes the size
    cap it rotates to ``.1`` (one generation kept): a long-lived plane
    whose resyncs reconcile forever — or a serving revision writing a
    span per request — must not grow a span log without bound, so the
    on-disk footprint is bounded at ~2x the cap per process.
    ``KFX_SPAN_LOG_MAX_MB`` tunes the cap (default 32; a busy serving
    fleet typically wants it smaller). The rotated generation keeps
    the .jsonl suffix so the timeline collector still merges it."""

    DEFAULT_MAX_MB = 32
    ROTATE_CHECK_EVERY = 512

    def __init__(self, directory: str, component: str):
        self.directory = os.path.abspath(directory)
        self.component = component
        try:
            max_mb = float(os.environ.get("KFX_SPAN_LOG_MAX_MB", "") or
                           self.DEFAULT_MAX_MB)
        except ValueError:
            max_mb = float(self.DEFAULT_MAX_MB)
        self.max_bytes = max(int(max_mb * 1024 * 1024), 4096)
        self.path = os.path.join(self.directory,
                                 f"{component}-{os.getpid()}.jsonl")
        self._file = None
        self._lock = threading.Lock()
        self.written = 0

    def write(self, record: Dict) -> None:
        record = dict(record)
        record["proc"] = self.component
        record["pid"] = os.getpid()
        line = json.dumps(record, separators=(",", ":")) + "\n"
        with self._lock:
            if self._file is None:
                os.makedirs(self.directory, exist_ok=True)
                self._file = open(self.path, "a", buffering=1)
            self._file.write(line)
            self.written += 1
            if self.written % self.ROTATE_CHECK_EVERY == 0 and \
                    self._file.tell() > self.max_bytes:
                self._file.close()
                os.replace(self.path,
                           self.path[:-len(".jsonl")] + ".1.jsonl")
                self._file = open(self.path, "a", buffering=1)

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None


_sink_lock = threading.Lock()
_sink: Optional[_SpanSink] = None
_sink_resolved = False
# {component: spans written} across every sink this process configured —
# what `collect` mirrors into kfx_spans_recorded_total.
_recorded: Dict[str, int] = {}


def set_span_sink(directory: str, component: str) -> str:
    """Point this process's span log at ``<directory>/`` (created on
    first write) labelled ``component``. Returns the file path."""
    global _sink, _sink_resolved
    with _sink_lock:
        if _sink is not None:
            _sink.close()
        _sink = _SpanSink(directory, component)
        _sink_resolved = True
        return _sink.path


def default_component() -> str:
    """This process's component label: KFX_COMPONENT (gang members get
    their replica id, model servers their revision), else the replica
    env pair, else "proc"."""
    comp = os.environ.get(COMPONENT_ENV, "")
    if comp:
        return comp
    rtype = os.environ.get("KFX_REPLICA_TYPE", "")
    if rtype:
        idx = os.environ.get("KFX_REPLICA_INDEX", "0")
        return f"{rtype.lower()}-{idx}"
    return "proc"


def _resolve_sink() -> Optional[_SpanSink]:
    """The active sink, auto-configured once from KFX_WORKDIR for
    processes nobody wired explicitly (gang replicas, model servers).
    No workdir -> spans are dropped (standalone scripts)."""
    global _sink, _sink_resolved
    sink = _sink
    if sink is not None or _sink_resolved:
        return sink
    with _sink_lock:
        if _sink is None and not _sink_resolved:
            workdir = os.environ.get("KFX_WORKDIR", "")
            if workdir:
                _sink = _SpanSink(os.path.join(workdir, SPANS_DIRNAME),
                                  default_component())
            _sink_resolved = True
        return _sink


def span_sink_path() -> Optional[str]:
    sink = _resolve_sink()
    return sink.path if sink else None


def _emit(sp: Span) -> None:
    sink = _resolve_sink()
    if sink is None:
        return
    try:
        sink.write(sp.to_record())
    except OSError:
        return  # tracing is an observer, never a failure path
    with _sink_lock:
        _recorded[sink.component] = _recorded.get(sink.component, 0) + 1


def spans_recorded() -> Dict[str, int]:
    """Spans written by this process, by component label."""
    with _sink_lock:
        return dict(_recorded)


def collect(reg) -> None:
    """Pull-time collector: export this process's span-write totals as
    ``kfx_spans_recorded_total{component=...}`` — /metrics proof that
    spans are flowing (registered by the plane and the model server)."""
    counts = spans_recorded()
    if not counts:
        return
    c = reg.counter("kfx_spans_recorded_total",
                    "Trace spans written to the span log by component.")
    for comp, n in counts.items():
        c.set_total(n, component=comp)


# -- span lifecycle -----------------------------------------------------------

def _stack() -> List[Span]:
    stack = getattr(_tls, "span_stack", None)
    if stack is None:
        stack = _tls.span_stack = []
    return stack


def start_span(name: str, trace_id: str = "", parent_id: str = "",
               ts: Optional[float] = None, **attrs: str) -> Span:
    """Open a span on the calling thread. Trace defaults to the current
    context (thread-local, then KFX_TRACE_ID); parent to the innermost
    open span (then KFX_SPAN_ID). ``ts`` backdates the start (a process
    describing work that began before it could instrument, e.g. its own
    interpreter startup). Must be closed with finish_span."""
    tid = trace_id or current_trace_id()
    parent = parent_id or current_span_id()
    sp = Span(name, tid, parent_id=parent, ts=ts,
              attrs={k: str(v) for k, v in attrs.items()})
    sp._prev_trace = getattr(_tls, "trace_id", "")
    _tls.trace_id = tid
    _stack().append(sp)
    if _annotation_factory is not None:
        sp._annotation = _annotation_factory(name, **sp.attrs)
        sp._annotation.__enter__()
    return sp


def finish_span(sp: Span, status: str = "") -> Span:
    """Close a span: stamp duration/status, restore the thread context,
    append it to the process span log."""
    if sp._annotation is not None:
        sp._annotation.__exit__(None, None, None)
        sp._annotation = None
    sp.elapsed = time.perf_counter() - sp.started
    sp.duration = max(time.time() - sp.start, 0.0)
    if status:
        sp.status = status
    stack = _stack()
    if sp in stack:
        # Pop through sp: a leaked inner span must not re-parent every
        # later span on this thread to itself forever.
        del stack[stack.index(sp):]
    _tls.trace_id = sp._prev_trace
    _emit(sp)
    return sp


def record_span(name: str, ts: float, duration: float, trace_id: str = "",
                parent_id: str = "", status: str = "ok",
                **attrs: str) -> Span:
    """Record an already-measured interval as a span (no thread scoping)
    — for call sites that only know the timing after the fact, like the
    runner's train-step windows."""
    sp = Span(name, trace_id or current_trace_id(),
              parent_id=parent_id or current_span_id(), ts=ts,
              attrs={k: str(v) for k, v in attrs.items()})
    sp.duration = max(duration, 0.0)
    sp.elapsed = sp.duration
    sp.status = status
    _emit(sp)
    return sp


@contextlib.contextmanager
def span(name: str, trace_id: str = "", histogram=None,
         parent_id: str = "", ts: Optional[float] = None,
         **labels: str) -> Iterator[Span]:
    """Scope a span (and its trace ID) onto the current thread and time
    the body. ``labels`` become span attributes; ``histogram`` (an obs
    Histogram) gets the duration observed with ``labels`` on exit —
    success or failure. An escaping exception marks status=error.
    ``ts`` backdates the start (see start_span)."""
    sp = start_span(name, trace_id=trace_id, parent_id=parent_id, ts=ts,
                    **labels)
    try:
        yield sp
    except BaseException:
        sp.status = "error"
        raise
    finally:
        finish_span(sp)
        if histogram is not None:
            histogram.observe(sp.elapsed, **labels)
