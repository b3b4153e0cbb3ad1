"""Hierarchical host-side spans with ``contextvars`` trace propagation.

The process-global :data:`telemetry` singleton
(:mod:`csvplus_tpu.utils.observe`) records a flat per-stage table — the
right shape for one pipeline run, and exactly the wrong shape for the
serving tier, where N concurrent queries interleave their stages into
one list and per-query attribution is lost.  This module adds the
missing structure:

* a :class:`Span` is one timed region with a ``trace_id`` / ``span_id``
  / ``parent_id`` triple, so spans form a tree;
* the *current* span rides a :mod:`contextvars` ``ContextVar`` — every
  thread (and every ``contextvars.Context``) sees its own current span,
  so concurrent queries each grow an isolated tree with zero locking on
  the hot path;
* worker threads that must contribute to a parent's trace adopt an
  explicitly captured context (:meth:`Tracer.capture` /
  :meth:`Tracer.adopt`) — the r07 rule that cross-thread state flows by
  explicit handoff, never ambient sharing;
* finished traces land in a bounded list the exporters
  (:mod:`csvplus_tpu.obs.export`) serialize to Chrome-trace JSON or
  span JSON-lines.

The existing ``telemetry.stage()`` API keeps working unchanged: it is
now a compatibility shim that ALSO opens a span whenever a trace is
active in the calling context (see ``utils/observe.py``), so every
already-instrumented stage (exec nodes, ingest, joins, serve dispatch)
shows up in span trees without touching its call site.

One emitter, one clock: every span opened while a trace is active also
opens ``jax.profiler.TraceAnnotation("csvplus:<name>")`` (:func:`_annotate`,
the only place in the package that writes into the profiler's host
trace), so a device profile taken over the same stretch shows what the
host did in each gap.  Spans keep ``perf_counter`` times; a :class:`Trace`
emits one ``csvplus:anchor`` annotation when it opens, carrying its id
and the ``perf_counter`` value of that moment, and every span opened as
a root or directly under one (``plan:execute``, ``serve:cycle``, a
milestone) carries ``perf_counter=<its start>`` in its annotation too:
any profile that holds one such annotation holds an anchor, whenever
the profiler was started, which is what places spans written after the
fact (:meth:`Tracer.record_span`) on the profiler's clock.

The journal beside the trace: a trace is opened by whoever wants one
stretch measured and is finished when it ends; the work a process does
ONCE per object (an ingest, an index build, a plan's admission and
first run, a server's start, a WAL recovery) mostly happens where no
trace is open.  :meth:`Tracer.milestone` marks such work: inside a
trace it is a plain child span; outside one it opens a root in
:attr:`Tracer.journal`, a process-lifetime :class:`Trace` that is never
finished, bounded by spans (:data:`MAX_JOURNAL_SPANS`; whole oldest
trees go first, counted in ``journal.dropped``), and makes it the
current context, so everything its body reaches through
``telemetry.stage`` / ``add_stage`` / :meth:`Tracer.span` lands beneath
it with no new call site.  jax's compile events join it as ``compile``
spans (:func:`journal_compiles`).  ``reset()`` leaves the journal alone.

Disabled-path cost: with no active trace and no open milestone,
:meth:`Tracer.span` is one ``ContextVar.get`` and a shared do-nothing
context manager — ``tests/test_journal.py::
test_disabled_path_costs_under_two_percent_of_a_micro_lookup`` holds
this under 2% on the micro lookup shape.  No annotation is constructed
and no span object made.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Tuple

#: Finished traces kept for export before the oldest are dropped.
MAX_FINISHED_TRACES = 512

#: Spans the process journal keeps; past it, whole oldest milestone
#: trees are dropped (down to 7/8, so trimming is rare).
MAX_JOURNAL_SPANS = 8192

#: The current (trace, open span_id) — per-thread / per-context by
#: ``contextvars`` semantics, which is what isolates concurrent queries.
_CURRENT: "contextvars.ContextVar[Optional[Tuple[Trace, int]]]" = (
    contextvars.ContextVar("csvplus_obs_current", default=None)
)


def _annotate(name: str, **meta):
    """An entered ``jax.profiler.TraceAnnotation("csvplus:<name>")``: the
    one place in the package that writes into the profiler's host trace.
    Only reached while a trace or a milestone is active; a process that
    has not imported jax (a host-only WAL recovery) gets a do-nothing
    stand-in, so ``import csvplus_tpu`` and the journal stay jax-free.
    With no profiler session running it costs one flag test inside jax."""
    if "jax" not in sys.modules:
        return _NO_SPAN
    from jax.profiler import TraceAnnotation

    ann = TraceAnnotation(f"csvplus:{name}", **meta)
    ann.__enter__()
    return ann


@dataclass
class Span:
    """One timed region inside a trace."""

    trace_id: int
    span_id: int
    parent_id: Optional[int]
    name: str
    t_start: float  # perf_counter seconds (trace-relative on export)
    t_end: float
    lane: str  # thread name or explicit worker lane
    attrs: Dict[str, Any] = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start

    def to_json(self) -> Dict[str, Any]:
        return {
            "trace": self.trace_id,
            "span": self.span_id,
            "parent": self.parent_id,
            "name": self.name,
            "t_start": round(self.t_start, 6),
            "ms": round(self.seconds * 1e3, 4),
            "lane": self.lane,
            "attrs": self.attrs,
        }


class Trace:
    """One span tree (one query / one pipeline run).

    Spans append under the trace's own lock: workers adopted into the
    trace may close spans concurrently with the owner, and the finished
    list must never interleave-corrupt (the exact failure mode of the
    flat telemetry list this module replaces).
    """

    __slots__ = ("trace_id", "name", "spans", "t_anchor", "root_id", "_lock")

    def __init__(self, trace_id: int, name: str):
        self.trace_id = trace_id
        self.name = name
        self.spans: List[Span] = []
        # the span id of the root, once it is open: a span opened directly
        # under it carries its own perf_counter into the profiler's trace
        self.root_id = 0
        # the anchor: this perf_counter value and the annotation's start
        # on the profiler's clock are the same moment
        self.t_anchor = time.perf_counter()
        self._announce()
        self._lock = threading.Lock()

    def _announce(self) -> None:
        _annotate(
            "anchor", trace_id=self.trace_id, perf_counter=self.t_anchor
        ).__exit__(None, None, None)

    def add(self, span: Span) -> None:
        with self._lock:
            self.spans.append(span)

    def root(self) -> Optional[Span]:
        with self._lock:
            for s in self.spans:
                if s.parent_id is None:
                    return s
        return None

    def span_ids(self) -> set:
        with self._lock:
            return {s.span_id for s in self.spans}

    def snapshot(self) -> List[Span]:
        """Consistent copy of the span list (safe while workers append)."""
        with self._lock:
            return list(self.spans)

    def to_json(self) -> Dict[str, Any]:
        with self._lock:
            spans = list(self.spans)
        return {
            "trace_id": self.trace_id,
            "name": self.name,
            "spans": [s.to_json() for s in spans],
        }


class Journal(Trace):
    """The process journal: a :class:`Trace` that is never finished.

    Its roots are milestones (and the compile events that fall outside
    any); it announces no anchor (it opens at import, before jax), holds
    instead the wall clock of its opening beside ``t_anchor`` so an
    exporter can place it, and is bounded: past :data:`MAX_JOURNAL_SPANS`
    spans, whole oldest trees whose root has closed are dropped and
    counted in :attr:`dropped`; spans of a tree still open go, oldest
    first, only where that alone does not make room.
    """

    __slots__ = ("t_anchor_ns", "dropped", "limit")

    def __init__(self, trace_id: int, limit: int = MAX_JOURNAL_SPANS):
        super().__init__(trace_id, "journal")
        self.t_anchor_ns = time.time_ns()
        self.dropped = 0  # milestone trees (a lone span is a tree of one)
        self.limit = int(limit)

    def _announce(self) -> None:
        return None

    def add(self, span: Span) -> None:
        with self._lock:
            self.spans.append(span)
            if len(self.spans) > self.limit:
                self._trim(self.limit - self.limit // 8)

    def _trim(self, keep: int) -> None:
        """Drop oldest closed trees until at most *keep* spans are left
        (the caller holds the lock)."""
        spans = self.spans
        # span id -> the id of its closed root (0: its tree is still open);
        # a parent closes, and so is appended, after its children
        root_of: Dict[int, int] = {}
        for s in reversed(spans):
            root_of[s.span_id] = (
                s.span_id if s.parent_id is None else root_of.get(s.parent_id, 0)
            )
        size: Dict[int, int] = {}
        for r in root_of.values():
            size[r] = size.get(r, 0) + 1
        excess = len(spans) - keep
        gone = set()
        for s in spans:  # roots in the order they closed
            if excess <= 0:
                break
            if s.parent_id is None:
                gone.add(s.span_id)
                excess -= size[s.span_id]
        self.dropped += len(gone)
        spans[:] = [s for s in spans if root_of[s.span_id] not in gone]
        if len(spans) > keep:  # one open tree outgrew the journal
            self.dropped += 1
            del spans[: len(spans) - keep]

    def recent(self, n: int = 16) -> List[Dict[str, Any]]:
        """The last *n* closed roots, oldest first, each with how many
        spans lie beneath it: what a flight dump attaches."""
        with self._lock:
            spans = list(self.spans)
        kids: Dict[Optional[int], int] = {}
        for s in spans:
            kids[s.parent_id] = kids.get(s.parent_id, 0) + 1
        roots = [s for s in spans if s.parent_id is None][-n:]
        return [dict(s.to_json(), children=kids.get(s.span_id, 0)) for s in roots]


class _OpenSpan:
    """Handle for a span opened via the low-level open/close API."""

    __slots__ = ("trace", "span", "token", "ann")

    def __init__(self, trace, span: Span, token, ann):
        self.trace = trace
        self.span = span
        self.token = token
        self.ann = ann  # the live profiler annotation, closed with the span


class _SharedSpans:
    """Where the spans of one :meth:`Tracer.shared` region collect until
    the region ends (list.append is atomic: adopted workers may add)."""

    __slots__ = ("trace_id", "root_id", "spans")

    def __init__(self) -> None:
        self.trace_id = 0
        self.root_id = 0  # the region's root alone carries its perf_counter
        self.spans: List[Span] = []

    def add(self, span: Span) -> None:
        self.spans.append(span)


class _NoSpan:
    """What :meth:`Tracer.span` hands out while no trace is active."""

    __slots__ = ()

    def __enter__(self) -> Dict[str, Any]:
        return {}

    def __exit__(self, *exc) -> None:
        return None


_NO_SPAN = _NoSpan()


class Tracer:
    """Process-global span collector (one instance: :data:`tracer`)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._finished: List[Trace] = []
        self._dropped = 0
        #: the process journal (module docstring): milestones land here
        #: when no trace is open; never finished, never reset
        self.journal = Journal(next(self._ids))

    # -- context -----------------------------------------------------------

    def active(self) -> bool:
        """True when a trace is open in the calling context."""
        return _CURRENT.get() is not None

    def capture(self) -> Optional[Tuple[Trace, int]]:
        """Snapshot of the current (trace, span) for explicit handoff to
        another thread; ``None`` when no trace is active."""
        return _CURRENT.get()

    @contextlib.contextmanager
    def adopt(self, ctx: Optional[Tuple[Trace, int]]) -> Iterator[None]:
        """Run the body inside a context captured elsewhere (a worker
        lane contributing spans to its coordinator's trace).  ``None``
        adopts nothing and the body runs untraced."""
        if ctx is None:
            yield
            return
        token = _CURRENT.set(ctx)
        try:
            yield
        finally:
            _CURRENT.reset(token)

    def suspend(self):
        """Leave the current context (a caller's callback run on a
        worker's thread must not inherit the worker's trace); returns
        what :meth:`resume` needs, None when no trace is active."""
        if _CURRENT.get() is None:
            return None
        return _CURRENT.set(None)

    def resume(self, token) -> None:
        if token is not None:
            _CURRENT.reset(token)

    # -- tracing -----------------------------------------------------------

    def _start(self, trace_id: int, parent_id: Optional[int], name: str, attrs) -> Span:
        """A span that starts now on this thread, still open (t_end 0)."""
        return Span(
            trace_id=trace_id,
            span_id=next(self._ids),
            parent_id=parent_id,
            name=name,
            t_start=time.perf_counter(),
            t_end=0.0,
            lane=threading.current_thread().name,
            attrs=dict(attrs) if attrs else {},
        )

    @contextlib.contextmanager
    def trace(self, name: str, **attrs) -> Iterator[Trace]:
        """Open a new root trace in this context; yields the
        :class:`Trace` and registers it in the finished list on exit."""
        t = Trace(next(self._ids), name)
        root = self._start(t.trace_id, None, name, attrs)
        t.root_id = root.span_id
        token = _CURRENT.set((t, root.span_id))
        ann = _annotate(name, perf_counter=root.t_start)
        try:
            yield t
        finally:
            ann.__exit__(None, None, None)
            _CURRENT.reset(token)
            root.t_end = time.perf_counter()
            t.add(root)
            with self._lock:
                self._finished.append(t)
                while len(self._finished) > MAX_FINISHED_TRACES:
                    self._finished.pop(0)
                    self._dropped += 1

    def open_span(self, name: str, **attrs) -> Optional[_OpenSpan]:
        """Low-level span open: returns ``None`` (and records nothing)
        when no trace is active — the disabled fast path."""
        ctx = _CURRENT.get()
        if ctx is None:
            return None
        return self._open(ctx[0], ctx[1], name, attrs)

    def _open(self, t, parent: Optional[int], name: str, attrs) -> _OpenSpan:
        """A live span of *t* under *parent*, made the current context.
        A root, or a span directly under its trace's root, carries its
        own ``perf_counter`` into the profiler's trace: an anchor."""
        span = self._start(t.trace_id, parent, name, attrs)
        token = _CURRENT.set((t, span.span_id))
        if parent is None or parent == t.root_id:
            ann = _annotate(name, perf_counter=span.t_start)
        else:
            ann = _annotate(name)
        return _OpenSpan(t, span, token, ann)

    def close_span(self, handle: Optional[_OpenSpan], **attrs) -> None:
        if handle is None:
            return
        handle.ann.__exit__(None, None, None)
        _CURRENT.reset(handle.token)
        handle.span.t_end = time.perf_counter()
        if attrs:
            handle.span.attrs.update(attrs)
        handle.trace.add(handle.span)

    def span(self, name: str, **attrs):
        """Child span under the current context, as a context manager
        that yields the span's attrs dict (the body may annotate it).
        With no trace active it is one ``ContextVar.get`` and a shared
        do-nothing manager yielding a throwaway dict."""
        ctx = _CURRENT.get()
        if ctx is None:
            return _NO_SPAN
        return self._live_span(name, attrs, ctx)

    def milestone(self, name: str, **attrs):
        """Once-per-object work (an ingest, an index build, an admission,
        a recovery), as a context manager yielding the span's attrs.
        Inside a trace it is :meth:`span`; outside one it opens a root
        in :attr:`journal` and makes it the current context, so the
        stages and spans its body reaches become its children.  It
        forces no device sync: ``telemetry.barrier`` stays keyed on
        collection."""
        return self._live_span(name, attrs, _CURRENT.get() or (self.journal, None))

    @contextlib.contextmanager
    def _live_span(self, name: str, attrs: Dict[str, Any], ctx) -> Iterator[Dict[str, Any]]:
        handle = self._open(ctx[0], ctx[1], name, attrs)
        try:
            yield handle.span.attrs
        except BaseException as e:
            handle.span.attrs["error"] = type(e).__name__
            raise
        finally:
            self.close_span(handle)

    @contextlib.contextmanager
    def shared(self, name: str, ctxs, **attrs) -> Iterator[Dict[str, Any]]:
        """One live region on the calling thread that works for several
        traces at once (the serving dispatcher's cycle over a coalesced
        batch).  While it runs it is the current context: spans opened
        under it — here, in the layers it calls, on adopted workers —
        are ordinary live spans with annotations.  When it ends, the
        finished subtree is copied under each distinct (trace, parent)
        of *ctxs* (captured contexts), with span ids of its own and the
        same names, times, lanes and attrs: every trace's tree holds
        the batch-shared work once.  Yields the region's attrs."""
        region = _SharedSpans()
        root = self._start(region.trace_id, None, name, attrs)
        token = _CURRENT.set((region, root.span_id))
        ann = _annotate(name, perf_counter=root.t_start)
        try:
            yield root.attrs
        finally:
            ann.__exit__(None, None, None)
            _CURRENT.reset(token)
            root.t_end = time.perf_counter()
            spans = [root] + region.spans
            seen = set()
            for trace, parent in ctxs:
                if (id(trace), parent) in seen:
                    continue
                seen.add((id(trace), parent))
                ids = {s.span_id: next(self._ids) for s in spans}
                for s in spans:
                    trace.add(
                        Span(
                            trace.trace_id,
                            ids[s.span_id],
                            ids.get(s.parent_id, parent),
                            s.name,
                            s.t_start,
                            s.t_end,
                            s.lane,
                            s.attrs,
                        )
                    )

    def add_span(
        self,
        name: str,
        seconds: float,
        *,
        lane: Optional[str] = None,
        t_end: Optional[float] = None,
        **attrs,
    ) -> Optional[Span]:
        """Pre-measured span under the current context (the
        ``add_stage`` analogue: work accumulated across many slices,
        e.g. a worker lane's total busy time).  ``t_end`` defaults to
        now, so the span covers [now - seconds, now]."""
        ctx = _CURRENT.get()
        if ctx is None:
            return None
        t, parent = ctx
        end = time.perf_counter() if t_end is None else t_end
        return self.record_span(
            t, parent, name, end - float(seconds), end, lane=lane, **attrs
        )

    def record_span(
        self,
        trace: Trace,
        parent_id: Optional[int],
        name: str,
        t_start: float,
        t_end: float,
        *,
        lane: Optional[str] = None,
        **attrs,
    ) -> Span:
        """Record a fully-specified span into *trace* from any thread —
        the serving dispatcher uses this to attribute batch-shared work
        back to each request's own trace."""
        span = Span(
            trace_id=trace.trace_id,
            span_id=next(self._ids),
            parent_id=parent_id,
            name=name,
            t_start=t_start,
            t_end=t_end,
            lane=lane or threading.current_thread().name,
            attrs=dict(attrs) if attrs else {},
        )
        trace.add(span)
        return span

    # -- export ------------------------------------------------------------

    def finished(self) -> List[Trace]:
        """Snapshot copy of the finished traces (oldest first)."""
        with self._lock:
            return list(self._finished)

    def drain(self) -> List[Trace]:
        """Finished traces, removing them from the tracer."""
        with self._lock:
            out = list(self._finished)
            self._finished.clear()
            return out

    def reset(self) -> None:
        with self._lock:
            self._finished.clear()
            self._dropped = 0

    @property
    def dropped(self) -> int:
        return self._dropped


#: Process-global tracer (mirrors the ``telemetry`` singleton pattern).
tracer = Tracer()


# -- jax's compile events, as ``compile`` spans ------------------------------

_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_OUTCOMES = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
# the persistent cache's verdict on the program this thread is compiling:
# jax reports it before the ``backend_compile`` duration that holds it
_PENDING = threading.local()
_LISTENING = False


def _on_compile_seconds(event: str, seconds: float, **kw) -> None:
    if event.startswith("/jax/core/compile/"):
        kind = event.rsplit("/", 1)[1].removesuffix("_duration")
    elif event == _CACHE_RETRIEVAL:
        kind = "cache_retrieval"
    else:
        return
    attrs = {"kind": kind}
    if "fun_name" in kw:
        attrs["fun_name"] = str(kw["fun_name"])
    if kind == "backend_compile":
        cache = _PENDING.__dict__.pop("cache", None)
        if cache is not None:
            attrs["cache"] = cache
    end = time.perf_counter()
    t, parent = _CURRENT.get() or (tracer.journal, None)
    tracer.record_span(t, parent, "compile", end - float(seconds), end, **attrs)


def _on_compile_event(event: str, **kw) -> None:
    outcome = _CACHE_OUTCOMES.get(event)
    if outcome is not None:
        _PENDING.cache = outcome


def journal_compiles() -> None:
    """Listen to ``jax.monitoring`` (once a process; called where the
    package first imports jax, ``obs/recompile.py:register_kernel``): each
    duration jax reports on its compile path — ``jaxpr_trace``,
    ``jaxpr_to_mlir_module``, ``backend_compile``, the persistent cache's
    ``cache_retrieval`` — becomes one ``compile`` span ending now, with
    its ``kind``, the ``fun_name`` jax passes and, on ``backend_compile``,
    ``cache`` ``hit`` | ``miss``; under the current context where one is
    open, else a root of the journal.  A warm execution compiles nothing
    and so records nothing."""
    global _LISTENING
    if _LISTENING:
        return
    _LISTENING = True
    import jax.monitoring as mon

    mon.register_event_duration_secs_listener(_on_compile_seconds)
    mon.register_event_listener(_on_compile_event)
