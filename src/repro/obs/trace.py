"""Wall-clock distributed tracing for the checkpoint service core.

One checkpoint's life — snapshot → client commit → encode → L1 puts →
L2 drain → L3 trickle → restore or redistribution — crosses the step
thread, the client's completer, every agent inbox worker, the drain pool
and its background lane, and (for a zero-stall resize) an overlap window.
This module stitches those hops into a single causal span tree per
``trace_id``:

* ``TraceContext`` — an immutable ``(trace_id, span_id, parent_id)``
  triple.  The *current* context is thread-local; queue hand-offs
  (agent ``_Op``s, drain submissions, background-lane closures) carry it
  explicitly and reinstate it with :meth:`TraceCollector.use` on the
  consuming thread.
* ``trace_id`` convention: ``"{app}/c{ckpt_id}"`` — derivable from any
  event payload that names the app and checkpoint, so late phases
  (drain retries, the L3 trickle, a restore hours later) re-join the
  tree without having had the context threaded to them: a span started
  with a ``trace_id`` but no parent attaches to that trace's root (and
  the first span of a trace with no root becomes its root).
* Work that runs before its checkpoint has an id (the snapshot, the
  commit's catalog entry) records under a :meth:`provisional` trace id;
  :meth:`adopt` moves it into the checkpoint's tree once the id exists.
* Spans live on the **wall clock**, ``time.perf_counter`` — the clock of
  ``jax.profiler``'s host events and of a benchmark's own spans, so all
  three nest by time in one process.  A duration the service models
  rather than measures (a simulated transfer) travels as the argument
  ``sim_s`` of an instant ``record`` stamped when it was made; with a
  ``clock`` (the cluster's ``SimClock``) each span also carries the sim
  time it started at, ``sim_t``, to line it up with the bus's events.
* :meth:`TraceCollector.enable` with ``annotate=True`` makes every
  :meth:`~TraceCollector.span` a ``jax.profiler.TraceAnnotation`` named
  ``icheck/<name>`` too, so a profile's ``.xplane.pb`` holds the
  program's spans on the device trace's clock.  While enabled, each
  garbage collection that runs inside a span is a ``gc`` span, a child
  of the span open on the collecting thread.
* Export is Chrome/Perfetto ``trace_event`` JSON
  (:meth:`TraceCollector.to_chrome_trace`): one *process* per track
  prefix (node, client, service), one *thread* per full track name, so
  ``chrome://tracing`` / https://ui.perfetto.dev render one lane per
  node/agent/service.

Disabled collectors (the default) are no-ops on the hot path: ``record``,
``span``, ``note`` and ``use`` test one attribute and return, so tracing
costs nothing unless asked for.  ``timed`` is the one entry point that
reads the clock while disabled: it times phases whose duration the
program keeps for itself (``EncodedRegion.encode_s``).
"""

from __future__ import annotations

import collections
import gc
import itertools
import json
import threading
import time
import weakref
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

ANNOTATION_PREFIX = "icheck/"


@dataclass(frozen=True)
class TraceContext:
    """Propagated identity of one span: where new child spans attach."""

    trace_id: str
    span_id: int
    parent_id: Optional[int] = None


@dataclass
class Span:
    """One completed operation on one track, on the wall clock."""

    name: str
    trace_id: str
    span_id: int
    parent_id: Optional[int]
    track: str
    t0: float
    dur_s: float
    args: Dict[str, Any] = field(default_factory=dict)
    sim_t: Optional[float] = None

    def as_dict(self) -> dict:
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "track": self.track,
            "t0": self.t0,
            "dur_s": self.dur_s,
            "sim_t": self.sim_t,
            "args": dict(self.args),
        }


class Timing:
    """Wall seconds of a :meth:`TraceCollector.timed` body, once it exits."""

    __slots__ = ("seconds",)

    def __init__(self) -> None:
        self.seconds = 0.0


def trace_id_for(app_id: str, ckpt_id) -> str:
    """The canonical trace id of one checkpoint's life."""
    return f"{app_id}/c{ckpt_id}"


def _gc_hook(collector: "TraceCollector"):
    """A ``gc.callbacks`` entry that holds its collector weakly."""
    ref = weakref.ref(collector)

    def hook(phase: str, info: dict) -> None:
        c = ref()
        if c is not None:
            c._on_gc(phase, info)

    return hook


class TraceCollector:
    """Bounded, thread-safe span sink with Chrome trace export.

    Off by default (``enabled=False``), which keeps every entry point a
    near-free no-op so the tracer can be wired unconditionally through
    the core; :meth:`enable` and :meth:`disable` switch it.
    """

    def __init__(self, clock=None, enabled: bool = False,
                 max_spans: int = 200_000):
        self.clock = clock
        self.enabled = False
        self.annotate = False
        self.max_spans = int(max_spans)
        self.dropped = 0
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        self._ids = itertools.count(1)
        # trace_id -> root span_id: parentless spans of a known trace
        # attach here, which is what keeps cross-thread phases (drain,
        # trickle, restore) connected without explicit context plumbing
        self._roots: Dict[str, int] = {}
        # provisional trace id -> its spans so far (moved by ``adopt``),
        # and adopted ids whose root span is still open -> (id, root)
        self._pending: Dict[str, List[Span]] = {}
        self._adopted: Dict[str, Tuple[str, int]] = {}
        self._tls = threading.local()
        # listeners observe completed spans (the flight recorder's ring)
        self._listeners: List[Any] = []
        # finished collections, appended by the gc hook without a lock
        # (it may run while this thread holds any lock) and turned into
        # spans on the next append
        self._gc_done: collections.deque = collections.deque()
        self._gc_hook = None
        self._annotation = None
        if enabled:
            self.enable()

    # ------------------------------------------------------------ switch
    def enable(self, annotate: bool = False) -> None:
        """Start recording; ``annotate`` mirrors each span into the
        profiler's trace as ``jax.profiler.TraceAnnotation("icheck/<name>")``
        on the thread that runs it."""
        if annotate and self._annotation is None:
            import jax.profiler

            self._annotation = jax.profiler.TraceAnnotation
        self.annotate = bool(annotate)
        if self._gc_hook is None:
            self._gc_hook = _gc_hook(self)
            gc.callbacks.append(self._gc_hook)
        self.enabled = True

    def disable(self) -> None:
        """Stop recording; spans already collected stay readable."""
        self.enabled = False
        self.annotate = False
        if self._gc_hook is not None:
            try:
                gc.callbacks.remove(self._gc_hook)
            except ValueError:
                pass
            self._gc_hook = None

    # ------------------------------------------------------------ context
    def current(self) -> Optional[TraceContext]:
        if not self.enabled:
            return None
        return getattr(self._tls, "ctx", None)

    @contextmanager
    def use(self, ctx: Optional[TraceContext]):
        """Reinstate a handed-off context on the consuming thread."""
        if not self.enabled or ctx is None:
            yield
            return
        prev = getattr(self._tls, "ctx", None)
        self._tls.ctx = ctx
        try:
            yield
        finally:
            self._tls.ctx = prev

    def unique(self, prefix: str) -> str:
        """A trace id no other trace of this collector has."""
        return f"{prefix}{next(self._ids)}"

    def provisional(self, prefix: str) -> Optional[str]:
        """A trace id for work whose checkpoint has no id yet; its spans
        join that checkpoint's tree when :meth:`adopt` is called."""
        if not self.enabled:
            return None
        trace_id = f"{prefix}/pending{next(self._ids)}"
        with self._lock:
            self._pending[trace_id] = []
        return trace_id

    def adopt(self, provisional: Optional[str], trace_id: str,
              parent: Optional[TraceContext] = None
              ) -> Optional[TraceContext]:
        """Move a provisional trace into ``trace_id``.

        Its spans take ``trace_id``; its root hangs under ``parent`` or,
        without one, becomes the root of ``trace_id`` — spans of the
        provisional trace still open (the caller's own) close into the
        new trace.  Returns the caller's current context, re-identified,
        when it belonged to the provisional trace.  A trace id that is
        not provisional (or already adopted) is left alone.
        """
        if not self.enabled or provisional is None:
            return None
        self._finish([])            # collections inside the trace first
        with self._lock:
            spans = self._pending.pop(provisional, None)
            if spans is None:
                return None
            root = self._roots.pop(provisional, None)
            for s in spans:
                s.trace_id = trace_id
                if s.parent_id is None and parent is not None:
                    s.parent_id = parent.span_id
            if root is not None:
                if parent is None:
                    self._roots.setdefault(trace_id, root)
                if not any(s.span_id == root for s in spans):
                    self._adopted[provisional] = (trace_id, root)
        cur = getattr(self._tls, "ctx", None)
        if cur is None or cur.trace_id != provisional:
            return None
        cur = TraceContext(trace_id=trace_id, span_id=cur.span_id,
                           parent_id=cur.parent_id)
        self._tls.ctx = cur
        return cur

    # ------------------------------------------------------------ recording
    def _sim_now(self) -> Optional[float]:
        return self.clock.now() if self.clock is not None else None

    def _parent_of(self, trace_id: str, span_id: int,
                   parent: Optional[TraceContext], root: bool
                   ) -> Optional[int]:
        """Parent span id of a new span (under the lock)."""
        if root:
            self._roots.setdefault(trace_id, span_id)
            return None
        if parent is not None and parent.trace_id == trace_id:
            return parent.span_id
        cur = getattr(self._tls, "ctx", None)
        if cur is not None and cur.trace_id == trace_id:
            return cur.span_id
        found = self._roots.get(trace_id)
        if found is None:
            self._roots[trace_id] = span_id
        return found

    def _append(self, span: Span) -> None:
        """Store a completed span (under the lock)."""
        if len(self._spans) >= self.max_spans:
            self.dropped += 1
            return
        self._spans.append(span)
        pending = self._pending.get(span.trace_id)
        if pending is not None:
            pending.append(span)

    def _finish(self, spans: List[Span]) -> None:
        """Append spans and tell the listeners, outside the lock."""
        with self._lock:
            spans = self._take_gc() + spans
            for s in spans:
                self._append(s)
            listeners = list(self._listeners)
        for s in spans:
            for listener in listeners:
                try:
                    listener(s)
                except Exception:  # noqa: BLE001 - observers must not break us
                    pass

    def record(self, name: str, trace_id: str, track: str,
               parent: Optional[TraceContext] = None,
               root: bool = False, **args) -> Optional[TraceContext]:
        """Append one instant span, stamped now on the wall clock; a
        modeled duration goes in ``args`` as ``sim_s``.

        Returns the span's :class:`TraceContext` (for hand-off to child
        operations), or ``None`` when the collector is disabled.
        """
        if not self.enabled:
            return None
        t = time.perf_counter()
        span_id = next(self._ids)
        with self._lock:
            parent_id = self._parent_of(trace_id, span_id, parent, root)
        self._finish([Span(name=name, trace_id=trace_id, span_id=span_id,
                           parent_id=parent_id, track=track, t0=t,
                           dur_s=0.0, args=dict(args),
                           sim_t=self._sim_now())])
        return TraceContext(trace_id=trace_id, span_id=span_id,
                            parent_id=parent_id)

    @contextmanager
    def _span(self, name: str, trace_id: Optional[str], track: str,
              parent: Optional[TraceContext], root: bool, args: dict,
              timing: Optional[Timing]):
        ann = None
        if self.annotate and self._annotation is not None:
            ann = self._annotation(ANNOTATION_PREFIX + name)
            ann.__enter__()
        sim_t = self._sim_now()
        t0 = time.perf_counter()
        if trace_id is None:
            ctx = None
            prev = prev_args = None
        else:
            span_id = next(self._ids)
            with self._lock:
                parent_id = self._parent_of(trace_id, span_id, parent, root)
            ctx = TraceContext(trace_id=trace_id, span_id=span_id,
                               parent_id=parent_id)
            prev = getattr(self._tls, "ctx", None)
            prev_args = getattr(self._tls, "args", None)
            self._tls.ctx = ctx
            self._tls.args = args
        try:
            yield ctx
        finally:
            dur = time.perf_counter() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            if timing is not None:
                timing.seconds = dur
            if ctx is not None:
                self._tls.ctx = prev
                self._tls.args = prev_args
                tid = trace_id
                adopted = self._adopted.get(trace_id)
                if adopted is not None:
                    tid = adopted[0]
                    if adopted[1] == ctx.span_id:
                        with self._lock:
                            self._adopted.pop(trace_id, None)
                self._finish([Span(name=name, trace_id=tid,
                                   span_id=ctx.span_id,
                                   parent_id=ctx.parent_id, track=track,
                                   t0=t0, dur_s=dur, args=args,
                                   sim_t=sim_t)])

    @contextmanager
    def span(self, name: str, trace_id: Optional[str], track: str,
             parent: Optional[TraceContext] = None, root: bool = False,
             **args):
        """Context-managed span: duration is the wall time of the body,
        and the body runs with the new span as the current context
        (children started inside attach to it)."""
        if not self.enabled:
            yield None
            return
        with self._span(name, trace_id, track, parent, root, args,
                        None) as ctx:
            yield ctx

    @contextmanager
    def timed(self, name: str, trace_id: Optional[str], track: str,
              parent: Optional[TraceContext] = None, **args):
        """A :meth:`span` that is timed even while the collector is off:
        yields a :class:`Timing` whose ``seconds`` is set on exit, from
        the same two clock reads as the span."""
        timing = Timing()
        if not self.enabled:
            t0 = time.perf_counter()
            try:
                yield timing
            finally:
                timing.seconds = time.perf_counter() - t0
            return
        with self._span(name, trace_id, track, parent, False, args, timing):
            yield timing

    def note(self, **args) -> None:
        """Add arguments (counts) to the innermost span open on this
        thread."""
        if not self.enabled:
            return
        open_args = getattr(self._tls, "args", None)
        if open_args is not None:
            open_args.update(args)

    def add_listener(self, listener) -> None:
        with self._lock:
            self._listeners.append(listener)

    # ------------------------------------------------------------ gc
    def _on_gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` entry: runs on the collecting thread, perhaps
        while it holds any lock, so it takes none."""
        tls = self._tls
        if phase == "start":
            ctx = getattr(tls, "ctx", None)
            if not self.enabled or ctx is None:
                return
            ann = None
            if self.annotate and self._annotation is not None:
                ann = self._annotation(ANNOTATION_PREFIX + "gc")
                ann.__enter__()
            tls.gc = (time.perf_counter(), ann, ctx)
            return
        started = getattr(tls, "gc", None)
        if started is None:
            return
        tls.gc = None
        t0, ann, ctx = started
        dur = time.perf_counter() - t0
        if ann is not None:
            ann.__exit__(None, None, None)
        self._gc_done.append((t0, dur, info.get("generation"),
                              info.get("collected"), ctx,
                              threading.get_ident()))

    def _take_gc(self) -> List[Span]:
        """Finished collections as spans (under the lock)."""
        out = []
        while self._gc_done:
            t0, dur, gen, collected, ctx, thread = self._gc_done.popleft()
            # a collection inside a commit span that has since been adopted
            trace_id = self._adopted.get(ctx.trace_id, (ctx.trace_id,))[0]
            out.append(Span(name="gc", trace_id=trace_id,
                            span_id=next(self._ids), parent_id=ctx.span_id,
                            track=f"gc/{thread}", t0=t0, dur_s=dur,
                            args={"generation": gen,
                                  "collected": collected}))
        return out

    # ------------------------------------------------------------ inspection
    def spans(self, trace_id: Optional[str] = None) -> List[Span]:
        if self._gc_done:
            self._finish([])
        with self._lock:
            if trace_id is None:
                return list(self._spans)
            return [s for s in self._spans if s.trace_id == trace_id]

    def trace_ids(self) -> List[str]:
        with self._lock:
            seen: Dict[str, None] = {}
            for s in self._spans:
                seen.setdefault(s.trace_id, None)
            return list(seen)

    def root_of(self, trace_id: str) -> Optional[int]:
        with self._lock:
            return self._roots.get(trace_id)

    # ------------------------------------------------------------ export
    def _track_ids(self, spans: List[Span]) -> Dict[str, Tuple[int, int]]:
        """Stable (pid, tid) per track: pid per prefix before the first
        '/', tid per full track name — one Perfetto lane per agent/lane."""
        pids: Dict[str, int] = {}
        tids: Dict[str, int] = {}
        out: Dict[str, Tuple[int, int]] = {}
        for s in spans:
            if s.track in out:
                continue
            proc = s.track.split("/", 1)[0]
            pid = pids.setdefault(proc, len(pids) + 1)
            tid = tids.setdefault(s.track, len(tids) + 1)
            out[s.track] = (pid, tid)
        return out

    def to_chrome_trace(self) -> dict:
        """Render every collected span as Chrome ``trace_event`` JSON
        (the dict form: load the file in chrome://tracing or Perfetto)."""
        spans = self.spans()
        tracks = self._track_ids(spans)
        events: List[dict] = []
        procs_done = set()
        for track, (pid, tid) in tracks.items():
            proc = track.split("/", 1)[0]
            if pid not in procs_done:
                procs_done.add(pid)
                events.append({"ph": "M", "name": "process_name",
                               "pid": pid, "tid": 0,
                               "args": {"name": proc}})
            events.append({"ph": "M", "name": "thread_name",
                           "pid": pid, "tid": tid,
                           "args": {"name": track}})
        for s in spans:
            pid, tid = tracks[s.track]
            events.append({
                "ph": "X",
                "name": s.name,
                "cat": "ckpt",
                "ts": s.t0 * 1e6,          # trace_event wants microseconds
                "dur": s.dur_s * 1e6,
                "pid": pid,
                "tid": tid,
                "args": {**s.args, "trace_id": s.trace_id,
                         "span_id": s.span_id, "parent_id": s.parent_id,
                         "sim_t": s.sim_t},
            })
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"clock": "wall", "dropped_spans": self.dropped}}

    def write_chrome_trace(self, path: str) -> str:
        import os

        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f, indent=1, sort_keys=True)
        return os.path.abspath(path)
