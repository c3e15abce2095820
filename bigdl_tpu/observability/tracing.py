"""Trace spans → in-memory ring buffer → Chrome-trace/Perfetto JSON.

``with span("train/step", step=i):`` brackets a host-side phase; completed
spans land in a fixed-capacity ring buffer (old entries fall off — a
long-running server never grows without bound) and can be exported as
Chrome trace-event JSON (``chrome://tracing`` / https://ui.perfetto.dev
both load it directly).

Span records are "X" (complete) events: name, ``ts``/``dur`` in
microseconds, ``pid``/``tid``, free-form ``args``. Nesting is tracked per
thread with a thread-local stack — the exported depth is what the trace
viewers use to stack the flame graph, and ``parent`` in args keeps the
relationship greppable in the raw JSON.

One clock: every record also carries ``t0``, its start in seconds on
``time.perf_counter()`` — the clock the host side of a JAX profile, the
``Request`` stamps and any benchmark around the process read — beside
the epoch ``ts`` the trace viewers want.

Optional JAX profiler passthrough: ``configure(jax_passthrough=True)``
additionally enters ``jax.profiler.StepTraceAnnotation`` for spans that
carry a ``step`` arg and ``jax.profiler.TraceAnnotation`` otherwise, so
the same ``span(...)`` sites label XLA's own device profile when one is
being captured. Off by default (it is not free) and silently skipped
when the profiler is unavailable. A site that belongs in every captured
profile (the LLM engine's pass phases) asks for its annotation itself,
``span(name, annotate=True)``: a ``TraceAnnotation`` costs a fraction
of a microsecond while no profile is being captured.

Disabled mode (:func:`bigdl_tpu.observability.enabled` False): ``span``
reads no clock and writes no buffer (an ``annotate=True`` span still
enters its annotation, which is the profiler's and not this ring's).
"""

from __future__ import annotations

import gc
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from bigdl_tpu.observability import _state
from bigdl_tpu.observability import request_context as rc


def _default_capacity() -> int:
    try:
        from bigdl_tpu.utils.conf import conf
        return conf.get_int("bigdl.observability.trace.capacity", 65536)
    except Exception:
        return 65536


class TraceBuffer:
    """Fixed-capacity ring of completed span records (dicts in
    trace-event form). Thread-safe; ``capacity`` bounds host memory."""

    def __init__(self, capacity: Optional[int] = None):
        self.capacity = capacity if capacity is not None \
            else _default_capacity()
        self._lock = threading.Lock()
        self._buf: List[Dict[str, Any]] = []
        self._head = 0          # insertion point once the ring is full
        self.dropped = 0

    def append(self, rec: Dict[str, Any]):
        with self._lock:
            if self.capacity <= 0:     # capacity 0 = tracing off
                self.dropped += 1
                return
            if len(self._buf) < self.capacity:
                self._buf.append(rec)
            else:
                self._buf[self._head] = rec
                self._head = (self._head + 1) % self.capacity
                self.dropped += 1

    def spans(self) -> List[Dict[str, Any]]:
        """Records in arrival order."""
        with self._lock:
            return self._buf[self._head:] + self._buf[:self._head]

    def for_trace(self, trace_id: str) -> List[Dict[str, Any]]:
        """Every retained span tagged with ``trace_id`` (the per-request
        assembly behind ``GET /debug/trace/<id>``), in start order."""
        out = [r for r in self.spans()
               if r.get("args", {}).get("trace") == trace_id]
        out.sort(key=lambda r: r.get("ts", 0.0))
        return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)

    def clear(self):
        with self._lock:
            self._buf = []
            self._head = 0
            self.dropped = 0

    def set_capacity(self, capacity: int):
        """Resize in place (the module-level ``TRACE`` is imported by
        value all over; rebinding it would strand those references).
        Keeps the newest ``capacity`` spans."""
        with self._lock:
            ordered = self._buf[self._head:] + self._buf[:self._head]
            self.capacity = int(capacity)
            self._buf = ordered[-self.capacity:] if self.capacity > 0 \
                else []
            self._head = 0

    def export_chrome_trace(self, path: Optional[str] = None) -> str:
        """Chrome trace-event JSON. Returns the JSON string; writes it to
        ``path`` when given (parent dirs created)."""
        doc = {"traceEvents": self.spans(), "displayTimeUnit": "ms"}
        text = json.dumps(doc)
        if path:
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            with open(path, "w") as f:
                f.write(text)
        return text


TRACE = TraceBuffer()

_tls = threading.local()
_jax_passthrough = False


def configure(jax_passthrough: Optional[bool] = None,
              capacity: Optional[int] = None):
    """Adjust tracing runtime knobs. ``capacity`` resizes the ring
    buffer in place (newest spans kept)."""
    global _jax_passthrough
    if jax_passthrough is not None:
        _jax_passthrough = bool(jax_passthrough)
    if capacity is not None:
        TRACE.set_capacity(capacity)


def _stack() -> List[str]:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def _jax_annotation(name: str, args: Dict[str, Any]):
    try:
        from jax import profiler as jprof
        if "step" in args and hasattr(jprof, "StepTraceAnnotation"):
            return jprof.StepTraceAnnotation(name,
                                             step_num=int(args["step"]))
        if hasattr(jprof, "TraceAnnotation"):
            return jprof.TraceAnnotation(name)
    except Exception:
        pass
    return None


class span:
    """``with span("train/step", step=i):`` records a host-side phase.
    Nestable; thread-aware; reads no clock and writes nothing when
    observability is disabled.

    ``with span(...) as sp`` hands the span itself: ``sp.args`` may be
    filled in while it is open (a count known only at the end), and
    ``sp.end()`` closes it before the block does, for a phase that ends
    inside a callee; the block's own exit is then a no-op. ``sp.t0`` and
    ``sp.t1`` are its bounds on ``time.perf_counter()`` (``None`` when
    disabled). ``annotate=True`` enters a ``jax.profiler.
    TraceAnnotation`` of the same name whatever ``configure`` says and
    whether or not observability records.

    When a :mod:`~bigdl_tpu.observability.request_context` is active
    (``activate(ctx)``), the span is additionally tagged with the
    request's ``trace``/``span``/``parent_span`` ids and becomes the
    ambient parent for anything opened inside it — the mechanism that
    stitches existing ``span()`` sites into cross-process traces."""

    __slots__ = ("name", "args", "t0", "t1", "_annotate", "_ann",
                 "_live", "_wall0", "_parent", "_ctx", "_token")

    def __init__(self, name: str, annotate: bool = False, **args: Any):
        self.name = name
        self.args = args
        self.t0 = self.t1 = None
        self._annotate = annotate
        self._ann = None
        self._live = False

    def __enter__(self) -> "span":
        live = self._live = _state.enabled
        if self._annotate or (live and _jax_passthrough):
            ann = _jax_annotation(self.name,
                                  {} if self._annotate else self.args)
            if ann is not None:
                try:
                    ann.__enter__()
                    self._ann = ann
                except Exception:
                    # a profiler-state hiccup must not crash the
                    # instrumented loop
                    pass
        if not live:
            return self
        stack = _stack()
        self._parent = stack[-1] if stack else None
        stack.append(self.name)
        ctx = rc.current()
        self._token = None
        if ctx is not None:
            # this span's own identity; children parent to it via the
            # contextvar for the duration of the block
            ctx = ctx.child()
            self._token = rc._current.set(ctx)
        self._ctx = ctx
        self.t0 = time.perf_counter()
        self._wall0 = time.time()
        return self

    def end(self, **args: Any) -> None:
        """Close the span now, adding ``args``; later calls, and the
        ``with`` block's exit, do nothing."""
        live, self._live = self._live, False
        if live:
            self.t1 = time.perf_counter()
        ann, self._ann = self._ann, None
        if ann is not None:
            try:
                ann.__exit__(None, None, None)
            except Exception:
                pass
        if not live:
            return
        _stack().pop()
        if self._token is not None:
            rc._current.reset(self._token)
        self.args.update(args)
        rec_args = dict(self.args)
        if self._parent is not None:
            rec_args["parent"] = self._parent
        ctx = self._ctx
        if ctx is not None:
            rec_args["trace"] = ctx.trace_id
            rec_args["span"] = ctx.span_id
            if ctx.parent_id:
                rec_args["parent_span"] = ctx.parent_id
        TRACE.append(make_complete(self.name, self._wall0,
                                   self.t1 - self.t0, self.t0,
                                   **rec_args))

    def __exit__(self, *exc) -> bool:
        self.end()
        return False


def make_complete(name: str, start_wall: float, dur_s: float,
                  t0: Optional[float] = None, /,
                  **args: Any) -> Dict[str, Any]:
    """Build (but do not record) a complete ("X") event record — the
    one schema owner, so hand-built dicts and shipped-across-processes
    spans can't drift from ``span``'s. ``start_wall`` is epoch
    seconds; ``t0`` the same instant on ``time.perf_counter()``, worked
    out from ``start_wall`` when the caller did not read it."""
    if t0 is None:
        t0 = time.perf_counter() - (time.time() - start_wall)
    return {
        "name": name,
        "ph": "X",
        "ts": start_wall * 1e6,           # trace-event ts is microseconds
        "dur": dur_s * 1e6,
        "t0": t0,
        "pid": os.getpid(),
        "tid": threading.get_ident(),
        "args": dict(args),
    }


def add_complete(name: str, start_wall: float, dur_s: float,
                 t0: Optional[float] = None, /, **args: Any):
    """Record an already-measured phase as a complete ("X") event — for
    call sites that timed the work themselves and must not re-bracket
    it. No-op when disabled."""
    if not _state.enabled:
        return
    TRACE.append(make_complete(name, start_wall, dur_s, t0, **args))


def export_chrome_trace(path: Optional[str] = None) -> str:
    return TRACE.export_chrome_trace(path)


# ---------------------------------------------------------------------------
# py/gc: the collector's pauses, counted always and recorded when long
# ---------------------------------------------------------------------------

#: a collection shorter than this is counted and leaves no record (the
#: young generations run hundreds of times a second for microseconds; a
#: record each would push a serving run's passes off the ring)
GC_RECORD_SECONDS = 0.5e-3

#: collections seen and the seconds they took while watched, by
#: generation (plain numbers, like the engine's ``steps``)
gc_collections_total = [0, 0, 0]
gc_seconds_total = [0.0, 0.0, 0.0]

_gc_lock = threading.Lock()
_gc_watchers = 0
# (t0, wall0, annotation) of the collection in progress: collections do
# not nest and start and stop are reported on the collecting thread
_gc_open = None


def _on_gc(phase: str, info: Dict[str, int]):
    global _gc_open
    if phase == "start":
        ann = None
        if info["generation"] == 2:
            # the old generation's sweep stops every thread for tens of
            # milliseconds: a captured profile names the hole
            ann = _jax_annotation("py/gc", {})
            try:
                if ann is not None:
                    ann.__enter__()
            except Exception:
                ann = None
        _gc_open = (time.perf_counter(), time.time(), ann)
        return
    opened, _gc_open = _gc_open, None
    if opened is None:
        return          # watched from the middle of a collection
    t0, wall0, ann = opened
    dur = time.perf_counter() - t0
    if ann is not None:
        try:
            ann.__exit__(None, None, None)
        except Exception:
            pass
    gen = info["generation"]
    gc_collections_total[gen] += 1
    gc_seconds_total[gen] += dur
    if dur >= GC_RECORD_SECONDS:
        add_complete("py/gc", wall0, dur, t0, generation=gen,
                     collected=info["collected"])


def watch_gc():
    """Count every collection of the interpreter's garbage collector
    into ``gc_collections_total`` / ``gc_seconds_total`` and record the
    ones of ``GC_RECORD_SECONDS`` or more as ``py/gc`` on the ring (on
    the thread that collected, which is the thread it stopped first). A
    process may hold several watchers (an engine each): the callback is
    installed by the first and removed with the last ``unwatch_gc``."""
    global _gc_watchers
    with _gc_lock:
        _gc_watchers += 1
        if _gc_watchers == 1:
            gc.callbacks.append(_on_gc)


def unwatch_gc():
    global _gc_watchers
    with _gc_lock:
        if not _gc_watchers:
            return
        _gc_watchers -= 1
        if not _gc_watchers:
            gc.callbacks.remove(_on_gc)


# ---------------------------------------------------------------------------
# Latency exemplars (ISSUE 3): the slowest-N request traces, by id
# ---------------------------------------------------------------------------

def _default_exemplar_capacity() -> int:
    try:
        from bigdl_tpu.utils.conf import conf
        return conf.get_int("bigdl.observability.exemplars", 8)
    except Exception:
        return 8


class ExemplarStore:
    """Slowest-N request exemplars: (latency, trace_id, meta) kept
    sorted, so an operator asking "what do my p99 requests look like"
    gets concrete trace ids to feed ``GET /debug/trace/<id>`` /
    ``tools/trace_report.py`` instead of an aggregate. The store holds
    ids, not spans — the spans live in the ring buffer (an exemplar of a
    very old request may therefore have partially fallen off; capacity
    the ring accordingly)."""

    def __init__(self, capacity: Optional[int] = None):
        self.capacity = capacity if capacity is not None \
            else _default_exemplar_capacity()
        self._lock = threading.Lock()
        self._items: List[Dict[str, Any]] = []   # sorted slowest-first

    def offer(self, trace_id: str, duration_s: float, **meta: Any):
        """Consider one finished request for retention. No-op when
        observability is disabled."""
        if not _state.enabled or not trace_id:
            return
        rec = {"trace_id": trace_id, "duration_s": float(duration_s),
               **meta}
        with self._lock:
            if self.capacity <= 0:
                return
            # one slot per trace id: a retried offer updates in place
            self._items = [r for r in self._items
                           if r["trace_id"] != trace_id]
            self._items.append(rec)
            self._items.sort(key=lambda r: -r["duration_s"])
            del self._items[self.capacity:]

    def items(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._items)

    def clear(self):
        with self._lock:
            self._items = []


EXEMPLARS = ExemplarStore()


def assemble_trace(trace_id: str) -> Dict[str, Any]:
    """Per-request span assembly: every retained span of one trace plus
    the per-stage rollup — the body ``GET /debug/trace/<id>`` serves and
    the input ``tools/trace_report.py`` renders as a waterfall."""
    spans = TRACE.for_trace(trace_id)
    stages: Dict[str, Dict[str, float]] = {}
    t0 = min((s["ts"] for s in spans), default=0.0)
    t1 = max((s["ts"] + s.get("dur", 0.0) for s in spans), default=0.0)
    for s in spans:
        stage = s.get("args", {}).get("stage", s["name"])
        agg = stages.setdefault(stage, {"count": 0, "seconds": 0.0})
        agg["count"] += 1
        agg["seconds"] += s.get("dur", 0.0) / 1e6
    return {"trace_id": trace_id, "span_count": len(spans),
            "wall_s": max(t1 - t0, 0.0) / 1e6, "stages": stages,
            "spans": spans}


def ingest_foreign_spans(spans):
    """Adopt span records produced by ANOTHER process (a queue consumer
    shipping its per-request spans back on the result record) into this
    process's ring, so ``/debug/trace`` on the frontend assembles the
    whole cross-process story. Same-pid records are skipped — in-proc
    deployments already wrote them to this very ring."""
    if not _state.enabled or not spans:
        return
    me = os.getpid()
    for rec in spans:
        if isinstance(rec, dict) and rec.get("pid") != me:
            TRACE.append(rec)


def debug_endpoint(path: str):
    """Shared ``GET /debug/trace*`` handling for the HTTP surfaces
    (ServingFrontend and LLMWorker serve identical bodies). Returns
    ``(status, json-able dict)`` or None when ``path`` is not ours.
    Disabled observability answers 404 — the surface is structurally
    absent, not empty."""
    if path == "/debug/traces":
        if not _state.enabled:
            return 404, {"error": "observability disabled"}
        return 200, {"exemplars": EXEMPLARS.items()}
    if path.startswith("/debug/trace/"):
        if not _state.enabled:
            return 404, {"error": "observability disabled"}
        trace_id = path[len("/debug/trace/"):].strip("/")
        asm = assemble_trace(trace_id)
        if not asm["span_count"]:
            return 404, {"error": f"no retained spans for trace "
                                  f"{trace_id!r}", "trace_id": trace_id}
        return 200, asm
    return None
