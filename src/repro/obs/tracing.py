"""Structured span tracing with Chrome trace-event export.

Spans carry explicit ``span_id``/``parent_id`` links — nesting is a
property of the data, not of wall-clock containment — so spans recorded
retrospectively (a request's queue/batch/decode children are emitted
when the request finishes, from its ``RequestTrace`` timestamps) link
exactly like spans recorded live around a synchronous call.

Parenting rules:
  * ``span(...)`` (context manager) nests via a thread-local stack: the
    enclosing live span on the same thread is the parent.
  * an explicit ``parent=`` always wins — this is how cross-thread
    lifecycles (request admitted on the caller thread, executed on the
    serving thread) attach their children.
  * ``add_span``/``event`` never touch the thread-local stack.

Clocks: every timestamp is ``time.monotonic()`` relative to the
tracer's epoch.  No wall-clock is recorded, so traces from restarted
processes never interleave misleadingly (Perfetto renders relative
time anyway).

The profiler bridge: a live ``span(...)`` also enters a
``jax.profiler.TraceAnnotation`` of the same name while a profiler
session runs, so the program's spans land on the device trace's own
clock, beside the device operations they drive.  That holds with the
tracer disabled too: then the annotation is all that is left.  With no
profiler session and the tracer disabled, ``span`` is one attribute
check plus the profiler's own ``is_enabled`` check, returning a shared
no-op singleton; nothing is allocated per call.  ``begin`` (ended from
any thread), ``add_span`` and ``event`` stay tracer-only: a profiler
annotation must end on the thread that began it.
"""
from __future__ import annotations

import itertools
import json
import threading
import time

from jax.profiler import TraceAnnotation

#: True while a profiler session collects host annotations (a static
#: check on the profiler's TraceMe, tens of nanoseconds)
profiling = TraceAnnotation.is_enabled


def _profiler_args(args: dict) -> dict:
    """The span args the profiler can carry: numbers, and strings free of
    the characters its annotation encoding reserves."""
    return {k: v for k, v in args.items()
            if isinstance(v, (bool, int, float))
            or (isinstance(v, str) and not any(c in v for c in "#,="))}


def _annotation(name: str, args: dict):
    ann = TraceAnnotation(name, **_profiler_args(args))
    ann.__enter__()
    return ann


class _NoopSpan:
    """Shared do-nothing span for the disabled path."""

    __slots__ = ()
    span_id = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **kw):
        return self

    def end(self, t=None):
        return self

    def drop(self):
        return self


NOOP_SPAN = _NoopSpan()


class _ProfilerSpan:
    """A span only the profiler sees: the tracer is disabled while a
    profiler session runs."""

    __slots__ = ("_ann",)
    span_id = None

    def __init__(self, name: str, args: dict):
        self._ann = _annotation(name, args)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def set(self, **kw):
        if self._ann is not None:
            self._ann.set_metadata(**_profiler_args(kw))
        return self

    def end(self, t=None):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        return self

    def drop(self):
        return self


class Span:
    __slots__ = ("name", "cat", "span_id", "parent_id", "t0", "t1",
                 "tid", "args", "_tracer", "_on_stack", "_ann", "_dropped")

    def __init__(self, tracer, name, cat, span_id, parent_id, t0, tid, args,
                 ann=None):
        self.name = name
        self.cat = cat
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0 = t0
        self.t1 = None
        self.tid = tid
        self.args = args
        self._tracer = tracer
        self._on_stack = False
        self._ann = ann
        self._dropped = False

    def set(self, **kw):
        self.args.update(kw)
        if self._ann is not None:
            self._ann.set_metadata(**_profiler_args(kw))
        return self

    def end(self, t: float | None = None):
        if self.t1 is None:
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
                self._ann = None
            self._tracer._finish(self, t)
        return self

    def drop(self):
        """Keep this span out of the tracer's records when it ends (the
        profiler still sees its annotation): for a wait that produced
        nothing worth a record."""
        self._dropped = True
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class Tracer:
    """Bounded in-memory span/event collector.

    ``capacity`` bounds retained records (oldest dropped); ``enabled``
    may be flipped at runtime (``clear()`` resets retained records and
    the drop counter, not the id sequence).
    """

    def __init__(self, enabled: bool = False, capacity: int = 65536):
        self._enabled = bool(enabled)
        self.capacity = int(capacity)
        self._epoch = time.monotonic()
        self._lock = threading.Lock()
        self._records: list[dict] = []
        self._dropped = 0
        self._ids = itertools.count(1)
        self._tls = threading.local()

    # -- state ---------------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self, on: bool = True) -> None:
        self._enabled = bool(on)

    def clear(self) -> None:
        with self._lock:
            self._records = []
            self._dropped = 0

    def now(self) -> float:
        """Seconds since the tracer epoch (monotonic)."""
        return time.monotonic() - self._epoch

    def rel(self, t: float) -> float:
        """Convert a raw ``time.monotonic()`` stamp to epoch-relative —
        for :meth:`add_span` callers holding timestamps taken elsewhere
        (e.g. a ``RequestTrace``)."""
        return t - self._epoch

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current_id(self) -> int | None:
        st = getattr(self._tls, "stack", None)
        return st[-1].span_id if st else None

    # -- recording -----------------------------------------------------------
    def span(self, name: str, cat: str = "", parent: int | None = None,
             **args):
        """Live nested span (context manager).  Parent defaults to the
        enclosing live span on this thread.  While a profiler session
        runs, the span is also a ``TraceAnnotation`` of the same name."""
        if not self._enabled:
            return _ProfilerSpan(name, args) if profiling() else NOOP_SPAN
        st = self._stack()
        pid = parent if parent is not None else (
            st[-1].span_id if st else None)
        sp = Span(self, name, cat, next(self._ids), pid, self.now(),
                  threading.get_ident(), args,
                  _annotation(name, args) if profiling() else None)
        sp._on_stack = True
        st.append(sp)
        return sp

    def begin(self, name: str, cat: str = "", parent: int | None = None,
              **args):
        """Manually-ended span; never joins the thread-local stack (safe
        to end from another thread)."""
        if not self._enabled:
            return NOOP_SPAN
        return Span(self, name, cat, next(self._ids), parent, self.now(),
                    threading.get_ident(), args)

    def _finish(self, sp: Span, t: float | None) -> None:
        sp.t1 = self.now() if t is None else t
        if sp._on_stack:
            st = self._stack()
            if sp in st:
                # pop through sp: tolerates a child left unended
                while st and st[-1] is not sp:
                    st.pop()
                if st:
                    st.pop()
        if sp._dropped:
            return
        self._append({"ph": "X", "name": sp.name, "cat": sp.cat,
                      "id": sp.span_id, "parent": sp.parent_id,
                      "t0": sp.t0, "t1": sp.t1, "tid": sp.tid,
                      "args": sp.args})

    def add_span(self, name: str, t0: float, t1: float, *, cat: str = "",
                 parent: int | None = None, tid: int | None = None,
                 **args) -> int | None:
        """Retrospective span from explicit epoch-relative times."""
        if not self._enabled:
            return None
        sid = next(self._ids)
        self._append({"ph": "X", "name": name, "cat": cat, "id": sid,
                      "parent": parent, "t0": float(t0), "t1": float(t1),
                      "tid": tid if tid is not None
                      else threading.get_ident(), "args": args})
        return sid

    def event(self, name: str, cat: str = "", parent: int | None = None,
              t: float | None = None, tid: int | None = None,
              **args) -> int | None:
        """Instant event (a point, not a duration)."""
        if not self._enabled:
            return None
        sid = next(self._ids)
        self._append({"ph": "i", "name": name, "cat": cat, "id": sid,
                      "parent": parent,
                      "t0": self.now() if t is None else float(t),
                      "t1": None,
                      "tid": tid if tid is not None
                      else threading.get_ident(), "args": args})
        return sid

    def _append(self, rec: dict) -> None:
        with self._lock:
            self._records.append(rec)
            if len(self._records) > self.capacity:
                drop = len(self._records) - self.capacity
                del self._records[:drop]
                self._dropped += drop

    # -- reads ---------------------------------------------------------------
    def records(self) -> list[dict]:
        with self._lock:
            return list(self._records)

    def __len__(self) -> int:
        with self._lock:
            return len(self._records)

    def export_chrome(self, extra=()) -> dict:
        """Chrome trace-event JSON (Perfetto-loadable): complete (``X``)
        events with microsecond ``ts``/``dur``; ``args`` carries the
        explicit ``span_id``/``parent_id`` links.  ``extra`` adds records
        of the same shape kept outside the tracer."""
        events = []
        for r in [*self.records(), *extra]:
            args = {"span_id": r["id"], "parent_id": r["parent"], **r["args"]}
            ev = {"name": r["name"], "cat": r["cat"] or "default",
                  "pid": 1, "tid": int(r["tid"]) & 0x7FFFFFFF,
                  "ts": round(r["t0"] * 1e6, 3), "args": args}
            if r["ph"] == "X":
                ev["ph"] = "X"
                ev["dur"] = round(max(0.0, (r["t1"] - r["t0"])) * 1e6, 3)
            else:
                ev["ph"] = "i"
                ev["s"] = "t"
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"dropped_records": self._dropped}}

    def export_chrome_json(self) -> str:
        return json.dumps(self.export_chrome())


#: shared disabled tracer: the default wiring target, so instrumented
#: code never branches on None
NOOP_TRACER = Tracer(enabled=False)

_GLOBAL = NOOP_TRACER


def span(name: str, cat: str = "", **args):
    """A live span for code that holds no tracer: recorded by the
    process-global tracer when one is installed (``set_tracer``), and a
    profiler annotation whenever a profiler session runs."""
    return _GLOBAL.span(name, cat, **args)


def get_tracer() -> Tracer:
    """Process-global tracer (disabled no-op until ``set_tracer``)."""
    return _GLOBAL


def set_tracer(tracer: Tracer | None) -> Tracer:
    """Install (or with None, reset) the process-global tracer used by
    compile-pass / plan instrumentation gated on the descriptor flag."""
    global _GLOBAL
    _GLOBAL = tracer if tracer is not None else NOOP_TRACER
    return _GLOBAL
