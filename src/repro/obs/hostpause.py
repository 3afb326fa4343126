"""Host pauses: the cyclic garbage collector's collections, timed.

A collection stops every Python thread of the process at once: the
thread that submits requests and the thread that serves them.  The
collector watch times each collection through ``gc.callbacks``:

* while a profiler session runs, the collection is a ``host.gc``
  annotation on the device trace's clock;
* ``(t_start, seconds, generation)`` goes into :data:`RING`, a bounded
  ring on ``time.monotonic()`` (:func:`pauses` clips it to a window), and
  the seconds into a running total per generation (:func:`totals`).

The callback takes no lock and calls into no tracer or registry: a
collection can start on a thread that already holds such a lock, and
waiting on it there would never end.  So everything else is derived from
the ring and the totals when it is read: :func:`records` gives a
tracer's ``host.gc`` spans, and :func:`register` puts
``host_gc_pause_seconds_total{generation}`` into a metrics registry as a
pulled counter.

The watch is process-wide.  :func:`install` and :func:`remove` take an
owner (``PipelineServer.start()``/``stop()`` pass the server): the
callback is registered while any owner holds it, and installing twice is
a no-op.  The ring and the totals outlive the watch.
"""
from __future__ import annotations

import gc
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation

from repro.obs.tracing import profiling

#: ``(t_start, seconds, generation)`` of the most recent collections
RING: deque = deque(maxlen=65536)

#: seconds the watched collections took, per generation; only the
#: callback writes it, and collections never overlap
_TOTALS = [0.0, 0.0, 0.0]

_lock = threading.Lock()
#: ids of the watch's owners
_owners: set = set()
#: (t_start, profiler annotation or None) of the collection under way;
#: collections never overlap (the interpreter runs one at a time)
_current = None


def _on_gc(phase: str, info: dict) -> None:
    global _current
    if phase == "start":
        ann = None
        if profiling():
            ann = TraceAnnotation("host.gc", generation=info["generation"])
            ann.__enter__()
        _current = (time.monotonic(), ann)
        return
    if _current is None:                  # watched from mid-collection
        return
    t0, ann = _current
    _current = None
    d = time.monotonic() - t0
    gen = int(info["generation"])
    if ann is not None:
        ann.__exit__(None, None, None)
    RING.append((t0, d, gen))             # atomic, takes no lock
    _TOTALS[gen] += d


def install(owner) -> None:
    """Watch the collector on behalf of ``owner`` (idempotent)."""
    global _current
    with _lock:
        _owners.add(id(owner))
        if _on_gc not in gc.callbacks:
            # a collection that began unwatched has no start to pair with
            _current = None
            gc.callbacks.append(_on_gc)


def remove(owner) -> None:
    """Drop ``owner``'s hold; the callback goes with the last owner."""
    global _current
    with _lock:
        _owners.discard(id(owner))
        if not _owners and _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)
            # nor may the start of one it left unfinished outlive it
            _current = None


def installed() -> bool:
    return _on_gc in gc.callbacks


def totals() -> tuple:
    """Seconds the watched collections took, per generation (0, 1, 2)."""
    return tuple(_TOTALS)


def register(registry) -> None:
    """Expose :func:`totals` in ``registry`` as the pulled counter
    ``host_gc_pause_seconds_total{generation}`` (process-wide: every
    registry shows the same totals)."""
    c = registry.counter(
        "host_gc_pause_seconds_total",
        "time the cyclic garbage collector held the process (all "
        "threads), while watched", ("generation",))
    for g in range(3):
        c.set_fn(lambda g=g: _TOTALS[g], (str(g),))


def pauses(t0: float | None = None, t1: float | None = None) -> list:
    """The ring's collections clipped to ``[t0, t1]`` (``time.monotonic()``
    seconds; None leaves that side open): ``[(start, seconds,
    generation)]``, oldest first."""
    lo = float("-inf") if t0 is None else t0
    hi = float("inf") if t1 is None else t1
    out = []
    for s, d, g in list(RING):
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b - a, g))
    return out


def records(tracer) -> list:
    """The ring's collections since ``tracer``'s epoch as ``host.gc`` span
    records on its clock (the shape ``Tracer.export_chrome`` takes), on a
    track of their own (``tid`` 0): a collection holds every thread."""
    t_epoch = time.monotonic() - tracer.now()
    return [{"ph": "X", "name": "host.gc", "cat": "host", "id": None,
             "parent": None, "t0": s - t_epoch, "t1": s + d - t_epoch,
             "tid": 0, "args": {"generation": g}}
            for s, d, g in pauses(t_epoch)]
