"""What the program stamps on its answers, for the per-layer readers.

``RequestTrace.phase_ms`` holds the phases of a request's micro-batch
from its close to the request's reply, and ``RequestTrace.work`` the
batch's delta of the engine's padded-work counters, one dict shared by
the requests of a batch.  A program without them (an older server) gives
the readers nothing to read, and they return None.
"""
from __future__ import annotations


def answered(run) -> list:
    """The ``RequestTrace`` of each request answered in the window."""
    out = []
    for _, _, req in run.window.sent:
        if (req is None or not req.done.is_set() or req.error is not None
                or req.trace.timed_out or req.result is None):
            continue
        out.append(req.trace)
    return out


def host_ms(tr) -> float | None:
    """A request's service time less the phases in which the serving
    thread waited for the device; None without phases."""
    phases = getattr(tr, "phase_ms", ())
    if not phases:
        return None
    return tr.service_ms - sum(ms for name, ms in phases
                               if name.startswith("device_wait:"))


def batch_work(run) -> list:
    """The padded-work delta of each batch answered in the window, once
    per batch."""
    seen, out = set(), []
    for tr in answered(run):
        w = getattr(tr, "work", None)
        if w and id(w) not in seen:
            seen.add(id(w))
            out.append(w)
    return out


def fill(works: list, kind: str) -> float | None:
    """Live share of ``kind`` (``rows`` or ``slots``) over the batches, in
    %; None where nothing of that kind was counted."""
    live = sum(w.get(f"{kind}_live", 0) for w in works)
    total = live + sum(w.get(f"{kind}_pad", 0) for w in works)
    return 100.0 * live / total if total > 0 else None
