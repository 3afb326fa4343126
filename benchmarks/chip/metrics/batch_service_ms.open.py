"""Median time from a request's micro-batch closing to its answer
(``RequestTrace.service_ms``): engine dispatch, the stages on the device,
the copy back and the server's row plumbing."""
import statistics


def read(run):
    xs = [r["service_ms"] for r in run.requests]
    return statistics.median(xs) if xs else None
