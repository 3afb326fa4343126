"""Median time an answered request of the window waited in the server's
queue before its micro-batch closed (``RequestTrace.queue_wait_ms``)."""
import statistics


def read(run):
    xs = [r["queue_wait_ms"] for r in run.requests]
    return statistics.median(xs) if xs else None
