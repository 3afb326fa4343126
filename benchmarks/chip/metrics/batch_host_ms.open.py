"""Median time the serving thread spent on the host for a request's
batch: ``service_ms`` less the ``device_wait:*`` phases of
``RequestTrace.phase_ms`` (cache lookup, stacking and padding, stage
dispatch, the copy back, cache writes and the reply)."""
import statistics

from chipbench import served


def read(run):
    xs = [x for x in map(served.host_ms, served.answered(run))
          if x is not None]
    return statistics.median(xs) if xs else None
