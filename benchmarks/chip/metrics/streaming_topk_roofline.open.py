"""``streaming_topk`` kernel time against its roofline, in %: the least
time its calls in the window need (``chipbench/kernel_cost.py``: the score
rows each call streams, read from the operand shapes in the event, at the
chip's peak HBM bandwidth) over the summed device time of its events.
Nothing when the kernel did not run (for instance when the fusion gate
kept the unfused chain)."""
from chipbench import kernel_cost

KERNELS = ("streaming_topk",)


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    events = run.trace.kernel_events.get("streaming_topk", [])
    busy = sum(ev.dur_ns for ev in events) * 1e-9
    if not events or busy <= 0:
        return None
    least = sum(kernel_cost.least_time_s(
        kernel_cost.streaming_topk_event(ev.name), run.peaks)[0]
        for ev in events)
    return 100.0 * least / busy
