"""Share of the traced window in which no operation ran on the device:
1 - (union of the "XLA Ops" intervals) / window, in %, averaged over the
chips used."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * run.trace.idle_share
