"""Share of the rows the engine dispatched that carried a request, in %:
over the window's batches, live rows over live and padding rows
(``engine_rows_total`` deltas in ``RequestTrace.work``)."""
from chipbench import served


def read(run):
    return served.fill(served.batch_work(run), "rows")
