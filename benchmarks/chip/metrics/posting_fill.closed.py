"""Share of the posting slots the sparse stage gathered that held a
posting of a live query term, in %: over the window's batches, the
posting-list lengths of the live rows' terms over rows dispatched x
term slots x the longest posting list
(``retrieve_posting_slots_total`` deltas in ``RequestTrace.work``)."""
from chipbench import served


def read(run):
    return served.fill(served.batch_work(run), "slots")
