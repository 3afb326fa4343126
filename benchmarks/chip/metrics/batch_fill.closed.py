"""Share of the padded ladder slots that carried a request: the sum of
batch sizes over the sum of the rungs they were padded to, in %.  Each
request of a batch of ``n`` padded to rung ``b`` stands for ``b / n`` of
the batch's slots."""


def read(run):
    reqs = [r for r in run.requests if r["batch_size"] and r["bucket"]]
    if not reqs:
        return None
    slots = sum(r["bucket"] / r["batch_size"] for r in reqs)
    return 100.0 * len(reqs) / slots
