"""Median wall time of the dense re-rank stage of a request's batch, with
the server's per-stage timing on (each stage ends in
``block_until_ready``), from ``RequestTrace.stage_ms``.

The served chain is ``[Retrieve, Cutoff(DenseRerank)]``: everything after
the sparse ``Retrieve`` stage is the re-rank and its cut to 10.  When the
fusion gate folds both into one ``FusedDenseRerank`` stage there is no
re-rank stage to time, and the reader returns nothing."""
import statistics


def read(run):
    xs = [sum(ms for _, ms in r["stage_ms"][1:]) for r in run.requests
          if len(r["stage_ms"]) > 1 and r["stage_ms"][0][0] == "Retrieve"]
    return statistics.median(xs) if xs else None
