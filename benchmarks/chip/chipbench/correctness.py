"""The comparison that decides ``correct``.

The served (or ranked) answers of a sample of the window's queries are
held against the configuration's plain reference, computed after the
window in float64 from the benchmark's own collection.  One number per
cell, ``score_gap``: the largest, over the sample and the k positions, of

    |served score_i - reference score of served doc_i|   (right doc scores)
    |served score_i - i-th best reference score|         (no better doc left out)

divided by max(1, best reference score).  Position by position the second
term is blind to the order of tied documents, and the first catches a
document that is not what its score says.  An answer with the wrong
number of documents, a repeated or out-of-range document, or a
non-finite score reads ``inf``.

The control (``control.py``) puts the same reference, computed in
bfloat16, in the program's place and reads the same number.
"""
from __future__ import annotations

import numpy as np


def answer_gap(docs, scores, ref: dict, k: int, n_docs: int,
               divide: bool = True) -> float:
    docs = np.asarray(docs).reshape(-1)
    scores = np.asarray(scores, np.float64).reshape(-1)
    if (docs.size != k or scores.size != k or np.unique(docs).size != k
            or docs.min() < 0 or docs.max() >= n_docs
            or not np.all(np.isfinite(scores))):
        return float("inf")
    own = np.abs(scores - ref["score_of"](docs))
    pos = np.abs(scores - ref["scores"][:k])
    scale = max(1.0, abs(float(ref["scores"][0]))) if divide else 1.0
    return float(max(own.max(), pos.max()) / scale)


def score_gap(answers: list, refs: list, k: int, n_docs: int,
              divide: bool = True) -> float:
    """``divide=False`` gives the gap in score units (``abs_score_gap``,
    printed beside the compared number, not compared)."""
    if not answers:
        return float("inf")
    return max(answer_gap(a["docids"], a["scores"], r, k, n_docs, divide)
               for a, r in zip(answers, refs))


def sample(n: int, size: int, seed: int, salt: int = 3) -> np.ndarray:
    """Indices of ``size`` of ``n`` answers, drawn from the seed."""
    rng = np.random.default_rng([seed & (2 ** 64 - 1), salt])
    return np.sort(rng.choice(n, size=min(size, n), replace=False))
