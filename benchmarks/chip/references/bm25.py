"""Plain BM25 top-k, straight from the collection's term stream.

Imports nothing of the program and takes nothing it made: document
lengths, document frequencies, the stop-word cut and the postings of the
query terms are all counted here from the benchmark's own collection.
Semantics, as the configuration states them:

* a term whose document frequency exceeds ``stop_df_fraction`` of the
  documents is a stop word and scores nothing;
* BM25 with k1 = 1.2 and b = 0.75 (Terrier's defaults),
  idf = ln(1 + (N - df + 0.5) / (df + 0.5)), a query term's score scaled
  by its weight, a document's score the sum over the query's terms;
* the top k by score, ties to the lowest document id.

``dtype`` is the arithmetic of every step: float64 for the reference, a
lower one (``ml_dtypes.bfloat16``) for the control.  Copied in spirit from
the bring-up smoke's ``numpy_bm25``, which read the program's postings.
"""
from __future__ import annotations

import numpy as np

K1, B = 1.2, 0.75


class Collection:
    def __init__(self, doc_terms: np.ndarray, doc_start: np.ndarray,
                 vocab: int, stop_df_fraction: float):
        self.doc_terms = doc_terms
        self.doc_start = doc_start
        self.vocab = vocab
        self.n_docs = len(doc_start) - 1
        self.doc_len = np.diff(doc_start)
        self.avg_doclen = float(self.doc_len.mean())
        self.doc_of = np.repeat(np.arange(self.n_docs, dtype=np.int32),
                                self.doc_len)
        self.stop = self._stop_words(stop_df_fraction)

    def _stop_words(self, fraction: float) -> np.ndarray:
        """Terms whose document frequency exceeds the cut.  Only a term
        whose collection frequency exceeds it can, so only those are
        counted document by document."""
        cut = fraction * self.n_docs
        cf = np.bincount(self.doc_terms, minlength=self.vocab)
        cand = np.flatnonzero(cf > cut)
        stop = np.zeros(self.vocab, bool)
        if cand.size:
            slot = np.full(self.vocab, -1, np.int64)
            slot[cand] = np.arange(cand.size)
            pos = np.flatnonzero(slot[self.doc_terms] >= 0)
            seen = np.zeros((cand.size, self.n_docs), bool)
            seen[slot[self.doc_terms[pos]], self.doc_of[pos]] = True
            stop[cand[seen.sum(axis=1) > cut]] = True
        return stop

    def postings(self, terms) -> dict:
        """term -> (doc ids ascending, term frequencies) for each distinct
        non-stop term of ``terms``."""
        want = np.zeros(self.vocab, bool)
        want[np.asarray(terms)] = True
        want &= ~self.stop
        pos = np.flatnonzero(want[self.doc_terms])
        key = (self.doc_terms[pos].astype(np.int64) * self.n_docs
               + self.doc_of[pos])
        uniq, tf = np.unique(key, return_counts=True)
        t, d = uniq // self.n_docs, (uniq % self.n_docs).astype(np.int64)
        bounds = np.flatnonzero(np.diff(t)) + 1
        return {int(tt[0]): (dd, ff) for tt, dd, ff in
                zip(np.split(t, bounds), np.split(d, bounds),
                    np.split(tf, bounds)) if tt.size}


def scores(coll: Collection, post: dict, terms, weights,
           dtype=np.float64) -> np.ndarray:
    """BM25 of one query against every document."""
    c = lambda x: np.asarray(x).astype(dtype)
    s = np.zeros(coll.n_docs, dtype)
    n, avg = c(coll.n_docs), c(coll.avg_doclen)
    for t, w in zip(terms, weights):
        if t < 0 or int(t) not in post:
            continue
        docs, tf = post[int(t)]
        df = c(docs.size)
        idf = np.log1p((n - df + c(0.5)) / (df + c(0.5)))
        norm = c(K1) * (c(1 - B) + c(B) * c(coll.doc_len[docs]) / avg)
        tf = c(tf)
        s[docs] = s[docs] + c(w) * (idf * tf * c(K1 + 1.0) / (tf + norm))
    return s


def top_k(s: np.ndarray, k: int) -> tuple:
    """(doc ids, scores) of the k best, descending, ties to the lowest id."""
    k = min(k, s.size)
    part = np.argpartition(-s.astype(np.float64), k - 1)[:k]
    kth = s[part].astype(np.float64).min()
    cand = np.flatnonzero(s.astype(np.float64) >= kth)
    order = np.lexsort((cand, -s[cand].astype(np.float64)))[:k]
    return cand[order], s[cand[order]]


def run(coll: Collection, Q: dict, params: dict, *, seed: int,
        dtype=np.float64) -> list:
    """Top-k (doc ids, scores) of every query of ``Q``, and for the
    comparison a function giving any document's score."""
    post = coll.postings(Q["terms"][Q["terms"] >= 0])
    out = []
    for terms, weights in zip(Q["terms"], Q["weights"]):
        s = scores(coll, post, terms, weights, dtype)
        docs, top = top_k(s, int(params["k"]))
        out.append({"docids": docs, "scores": top.astype(np.float64),
                    "score_of": (lambda d, s=s: s[d].astype(np.float64))})
    return out
