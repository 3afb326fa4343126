"""Plain BM25 >> dense rerank: BM25 top ``k_in`` candidates, each
re-scored as ``alpha * bm25 + <doc vector, query vector>``, top ``k``.

The encoder is the configuration's random projection, regenerated here
from its stated seeds (nothing is read from the program):

* projection matrices: ``numpy.random.default_rng(seed)
  .standard_normal((vocab, dim))`` as float32, divided by sqrt(dim); the
  document side uses ``doc_proj_seed``, the query side the run's seed;
* a document's vector is the unit-normalised sum over its distinct
  non-stop terms of ``proj[t] * log(1 + tf)``;
* a query's vector is the unit-normalised weighted sum of ``qproj[t]``
  over its terms (stop words included).

``dtype`` is the arithmetic of every step, as in ``bm25.py``;
``dense_dtype``, where given, that of the dense vectors and their
contraction alone.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np
import scipy.sparse

_spec = importlib.util.spec_from_file_location(
    "chipbench_reference_bm25_base", Path(__file__).with_name("bm25.py"))
bm25 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bm25)

Collection = bm25.Collection


def projection(seed: int, vocab: int, dim: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.standard_normal((vocab, dim)).astype(np.float32) / np.sqrt(dim)


def _unit(x, dtype):
    n = np.sqrt(np.sum(x * x, axis=-1, keepdims=True)).astype(dtype)
    return (x / np.maximum(n, np.asarray(1e-6).astype(dtype))).astype(dtype)


def doc_vectors(coll, docs: np.ndarray, proj: np.ndarray, dtype):
    """Unit rows ``sum_t proj[t] * log(1 + tf_t)`` of ``docs``, as one
    sparse product: its inputs rounded to ``dtype``, products and sums in
    float64 (float32 for a lower ``dtype``, as a TPU matmul accumulates),
    the result rounded to ``dtype``."""
    lens = coll.doc_len[docs]
    local = np.repeat(np.arange(docs.size), lens)
    starts = np.repeat(coll.doc_start[docs] - np.cumsum(lens) + lens, lens)
    pos = starts + np.arange(lens.sum())
    key = local.astype(np.int64) * coll.vocab + coll.doc_terms[pos]
    uniq, tf = np.unique(key, return_counts=True)
    row, term = uniq // coll.vocab, uniq % coll.vocab
    keep = ~coll.stop[term]
    row, term, tf = row[keep], term[keep], tf[keep]
    acc = np.float64 if np.dtype(dtype) == np.float64 else np.float32
    w = np.log1p(tf.astype(dtype)).astype(acc)
    sel, col = np.unique(term, return_inverse=True)
    m = scipy.sparse.csr_matrix((w, (row, col)), shape=(docs.size, sel.size))
    emb = (m @ proj[sel].astype(dtype).astype(acc)).astype(dtype)
    return _unit(emb, dtype)


def query_vector(terms, weights, qproj: np.ndarray, dtype):
    ok = terms >= 0
    v = np.sum(qproj[terms[ok]].astype(dtype)
               * weights[ok].astype(dtype)[:, None], axis=0).astype(dtype)
    return _unit(v, dtype)


def run(coll, Q: dict, params: dict, *, seed: int, dtype=np.float64,
        dense_dtype=None) -> list:
    dense_dtype = dtype if dense_dtype is None else dense_dtype
    dim = int(params["dim"])
    proj = projection(int(params["doc_proj_seed"]), coll.vocab, dim)
    qproj = projection(seed, coll.vocab, dim)
    alpha = np.asarray(params["alpha"]).astype(dtype)
    post = coll.postings(Q["terms"][Q["terms"] >= 0])
    out = []
    for terms, weights in zip(Q["terms"], Q["weights"]):
        s = bm25.scores(coll, post, terms, weights, dtype)
        cand, _ = bm25.top_k(s, int(params["k_in"]))
        qv = query_vector(terms, weights, qproj, dense_dtype)

        def combined(d, s=s, qv=qv):
            d = np.asarray(d)
            dense = (doc_vectors(coll, d, proj, dense_dtype) @ qv
                     ).astype(dense_dtype)
            return (alpha * s[d] + dense).astype(np.float64)

        comb = combined(cand)
        order = np.lexsort((cand, -comb))[:int(params["k"])]
        out.append({"docids": cand[order], "scores": comb[order],
                    "score_of": combined})
    return out
