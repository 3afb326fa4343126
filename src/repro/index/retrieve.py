"""Retrieval engines over the JAX inverted index.

Three evaluation strategies — the backend capabilities the pipeline
compiler's rewrite rules target (cf. paper §4):

* ``score_exhaustive``  — term-at-a-time over all postings, dense [D] scores,
                          full sort. The unoptimised ``Retrieve() % K`` path.
* ``retrieve_pruned``   — TPU-adapted BlockMaxWAND: per-block score upper
                          bounds, top-``n_blocks`` block selection (budget is
                          a function of K), sparse aggregation, k-dependent
                          work end-to-end.  The target of the RQ1 rewrite.
* ``retrieve_fat``      — single-pass *multi-model* retrieval: one postings
                          gather scores the ranking model AND every feature
                          model (fat postings [Macdonald et al.]).  The
                          target of the RQ2 rewrite.

Plus the unoptimised counterpart of fat: ``extract_features_docvectors``
(per-feature passes over the direct index, Asadi & Lin's doc-vectors).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.common import cdiv
from repro.index.inverted import BLOCK, InvertedIndex, gather_postings
from repro.index import scoring


def _posting_scores(index, post, weights, model):
    """Per-posting weighted scores [MAXQ, L] for one weighting model."""
    dl = index.doc_len[post["doc_ids"]]
    s = scoring.WEIGHTING_MODELS[model](
        post["tfs"], dl, post["df"][:, None], post["cf"][:, None], index.stats)
    return s * weights[:, None] * post["mask"]


@partial(jax.jit, static_argnames=("model", "max_postings"))
def score_exhaustive(index: InvertedIndex, terms, weights, *,
                     model: str = "BM25", max_postings: int) -> jax.Array:
    """Dense scores [n_docs] for one query (terms [MAXQ])."""
    post = gather_postings(index, terms, max_postings)
    s = _posting_scores(index, post, weights, model)
    return jnp.zeros((index.n_docs,), jnp.float32).at[
        post["doc_ids"].reshape(-1)].add(s.reshape(-1))


@partial(jax.jit, static_argnames=("model", "max_postings", "k"))
def retrieve_topk(index: InvertedIndex, terms, weights, *, model: str,
                  k: int, max_postings: int):
    scores = score_exhaustive(index, terms, weights, model=model,
                              max_postings=max_postings)
    top_s, top_d = jax.lax.top_k(scores, k)
    return top_d.astype(jnp.int32), top_s


# ---------------------------------------------------------------------------
# block-max pruned retrieval
# ---------------------------------------------------------------------------

def block_budget(k: int, n_terms: int) -> int:
    """Block budget as a function of K — the dynamic-pruning dial that the
    RQ1 rewrite turns.  ~4x oversampling plus a floor per query term."""
    return max(4 * n_terms, 4 * cdiv(4 * k, BLOCK) * n_terms)


def _aggregate_sparse(doc_ids, scores, k):
    """Combine duplicate doc ids (sort + boundary segment-sum) then top-k."""
    n = doc_ids.shape[0]
    order = jnp.argsort(doc_ids)
    d = doc_ids[order]
    s = scores[order]
    seg = jnp.cumsum(jnp.concatenate([jnp.zeros(1, jnp.int32),
                                      (d[1:] != d[:-1]).astype(jnp.int32)]))
    agg = jax.ops.segment_sum(s, seg, num_segments=n)
    first = jnp.concatenate([jnp.ones(1, bool), d[1:] != d[:-1]])
    rep = jnp.where(first, agg[seg], -jnp.inf)
    rep = jnp.where(d >= 0, rep, -jnp.inf)     # drop padding docs
    top_s, idx = jax.lax.top_k(rep, k)
    return d[idx].astype(jnp.int32), top_s


@partial(jax.jit, static_argnames=("model", "k", "n_blocks", "max_blocks_per_term"))
def retrieve_pruned(index: InvertedIndex, terms, weights, *, model: str,
                    k: int, n_blocks: int, max_blocks_per_term: int):
    """Approximate top-k via block-max pruning (TPU-adapted BMW).

    1. per (term, block): score upper bound from (block_max_tf, block_min_dl)
    2. global top-``n_blocks`` blocks by UB        (the block skip)
    3. gather + score ONLY those blocks' postings  (k-dependent work)
    4. sparse aggregate + top-k
    """
    MAXQ = terms.shape[0]
    t = jnp.maximum(terms, 0)
    start_blk = (index.term_start[t] // BLOCK).astype(jnp.int32)
    n_blk = ((index.term_start[t + 1] - index.term_start[t]) // BLOCK).astype(jnp.int32)
    blk_idx = start_blk[:, None] + jnp.arange(max_blocks_per_term)[None, :]
    blk_valid = (jnp.arange(max_blocks_per_term)[None, :] < n_blk[:, None]) & \
        (terms >= 0)[:, None]
    blk_idx = jnp.minimum(blk_idx, index.block_max_tf.shape[0] - 1)

    ub = scoring.upper_bound(
        model, index.block_max_tf[blk_idx], index.block_min_dl[blk_idx],
        index.df[t][:, None], index.cf[t][:, None], index.stats)
    ub = jnp.where(blk_valid, ub * weights[:, None], -jnp.inf)

    flat_ub = ub.reshape(-1)
    _, sel = jax.lax.top_k(flat_ub, n_blocks)          # block selection
    sel_term = sel // max_blocks_per_term               # term providing df/cf
    sel_blk = blk_idx.reshape(-1)[sel]
    sel_valid = jnp.isfinite(flat_ub[sel])

    pos = sel_blk[:, None].astype(jnp.int64) * BLOCK + jnp.arange(BLOCK)[None, :]
    docs = index.doc_ids[pos]
    tfs = index.tfs[pos]
    mask = sel_valid[:, None] & (docs >= 0)
    dl = index.doc_len[jnp.maximum(docs, 0)]
    df = index.df[t][sel_term][:, None]
    cf = index.cf[t][sel_term][:, None]
    s = scoring.WEIGHTING_MODELS[model](tfs, dl, df, cf, index.stats)
    s = s * weights[sel_term][:, None] * mask
    flat_docs = jnp.where(mask, docs, -1).reshape(-1)
    return _aggregate_sparse(flat_docs, s.reshape(-1), k)


# ---------------------------------------------------------------------------
# fat (single-pass multi-model) retrieval — RQ2 optimised path
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("rank_model", "feature_models",
                                   "max_postings", "k"))
def retrieve_fat(index: InvertedIndex, terms, weights, *, rank_model: str,
                 feature_models: tuple[str, ...], k: int, max_postings: int):
    """One postings pass -> candidate top-k under ``rank_model`` PLUS all
    ``feature_models`` scores for the candidates.  Returns (docids [k],
    scores [k], features [k, F])."""
    post = gather_postings(index, terms, max_postings)
    dl = index.doc_len[post["doc_ids"]]
    models = (rank_model,) + tuple(feature_models)
    all_s = scoring.score_all(list(models), post["tfs"], dl,
                              post["df"][:, None], post["cf"][:, None],
                              index.stats)
    all_s = all_s * (weights[:, None, None] *
                     post["mask"][..., None].astype(jnp.float32))
    flat_docs = post["doc_ids"].reshape(-1)
    dense = jnp.zeros((index.n_docs, len(models)), jnp.float32).at[
        flat_docs].add(all_s.reshape(-1, len(models)))
    top_s, top_d = jax.lax.top_k(dense[:, 0], k)
    feats = dense[top_d, 1:]
    return top_d.astype(jnp.int32), top_s, feats


@partial(jax.jit, static_argnames=("models", "max_postings", "k"))
def retrieve_multi(index: InvertedIndex, terms, weights, model_weights, *,
                   models: tuple[str, ...], k: int, max_postings: int):
    """Weighted multi-model retrieval in ONE postings pass — the target of
    the LinearFusion rewrite (w1·Retrieve(m1) + w2·Retrieve(m2) fused)."""
    post = gather_postings(index, terms, max_postings)
    dl = index.doc_len[post["doc_ids"]]
    all_s = scoring.score_all(list(models), post["tfs"], dl,
                              post["df"][:, None], post["cf"][:, None],
                              index.stats)
    s = jnp.einsum("qpf,f->qp", all_s, model_weights)
    s = s * weights[:, None] * post["mask"]
    dense = jnp.zeros((index.n_docs,), jnp.float32).at[
        post["doc_ids"].reshape(-1)].add(s.reshape(-1))
    top_s, top_d = jax.lax.top_k(dense, k)
    return top_d.astype(jnp.int32), top_s


# ---------------------------------------------------------------------------
# kernel-fused retrieval — targets of the IR lowering pass (core/passes.py)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("model", "max_postings", "k"))
def retrieve_topk_fused(index: InvertedIndex, terms, weights, *, model: str,
                        k: int, max_postings: int):
    """``Retrieve >> … % K`` lowered through the streaming top-k kernel:
    exhaustive scoring feeds ``kernels/topk`` (block-max skipping on TPU,
    ``lax.top_k`` oracle elsewhere) at the *cutoff* depth K, so the dense
    [n_docs] score vector is never sorted to the retriever's full k."""
    from repro.kernels.topk.ops import streaming_topk
    scores = score_exhaustive(index, terms, weights, model=model,
                              max_postings=max_postings)
    vals, idxs = streaming_topk(scores, k=k)
    return idxs.astype(jnp.int32), vals


@partial(jax.jit, static_argnames=("rank_model", "feature_models",
                                   "max_postings", "k"))
def retrieve_fat_fused(index: InvertedIndex, terms, weights, *,
                       rank_model: str, feature_models: tuple[str, ...],
                       k: int, max_postings: int):
    """``Retrieve >> (Extract ** …) % K`` lowered through the fused-scoring
    kernel: one postings gather, every weighting model's math on the same
    VMEM tile (``kernels/fused_scoring``), candidates cut to K directly."""
    from repro.kernels.fused_scoring.ops import fused_scoring
    post = gather_postings(index, terms, max_postings)
    dl = index.doc_len[post["doc_ids"]]
    models = (rank_model,) + tuple(feature_models)
    MAXQ, L = post["tfs"].shape
    df = jnp.broadcast_to(post["df"][:, None], (MAXQ, L))
    cf = jnp.broadcast_to(post["cf"][:, None], (MAXQ, L))
    flat = lambda x: x.reshape(-1)
    all_s = fused_scoring(flat(post["tfs"]), flat(dl), flat(df), flat(cf),
                          models=models, stats=index.stats)
    all_s = all_s.reshape(MAXQ, L, len(models))
    all_s = all_s * (weights[:, None, None] *
                     post["mask"][..., None].astype(jnp.float32))
    dense = jnp.zeros((index.n_docs, len(models)), jnp.float32).at[
        post["doc_ids"].reshape(-1)].add(all_s.reshape(-1, len(models)))
    top_s, top_d = jax.lax.top_k(dense[:, 0], k)
    feats = dense[top_d, 1:]
    return top_d.astype(jnp.int32), top_s, feats


@partial(jax.jit, static_argnames=("model", "k_in", "k", "alpha",
                                   "max_postings"))
def retrieve_dense_rerank(index: InvertedIndex, emb, terms, weights, qvec, *,
                          model: str, k_in: int, k: int, alpha: float,
                          max_postings: int):
    """The unfused ``Retrieve >> DenseRerank % K`` chain as one per-query
    program: sparse top-k_in candidates, dense re-scoring
    (``alpha * sparse + emb @ qvec``), full sort, slice to K.  The fusion
    gate's unfused pricing candidate — and the semantics the fused form
    below must reproduce exactly."""
    docs, scores = retrieve_topk(index, terms, weights, model=model, k=k_in,
                                 max_postings=max_postings)
    from repro.kernels.dense_scoring.ref import dense_scores
    ds = jnp.where(docs >= 0,
                   alpha * scores + dense_scores(emb[jnp.maximum(docs, 0)],
                                                 qvec),
                   -jnp.inf)
    order = jnp.argsort(-ds)
    return docs[order][:k].astype(jnp.int32), ds[order][:k]


@partial(jax.jit, static_argnames=("model", "k_in", "k", "alpha",
                                   "max_postings"))
def retrieve_dense_rerank_fused(index: InvertedIndex, emb, terms, weights,
                                qvec, *, model: str, k_in: int, k: int,
                                alpha: float, max_postings: int):
    """``Retrieve >> DenseRerank % K`` lowered through the dense-scoring
    kernel: the sparse contribution rides in as the kernel's ``base`` score
    and the streaming top-k runs at the *cutoff* depth K, so the candidate
    list is never fully sorted (``kernels/dense_scoring``)."""
    from repro.index.dense import NEG
    from repro.kernels.dense_scoring.ops import streaming_dense_topk
    docs, scores = retrieve_topk(index, terms, weights, model=model, k=k_in,
                                 max_postings=max_postings)
    base = jnp.where(docs >= 0, alpha * scores, NEG)
    vals, idxs = streaming_dense_topk(emb[jnp.maximum(docs, 0)], qvec, base,
                                      k=k)
    ok = vals > NEG / 2
    out_docs = jnp.where(ok, docs[idxs], -1)
    return out_docs.astype(jnp.int32), jnp.where(ok, vals, -jnp.inf)


# ---------------------------------------------------------------------------
# doc-vectors feature extraction — the unoptimised per-feature pass
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("model", "max_fwd"))
def extract_feature_docvectors(index: InvertedIndex, terms, weights,
                               docids, *, model: str, max_fwd: int):
    """Score ``docids`` [K] under one weighting model via the direct index
    (one full pass over each candidate's doc vector per feature)."""
    d = jnp.maximum(docids, 0)
    start = index.fwd_start[d]
    length = index.fwd_start[d + 1] - start
    pos = start[:, None] + jnp.arange(max_fwd)[None, :]
    in_rng = jnp.arange(max_fwd)[None, :] < length[:, None]
    pos = jnp.minimum(pos, index.fwd_terms.shape[0] - 1)
    dterms = jnp.where(in_rng, index.fwd_terms[pos], -1)    # [K, L]
    dtfs = jnp.where(in_rng, index.fwd_tfs[pos], 0)

    # match doc terms against query terms: [K, L, MAXQ]
    eq = (dterms[:, :, None] == terms[None, None, :]) & (terms >= 0)[None, None, :]
    tf_q = jnp.einsum("klq,kl->kq", eq.astype(jnp.float32),
                      dtfs.astype(jnp.float32))             # [K, MAXQ]
    dl = index.doc_len[d][:, None]
    t = jnp.maximum(terms, 0)
    s = scoring.WEIGHTING_MODELS[model](
        tf_q, dl, index.df[t][None, :], index.cf[t][None, :], index.stats)
    s = s * weights[None, :] * (terms >= 0)[None, :]
    s = jnp.where((docids >= 0)[:, None], s, 0.0)
    return jnp.sum(s, axis=1)                               # [K]


# ---------------------------------------------------------------------------
# RM3 query expansion via the direct index
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("fb_terms", "max_fwd"))
def rm3_expand(index: InvertedIndex, terms, weights, docids, scores, *,
               fb_terms: int = 10, alpha: float = 0.5, max_fwd: int):
    """Relevance-model expansion from the top feedback docs.

    Returns (new_terms [MAXQ], new_weights [MAXQ]) where expansion terms are
    appended after the original query terms.
    """
    MAXQ = terms.shape[0]
    d = jnp.maximum(docids, 0)
    start = index.fwd_start[d]
    length = index.fwd_start[d + 1] - start
    pos = start[:, None] + jnp.arange(max_fwd)[None, :]
    in_rng = jnp.arange(max_fwd)[None, :] < length[:, None]
    pos = jnp.minimum(pos, index.fwd_terms.shape[0] - 1)
    dterms = jnp.where(in_rng, index.fwd_terms[pos], 0)
    dtfs = jnp.where(in_rng, index.fwd_tfs[pos].astype(jnp.float32), 0.0)

    p_rel = jax.nn.softmax(jnp.where(docids >= 0, scores, -jnp.inf))
    p_t_d = dtfs / jnp.maximum(index.doc_len[d][:, None].astype(jnp.float32), 1.0)
    w_contrib = (p_rel[:, None] * p_t_d).reshape(-1)
    rm = jnp.zeros((index.vocab,), jnp.float32).at[dterms.reshape(-1)].add(w_contrib)
    # don't re-select original terms
    rm = rm.at[jnp.maximum(terms, 0)].set(
        jnp.where(terms >= 0, 0.0, rm[jnp.maximum(terms, 0)]))
    exp_w, exp_t = jax.lax.top_k(rm, fb_terms)
    exp_w = exp_w / jnp.maximum(exp_w.sum(), 1e-9)

    n_orig = jnp.sum(terms >= 0)
    slots = jnp.arange(MAXQ)
    exp_slot = slots[None, :] == (n_orig + jnp.arange(fb_terms))[:, None]
    new_terms = jnp.where(terms >= 0, terms,
                          (exp_slot * (exp_t[:, None] + 1)).sum(0) - 1)
    w_norm = weights / jnp.maximum(jnp.sum(weights * (terms >= 0)), 1e-9)
    new_weights = jnp.where(terms >= 0, alpha * w_norm,
                            (1 - alpha) * (exp_slot * exp_w[:, None]).sum(0))
    return new_terms.astype(jnp.int32), new_weights
