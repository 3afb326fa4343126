"""Dense (embedding) index: brute-force chunked-matmul scoring + top-k,
the IVF-flat ANN layout, and the IVF-PQ compressed layout for
memory-scale dense candidate generation.

Used by neural re-rank stages and dense-retrieval transformers.  Document
embeddings come either from a trained encoder or, for infrastructure tests,
from deterministic random-projection of term-count vectors (fast, content-
correlated, no training required).

The IVF-flat index (:class:`IVFDenseIndex`) groups documents by a coarse
quantiser (spherical k-means over the doc embeddings); a query probes its
``nprobe`` closest lists and scores only those lists' embeddings — the
k-dependent-work analogue of block-max pruning for the dense stage.  Search
comes in two strategies, mirroring ``index/retrieve.py``:

* ``*_topk``        — gather candidates, score with one matmul, oracle
                      ``lax.top_k``.  The unfused interpreter path.
* ``*_topk_fused``  — same candidates through the blocked matmul +
                      streaming top-k Pallas kernel
                      (``kernels/dense_scoring``) at the *cutoff* depth.
                      The target of the cost-gated IR lowering.

Both score candidates with the same expression (``emb @ qvec + base``), so
the fusion gate's HLO proxies tie exactly when nothing is saved.

The IVF-PQ index (:class:`IVFPQIndex`) replaces the float list store with
per-subspace product-quantised uint8 codes behind the same CSR
``list_start`` layout (``dim * 4 / m`` compression of the scoring store).
Search is two-level: candidates are scored with an asymmetric-distance
(ADC) table built once per query, the top ``refine * k`` shortlist is
re-scored with exact float dot products against the (shared, not
duplicated) flat embedding store, and the final top-k is taken from the
exact scores.  The ADC stage again has a ref and a fused kernel strategy
(``kernels/pq_scoring``), both bit-identical under ties.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.index.inverted import InvertedIndex
from repro.kernels.dense_scoring.ref import dense_scores

#: mask score for padded / invalid candidate rows — same constant the
#: streaming kernels use, so fused and unfused paths rank identically
NEG = -3.0e38


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DenseIndex:
    emb: jax.Array       # [D, dim] unit-normalised
    dim: int

    def tree_flatten(self):
        return (self.emb,), (self.dim,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


def build_dense_index(index: InvertedIndex, dim: int = 64, seed: int = 0,
                      chunk: int = 1 << 21) -> DenseIndex:
    """Random-projection doc embeddings from the forward file: each doc's
    ``sum_t proj[t] * log1p(tf_t)``, unit-normalised.  Built on the device
    where the forward file lives, in ranges of ``chunk`` forward entries
    (``proj[fwd_terms]`` whole would be an [nnz, dim] buffer, tens of GB at
    Robust scale): each range is one scatter-add into the doc rows."""
    rng = np.random.default_rng(seed)
    proj = jnp.asarray(rng.standard_normal((index.vocab, dim))
                       .astype(np.float32) / np.sqrt(dim))
    fwd_start = jnp.asarray(index.fwd_start)
    fwd_terms = jnp.asarray(index.fwd_terms)
    fwd_tfs = jnp.asarray(index.fwd_tfs)
    n = int(fwd_terms.shape[0])
    size = max(1, min(chunk, n))
    emb = jnp.zeros((index.n_docs, dim), jnp.float32)
    for s in range(0, n, size):
        pad = (0, size - min(size, n - s))
        emb = _embed_range(emb, proj, fwd_start,
                           jnp.pad(fwd_terms[s:s + size], pad),
                           jnp.pad(fwd_tfs[s:s + size], pad), s)
    return DenseIndex(_unit_rows(emb), dim)


@partial(jax.jit, donate_argnums=(0,))
def _embed_range(emb, proj, fwd_start, terms, tfs, start):
    """Add one range of forward entries (zero-padded past the file's end)
    into their documents' rows."""
    pos = start + jnp.arange(terms.shape[0])
    doc = jnp.searchsorted(fwd_start, pos, side="right") - 1
    doc = jnp.where(pos < fwd_start[-1], doc, emb.shape[0])   # padding: drop
    contrib = proj[terms] * jnp.log1p(tfs.astype(jnp.float32))[:, None]
    return emb.at[doc].add(contrib, mode="drop")


@jax.jit
def _unit_rows(emb):
    return emb / jnp.maximum(jnp.linalg.norm(emb, axis=1, keepdims=True), 1e-6)


def embed_query(dense: DenseIndex, index: InvertedIndex, terms, weights,
                proj_seed: int = 0):
    """Project a sparse query into the dense space (same projection)."""
    rng = np.random.default_rng(proj_seed)
    proj = jnp.asarray(rng.standard_normal((index.vocab, dense.dim))
                       .astype(np.float32) / np.sqrt(dense.dim))
    t = jnp.maximum(terms, 0)
    vec = jnp.sum(proj[t] * (weights * (terms >= 0))[:, None], axis=0)
    return vec / jnp.maximum(jnp.linalg.norm(vec), 1e-6)


@partial(jax.jit, static_argnames=("k",))
def dense_topk(dense: DenseIndex, qvec: jax.Array, *, k: int):
    scores = dense_scores(dense.emb, qvec)
    top_s, top_d = jax.lax.top_k(scores, k)
    return top_d.astype(jnp.int32), top_s


@jax.jit
def dense_score(dense: DenseIndex, qvec: jax.Array, docids: jax.Array):
    return jnp.where(docids >= 0,
                     dense_scores(dense.emb[jnp.maximum(docids, 0)], qvec), 0.0)


# ---------------------------------------------------------------------------
# IVF-flat ANN index (coarse k-means quantiser + list-ordered flat store)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class IVFDenseIndex:
    """IVF-flat layout over a :class:`DenseIndex`.

    ``emb`` holds the document embeddings *reordered by list* so a probed
    list is one contiguous gather; ``doc_ids[i]`` maps row ``i`` of the
    reordered store back to the original document id.  ``list_start`` is the
    CSR offset array (``[n_lists + 1]``); ``max_list_len`` bounds every
    list, giving probes a static gather shape.

    ``emb`` may be ``None`` (``build_ivf_index(..., keep_flat=False)``):
    the index then carries only the coarse-quantiser skeleton — enough to
    back an :class:`IVFPQIndex`, whose exact final-K re-scoring is served
    by the flat :class:`DenseIndex` store — without duplicating the full
    float embedding array in list order.
    """
    centroids: jax.Array            # [n_lists, dim] unit-normalised
    emb: jax.Array | None           # [D, dim] embeddings in list order
    doc_ids: jax.Array              # [D] row -> original doc id
    list_start: jax.Array           # [n_lists + 1] CSR offsets
    dim: int
    n_lists: int
    max_list_len: int

    def tree_flatten(self):
        return ((self.centroids, self.emb, self.doc_ids, self.list_start),
                (self.dim, self.n_lists, self.max_list_len))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def default_n_lists(n_docs: int) -> int:
    """sqrt(D) coarse lists (the usual IVF operating point), capped so tiny
    corpora still get multi-document lists."""
    return int(max(1, min(4096, round(n_docs ** 0.5))))


def _coarse_quantise(emb: np.ndarray, n_lists: int, iters: int, seed: int,
                     chunk: int):
    """Spherical k-means skeleton shared by the IVF-flat and IVF-PQ builds:
    centroids, the stable list-order permutation, and the CSR offsets."""
    D = emb.shape[0]
    rng = np.random.default_rng(seed)
    cent = emb[rng.choice(D, size=n_lists, replace=False)].copy()
    assign = np.zeros(D, np.int64)
    for it in range(max(1, iters)):
        for s in range(0, D, chunk):
            e = min(s + chunk, D)
            assign[s:e] = np.argmax(emb[s:e] @ cent.T, axis=1)
        # per-dim bincount scatter: np.add.at is an unbuffered per-element
        # loop and would dominate the build at Robust scale
        sums = np.stack([np.bincount(assign, weights=emb[:, d],
                                     minlength=n_lists)
                         for d in range(emb.shape[1])], axis=1)
        sums = sums.astype(np.float32)
        norms = np.linalg.norm(sums, axis=1, keepdims=True)
        # an emptied list keeps its previous centroid (stays probeable)
        cent = np.where(norms > 1e-9, sums / np.maximum(norms, 1e-9), cent)
    for s in range(0, D, chunk):
        e = min(s + chunk, D)
        assign[s:e] = np.argmax(emb[s:e] @ cent.T, axis=1)
    order = np.argsort(assign, kind="stable").astype(np.int32)
    counts = np.bincount(assign, minlength=n_lists)
    list_start = np.zeros(n_lists + 1, np.int32)
    list_start[1:] = np.cumsum(counts, dtype=np.int64)
    return cent.astype(np.float32), order, list_start, counts


def build_ivf_index(dense: DenseIndex, *, n_lists: int | None = None,
                    iters: int = 6, seed: int = 0, chunk: int = 1 << 16,
                    keep_flat: bool = True) -> IVFDenseIndex:
    """Spherical k-means over the doc embeddings -> IVF-flat index.

    Pure function of (embeddings, config): rebuilding from the same dense
    index and params yields identical arrays, which is what lets the plan
    cache digest the IVF by its config instead of its contents.  Host-side
    numpy with the [D, n_lists] assignment matmul chunked over docs to
    bound memory at Robust scale.

    ``keep_flat=False`` skips materialising the list-ordered float copy of
    the embeddings (``emb=None``) — the skeleton for a PQ-only deployment
    where flat-IVF search is never run and the exact final-K pass is served
    by PQ re-scoring against the original flat store.
    """
    emb = np.asarray(dense.emb)
    D = emb.shape[0]
    n_lists = default_n_lists(D) if n_lists is None else int(n_lists)
    n_lists = max(1, min(n_lists, D))
    cent, order, list_start, counts = _coarse_quantise(
        emb, n_lists, iters, seed, chunk)
    return IVFDenseIndex(
        centroids=jnp.asarray(cent),
        emb=jnp.asarray(emb[order]) if keep_flat else None,
        doc_ids=jnp.asarray(order),
        list_start=jnp.asarray(list_start),
        dim=dense.dim, n_lists=int(n_lists),
        max_list_len=int(counts.max()))


def _ivf_probe(index, qvec, *, nprobe: int):
    """Fixed-shape probe shared by the flat and PQ layouts: each candidate
    row's position into the list-ordered store [nprobe * L] and a
    NEG-masked base score [nprobe * L]."""
    c_scores = index.centroids @ qvec
    _, lists = jax.lax.top_k(c_scores, nprobe)
    L = index.max_list_len
    start = index.list_start[lists]
    length = index.list_start[lists + 1] - start
    slot = jnp.arange(L, dtype=jnp.int32)
    valid = slot[None, :] < length[:, None]
    pos = jnp.minimum(start[:, None] + slot[None, :],
                      index.doc_ids.shape[0] - 1).reshape(-1)
    base = jnp.where(valid.reshape(-1), 0.0, NEG)
    return pos, base


def _ivf_candidates(ivf: IVFDenseIndex, qvec, *, nprobe: int):
    """Fixed-shape candidate block for one query: the ``nprobe`` best lists'
    embeddings [nprobe * L, dim], a NEG-masked base score [nprobe * L], and
    each row's position into the list-ordered store."""
    if ivf.emb is None:
        raise ValueError(
            "IVF-flat search needs the list-ordered float store; this index "
            "was built with keep_flat=False (PQ-only skeleton)")
    pos, base = _ivf_probe(ivf, qvec, nprobe=nprobe)
    return ivf.emb[pos], base, pos


def _pad_candidates(emb_c, base, pos, k: int):
    """Guarantee at least ``k`` candidate rows (tiny nprobe x short lists):
    padded rows score NEG and surface as docid -1 / -inf."""
    n = base.shape[0]
    if n >= k:
        return emb_c, base, pos
    pad = k - n
    return (jnp.pad(emb_c, ((0, pad), (0, 0))),
            jnp.pad(base, (0, pad), constant_values=NEG),
            jnp.pad(pos, (0, pad)))


def _finish_search(ivf: IVFDenseIndex, pos, vals, idxs):
    ok = vals > NEG / 2
    docs = jnp.where(ok, ivf.doc_ids[pos[idxs]], -1)
    return docs.astype(jnp.int32), jnp.where(ok, vals, -jnp.inf)


@partial(jax.jit, static_argnames=("k", "nprobe"))
def ivf_retrieve_topk(ivf: IVFDenseIndex, qvec, *, k: int, nprobe: int):
    """IVF probe + matmul scoring + oracle top-k (the unfused path)."""
    from repro.kernels.dense_scoring.ref import dense_topk_ref
    emb_c, base, pos = _ivf_candidates(ivf, qvec, nprobe=nprobe)
    emb_c, base, pos = _pad_candidates(emb_c, base, pos, k)
    vals, idxs = dense_topk_ref(emb_c, qvec, base, k=k)
    return _finish_search(ivf, pos, vals, idxs)


@partial(jax.jit, static_argnames=("k", "nprobe"))
def ivf_retrieve_topk_fused(ivf: IVFDenseIndex, qvec, *, k: int, nprobe: int):
    """IVF probe through the blocked-matmul + streaming-top-k kernel at the
    cutoff depth (``dense_retrieve % K`` lowered by the fusion pass)."""
    from repro.kernels.dense_scoring.ops import streaming_dense_topk
    emb_c, base, pos = _ivf_candidates(ivf, qvec, nprobe=nprobe)
    emb_c, base, pos = _pad_candidates(emb_c, base, pos, k)
    vals, idxs = streaming_dense_topk(emb_c, qvec, base, k=k)
    return _finish_search(ivf, pos, vals, idxs)


@partial(jax.jit, static_argnames=("k",))
def dense_retrieve_exact(dense: DenseIndex, qvec, *, k: int):
    """Brute-force dense top-k over every document (nprobe=0 mode)."""
    from repro.kernels.dense_scoring.ref import dense_topk_ref
    vals, idxs = dense_topk_ref(dense.emb, qvec, None, k=k)
    return idxs.astype(jnp.int32), vals


@partial(jax.jit, static_argnames=("k",))
def dense_retrieve_exact_fused(dense: DenseIndex, qvec, *, k: int):
    """Brute-force dense top-k through the streaming kernel."""
    from repro.kernels.dense_scoring.ops import streaming_dense_topk
    vals, idxs = streaming_dense_topk(dense.emb, qvec, None, k=k)
    return idxs.astype(jnp.int32), vals


# ---------------------------------------------------------------------------
# Product quantisation (PQ): per-subspace codebooks + uint8 codes
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PQCodebook:
    """Per-subspace k-means codebooks: the embedding space is split into
    ``m`` contiguous subspaces of ``dsub = dim // m`` dims, each quantised
    independently against ``n_codes`` (<= 256, so codes fit uint8)
    centroids."""
    codebooks: jax.Array        # [m, n_codes, dsub] float32
    m: int
    dsub: int
    n_codes: int

    def tree_flatten(self):
        return (self.codebooks,), (self.m, self.dsub, self.n_codes)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


def build_pq_codebook(emb, *, m: int = 8, iters: int = 10, seed: int = 0,
                      sample: int = 1 << 17,
                      chunk: int = 1 << 16) -> PQCodebook:
    """Train per-subspace k-means codebooks host-side (chunked, like the
    coarse quantiser).  L2 k-means on the subvectors minimises the
    reconstruction MSE, which bounds the inner-product ADC error by
    Cauchy-Schwarz (|x.q - x_hat.q| <= ||x - x_hat|| for unit queries)."""
    emb = np.asarray(emb)
    D, dim = emb.shape
    m = int(m)
    if m < 1 or dim % m != 0:
        raise ValueError(f"m={m} must divide dim={dim}")
    dsub = dim // m
    n_codes = int(min(256, D))
    rng = np.random.default_rng(seed)
    train = emb if D <= sample else emb[rng.choice(D, size=sample,
                                                   replace=False)]
    T = train.shape[0]
    books = np.zeros((m, n_codes, dsub), np.float32)
    for s in range(m):
        X = np.ascontiguousarray(train[:, s * dsub:(s + 1) * dsub])
        cent = X[rng.choice(T, size=n_codes, replace=False)].copy()
        assign = np.zeros(T, np.int64)
        for _ in range(max(1, iters)):
            c2 = np.sum(cent * cent, axis=1)
            for lo in range(0, T, chunk):
                hi = min(lo + chunk, T)
                # argmin ||x - c||^2 == argmin (||c||^2 - 2 x.c)
                assign[lo:hi] = np.argmin(c2[None, :] - 2.0 * (X[lo:hi]
                                                               @ cent.T),
                                          axis=1)
            counts = np.bincount(assign, minlength=n_codes)
            sums = np.stack([np.bincount(assign, weights=X[:, d],
                                         minlength=n_codes)
                             for d in range(dsub)], axis=1).astype(np.float32)
            # an emptied code keeps its previous centroid
            nz = counts > 0
            cent[nz] = sums[nz] / counts[nz, None]
        books[s] = cent
    return PQCodebook(jnp.asarray(books), m, dsub, n_codes)


def pq_encode(cb: PQCodebook, emb, chunk: int = 1 << 16) -> np.ndarray:
    """Quantise embeddings to uint8 codes [D, m] (host-side, chunked)."""
    emb = np.asarray(emb)
    books = np.asarray(cb.codebooks)
    D = emb.shape[0]
    codes = np.zeros((D, cb.m), np.uint8)
    for s in range(cb.m):
        X = emb[:, s * cb.dsub:(s + 1) * cb.dsub]
        cent = books[s]
        c2 = np.sum(cent * cent, axis=1)
        for lo in range(0, D, chunk):
            hi = min(lo + chunk, D)
            codes[lo:hi, s] = np.argmin(c2[None, :] - 2.0 * (X[lo:hi]
                                                             @ cent.T),
                                        axis=1).astype(np.uint8)
    return codes


def pq_decode(cb: PQCodebook, codes: jax.Array) -> jax.Array:
    """Reconstruct approximate embeddings [N, dim] from codes [N, m]."""
    idx = codes.astype(jnp.int32)
    parts = [cb.codebooks[s][idx[:, s]] for s in range(cb.m)]
    return jnp.concatenate(parts, axis=1)


def adc_table(cb: PQCodebook, qvec: jax.Array) -> jax.Array:
    """Per-query asymmetric-distance lookup table [m, n_codes]: entry
    ``(s, c)`` is the inner product of the query's s-th subvector with
    code ``c`` of subspace ``s``; an ADC score is the sum of ``m`` table
    lookups."""
    q = qvec.reshape(cb.m, cb.dsub)
    return jnp.einsum("mcd,md->mc", cb.codebooks, q)


# ---------------------------------------------------------------------------
# IVF-PQ: uint8 codes in list order behind the same CSR layout
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class IVFPQIndex:
    """IVF-PQ layout: the float list store of :class:`IVFDenseIndex` is
    replaced by product-quantised uint8 ``codes`` (list order, same CSR
    ``list_start`` offsets).  ``emb`` is the *flat* (doc-id-ordered) float
    store shared with the source :class:`DenseIndex` — it backs the exact
    re-scoring of the final-K shortlist and is a reference, not a copy.
    ``emb=None`` drops exact re-scoring: search returns ADC-approximate
    scores (codes-only memory footprint)."""
    centroids: jax.Array            # [n_lists, dim]
    codes: jax.Array                # [D, m] uint8, list order
    doc_ids: jax.Array              # [D] row -> original doc id
    list_start: jax.Array           # [n_lists + 1] CSR offsets
    codebook: PQCodebook
    emb: jax.Array | None           # [D, dim] float32, DOC-ID order
    dim: int
    n_lists: int
    max_list_len: int

    def tree_flatten(self):
        return ((self.centroids, self.codes, self.doc_ids, self.list_start,
                 self.codebook, self.emb),
                (self.dim, self.n_lists, self.max_list_len))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def m(self) -> int:
        return self.codebook.m


def pq_store_bytes(pq: IVFPQIndex) -> int:
    """Bytes of the PQ scoring store: codes + codebooks + coarse centroids
    (the flat re-score store is shared with the DenseIndex, not owned)."""
    return int(pq.codes.size * pq.codes.dtype.itemsize
               + pq.codebook.codebooks.size * 4
               + pq.centroids.size * 4)


def build_ivfpq_index(dense: DenseIndex, *, n_lists: int | None = None,
                      iters: int = 6, seed: int = 0, m: int = 8,
                      pq_iters: int = 10, chunk: int = 1 << 16,
                      keep_flat: bool = True,
                      ivf: IVFDenseIndex | None = None) -> IVFPQIndex:
    """Build an IVF-PQ index over a dense index.

    Reuses an existing IVF skeleton when given (sharing the coarse
    quantiser with a flat index built from the same config); otherwise
    builds one with ``keep_flat=False`` so no list-ordered float copy is
    ever materialised.  ``keep_flat`` here controls the exact re-score
    store: ``True`` shares the flat ``dense.emb`` reference, ``False``
    stores no float embeddings at all (ADC-only search).
    """
    if ivf is None:
        ivf = build_ivf_index(dense, n_lists=n_lists, iters=iters, seed=seed,
                              chunk=chunk, keep_flat=False)
    cb = build_pq_codebook(dense.emb, m=m, iters=pq_iters, seed=seed,
                           chunk=chunk)
    codes = pq_encode(cb, dense.emb, chunk=chunk)
    order = np.asarray(ivf.doc_ids)
    return IVFPQIndex(
        centroids=ivf.centroids,
        codes=jnp.asarray(codes[order]),
        doc_ids=ivf.doc_ids,
        list_start=ivf.list_start,
        codebook=cb,
        emb=dense.emb if keep_flat else None,
        dim=dense.dim, n_lists=ivf.n_lists,
        max_list_len=ivf.max_list_len)


def _pq_finish(pq: IVFPQIndex, qvec, pos_r, vals_a, *, k: int):
    """Exact float re-scoring of the ADC shortlist + final top-k.  With no
    float store the ADC scores stand (already sorted desc by the shortlist
    stage, so the top-k is a prefix selection)."""
    ok = vals_a > NEG / 2
    docs = pq.doc_ids[pos_r]
    if pq.emb is not None:
        vals = jnp.where(ok, dense_scores(pq.emb[docs], qvec), NEG)
    else:
        vals = jnp.where(ok, vals_a, NEG)
    top_v, sel = jax.lax.top_k(vals, k)
    ok_k = top_v > NEG / 2
    docs_k = jnp.where(ok_k, docs[sel], -1)
    return docs_k.astype(jnp.int32), jnp.where(ok_k, top_v, -jnp.inf)


def _pq_shortlist_depth(k: int, refine: int, n_cand: int) -> int:
    return max(k, min(int(refine) * k, n_cand))


def _pq_resolve_depth(k: int, refine: int, n_cand: int,
                      shortlist: int | None) -> int:
    """An explicit ``shortlist`` overrides the refine*k default — the
    fusion gate uses it to replicate the *unfused* chain's shortlist depth
    (computed from the pre-cutoff k) so ``fused(K) == cutoff(unfused(k_in),
    K)`` holds exactly; clamped to [k, n_cand] for top-k legality."""
    if shortlist is None:
        return _pq_shortlist_depth(k, refine, n_cand)
    return max(k, min(int(shortlist), n_cand))


@partial(jax.jit, static_argnames=("k", "nprobe", "refine", "shortlist"))
def ivfpq_retrieve_topk(pq: IVFPQIndex, qvec, *, k: int, nprobe: int,
                        refine: int = 4, shortlist: int | None = None):
    """Two-level IVF-PQ search, unfused ADC stage: probe + code gather +
    table-lookup scoring + oracle top-(refine*k) shortlist, then exact
    float re-scoring of the shortlist."""
    from repro.kernels.pq_scoring.ref import pq_topk_ref
    pos, base = _ivf_probe(pq, qvec, nprobe=nprobe)
    r = _pq_resolve_depth(k, refine, pos.shape[0], shortlist)
    table = adc_table(pq.codebook, qvec)
    codes_c, base, pos = _pad_candidates(pq.codes[pos], base, pos, r)
    vals_a, idxs = pq_topk_ref(codes_c, table, base, k=r)
    return _pq_finish(pq, qvec, pos[idxs], vals_a, k=k)


@partial(jax.jit, static_argnames=("k", "nprobe", "refine", "block",
                                   "shortlist"))
def ivfpq_retrieve_topk_fused(pq: IVFPQIndex, qvec, *, k: int, nprobe: int,
                              refine: int = 4, block: int | None = None,
                              shortlist: int | None = None):
    """Two-level IVF-PQ search with the ADC stage through the fused
    code-gather + table-add + streaming-top-k kernel."""
    from repro.kernels.pq_scoring.ops import streaming_pq_topk
    pos, base = _ivf_probe(pq, qvec, nprobe=nprobe)
    r = _pq_resolve_depth(k, refine, pos.shape[0], shortlist)
    table = adc_table(pq.codebook, qvec)
    codes_c, base, pos = _pad_candidates(pq.codes[pos], base, pos, r)
    kw = {} if block is None else {"block": int(block)}
    vals_a, idxs = streaming_pq_topk(codes_c, table, base, k=r, **kw)
    return _pq_finish(pq, qvec, pos[idxs], vals_a, k=k)


# ---------------------------------------------------------------------------
# Doc-axis sharding: per-shard top-k + cross-shard merge
# ---------------------------------------------------------------------------

def shard_dense_index(dense: DenseIndex,
                      n_shards: int) -> list[tuple[DenseIndex, int]]:
    """Partition the document axis into ``n_shards`` contiguous slices.
    Returns ``(shard, offset)`` pairs; ``offset`` maps shard-local row ids
    back to global doc ids.  Contiguity is what makes the cross-shard merge
    tie-break identically to the single-index oracle (lower global id
    wins in both)."""
    D = int(dense.emb.shape[0])
    n_shards = int(n_shards)
    if n_shards < 1 or n_shards > D:
        raise ValueError(f"n_shards={n_shards} outside [1, {D}]")
    cuts = [round(i * D / n_shards) for i in range(n_shards + 1)]
    return [(DenseIndex(dense.emb[lo:hi], dense.dim), lo)
            for lo, hi in zip(cuts[:-1], cuts[1:])]


def sharded_dense_topk(shards, qvec, *, k: int):
    """Per-shard exact top-k + ``lax`` gather-merge (one query).

    Bit-identical to ``dense_retrieve_exact`` on the unsharded index:
    per-row dot products don't depend on the other rows, per-shard
    ``lax.top_k`` keeps ties in ascending local (= global, shards are
    contiguous) id order, and the merge's ``lax.top_k`` over the
    shard-ordered concatenation therefore resolves ties to the lowest
    global doc id — exactly the oracle's rule.  Traceable: wrap in
    jit/vmap at the call site.
    """
    docs_parts, vals_parts = [], []
    for shard, offset in shards:
        ks = min(k, int(shard.emb.shape[0]))
        d, v = dense_retrieve_exact(shard, qvec, k=ks)
        docs_parts.append(d + jnp.int32(offset))
        vals_parts.append(v)
    vals = jnp.concatenate(vals_parts)
    docs = jnp.concatenate(docs_parts)
    top_v, sel = jax.lax.top_k(vals, k)
    return docs[sel].astype(jnp.int32), top_v
