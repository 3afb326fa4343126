"""Concrete IR transformers (paper Table 1) over the JAX backend.

Leaf stages close over *static* config only; array state (learned weights)
lives in ``self.state`` and is trained through ``fit()``.  Execution is
vmapped over the query axis and chunked by the backend (the DP dimension of
the multi-pod deployment).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import data as D
from repro.core.transformer import Transformer
from repro.index import retrieve as RT
from repro.index import scoring
from repro.index.inverted import BLOCK


# ---------------------------------------------------------------------------
# retrieval stages
# ---------------------------------------------------------------------------

class Retrieve(Transformer):
    """Exhaustive top-k retrieval under one weighting model (Q -> R)."""
    kind = "retrieve"
    reads_results = False

    def __init__(self, model: str = "BM25", k: int | None = None):
        super().__init__(model=model, k=k)

    def execute(self, ctx, Q, R):
        # clamp to corpus size like the dense stages: lax.top_k cannot take
        # more entries than exist, and parity across engines requires every
        # path to clamp identically
        k = min(self.params["k"] or ctx.backend.default_k,
                ctx.backend.index.n_docs)
        model = self.params["model"]

        def one(terms, weights):
            return RT.retrieve_topk(ctx.backend.index, terms, weights,
                                    model=model, k=k,
                                    max_postings=ctx.backend.max_postings)

        docs, scores = ctx.backend.vmap_queries(one, Q, key=self.key(),
                                                postings=True)
        return Q, {"qid": Q["qid"], "docids": docs, "scores": scores}


class PrunedRetrieve(Transformer):
    """Block-max pruned top-k — the RQ1-optimised Retrieve (created by the
    CutoffPushdown rewrite; can also be used directly)."""
    kind = "pruned_retrieve"
    reads_results = False

    def __init__(self, model: str = "BM25", k: int = 10, n_terms: int = 8):
        super().__init__(model=model, k=k, n_terms=n_terms)

    def execute(self, ctx, Q, R):
        k = min(self.params["k"], ctx.backend.index.n_docs)
        model = self.params["model"]
        budget = RT.block_budget(k, self.params["n_terms"])
        budget = min(budget, ctx.backend.total_blocks)
        mbt = ctx.backend.max_blocks_per_term

        def one(terms, weights):
            return RT.retrieve_pruned(ctx.backend.index, terms, weights,
                                      model=model, k=k, n_blocks=budget,
                                      max_blocks_per_term=mbt)

        docs, scores = ctx.backend.vmap_queries(one, Q, key=self.key())
        return Q, {"qid": Q["qid"], "docids": docs, "scores": scores}


class MultiRetrieve(Transformer):
    """Single-pass weighted multi-model retrieval (created by the
    LinearFusion rewrite — beyond-paper optimisation)."""
    kind = "multi_retrieve"
    reads_results = False

    def __init__(self, models: tuple[str, ...], weights: tuple[float, ...],
                 k: int | None = None):
        super().__init__(models=tuple(models), weights=tuple(weights), k=k)

    def execute(self, ctx, Q, R):
        k = min(self.params["k"] or ctx.backend.default_k,
                ctx.backend.index.n_docs)
        models = self.params["models"]
        mw = jnp.asarray(self.params["weights"], jnp.float32)

        def one(terms, weights):
            return RT.retrieve_multi(ctx.backend.index, terms, weights, mw,
                                     models=models, k=k,
                                     max_postings=ctx.backend.max_postings)

        docs, scores = ctx.backend.vmap_queries(one, Q, key=self.key(),
                                                postings=True)
        return Q, {"qid": Q["qid"], "docids": docs, "scores": scores}


class FatRetrieve(Transformer):
    """Single-pass retrieval + multi-model feature extraction (fat postings —
    the RQ2-optimised form of Retrieve >> (Extract ** ... ** Extract))."""
    kind = "fat_retrieve"
    reads_results = False

    def __init__(self, model: str = "BM25",
                 features: tuple[str, ...] = (), k: int | None = None):
        super().__init__(model=model, features=tuple(features), k=k)

    def execute(self, ctx, Q, R):
        k = min(self.params["k"] or ctx.backend.default_k,
                ctx.backend.index.n_docs)

        def one(terms, weights):
            return RT.retrieve_fat(
                ctx.backend.index, terms, weights,
                rank_model=self.params["model"],
                feature_models=self.params["features"], k=k,
                max_postings=ctx.backend.max_postings)

        docs, scores, feats = ctx.backend.vmap_queries(
            one, Q, key=self.key(), postings=True)
        return Q, {"qid": Q["qid"], "docids": docs, "scores": scores,
                   "features": feats}


class FusedTopKRetrieve(Transformer):
    """``Retrieve >> … % K`` lowered to the streaming top-k kernel path
    (``kernels/topk``), created by the cost-gated IR lowering pass
    (core/passes.py).  Exact — same scores as Retrieve, the top-k is just
    taken at the cutoff depth instead of sort-at-full-k-then-slice."""
    kind = "fused_topk_retrieve"
    reads_results = False

    def __init__(self, model: str = "BM25", k: int = 10):
        super().__init__(model=model, k=int(k))

    def execute(self, ctx, Q, R):
        k = min(self.params["k"], ctx.backend.index.n_docs)
        model = self.params["model"]

        def one(terms, weights):
            return RT.retrieve_topk_fused(ctx.backend.index, terms, weights,
                                          model=model, k=k,
                                          max_postings=ctx.backend.max_postings)

        docs, scores = ctx.backend.vmap_queries(one, Q, key=self.key(),
                                                postings=True)
        return Q, {"qid": Q["qid"], "docids": docs, "scores": scores}


class FusedFatRetrieve(Transformer):
    """``Retrieve >> (Extract ** …) % K`` lowered to the fused-scoring
    kernel path (``kernels/fused_scoring``) at the cutoff depth — the
    cost-gated kernel form of FatRetrieve % K."""
    kind = "fused_fat_retrieve"
    reads_results = False

    def __init__(self, model: str = "BM25",
                 features: tuple[str, ...] = (), k: int = 10):
        super().__init__(model=model, features=tuple(features), k=int(k))

    def execute(self, ctx, Q, R):
        k = min(self.params["k"], ctx.backend.index.n_docs)

        def one(terms, weights):
            return RT.retrieve_fat_fused(
                ctx.backend.index, terms, weights,
                rank_model=self.params["model"],
                feature_models=self.params["features"], k=k,
                max_postings=ctx.backend.max_postings)

        docs, scores, feats = ctx.backend.vmap_queries(
            one, Q, key=self.key(), postings=True)
        return Q, {"qid": Q["qid"], "docids": docs, "scores": scores,
                   "features": feats}


class DenseRetrieve(Transformer):
    """ANN-style dense candidate generation over the IVF dense index
    (Q -> R): embed the query, probe the ``nprobe`` closest coarse lists,
    score only those lists' documents.  ``nprobe=0`` scores every document
    (exact brute force) — the mode dense equivalence tests pin against.
    ``pq=True`` scores candidates against the compressed IVF-PQ store
    (ADC table lookups + exact float re-scoring of the final-K shortlist)
    instead of the float list store."""
    kind = "dense_retrieve"
    reads_results = False

    def __init__(self, k: int | None = None, nprobe: int = 8,
                 pq: bool = False):
        super().__init__(k=k, nprobe=int(nprobe), pq=bool(pq))

    def execute(self, ctx, Q, R):
        from repro.index import dense as DN
        be = ctx.backend
        k = min(self.params["k"] or be.default_k, be.index.n_docs)
        nprobe = self.params["nprobe"]
        qvecs = be.embed_queries(Q)
        if nprobe and self.params["pq"]:
            pq = be.ivfpq
            npb = min(nprobe, pq.n_lists)
            refine = be.pq_refine
            one = lambda qv: DN.ivfpq_retrieve_topk(pq, qv, k=k, nprobe=npb,
                                                    refine=refine)
        elif nprobe:
            ivf = be.ivf
            npb = min(nprobe, ivf.n_lists)
            one = lambda qv: DN.ivf_retrieve_topk(ivf, qv, k=k, nprobe=npb)
        else:
            dense = be.dense
            one = lambda qv: DN.dense_retrieve_exact(dense, qv, k=k)
        docs, scores = be.vmap_queries(one, None, qvecs, key=self.key())
        return Q, {"qid": Q["qid"], "docids": docs, "scores": scores}


class FusedDenseRetrieve(Transformer):
    """``DenseRetrieve % K`` lowered to the blocked-matmul + streaming-top-k
    kernel path (``kernels/dense_scoring``, or ``kernels/pq_scoring`` when
    ``pq=True``) at the cutoff depth, created by the cost-gated IR lowering
    pass (core/passes.py).  ``pq_block`` pins the PQ kernel's candidate
    block size (autotuned; ``None`` = package default); ``pq_shortlist``
    pins the ADC shortlist depth (the gate sets it to the *unfused*
    chain's depth so fusion is an exact rewrite; ``None`` = refine*k)."""
    kind = "fused_dense_retrieve"
    reads_results = False

    def __init__(self, k: int = 10, nprobe: int = 8, pq: bool = False,
                 pq_block: int | None = None,
                 pq_shortlist: int | None = None):
        super().__init__(
            k=int(k), nprobe=int(nprobe), pq=bool(pq),
            pq_block=None if pq_block is None else int(pq_block),
            pq_shortlist=None if pq_shortlist is None else int(pq_shortlist))

    def execute(self, ctx, Q, R):
        from repro.index import dense as DN
        be = ctx.backend
        k = min(self.params["k"], be.index.n_docs)
        nprobe = self.params["nprobe"]
        qvecs = be.embed_queries(Q)
        if nprobe and self.params["pq"]:
            pq = be.ivfpq
            npb = min(nprobe, pq.n_lists)
            refine = be.pq_refine
            block = self.params["pq_block"]
            shortlist = self.params["pq_shortlist"]
            one = lambda qv: DN.ivfpq_retrieve_topk_fused(
                pq, qv, k=k, nprobe=npb, refine=refine, block=block,
                shortlist=shortlist)
        elif nprobe:
            ivf = be.ivf
            npb = min(nprobe, ivf.n_lists)
            one = lambda qv: DN.ivf_retrieve_topk_fused(ivf, qv, k=k,
                                                        nprobe=npb)
        else:
            dense = be.dense
            one = lambda qv: DN.dense_retrieve_exact_fused(dense, qv, k=k)
        docs, scores = be.vmap_queries(one, None, qvecs, key=self.key())
        return Q, {"qid": Q["qid"], "docids": docs, "scores": scores}


class FusedDenseRerank(Transformer):
    """``Retrieve >> DenseRerank % K`` lowered to one fused per-query
    program: sparse candidates at depth ``k_in``, dense re-scoring on the
    kernel with the sparse score as the additive base, streaming top-k at
    the cutoff depth ``k`` — the cost-gated kernel form of the dense second
    stage (core/passes.py)."""
    kind = "fused_dense_rerank"
    reads_results = False

    def __init__(self, model: str = "BM25", k_in: int = 1000, k: int = 10,
                 alpha: float = 0.0):
        super().__init__(model=model, k_in=int(k_in), k=int(k),
                         alpha=float(alpha))

    def execute(self, ctx, Q, R):
        be = ctx.backend
        p = self.params
        k_in = min(p["k_in"], be.index.n_docs)
        k = min(p["k"], be.index.n_docs)
        qvecs = be.embed_queries(Q)
        emb = be.dense.emb

        def one(terms, weights, qv):
            return RT.retrieve_dense_rerank_fused(
                be.index, emb, terms, weights, qv, model=p["model"],
                k_in=k_in, k=k, alpha=p["alpha"],
                max_postings=be.max_postings)

        docs, scores = be.vmap_queries(one, Q, qvecs, key=self.key(),
                                       postings=True)
        return Q, {"qid": Q["qid"], "docids": docs, "scores": scores}


# ---------------------------------------------------------------------------
# query rewriting / expansion
# ---------------------------------------------------------------------------

class SDMRewrite(Transformer):
    """Sequential-dependence-style rewrite (Q -> Q).

    Positions are not stored in the index, so the proximity operators (#1,
    #uw8) are adapted as weight redistribution over the original terms
    (unigram 0.85 emphasis) plus duplicated high-weight lead terms — a
    rank-affecting, semantics-documented analogue (DESIGN.md §2).
    """
    kind = "sdm_rewrite"
    out_kind = "Q"
    reads_results = False

    def __init__(self, unigram: float = 0.85):
        super().__init__(unigram=unigram)

    def execute(self, ctx, Q, R):
        w = Q["weights"]
        u = self.params["unigram"]
        n = jnp.maximum(jnp.sum(Q["terms"] >= 0, 1, keepdims=True), 1)
        lead = (jnp.arange(w.shape[1])[None, :] < jnp.maximum(n // 2, 1))
        w2 = w * (u + (1 - u) * 2 * lead)
        return {**Q, "weights": w2}, R


class StemRewrite(Transformer):
    """Context-sensitive-stemming analogue: adds a same-frequency-band
    variant term (synthetic stem class neighbour) at reduced weight."""
    kind = "stem_rewrite"
    out_kind = "Q"
    reads_results = False

    def __init__(self, weight: float = 0.4):
        super().__init__(weight=weight)

    def execute(self, ctx, Q, R):
        t, w = Q["terms"], Q["weights"]
        n = jnp.sum(t >= 0, 1, keepdims=True)
        L = t.shape[1]
        variant = jnp.where(t >= 0, t ^ 1, -1)          # stem-class sibling
        idx = jnp.arange(L)[None, :]
        shifted = idx - n
        take = (shifted >= 0) & (shifted < n)
        sh = jnp.clip(shifted, 0, L - 1)
        t2 = jnp.where(t >= 0, t,
                       jnp.where(take, jnp.take_along_axis(variant, sh, 1), -1))
        w2 = jnp.where(t >= 0, w,
                       jnp.where(take,
                                 jnp.take_along_axis(w, sh, 1) * self.params["weight"],
                                 0.0))
        return {**Q, "terms": t2, "weights": w2}, R


class RM3Expand(Transformer):
    """Pseudo-relevance-feedback expansion (Q × R -> Q'), paper eq. (5)."""
    kind = "rm3"
    out_kind = "Q"          # R passes through untouched
    reads_results = True    # ... but fb_docs are read from it

    def __init__(self, fb_terms: int = 10, fb_docs: int = 10, alpha: float = 0.5):
        super().__init__(fb_terms=fb_terms, fb_docs=fb_docs, alpha=alpha)

    def execute(self, ctx, Q, R):
        assert R is not None, "RM3 needs retrieved results (use after Retrieve)"
        fb_docs = self.params["fb_docs"]

        def one(terms, weights, docids, scores):
            return RT.rm3_expand(ctx.backend.index, terms, weights,
                                 docids[:fb_docs], scores[:fb_docs],
                                 fb_terms=self.params["fb_terms"],
                                 alpha=self.params["alpha"],
                                 max_fwd=ctx.backend.index.max_fwd_len)

        t2, w2 = ctx.backend.vmap_queries(one, Q, R["docids"], R["scores"],
                                          key=self.key())
        return {**Q, "terms": t2, "weights": w2}, R


# ---------------------------------------------------------------------------
# feature extraction / re-ranking
# ---------------------------------------------------------------------------

class Extract(Transformer):
    """Per-feature doc-vectors pass (Q × R -> R+feature) — the unoptimised
    feature extractor the RQ2 rewrite replaces."""
    kind = "extract"

    def __init__(self, model: str):
        super().__init__(model=model)

    def execute(self, ctx, Q, R):
        def one(terms, weights, docids):
            return RT.extract_feature_docvectors(
                ctx.backend.index, terms, weights, docids,
                model=self.params["model"], max_fwd=ctx.backend.index.max_fwd_len)

        f = ctx.backend.vmap_queries(one, Q, R["docids"],      # [NQ, K]
                                     key=self.key())
        feats = R.get("features")
        f = f[..., None]
        feats = f if feats is None else jnp.concatenate([feats, f], -1)
        return Q, {**R, "features": feats}


def _sort_by_scores(R, new_scores):
    order = jnp.argsort(-new_scores, axis=1)
    out = {**R, "docids": jnp.take_along_axis(R["docids"], order, 1),
           "scores": jnp.take_along_axis(new_scores, order, 1)}
    if "features" in R:
        out["features"] = jnp.take_along_axis(R["features"], order[..., None], 1)
    return out


class LTRRerank(Transformer):
    """Learning-to-rank stage over feature columns (LambdaMART slot).

    A pairwise-logistic MLP trained with the framework optimizer — the
    xgBoost stage of Listing 1 realised JAX-natively.
    """
    kind = "ltr"
    stateful = True

    def __init__(self, n_features: int, hidden: int = 32, lr: float = 0.05,
                 epochs: int = 30, seed: int = 0):
        super().__init__(n_features=n_features, hidden=hidden, lr=lr,
                         epochs=epochs, seed=seed)
        k1, k2 = jax.random.split(jax.random.key(seed))
        F, H = n_features, hidden
        self.state = {
            "w1": jax.random.normal(k1, (F, H), jnp.float32) / np.sqrt(F),
            "b1": jnp.zeros((H,), jnp.float32),
            "w2": jax.random.normal(k2, (H, 1), jnp.float32) / np.sqrt(H),
        }

    def _score(self, state, feats):
        h = jnp.tanh(feats @ state["w1"] + state["b1"])
        return (h @ state["w2"])[..., 0]

    def execute(self, ctx, Q, R):
        assert "features" in R, "LTRRerank needs feature columns (use ** / Extract)"
        s = self._score(self.state, R["features"])
        s = jnp.where(R["docids"] >= 0, s, -jnp.inf)
        return Q, _sort_by_scores(R, s)

    def _fit_local(self, ctx, Q, R, qrels, Q_valid, R_valid, qrels_valid):
        feats = R["features"]
        labels = ctx.backend.label_results(Q, R, qrels)      # [NQ, K] float
        valid = (R["docids"] >= 0)

        def loss_fn(state):
            s = self._score(state, feats)
            # pairwise logistic over intra-query pairs
            ds = s[:, :, None] - s[:, None, :]
            dl = labels[:, :, None] - labels[:, None, :]
            pair = (dl > 0) & valid[:, :, None] & valid[:, None, :]
            losses = jnp.logaddexp(0.0, -ds) * pair
            return jnp.sum(losses) / jnp.maximum(jnp.sum(pair), 1.0)

        lr = self.params["lr"]
        grad_fn = jax.jit(jax.value_and_grad(loss_fn))
        state = self.state
        for _ in range(self.params["epochs"]):
            _, g = grad_fn(state)
            state = jax.tree.map(lambda p, gg: p - lr * gg, state, g)
        self.state = state
        self.version += 1


# ---------------------------------------------------------------------------
# generation (RAG answer stage)
# ---------------------------------------------------------------------------

def assemble_prompt_fn(index, *, vocab: int, max_prompt_len: int,
                       prompt_docs: int):
    """Per-query prompt assembler ``(terms, weights, docids) -> [P] int32``.

    Deterministic static-shape assembly: the query's terms followed by the
    forward-index terms of the top ``prompt_docs`` documents, mapped into
    the LM vocab (ids 0/1 reserved for pad/bos), compacted to the front and
    *cyclically repeated* to fill exactly ``max_prompt_len`` positions — a
    fixed prompt length means one prefill shape per decode batch size, so
    the bucket ladder keeps generation recompile-free."""
    fwd_start = index.fwd_start
    fwd_terms = index.fwd_terms
    max_fwd = int(index.max_fwd_len)
    n_terms = int(fwd_terms.shape[0])
    P = int(max_prompt_len)

    def one(terms, weights, docids):
        d = docids[:prompt_docs]
        d0 = jnp.maximum(d, 0)
        start = fwd_start[d0]
        count = fwd_start[d0 + 1] - start
        win = jnp.arange(max_fwd)
        idx = start[:, None] + win[None, :]
        dterm = fwd_terms[jnp.clip(idx, 0, n_terms - 1)]
        dvalid = (win[None, :] < count[:, None]) & (d >= 0)[:, None]
        dterm = jnp.where(dvalid, dterm, -1)
        cand = jnp.concatenate([terms.astype(jnp.int32),
                                dterm.reshape(-1).astype(jnp.int32)])
        valid = cand >= 0
        tok = (2 + jnp.maximum(cand, 0) % (vocab - 2)).astype(jnp.int32)
        pos = jnp.cumsum(valid) - 1
        slot = jnp.where(valid & (pos < P), pos, P)
        prompt = jnp.zeros((P + 1,), jnp.int32).at[slot].set(tok)[:P]
        n = jnp.clip(jnp.sum(valid), 1, P)
        fill = jnp.arange(P)
        return jnp.where(fill < n, prompt, prompt[fill % n])

    return one


def greedy_generate_fn(cfg, *, max_prompt_len: int, max_new_tokens: int):
    """Batched oracle decode ``(params, prompts [B, P]) -> tokens [B, T]``:
    one prefill over the prompt block, then a ``lax.scan`` of greedy
    decode steps against a [B, P+T] KV cache.  Same argmax/cache math as
    the serving-side ragged decode (``serve/batching.py``), so served
    output is comparable token-for-token."""
    from repro.models import transformer_lm as tlm
    P, T = int(max_prompt_len), int(max_new_tokens)

    def gen(params, prompts):
        B = prompts.shape[0]
        # the serving pools' cache length, so both attend over the same
        # (masked) key range and sum it in the same order
        cache = tlm.init_kv_cache(cfg, B, P + T + 1)
        logits, cache = tlm.prefill(cfg, params, prompts, cache)
        first = jnp.argmax(logits, -1).astype(jnp.int32)

        def body(carry, t):
            tok, cache = carry
            logits, cache = tlm.decode_step(cfg, params, tok[:, None],
                                            cache, t)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return (nxt, cache), nxt

        (_, _), rest = jax.lax.scan(
            body, (first, cache), P + jnp.arange(T - 1, dtype=jnp.int32))
        return jnp.concatenate([first[:, None], rest.T], axis=1)

    return gen


class Generate(Transformer):
    """RAG answer stage (R -> A): assemble the top-``prompt_docs`` documents
    into a fixed-length prompt and decode ``max_new_tokens`` greedy tokens
    with the named backend-registered LM (``backend.register_lm``).

    All params are scalar statics — model *name*, prompt/decode lengths —
    so the op stays content-addressable (CSE, serving digests, engine jit
    keys) and every compiled shape is fixed at compile time.  The output is
    the answer-bearing A relation: the incoming ranking plus a
    ``tokens [NQ, max_new_tokens]`` column block; A is terminal, no ranking
    stage may consume it (core/passes.py schema rules)."""
    kind = "generate"
    out_kind = "A"
    reads_results = True

    def __init__(self, model: str, max_new_tokens: int = 16,
                 max_prompt_len: int = 64, prompt_docs: int = 4):
        super().__init__(model=model, max_new_tokens=int(max_new_tokens),
                         max_prompt_len=int(max_prompt_len),
                         prompt_docs=int(prompt_docs))

    def assemble(self, ctx, Q, R):
        """Prompts [NQ, max_prompt_len] for the incoming ranking (shared by
        the offline path below and the server's decode pool)."""
        be = ctx.backend
        cfg, _ = be.lm(self.params["model"])
        one = assemble_prompt_fn(
            be.index, vocab=cfg.vocab,
            max_prompt_len=self.params["max_prompt_len"],
            prompt_docs=self.params["prompt_docs"])
        return be.vmap_queries(one, Q, R["docids"], key=self.key())

    def execute(self, ctx, Q, R):
        assert R is not None, "Generate needs retrieved results"
        be = ctx.backend
        cfg, params = be.lm(self.params["model"])
        prompts = self.assemble(ctx, Q, R)
        gen = greedy_generate_fn(
            cfg, max_prompt_len=self.params["max_prompt_len"],
            max_new_tokens=self.params["max_new_tokens"])
        if be.engine is not None:
            from repro.core.engine import StageProgram
            prog = StageProgram(key=(be.uid, self.key(), "generate"), fn=gen)
            tokens = be.engine.run_pinned(prog, params, prompts)
        else:
            tokens = gen(params, prompts)
        return Q, {"qid": Q["qid"], "docids": R["docids"],
                   "scores": R["scores"], "tokens": tokens}


class DenseRerank(Transformer):
    """Dense (embedding) re-scoring of the candidate set — the neural
    re-ranker slot (CEDR/BERT in Listing 1), backed by the dense index."""
    kind = "dense_rerank"

    def __init__(self, alpha: float = 0.0):
        super().__init__(alpha=alpha)

    def execute(self, ctx, Q, R):
        from repro.kernels.dense_scoring.ref import dense_scores
        qvecs = ctx.backend.embed_queries(Q)                  # [NQ, dim]
        emb = ctx.backend.dense.emb

        def one(qv, docids, scores):
            d = dense_scores(emb[jnp.maximum(docids, 0)], qv)
            return jnp.where(docids >= 0,
                             self.params["alpha"] * scores + d, -jnp.inf)

        s = ctx.backend.vmap_queries(one, None, qvecs, R["docids"],
                                     R["scores"], key=self.key())
        return Q, _sort_by_scores(R, s)
