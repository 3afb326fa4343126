"""Pipeline compiler + executor (paper §4).

``run_pipeline`` = lower to the typed IR -> run the pass-manager compiler
(canonicalise, schema inference, rewrite rules, CSE, cost-gated kernel
fusion — ``core/passes.py``) -> execute the IR with hash-consed result
caching (identical sub-pipelines run once per query set — the paper's
grid-search/common-prefix caching).  Combinator ops are interpreted here;
leaf ops delegate to their stage payload, which calls jitted index ops with
the op's content key naming the engine's jit-cache entry.

Result identity is *content-addressed*: the memo key for a node is
``(node.key(), token)`` where ``token`` digests the actual input arrays at
the pipeline source and is then derived structurally
(``token' = H(node.key(), token)``) as data flows through the DAG.  See
DESIGN.md §Planner for why ``id()``-based tokens are unsound (ids are
recycled once arrays are garbage-collected, so a long-lived shared Context
could serve stale results).
"""
from __future__ import annotations

import dataclasses
import hashlib
import itertools
import os
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.descriptor import BackendDescriptor
from repro.core.engine import ShardedQueryEngine, StageProgram
from repro.core.ir import Op, lower
from repro.core.transformer import Transformer
from repro.index.dense import DenseIndex, build_dense_index
from repro.index.inverted import BLOCK, InvertedIndex


# ---------------------------------------------------------------------------
# backend
# ---------------------------------------------------------------------------

#: monotonic backend ids for engine jit-cache scoping (id() would recycle)
_BACKEND_UID = itertools.count()


class JaxBackend:
    """Execution backend over the JAX-native index (capability descriptor +
    sharded bucketed query execution + query embedding + registered LMs
    for the generate stage).

    The optimisation surface consulted by the rewrite/fusion passes (paper
    §4: BMW cutoff on Anserini; fat postings on Terrier — our backend
    supports all, plus the Pallas kernel lowerings the fusion pass
    cost-gates) lives on ``self.descriptor`` (a
    :class:`~repro.core.descriptor.BackendDescriptor`); pass
    ``descriptor=BackendDescriptor.default(capability_set)`` to restrict
    it.  The pre-descriptor ``capabilities=`` constructor kwarg was removed
    after its deprecation cycle."""

    def __init__(self, index: InvertedIndex, dense: DenseIndex | None = None,
                 *, default_k: int = 1000, query_chunk: int = 16,
                 stop_df_fraction: float = 0.1, seed: int = 0,
                 descriptor: BackendDescriptor | None = None,
                 sharded: bool | None = None,
                 engine: ShardedQueryEngine | None = None,
                 bucket_ladder=None, ivf=None, ivf_lists: int | None = None,
                 ivf_iters: int = 6, ivf_seed: int = 0,
                 ivf_keep_flat: bool = True, ivfpq=None, pq_m: int = 8,
                 pq_iters: int = 10, pq_refine: int = 4):
        self.index = index
        self.uid = next(_BACKEND_UID)
        self.default_k = min(default_k, index.n_docs)
        self.query_chunk = query_chunk
        self.descriptor = (descriptor if descriptor is not None
                           else BackendDescriptor.default())
        #: name -> (LMConfig, params): decoder LMs the generate stage
        #: resolves by name.  Registration keeps Generate's params scalar
        #: (the model *name* is the content key, not the weight arrays), so
        #: CSE / serving digests / engine jit keys stay stable.
        self._lms: dict = {}
        # stopwords are removed at index time (build_index), so the global
        # max posting-list length is the safe static gather width
        lens = np.diff(np.asarray(index.term_start))
        #: host copy of each term's (block-padded) posting-list length:
        #: live posting slots are counted from it, never from the device
        self.posting_lens = lens
        self.max_postings = int(lens.max())
        self.max_blocks_per_term = self.max_postings // BLOCK
        self.total_blocks = int(index.doc_ids.shape[0]) // BLOCK
        self.dense = dense if dense is not None else build_dense_index(index)
        # IVF-flat config: the index itself is built lazily on first dense
        # retrieval (a pure function of dense.emb + these statics, which is
        # what lets plan.backend_digest key it by config, not contents)
        self._ivf = ivf
        #: an externally supplied IVF is digested by content (its arrays are
        #: not derivable from the backend's own config)
        self._ivf_external = ivf is not None
        self.ivf_lists = ivf_lists
        self.ivf_iters = ivf_iters
        self.ivf_seed = ivf_seed
        #: keep_flat=False drops the list-ordered float duplicate from the
        #: lazily built IVF (PQ-only deployments; flat-IVF search then
        #: raises).  Digest-relevant: it changes which paths can execute.
        self.ivf_keep_flat = ivf_keep_flat
        # IVF-PQ config: same lazy-build/config-digest story as the IVF
        self._ivfpq = ivfpq
        self._ivfpq_external = ivfpq is not None
        self.pq_m = int(pq_m)
        self.pq_iters = int(pq_iters)
        self.pq_refine = int(pq_refine)
        rng = np.random.default_rng(seed)
        self._qproj = jnp.asarray(
            rng.standard_normal((index.vocab, self.dense.dim)).astype(np.float32)
            / np.sqrt(self.dense.dim))
        # sharded engine is the default execution path; REPRO_ENGINE=sequential
        # (or sharded=False) preserves the seed's single-device chunked loop
        if sharded is None:
            sharded = os.environ.get("REPRO_ENGINE", "sharded") != "sequential"
        self.engine = (engine if engine is not None
                       else ShardedQueryEngine(ladder=bucket_ladder)
                       if sharded else None)
        # posting slots the exhaustive sparse stages gathered for live query
        # terms (live) or for nothing (pad); kept in the engine's registry,
        # so backends sharing an engine share the series
        self._m_slots = None
        if self.engine is not None:
            self._m_slots = self.engine.metrics.counter(
                "retrieve_posting_slots_total",
                "posting slots gathered by exhaustive sparse stages, live or "
                "padding", ("kind",))
            for kind in ("live", "pad"):
                self._m_slots.touch((kind,))

    @property
    def capabilities(self) -> frozenset:
        """Read-only alias for ``self.descriptor.capabilities`` (the flat
        capability set the rewrite passes probe)."""
        return self.descriptor.capabilities

    # -- generate-stage LMs --------------------------------------------------
    def register_lm(self, name: str, cfg, params=None, *, seed: int = 0):
        """Register a decoder LM under ``name`` for the generate stage.

        ``cfg`` is a :class:`repro.models.transformer_lm.LMConfig`;
        ``params`` defaults to a fresh :func:`init_params` draw from
        ``seed``.  The generate stage refers to the model by name only, so
        its IR params stay scalar and content-addressable."""
        from repro.models import transformer_lm as tlm
        if params is None:
            params = tlm.init_params(cfg, jax.random.key(seed))
        self._lms[name] = (cfg, params)
        return self

    def lm(self, name: str):
        """(cfg, params) of a registered LM; KeyError names the gap."""
        try:
            return self._lms[name]
        except KeyError:
            raise KeyError(
                f"no LM registered as {name!r} on this backend "
                f"(have {sorted(self._lms)}); call "
                f"backend.register_lm(name, cfg) first") from None

    @property
    def ivf(self):
        """IVF-flat dense index (``repro.index.dense.IVFDenseIndex``),
        built on first use from the dense embeddings + the backend's
        ``ivf_*`` config."""
        if self._ivf is None:
            from repro.index.dense import build_ivf_index
            self._ivf = build_ivf_index(self.dense, n_lists=self.ivf_lists,
                                        iters=self.ivf_iters,
                                        seed=self.ivf_seed,
                                        keep_flat=self.ivf_keep_flat)
        return self._ivf

    @property
    def ivfpq(self):
        """IVF-PQ compressed dense index
        (``repro.index.dense.IVFPQIndex``), built on first use.  Shares the
        coarse quantiser with ``self.ivf`` when that is already built (or
        external); otherwise builds a ``keep_flat=False`` skeleton so no
        list-ordered float copy is ever materialised."""
        if self._ivfpq is None:
            from repro.index.dense import build_ivfpq_index
            self._ivfpq = build_ivfpq_index(
                self.dense, n_lists=self.ivf_lists, iters=self.ivf_iters,
                seed=self.ivf_seed, m=self.pq_m, pq_iters=self.pq_iters,
                ivf=self._ivf)
        return self._ivfpq

    # -- query-axis execution ----------------------------------------------
    def vmap_queries(self, fn, Q, *extra, key=None, postings: bool = False):
        """vmap ``fn(terms, weights, *extra_i)`` over queries.  If Q is None,
        ``fn(*extra_i)`` is mapped over the extra arrays.  Routed through the
        sharded bucketed engine when one is attached (the default); ``key``
        (a stage's structural key) names the engine's persistent jit-cache
        entry, scoped by this backend's uid — stage keys do not embed index
        contents, so on an engine shared across backends an unscoped key
        would serve one backend's closure-captured index/embeddings to the
        other.  ``postings=True`` marks a stage that gathers
        ``max_postings`` postings per query-term slot: its slots are
        counted (``retrieve_posting_slots_total``).  Falls back to the
        sequential single-device chunked loop."""
        if self.engine is not None:
            scoped = None if key is None else (self.uid, key)
            out = self.engine.run(StageProgram(key=scoped, fn=fn), Q, *extra)
            if postings:
                self._count_slots(Q)
            return out
        return self.vmap_queries_sequential(fn, Q, *extra)

    def _count_slots(self, Q) -> None:
        """Posting slots of the engine's last dispatch on this thread, from
        host arrays only: terms still on the device are not counted
        (reading them would wait for the device)."""
        terms = Q["terms"]
        if not isinstance(terms, np.ndarray):
            return
        live, rows = self.engine.last_rows()
        t = terms[:live]
        slots_live = int(self.posting_lens[t[t >= 0]].sum())
        self._m_slots.inc(slots_live, ("live",))
        self._m_slots.inc(rows * terms.shape[1] * self.max_postings
                          - slots_live, ("pad",))

    def work_counts(self) -> dict:
        """Totals of the padded-work counters of this backend's engine:
        ``rows_live``, ``rows_pad``, ``slots_live``, ``slots_pad``.  A
        caller takes the difference of two reads around its work; that
        difference is its own only while no other thread dispatches on the
        engine."""
        s = self._m_slots
        return {**self.engine.row_counts(),
                "slots_live": int(s.value(("live",))),
                "slots_pad": int(s.value(("pad",)))}

    def vmap_queries_sequential(self, fn, Q, *extra):
        """The seed's single-device chunked-vmap loop, kept as the engine's
        baseline (benchmarks) and escape hatch (REPRO_ENGINE=sequential)."""
        args = ((Q["terms"], Q["weights"]) if Q is not None else ()) + extra
        nq = args[0].shape[0]
        if nq == 0:
            # parity with the engine path (chunk_plan raises the same):
            # nothing downstream can infer output shapes from zero queries
            raise ValueError("empty query batch")
        c = min(self.query_chunk, nq)
        vf = jax.vmap(fn)
        outs = []
        for s in range(0, nq, c):
            chunk = tuple(a[s:s + c] for a in args)
            if chunk[0].shape[0] < c:  # pad tail chunk to keep shapes static
                pad = c - chunk[0].shape[0]
                chunk = tuple(jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                              for a in chunk)
                out = vf(*chunk)
                out = jax.tree.map(lambda x: x[:-pad], out)
            else:
                out = vf(*chunk)
            outs.append(out)
        return jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *outs)

    def embed_queries(self, Q):
        t = jnp.maximum(Q["terms"], 0)
        w = Q["weights"] * (Q["terms"] >= 0)
        vec = jnp.einsum("qld,ql->qd", self._qproj[t], w)
        return vec / jnp.maximum(
            jnp.linalg.norm(vec, axis=-1, keepdims=True), 1e-6)

    def label_results(self, Q, R, qrels: dict[int, dict[int, int]]):
        """Join a result list with qrels -> dense grade matrix [NQ, K]."""
        qids = np.asarray(Q["qid"])
        docids = np.asarray(R["docids"])
        labels = np.zeros(docids.shape, np.float32)
        for i, q in enumerate(qids):
            g = qrels.get(int(q), {})
            if g:
                labels[i] = [g.get(int(d), 0) if d >= 0 else 0 for d in docids[i]]
        return jnp.asarray(labels)


# ---------------------------------------------------------------------------
# combinator semantics (paper Table 2 relational definitions)
# ---------------------------------------------------------------------------

def _aggregate_rows(docs, scores, k_out):
    """Per-query CombSUM: sum scores of duplicate docids, top-k_out."""
    order = jnp.argsort(docs)
    d, s = docs[order], scores[order]
    seg = jnp.cumsum(jnp.concatenate(
        [jnp.zeros(1, jnp.int32), (d[1:] != d[:-1]).astype(jnp.int32)]))
    agg = jax.ops.segment_sum(s, seg, num_segments=d.shape[0])
    first = jnp.concatenate([jnp.ones(1, bool), d[1:] != d[:-1]])
    rep = jnp.where(first & (d >= 0), agg[seg], -jnp.inf)
    top_s, idx = jax.lax.top_k(rep, k_out)
    return jnp.where(jnp.isfinite(top_s), d[idx], -1).astype(jnp.int32), \
        jnp.where(jnp.isfinite(top_s), top_s, -jnp.inf)


@jax.jit
def _combine_linear(all_docs, all_scores, weights):
    """all_docs [NQ, C, K]; weights [C] -> CombSUM over the union."""
    NQ, C, K = all_docs.shape
    w = weights[None, :, None]
    s = jnp.where(all_docs >= 0, all_scores * w, 0.0)
    flat_d = all_docs.reshape(NQ, C * K)
    flat_s = s.reshape(NQ, C * K)
    return jax.vmap(lambda d, sc: _aggregate_rows(d, sc, K))(flat_d, flat_s)


@jax.jit
def _setop_union(d1, s1, d2, s2):
    """Union of two result lists; scores are ⊥ (=0, to be re-ranked)."""
    docs = jnp.concatenate([d1, d2], 1)
    order = jnp.argsort(docs, 1)
    d = jnp.take_along_axis(docs, order, 1)
    first = jnp.concatenate([jnp.ones_like(d[:, :1], bool),
                             d[:, 1:] != d[:, :-1]], 1) & (d >= 0)
    key = jnp.where(first, d, jnp.iinfo(jnp.int32).max)
    order2 = jnp.argsort(key, 1)
    d = jnp.where(jnp.take_along_axis(first, order2, 1),
                  jnp.take_along_axis(d, order2, 1), -1)
    return d, jnp.where(d >= 0, 0.0, -jnp.inf)


@jax.jit
def _setop_intersect(d1, s1, d2, s2):
    member = ((d1[:, :, None] == d2[:, None, :]) & (d1 >= 0)[:, :, None]).any(2)
    key = jnp.where(member, d1, jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(key, 1)
    d = jnp.where(jnp.take_along_axis(member, order, 1),
                  jnp.take_along_axis(d1, order, 1), -1)
    return d, jnp.where(d >= 0, 0.0, -jnp.inf)


@jax.jit
def _concat_rankings(d1, s1, d2, s2, eps=1e-3):
    """Paper ^: append R2\\R1 below R1 with shifted scores."""
    dup = ((d2[:, :, None] == d1[:, None, :]) & (d2 >= 0)[:, :, None]).any(2)
    v1 = d1 >= 0
    v2 = (d2 >= 0) & ~dup
    min1 = jnp.min(jnp.where(v1, s1, jnp.inf), 1, keepdims=True)
    max2 = jnp.max(jnp.where(v2, s2, -jnp.inf), 1, keepdims=True)
    min1 = jnp.where(jnp.isfinite(min1), min1, 0.0)
    max2 = jnp.where(jnp.isfinite(max2), max2, 0.0)
    s2n = s2 - max2 + min1 - eps
    docs = jnp.concatenate([jnp.where(v1, d1, -1), jnp.where(v2, d2, -1)], 1)
    scores = jnp.concatenate([jnp.where(v1, s1, -jnp.inf),
                              jnp.where(v2, s2n, -jnp.inf)], 1)
    order = jnp.argsort(-scores, 1)
    return (jnp.take_along_axis(docs, order, 1),
            jnp.take_along_axis(scores, order, 1))


def _feature_columns(R):
    if "features" in R:
        return R["features"]
    return R["scores"][..., None]


@jax.jit
def _align_features(base_docs, child_docs, child_feats):
    """Align child feature rows onto base docids ((qid,docid) join)."""
    eq = (base_docs[:, :, None] == child_docs[:, None, :]) & \
        (base_docs >= 0)[:, :, None]
    aligned = jnp.einsum("qbc,qcf->qbf", eq.astype(child_feats.dtype),
                         child_feats)
    return aligned


# op-kind -> executor for combinator IR ops; each receives the content token
# of its input so sub-pipeline results can be memoised soundly
def _exec_then(op, ctx, Q, R, tok):
    for child in op.inputs:
        Q, R, tok = _execute(child, ctx, Q, R, tok)
    return Q, R


def _exec_linear(op, ctx, Q, R, tok):
    outs = [_execute(c, ctx, Q, R, tok)[1] for c in op.inputs]
    K = max(o["docids"].shape[1] for o in outs)
    pad = lambda o: jnp.pad(o["docids"], ((0, 0), (0, K - o["docids"].shape[1])),
                            constant_values=-1)
    pads = lambda o: jnp.pad(o["scores"], ((0, 0), (0, K - o["scores"].shape[1])),
                             constant_values=-jnp.inf)
    docs = jnp.stack([pad(o) for o in outs], 1)
    scores = jnp.stack([pads(o) for o in outs], 1)
    w = jnp.asarray(op.params["weights"], jnp.float32)
    d, s = _combine_linear(docs, scores, w)
    return Q, {"qid": Q["qid"], "docids": d, "scores": s}


def _exec_scale(op, ctx, Q, R, tok):
    Q, R1, _ = _execute(op.inputs[0], ctx, Q, R, tok)
    a = op.params["alpha"]
    return Q, {**R1, "scores": jnp.where(R1["docids"] >= 0,
                                         R1["scores"] * a, -jnp.inf)}


def _exec_cutoff(op, ctx, Q, R, tok):
    Q, R1, _ = _execute(op.inputs[0], ctx, Q, R, tok)
    k = op.params["k"]
    out = {**R1, "docids": R1["docids"][:, :k], "scores": R1["scores"][:, :k]}
    if "features" in R1:
        out["features"] = R1["features"][:, :k]
    return Q, out


def _exec_setop(op, ctx, Q, R, tok):
    _, R1, _ = _execute(op.inputs[0], ctx, Q, R, tok)
    _, R2, _ = _execute(op.inputs[1], ctx, Q, R, tok)
    fn = _setop_union if op.params["op"] == "union" else _setop_intersect
    d, s = fn(R1["docids"], R1["scores"], R2["docids"], R2["scores"])
    return Q, {"qid": Q["qid"], "docids": d, "scores": s}


def _exec_concat(op, ctx, Q, R, tok):
    _, R1, _ = _execute(op.inputs[0], ctx, Q, R, tok)
    _, R2, _ = _execute(op.inputs[1], ctx, Q, R, tok)
    d, s = _concat_rankings(R1["docids"], R1["scores"],
                            R2["docids"], R2["scores"])
    return Q, {"qid": Q["qid"], "docids": d, "scores": s}


def _exec_feature_union(op, ctx, Q, R, tok):
    outs = [_execute(c, ctx, Q, R, tok)[1] for c in op.inputs]
    base = outs[0]
    cols = [_feature_columns(base)]
    for o in outs[1:]:
        cols.append(_align_features(base["docids"], o["docids"],
                                    _feature_columns(o)))
    feats = jnp.concatenate(cols, -1)
    return Q, {**base, "features": feats}


_COMBINATORS = {
    "then": _exec_then, "linear": _exec_linear, "scale": _exec_scale,
    "cutoff": _exec_cutoff, "setop": _exec_setop, "concat": _exec_concat,
    "feature_union": _exec_feature_union,
}


# ---------------------------------------------------------------------------
# execution engine with content-addressed result caching
# ---------------------------------------------------------------------------

def content_token(tree) -> str:
    """Digest of the actual array contents of a (Q, R)-like pytree.

    This is the *source* token of a pipeline run: unlike ``id()``-keyed
    tokens it cannot alias after garbage collection (CPython recycles object
    ids), so a long-lived shared Context stays sound.
    """
    leaves, treedef = jax.tree.flatten(tree)
    h = hashlib.sha256(repr(treedef).encode())
    for leaf in leaves:
        a = np.asarray(leaf)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def derive_token(node_key, token_in: str) -> str:
    """Token of a node's output: H(producing node key, input token).  Pure
    function of pipeline structure + source content, so identical
    sub-pipelines over the same query set share one token across pipelines,
    Experiments, and grid-search candidates."""
    h = hashlib.sha256(repr(node_key).encode())
    h.update(token_in.encode())
    return h.hexdigest()


@dataclasses.dataclass
class Context:
    """Shared execution state: result memo keyed by (node key, input token),
    plus per-node execution counters (used by the planner's exactly-once
    tests and the benchmark's sharing report)."""
    backend: JaxBackend
    memo: dict = dataclasses.field(default_factory=dict)
    exec_counts: dict = dataclasses.field(default_factory=dict)
    #: strong refs to executed nodes — node keys embed id()s of non-scalar
    #: params (e.g. Generic fns), which stay unique only while alive
    _pins: dict = dataclasses.field(default_factory=dict)
    #: id -> (weakref, digest): avoids re-hashing the same live arrays on
    #: every run (grid search presents the same topics per candidate)
    _leaf_tokens: dict = dataclasses.field(default_factory=dict)

    def pin(self, node: Transformer) -> None:
        self._pins[id(node)] = node

    def _leaf_token(self, leaf) -> str:
        ent = self._leaf_tokens.get(id(leaf))
        if ent is not None and ent[0]() is leaf:
            # identity check makes the id-keyed cache sound: a dead ref can
            # never vouch for a recycled id
            return ent[1]
        a = np.asarray(leaf)
        h = hashlib.sha256(str((a.dtype, a.shape)).encode())
        h.update(a.tobytes())
        tok = h.hexdigest()
        try:
            self._leaf_tokens[id(leaf)] = (weakref.ref(leaf), tok)
        except TypeError:
            pass                      # non-weakrefable leaf: just rehash
        return tok

    def source_token(self, Q, R) -> str:
        leaves, treedef = jax.tree.flatten((Q, R))
        h = hashlib.sha256(repr(treedef).encode())
        for leaf in leaves:
            h.update(self._leaf_token(leaf).encode())
        return h.hexdigest()


def _execute(op, ctx: Context, Q, R, tok: str | None = None):
    """Execute an IR op on (Q, R); returns ``(Q', R', token')`` where
    ``token'`` content-addresses the output.  A ``Transformer`` is accepted
    for compatibility and lowered on the fly (keys are representation-
    independent, so the memo stays shared either way)."""
    if isinstance(op, Transformer):
        op = lower(op)
    if tok is None:
        tok = ctx.source_token(Q, R)
    ctx.pin(op)
    if op.ref is not None:
        ctx.pin(op.ref)
    key = op.key()
    memo_key = (key, tok)
    hit = ctx.memo.get(memo_key)
    if hit is not None:
        return hit
    fn = _COMBINATORS.get(op.kind)
    if fn is not None:
        Q2, R2 = fn(op, ctx, Q, R, tok)
    else:
        ctx.exec_counts[key] = ctx.exec_counts.get(key, 0) + 1
        Q2, R2 = op.ref.execute(ctx, Q, R)
    out = (Q2, R2, derive_token(key, tok))
    ctx.memo[memo_key] = out
    return out


def run_pipeline(node: Transformer | Op, Q, R=None, *, backend: JaxBackend,
                 optimize: bool = True, ctx: Context | None = None):
    from repro.core.passes import compile_pipeline
    # Op inputs go through the same compile path (the passes are idempotent
    # on already-compiled IR): skipping it would silently drop optimisation
    # AND schema validation exactly when the caller hands over raw IR
    op = compile_pipeline(node, backend, optimize=optimize)
    ctx = ctx or Context(backend)
    Q2, R2, _ = _execute(op, ctx, Q, R)
    return R2 if R2 is not None else Q2


def fit_pipeline(root: Transformer, Q_train, qrels_train, Q_valid,
                 qrels_valid, *, backend: JaxBackend):
    """Depth-first fit: run the pipeline; each stateful node receives the
    (Q, R) flowing into it plus qrels (paper eq. 9 semantics)."""
    ctx = Context(backend)

    def walk(node, st, sv):
        # st / sv: (Q, R, token) train / validation streams
        if node.kind == "then":
            for child in node.children:
                st, sv = walk(child, st, sv)
            return st, sv
        # fit children first (they feed this node)
        for child in node.children:
            walk(child, st, sv)
        return _execute_prefit(node, st), \
            (_execute_prefit(node, sv) if sv is not None else None)

    def _execute_prefit(node, state):
        Q, R, tok = state
        if node.stateful:
            # must fit BEFORE executing (execute needs trained state)
            node._fit_local(ctx, Q, R, qrels_train, None, None, qrels_valid)
        return _execute(node, ctx, Q, R, tok)

    sv0 = None
    if Q_valid is not None:
        sv0 = (Q_valid, None, ctx.source_token(Q_valid, None))
    walk(root, (Q_train, None, ctx.source_token(Q_train, None)), sv0)
    return root
