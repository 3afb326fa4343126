"""Pass-manager compiler over the typed pipeline IR (paper §4).

Replaces the ad-hoc fixpoint rewriter (the late ``core/rewrite.py``) with
an explicit ordered pipeline of IR-to-IR passes:

  canonicalise        — re-establish the canonical variadic forms (flatten
                        Then-of-Then / FeatureUnion nests, inline Scale and
                        Linear children into Linear weights)
  schema_inference    — infer per-op :class:`~repro.core.ir.Schema` (Q/R/F
                        stream, static k, feature width) and validate the
                        typing rules (a rank cutoff must attach to an
                        R-producing expression)
  rewrite             — the equivalence rules (cutoff merge/into-then/
                        scale-swap/pushdown, fat/extract/linear fusion,
                        scale folding) re-expressed over IR ops, applied
                        bottom-up to fixpoint against the backend
                        capability descriptor
  cse                 — hash-cons structurally identical subgraphs into
                        shared op instances; the interning table can span
                        pipelines, so ``ExperimentPlan`` feeds the plan trie
                        with literally shared prefix ops
  fusion              — cost-gated lowering to the Pallas kernel paths:
                        ``cutoff(retrieve)`` -> FusedTopKRetrieve
                        (kernels/topk), ``cutoff(fat_retrieve)`` ->
                        FusedFatRetrieve (kernels/fused_scoring),
                        ``cutoff(dense_retrieve)`` -> FusedDenseRetrieve
                        and ``retrieve >> cutoff(dense_rerank)`` ->
                        FusedDenseRerank (kernels/dense_scoring, behind the
                        ``dense_topk`` / ``fused_dense`` capabilities),
                        accepted only when the HLO cost model
                        (:func:`repro.analysis.hlo_cost.estimate_callable`)
                        prices the fused form strictly cheaper; otherwise
                        the unfused interpreter path is kept
  schema_check        — re-infer/validate schemas on the final graph

``compile_pipeline`` is the single optimization entry point — the executor
(``compiler.run_pipeline``), the planner (``plan.ExperimentPlan``), the
experiment/tuning drivers and the serving layer all go through it;
``explain_pipeline`` renders the IR before/after each pass for
``pipeline.explain()``.
"""
from __future__ import annotations

import time
from typing import Callable

from repro.core import stages as S
from repro.core.descriptor import BackendDescriptor, as_descriptor
from repro.obs.metrics import CounterMap, MetricsRegistry
from repro.obs.tracing import NOOP_TRACER, get_tracer
from repro.core.ir import (COMBINATOR_KINDS, Op, Schema, SchemaError, chain,
                           leaf, lower, pretty)
from repro.core.transformer import Transformer

#: query-term width used for cost-gate lowering AND probe measurement (only
#: cost *ratios* gate decisions, and they are monotone in the query width);
#: doubles as the tuning profile's bucket key
GATE_MAXQ = 8


# ---------------------------------------------------------------------------
# schema inference
# ---------------------------------------------------------------------------

_RETRIEVER_KINDS = frozenset({"retrieve", "pruned_retrieve", "multi_retrieve",
                              "fused_topk_retrieve", "dense_retrieve",
                              "fused_dense_retrieve", "fused_dense_rerank"})
_FAT_KINDS = frozenset({"fat_retrieve", "fused_fat_retrieve"})


def _carry(s_in: Schema | None):
    return (None, None) if s_in is None else (s_in.k, s_in.width)


def _reject_answer(st: Schema, where: str, child: Op) -> None:
    """A is terminal: no ranking combinator may consume an answer stream."""
    if st.out == "A":
        raise SchemaError(
            f"{where} typed against an answer-bearing (A) expression "
            f"({child.label()}): generate is terminal — no ranking stage "
            f"may consume its output")


def _stage_schema(op: Op, s_in: Schema | None, backend,
                  annot: dict | None) -> Schema:
    """Schema of ``op``'s output stream given the schema of the incoming R
    stream (None = statically unknown / absent)."""
    kind = op.kind
    k_in, w_in = _carry(s_in)
    if s_in is not None and s_in.out == "A":
        raise SchemaError(
            f"stage {op.label()} typed against an answer-bearing (A) "
            f"stream: generate is terminal — no stage may consume its "
            f"output")
    if kind in _RETRIEVER_KINDS:
        k = op.params.get("k") or (backend.default_k if backend else None)
        out = Schema("R", k, None, False)
    elif kind in _FAT_KINDS:
        k = op.params.get("k") or (backend.default_k if backend else None)
        out = Schema("F", k, len(op.params["features"]), False)
    elif kind == "extract":
        out = Schema("F", k_in, None if s_in is None else (w_in or 0) + 1,
                     True)
    elif kind in ("sdm_rewrite", "stem_rewrite"):
        out = Schema("Q", k_in, w_in, False)
    elif kind == "rm3":
        out = Schema("Q", k_in, w_in, True)
    elif kind == "ltr":
        out = Schema("F", k_in, w_in, True)
    elif kind == "dense_rerank":
        out = Schema("F" if s_in is not None and s_in.out == "F" else "R",
                     k_in, w_in, True)
    elif kind == "generate":
        if s_in is None:
            raise SchemaError(
                f"generate ({op.label()}) typed against a pure Q -> Q "
                f"expression: prompt assembly reads ranked results, so "
                f"generate may only follow an R-producing expression")
        # A: answer-bearing results.  k carries the (static) result depth
        # the prompt reads; width carries the static decode length — both
        # fixed at compile time so the bucket ladder stays recompile-free.
        out = Schema("A", k_in, op.params["max_new_tokens"], True)
    elif kind == "then":
        r_sch = s_in
        child_outs = []
        for c in op.inputs:
            st = _stage_schema(c, r_sch, backend, annot)
            child_outs.append(st)
            if st.out != "Q":
                r_sch = st
        if all(st.out == "Q" for st in child_outs):
            out = Schema("Q", *_carry(r_sch),
                         any(st.reads_results for st in child_outs))
        else:
            out = Schema(r_sch.out, r_sch.k, r_sch.width,
                         any(st.reads_results for st in child_outs))
    elif kind == "cutoff":
        st = _stage_schema(op.inputs[0], s_in, backend, annot)
        if st.out == "Q":
            raise SchemaError(
                f"rank cutoff %{op.params['k']} typed against a pure "
                f"Q -> Q expression ({op.inputs[0].label()}): a cutoff may "
                f"only attach to an R-producing expression")
        if st.out == "A":
            raise SchemaError(
                f"rank cutoff %{op.params['k']} typed against an "
                f"answer-bearing (A) expression ({op.inputs[0].label()}): "
                f"generate is terminal — apply the cutoff before it")
        K = op.params["k"]
        out = Schema(st.out, K if st.k is None else min(K, st.k), st.width,
                     st.reads_results)
    elif kind == "scale":
        st = _stage_schema(op.inputs[0], s_in, backend, annot)
        _reject_answer(st, "score scale", op.inputs[0])
        out = Schema(st.out, st.k, st.width, st.reads_results)
    elif kind == "linear":
        sts = [_stage_schema(c, s_in, backend, annot) for c in op.inputs]
        for st, c in zip(sts, op.inputs):
            _reject_answer(st, "linear combination", c)
        ks = [st.k for st in sts]
        out = Schema("R", None if any(k is None for k in ks) else max(ks),
                     None, any(st.reads_results for st in sts))
    elif kind in ("setop", "concat"):
        s1 = _stage_schema(op.inputs[0], s_in, backend, annot)
        s2 = _stage_schema(op.inputs[1], s_in, backend, annot)
        _reject_answer(s1, f"{kind} operand", op.inputs[0])
        _reject_answer(s2, f"{kind} operand", op.inputs[1])
        if kind == "setop" and op.params.get("op") == "intersect":
            k = s1.k
        else:
            k = None if s1.k is None or s2.k is None else s1.k + s2.k
        out = Schema("R", k, None, s1.reads_results or s2.reads_results)
    elif kind == "feature_union":
        sts = [_stage_schema(c, s_in, backend, annot) for c in op.inputs]
        for st, c in zip(sts, op.inputs):
            _reject_answer(st, "feature union", c)
        widths = [st.width if st.width else 1 for st in sts]
        out = Schema("F", sts[0].k,
                     None if any(st.out == "F" and st.width is None
                                 for st in sts) else sum(widths),
                     any(st.reads_results for st in sts))
    else:
        # unknown leaf (Generic, user extensions): class attrs, no statics
        ref = op.ref
        out = Schema(ref.out_kind if ref is not None else "R", None, None,
                     ref.reads_results if ref is not None else True)
    if annot is not None:
        annot[id(op)] = out
    return out


def annotate(root: Op, backend=None) -> dict[int, Schema]:
    """id(op) -> Schema for every op in ``root`` (validates as it goes)."""
    annot: dict[int, Schema] = {}
    _stage_schema(root, None, backend, annot)
    return annot


def expr_schema(op: Op, backend=None) -> Schema:
    """Schema of an expression evaluated against an unknown input stream
    (``out == "Q"`` = pure query rewrite) — the bits rewrite rules guard
    on."""
    return _stage_schema(op, None, backend, None)


# ---------------------------------------------------------------------------
# pass infrastructure
# ---------------------------------------------------------------------------

class PassContext:
    """Shared state for one compile: backend + its descriptor, rewrite
    trace, fusion-gate decisions and tuning counters, optional
    cross-pipeline CSE table, per-pass IR snapshots."""

    def __init__(self, backend, *, trace: list | None = None,
                 cse_table: dict | None = None, keep_snapshots: bool = False,
                 descriptor: BackendDescriptor | None = None):
        self.backend = backend
        self.descriptor = descriptor if descriptor is not None \
            else as_descriptor(backend)
        self.trace = trace if trace is not None else []
        self.cse_table = cse_table if cse_table is not None else {}
        self.decisions: list[dict] = []
        self.snapshots: list[tuple[str, Op]] = []
        self.keep_snapshots = keep_snapshots
        self.timings: list[tuple[str, float]] = []
        #: per-compile metrics registry; ``pipeline.explain()`` and the
        #: compile report read tuning counts through it (one source of
        #: truth with the serving-side registries)
        self.metrics = MetricsRegistry()
        #: spans route to the process-global tracer only when the
        #: descriptor opted in — the default is the shared no-op
        self.tracer = (get_tracer()
                       if getattr(self.descriptor, "observability", False)
                       else NOOP_TRACER)
        #: the acceptance counters for the warm-reuse property: a compile
        #: served entirely from a persisted TuningProfile must show zero
        #: gate_estimates (candidate compiles) and zero probe_measurements.
        #: Dict-shaped view over the registry's ``compile_tuning_total``.
        self.counters = CounterMap(
            self.metrics.counter(
                "compile_tuning_total",
                "fusion-gate and autotune work per compile", ("counter",)),
            ("gate_estimates", "probe_measurements",
             "profile_hits", "profile_misses", "gate_errors"))


class Pass:
    name = "pass"

    def run(self, op: Op, pctx: PassContext) -> Op:
        raise NotImplementedError


class PassManager:
    def __init__(self, passes: list[Pass]):
        self.passes = list(passes)

    def run(self, op: Op, pctx: PassContext) -> Op:
        if pctx.keep_snapshots:
            pctx.snapshots.append(("lower", op))
        with pctx.tracer.span("compile.pipeline", "compile",
                              n_passes=len(self.passes)):
            for p in self.passes:
                t0 = time.perf_counter()
                with pctx.tracer.span(f"compile.pass.{p.name}", "compile"):
                    op = p.run(op, pctx)
                pctx.timings.append((p.name, time.perf_counter() - t0))
                if pctx.keep_snapshots:
                    pctx.snapshots.append((p.name, op))
        return op


def _rebuild(op: Op, new_inputs: list[Op]) -> Op:
    if len(new_inputs) == len(op.inputs) and \
            all(a is b for a, b in zip(new_inputs, op.inputs)):
        return op
    return op.with_inputs(new_inputs)


# ---------------------------------------------------------------------------
# canonicalise
# ---------------------------------------------------------------------------

class CanonicalizePass(Pass):
    """Re-establish the canonical variadic node forms on IR (the operator
    constructors guarantee them at build time; rewrites re-run this)."""
    name = "canonicalise"

    def run(self, op: Op, pctx: PassContext) -> Op:
        return self._walk(op)

    def _walk(self, op: Op) -> Op:
        op = _rebuild(op, [self._walk(i) for i in op.inputs])
        if op.kind == "then" and any(i.kind == "then" for i in op.inputs):
            flat: list[Op] = []
            for i in op.inputs:
                flat.extend(i.inputs if i.kind == "then" else [i])
            return Op("then", {}, flat)
        if op.kind == "feature_union" and \
                any(i.kind == "feature_union" for i in op.inputs):
            flat = []
            for i in op.inputs:
                flat.extend(i.inputs if i.kind == "feature_union" else [i])
            return Op("feature_union", {}, flat)
        if op.kind == "linear" and \
                any(i.kind in ("linear", "scale") for i in op.inputs):
            ws, cs = [], []
            for w, c in zip(op.params["weights"], op.inputs):
                if c.kind == "linear":
                    ws.extend(w * wi for wi in c.params["weights"])
                    cs.extend(c.inputs)
                elif c.kind == "scale":
                    ws.append(w * c.params["alpha"])
                    cs.append(c.inputs[0])
                else:
                    ws.append(w)
                    cs.append(c)
            return Op("linear", {"weights": tuple(ws)}, cs)
        return op


# ---------------------------------------------------------------------------
# schema inference / validation
# ---------------------------------------------------------------------------

class SchemaPass(Pass):
    """Infer + validate schemas over the whole graph (raises SchemaError on
    ill-typed pipelines; the inferred annotations drive explain())."""

    def __init__(self, name: str = "schema_inference"):
        self.name = name

    def run(self, op: Op, pctx: PassContext) -> Op:
        annotate(op, pctx.backend)
        return op


# ---------------------------------------------------------------------------
# rewrite rules over IR
# ---------------------------------------------------------------------------

IRRule = Callable[[Op, PassContext], "Op | None"]
#: (name, rule, required capability or None) — capability-gated rules are
#: filtered once at pass construction against the backend descriptor, not
#: string-probed per match (the descriptor refactor)
IR_RULES: list[tuple[str, IRRule, str | None]] = []


def ir_rule(name: str, requires: str | None = None):
    def deco(fn):
        IR_RULES.append((name, fn, requires))
        return fn
    return deco


@ir_rule("cutoff_merge")
def cutoff_merge(op, pctx):
    if op.kind == "cutoff" and op.inputs[0].kind == "cutoff":
        inner = op.inputs[0]
        k = min(op.params["k"], inner.params["k"])
        return Op("cutoff", {"k": k}, (inner.inputs[0],))
    return None


@ir_rule("cutoff_into_then")
def cutoff_into_then(op, pctx):
    """(A >> B) % K -> A >> (B % K), guarded on B's schema: a rank cutoff is
    only typed for R-producing expressions.  Trailing Q -> Q rewrites that
    never read R (SDM, stemming) are hopped over — sound, they cannot
    observe the truncation — so the cutoff lands on the last R-producing
    stage and stays eligible for the RQ1 pushdown / kernel lowering.  An
    R-*reading* query rewrite (RM3 reads fb_docs from R) blocks the push."""
    if not (op.kind == "cutoff" and op.inputs[0].kind == "then"):
        return None
    kids = list(op.inputs[0].inputs)
    be = pctx.backend
    i, st = len(kids) - 1, None
    while i >= 0:
        st = expr_schema(kids[i], be)
        if not (st.out == "Q" and not st.reads_results):
            break
        i -= 1
    if i < 0 or st is None or st.out == "Q":
        return None
    last = Op("cutoff", {"k": op.params["k"]}, (kids[i],))
    return Op("then", {}, (*kids[:i], last, *kids[i + 1:]))


@ir_rule("cutoff_scale_swap")
def cutoff_scale_swap(op, pctx):
    if op.kind == "cutoff" and op.inputs[0].kind == "scale":
        sc = op.inputs[0]
        if sc.params["alpha"] > 0:
            inner = Op("cutoff", {"k": op.params["k"]}, (sc.inputs[0],))
            return Op("scale", {"alpha": sc.params["alpha"]}, (inner,))
    return None


@ir_rule("cutoff_pushdown", requires="pruned_topk")
def cutoff_pushdown(op, pctx):
    """Retrieve % K -> PrunedRetrieve(K): the RQ1 dynamic-pruning rewrite."""
    if op.kind == "cutoff" and op.inputs[0].kind == "retrieve":
        ret = op.inputs[0]
        K = op.params["k"]
        if ret.params["k"] is None or ret.params["k"] >= K:
            return leaf(S.PrunedRetrieve(model=ret.params["model"], k=K))
    return None


def _as_extract_models(inputs) -> tuple[str, ...] | None:
    models = []
    for c in inputs:
        if c.kind != "extract":
            return None
        models.append(c.params["model"])
    return tuple(models)


@ir_rule("fat_fusion", requires="fat")
def fat_fusion(op, pctx):
    """Retrieve >> (Extract ** ... ** Extract) -> FatRetrieve: RQ2 (a single
    Extract is the degenerate one-feature case)."""
    if op.kind != "then":
        return None
    kids = list(op.inputs)
    for i in range(len(kids) - 1):
        a, b = kids[i], kids[i + 1]
        if a.kind != "retrieve":
            continue
        if b.kind == "feature_union":
            models = _as_extract_models(b.inputs)
        elif b.kind == "extract":
            models = (b.params["model"],)
        else:
            continue
        if models is None:
            continue
        fat = leaf(S.FatRetrieve(model=a.params["model"], features=models,
                                 k=a.params["k"]))
        new_kids = kids[:i] + [fat] + kids[i + 2:]
        return new_kids[0] if len(new_kids) == 1 else Op("then", {}, new_kids)
    return None


@ir_rule("linear_fusion", requires="multi_model")
def linear_fusion(op, pctx):
    """Σ wᵢ·Retrieve(mᵢ, k) on one index -> MultiRetrieve (one postings
    pass instead of N — beyond-paper rewrite enabled by score_all).  The
    uniform-k guard is the equivalence boundary; mixed-k fusion is handled
    by the AutotunePass, which only takes it when *measured* faster."""
    if op.kind != "linear":
        return None
    ks = set()
    models = []
    for c in op.inputs:
        if c.kind != "retrieve":
            return None
        ks.add(c.params["k"])
        models.append(c.params["model"])
    if len(ks) != 1 or len(models) < 2:
        return None
    return leaf(S.MultiRetrieve(models=tuple(models),
                                weights=tuple(op.params["weights"]),
                                k=ks.pop()))


@ir_rule("scale_fold")
def scale_fold(op, pctx):
    if op.kind != "scale":
        return None
    inner = op.inputs[0]
    a = op.params["alpha"]
    if a == 1.0:
        return inner
    if inner.kind == "scale":
        return Op("scale", {"alpha": a * inner.params["alpha"]},
                  (inner.inputs[0],))
    if inner.kind == "linear":
        return Op("linear",
                  {"weights": tuple(a * w for w in inner.params["weights"])},
                  inner.inputs)
    return None


class RewritePass(Pass):
    """Bottom-up application of the equivalence rules to a fixpoint — the
    IR re-expression of the old ad-hoc rewriter loop.

    Capability-gated rules are filtered ONCE against the backend descriptor
    at pass construction; the match loop never probes the backend."""
    name = "rewrite"

    def __init__(self, descriptor: BackendDescriptor | None = None,
                 max_iters: int = 20):
        self.max_iters = max_iters
        self.descriptor = descriptor
        self._rules: list[tuple[str, IRRule]] | None = (
            None if descriptor is None else _eligible_rules(descriptor))

    def run(self, op: Op, pctx: PassContext) -> Op:
        # a pass built without a descriptor (legacy direct construction)
        # resolves its rule set from the context's descriptor per run
        rules = self._rules if self._rules is not None \
            else _eligible_rules(pctx.descriptor)
        for _ in range(self.max_iters):
            new = self._walk(op, pctx, rules)
            if new.key() == op.key():
                return new
            op = new
        return op

    def _walk(self, op: Op, pctx: PassContext, rules) -> Op:
        op = _rebuild(op, [self._walk(i, pctx, rules) for i in op.inputs])
        for name, rule in rules:
            out = rule(op, pctx)
            if out is not None and out.key() != op.key():
                pctx.trace.append((name, op, out))
                return self._walk(out, pctx, rules)
        return op


def _eligible_rules(desc: BackendDescriptor) -> list[tuple[str, IRRule]]:
    return [(name, rule) for name, rule, req in IR_RULES
            if req is None or desc.supports(req)]


# ---------------------------------------------------------------------------
# common-subexpression elimination
# ---------------------------------------------------------------------------

class CSEPass(Pass):
    """Hash-cons structurally identical subgraphs into shared op instances.

    Keys are content keys, so two pipelines building ``Retrieve("BM25")``
    separately intern to ONE op; with a cross-pipeline table
    (``PassContext.cse_table`` shared by ``ExperimentPlan``) the plan trie
    receives literally shared prefix ops.  Stateful stages and
    object-identity params embed uid/id in their key, so distinct live
    objects never merge."""
    name = "cse"

    def run(self, op: Op, pctx: PassContext) -> Op:
        return self._intern(op, pctx.cse_table)

    def _intern(self, op: Op, table: dict) -> Op:
        op = _rebuild(op, [self._intern(i, table) for i in op.inputs])
        hit = table.get(op.key())
        if hit is None:
            table[op.key()] = op
            return op
        return hit


# ---------------------------------------------------------------------------
# cost-gated fusion / kernel lowering
# ---------------------------------------------------------------------------

def _abstract_sds(tree):
    import jax
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _abstract_args(backend):
    """``((index,), (terms, weights))`` — a candidate's per-backend and
    per-query arguments, as every gate ``args`` pair is split."""
    import jax
    import jax.numpy as jnp
    idx = _abstract_sds(backend.index)
    t = jax.ShapeDtypeStruct((GATE_MAXQ,), jnp.int32)
    w = jax.ShapeDtypeStruct((GATE_MAXQ,), jnp.float32)
    return (idx,), (t, w)


def _abstract_qvec(backend):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct((backend.dense.dim,), jnp.float32)


def _abstract_dense_rerank_args(backend):
    """((index, doc embeddings), (terms, weights, query vector)) — the
    signature of the fused/unfused dense-rerank candidates."""
    (idx,), (t, w) = _abstract_args(backend)
    emb = _abstract_sds(backend.dense.emb)
    return (idx, emb), (t, w, _abstract_qvec(backend))


def _error_line(exc: BaseException) -> str:
    """``ExcType: first line`` — what a gate decision records when a
    candidate fails to lower, compile or run."""
    first = (str(exc).strip().splitlines() or [""])[0]
    return f"{type(exc).__name__}: {first}"


def _estimate(backend, desc: BackendDescriptor, key, build, args,
              counters: dict | None = None):
    """Cost estimate for one candidate per-query program, cached on the
    backend by content key (compilation dominates; estimates are pure
    functions of backend + static params + the descriptor's peaks).
    ``args`` is ``(static, queries)``: the program is priced as the engine
    runs it, vmapped over a batch of one query with the static (index)
    arguments unbatched.  (A 1-D ``lax.top_k`` over a whole collection
    also takes the TPU compiler ~30 s, against ~1 s batched.)
    Returns ``(estimate, None)``, or ``(None, error line)`` when the
    candidate cannot be lowered or compiled — the caller records that in
    its decision instead of treating it as a cost verdict.

    The cache is scoped by the descriptor's host/peak digest: an estimate
    priced under one set of peak constants (or computed on another host and
    carried over in a deserialised profile) must never answer for a
    differently calibrated descriptor."""
    scope = backend.__dict__.setdefault("_cost_estimates", {})
    cache = scope.setdefault(desc.peak_digest, {})
    if key in cache:
        return cache[key]
    from repro.analysis.hlo_cost import estimate_callable
    if counters is not None:
        counters["gate_estimates"] += 1
    try:
        import jax
        static, queries = args
        fn = jax.vmap(build(),
                      in_axes=(None,) * len(static) + (0,) * len(queries))
        one = [jax.ShapeDtypeStruct((1,) + q.shape, q.dtype) for q in queries]
        out = (estimate_callable(
            fn, *static, *one,
            peaks=(desc.peak_flops_per_s, desc.peak_bytes_per_s)), None)
    except Exception as e:     # recorded in the decision, never fused blind
        out = (None, _error_line(e))
    cache[key] = out
    return out


def _backend_gate_digest(backend) -> str:
    """Content digest keying this backend's tuning-profile entries (lazy
    import: plan imports this module at load time)."""
    from repro.core.plan import backend_digest
    try:
        return backend_digest(backend)
    except Exception:
        # duck-typed test backends without index arrays: scope by uid so
        # entries at least never cross live backends
        return f"uid:{getattr(backend, 'uid', id(backend))}"


def _probe_queries(backend, n: int):
    """Concrete synthetic (terms, weights) probe batch [n, GATE_MAXQ] —
    deterministic, so probe timings are comparable across candidates."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(0)
    vocab = backend.index.vocab
    terms = rng.integers(0, vocab, (n, GATE_MAXQ)).astype(np.int32)
    weights = np.ones((n, GATE_MAXQ), np.float32)
    return jnp.asarray(terms), jnp.asarray(weights)


def _probe_qvecs(backend, n: int):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(0)
    qv = rng.standard_normal((n, backend.dense.dim)).astype(np.float32)
    qv /= np.maximum(np.linalg.norm(qv, axis=-1, keepdims=True), 1e-6)
    return jnp.asarray(qv)


def _measure_callable(fn, static_args, batched_args, repeats: int) -> float:
    """Wall-clock one candidate on a concrete probe batch: jit(vmap(fn)),
    one warm-up call (compile excluded), then min-of-repeats seconds."""
    import jax
    in_axes = (None,) * len(static_args) + (0,) * len(batched_args)
    vf = jax.jit(jax.vmap(fn, in_axes=in_axes))
    args = (*static_args, *batched_args)
    jax.block_until_ready(vf(*args))
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(vf(*args))
        best = min(best, time.perf_counter() - t0)
    return best


class FusionPass(Pass):
    """Lower ``cutoff(retrieve)`` / ``cutoff(fat_retrieve)`` /
    ``cutoff(dense_retrieve)`` chains — and the two-stage
    ``retrieve >> cutoff(dense_rerank)`` pattern — onto the Pallas kernel
    paths, gated by the HLO cost model: the fused candidate must price
    *strictly* cheaper than the unfused chain it replaces, else the unfused
    interpreter path is kept.  Every decision (either way) is recorded in
    ``PassContext.decisions`` and, when the descriptor carries a
    :class:`~repro.core.descriptor.TuningProfile`, persisted so the next
    compile against the same backend replays it with zero candidate
    compiles.  Enablement and kernel-native limits come from the backend
    descriptor, received at pass construction."""
    name = "fusion"

    def __init__(self, descriptor: BackendDescriptor | None = None):
        self.descriptor = descriptor

    def _desc(self, pctx: PassContext) -> BackendDescriptor:
        return self.descriptor if self.descriptor is not None \
            else pctx.descriptor

    def run(self, op: Op, pctx: PassContext) -> Op:
        out = self._walk(op, pctx)
        prof = self._desc(pctx).profile
        if prof is not None:
            prof.save()           # no-op unless dirty (or in-memory)
        return out

    def _walk(self, op: Op, pctx: PassContext) -> Op:
        op = _rebuild(op, [self._walk(i, pctx) for i in op.inputs])
        desc = self._desc(pctx)
        if op.kind == "then":
            return self._fuse_dense_rerank_pairs(op, pctx)
        if op.kind == "linear":
            return self._tune_mixed_linear(op, pctx)
        if op.kind != "cutoff" or not op.inputs[0].is_leaf:
            return op
        inner = op.inputs[0]
        be = pctx.backend
        K = op.params["k"]
        k_in = inner.params.get("k") or be.default_k
        if K > k_in:
            return op
        # gate candidates must lower with legal shapes: clamp to the corpus
        # size exactly like the stage executors do (top-k cannot return more
        # entries than documents exist)
        K = min(K, be.index.n_docs)
        k_in = min(k_in, be.index.n_docs)
        from repro.index import retrieve as RT
        mp = be.max_postings
        if inner.kind == "dense_retrieve":
            need = "pq_topk" if (inner.params.get("pq")
                                 and inner.params.get("nprobe")) \
                else "dense_topk"
            if desc.supports(need):
                return self._fuse_dense_retrieve(op, inner, K, k_in, pctx)
            return op
        if inner.kind == "retrieve" and desc.supports("fused_topk"):
            model = inner.params["model"]
            fused = leaf(S.FusedTopKRetrieve(model=model, k=K))
            if self._gate(pctx, "topk",
                          kernel_native=desc.kernel_native("topk", K),
                          args=_abstract_args(be),
                          unfused=("topk_unfused", model, k_in, mp),
                          fused=("topk_fused", model, K, mp),
                          build_unfused=lambda: (
                              lambda ix, t, w: RT.retrieve_topk(
                                  ix, t, w, model=model, k=k_in,
                                  max_postings=mp)),
                          build_fused=lambda: (
                              lambda ix, t, w: RT.retrieve_topk_fused(
                                  ix, t, w, model=model, k=K,
                                  max_postings=mp)),
                          probe=lambda n: ((be.index,),
                                           _probe_queries(be, n))):
                pctx.trace.append(("fuse_topk", op, fused))
                return fused
        elif inner.kind == "fat_retrieve" and desc.supports("fused_scoring"):
            from repro.kernels.fused_scoring.ops import models_supported
            model = inner.params["model"]
            feats = tuple(inner.params["features"])
            if not models_supported((model,) + feats):
                return op
            fused = leaf(S.FusedFatRetrieve(model=model, features=feats, k=K))
            if self._gate(pctx, "fat",
                          kernel_native=desc.kernel_native("fat", K),
                          args=_abstract_args(be),
                          unfused=("fat_unfused", model, feats, k_in, mp),
                          fused=("fat_fused", model, feats, K, mp),
                          build_unfused=lambda: (
                              lambda ix, t, w: RT.retrieve_fat(
                                  ix, t, w, rank_model=model,
                                  feature_models=feats, k=k_in,
                                  max_postings=mp)),
                          build_fused=lambda: (
                              lambda ix, t, w: RT.retrieve_fat_fused(
                                  ix, t, w, rank_model=model,
                                  feature_models=feats, k=K,
                                  max_postings=mp)),
                          probe=lambda n: ((be.index,),
                                           _probe_queries(be, n))):
                pctx.trace.append(("fuse_fat", op, fused))
                return fused
        return op

    # -- mixed-k linear fusion: measured-only (AutotunePass) ----------------
    def _tune_mixed_linear(self, op: Op, pctx: PassContext) -> Op:
        """Hook for the AutotunePass's mixed-k ``linear()`` fusion.  The
        static pass never takes it (uniform-k is the equivalence-rule
        boundary; mixed-k changes the per-model truncation depths, so it is
        only acceptable when *measured* faster)."""
        return op

    # -- dense candidate generation: cutoff(dense_retrieve) -----------------
    def _fuse_dense_retrieve(self, op: Op, inner: Op, K: int, k_in: int,
                             pctx: PassContext) -> Op:
        from repro.index import dense as DN
        be = pctx.backend
        desc = self._desc(pctx)
        nprobe = inner.params["nprobe"]
        qv = _abstract_qvec(be)
        if nprobe and inner.params.get("pq"):
            # two-level IVF-PQ: the fused candidate replicates the *unfused*
            # chain's ADC shortlist depth (computed from the pre-cutoff
            # k_in) so fusion stays an exact rewrite — cutoff(topK) of the
            # re-scored shortlist commutes with selecting K directly.  The
            # kernel-native predicate is evaluated at that depth: it is the
            # k the streaming kernel must carry.
            pqi = be.ivfpq
            npb = min(nprobe, pqi.n_lists)
            refine = be.pq_refine
            r = DN._pq_shortlist_depth(k_in, refine, npb * pqi.max_list_len)
            fused = leaf(S.FusedDenseRetrieve(k=K, nprobe=nprobe, pq=True,
                                              pq_shortlist=r))
            if self._gate(pctx, "pq_topk",
                          kernel_native=desc.kernel_native("pq_topk", r),
                          args=((_abstract_sds(pqi),), (qv,)),
                          unfused=("pq_topk_unfused", k_in, nprobe, refine),
                          fused=("pq_topk_fused", K, nprobe, refine, r),
                          build_unfused=lambda: (
                              lambda ix, q: DN.ivfpq_retrieve_topk(
                                  ix, q, k=k_in, nprobe=npb, refine=refine)),
                          build_fused=lambda: (
                              lambda ix, q: DN.ivfpq_retrieve_topk_fused(
                                  ix, q, k=K, nprobe=npb, refine=refine,
                                  shortlist=r)),
                          probe=lambda n: ((pqi,), (_probe_qvecs(be, n),))):
                pctx.trace.append(("fuse_pq_topk", op, fused))
                return self._tune_dense_knobs(fused, pctx)
            return op
        fused = leaf(S.FusedDenseRetrieve(k=K, nprobe=nprobe))
        if nprobe:
            npb = min(nprobe, be.ivf.n_lists)
            args = ((_abstract_sds(be.ivf),), (qv,))
            build_u = lambda: (lambda ivf, q: DN.ivf_retrieve_topk(
                ivf, q, k=k_in, nprobe=npb))
            build_f = lambda: (lambda ivf, q: DN.ivf_retrieve_topk_fused(
                ivf, q, k=K, nprobe=npb))
            probe = lambda n: ((be.ivf,), (_probe_qvecs(be, n),))
        else:
            args = ((_abstract_sds(be.dense),), (qv,))
            build_u = lambda: (lambda dn, q: DN.dense_retrieve_exact(
                dn, q, k=k_in))
            build_f = lambda: (lambda dn, q: DN.dense_retrieve_exact_fused(
                dn, q, k=K))
            probe = lambda n: ((be.dense,), (_probe_qvecs(be, n),))
        if self._gate(pctx, "dense_topk",
                      kernel_native=desc.kernel_native("dense_topk", K),
                      args=args,
                      unfused=("dense_topk_unfused", k_in, nprobe),
                      fused=("dense_topk_fused", K, nprobe),
                      build_unfused=build_u, build_fused=build_f,
                      probe=probe):
            pctx.trace.append(("fuse_dense_topk", op, fused))
            return self._tune_dense_knobs(fused, pctx) if nprobe else fused
        return op

    def _tune_dense_knobs(self, op: Op, pctx: PassContext) -> Op:
        """Hook for the AutotunePass's IVF knob search (``nprobe``, PQ
        candidate block).  The static pass keeps the configured knobs: a
        different ``nprobe`` changes which lists are scanned, so it is only
        acceptable when *measured* both faster and within the descriptor's
        result-overlap band."""
        return op

    # -- dense second stage: retrieve >> cutoff(dense_rerank) --------------
    def _fuse_dense_rerank_pairs(self, op: Op, pctx: PassContext) -> Op:
        """Within a ``then`` chain, lower each adjacent
        ``retrieve, cutoff(dense_rerank)`` pair to one FusedDenseRerank
        stage (the rewrite pass has already pushed the pipeline-level cutoff
        onto the last R-producer, so the paper's ``bm25 >> neural % K``
        arrives here in exactly this shape)."""
        if not self._desc(pctx).supports("fused_dense"):
            return op
        kids = list(op.inputs)
        changed = False
        i = 0
        while i < len(kids) - 1:
            fused = self._try_dense_rerank_pair(kids[i], kids[i + 1], pctx)
            if fused is not None:
                kids[i:i + 2] = [fused]
                changed = True
            else:
                i += 1
        if not changed:
            return op
        return kids[0] if len(kids) == 1 else Op("then", {}, kids)

    def _try_dense_rerank_pair(self, a: Op, b: Op,
                               pctx: PassContext) -> Op | None:
        if not (a.kind == "retrieve" and b.kind == "cutoff"
                and b.inputs[0].kind == "dense_rerank"):
            return None
        from repro.index import retrieve as RT
        be = pctx.backend
        desc = self._desc(pctx)
        K = b.params["k"]
        k_in = a.params.get("k") or be.default_k
        if K > k_in:
            return None
        K = min(K, be.index.n_docs)
        k_in = min(k_in, be.index.n_docs)
        model = a.params["model"]
        alpha = b.inputs[0].params["alpha"]
        mp = be.max_postings
        fused = leaf(S.FusedDenseRerank(model=model, k_in=k_in, k=K,
                                        alpha=alpha))
        if self._gate(pctx, "dense_rerank",
                      kernel_native=desc.kernel_native("dense_rerank", K),
                      args=_abstract_dense_rerank_args(be),
                      unfused=("dense_rerank_unfused", model, k_in, K,
                               alpha, mp),
                      fused=("dense_rerank_fused", model, k_in, K,
                             alpha, mp),
                      build_unfused=lambda: (
                          lambda ix, emb, t, w, q: RT.retrieve_dense_rerank(
                              ix, emb, t, w, q, model=model, k_in=k_in, k=K,
                              alpha=alpha, max_postings=mp)),
                      build_fused=lambda: (
                          lambda ix, emb, t, w, q:
                          RT.retrieve_dense_rerank_fused(
                              ix, emb, t, w, q, model=model, k_in=k_in, k=K,
                              alpha=alpha, max_postings=mp)),
                      probe=lambda n: (
                          (be.index, be.dense.emb),
                          (*_probe_queries(be, n), _probe_qvecs(be, n)))):
            pctx.trace.append(("fuse_dense_rerank", Op("then", {}, (a, b)),
                               fused))
            return fused
        return None

    def _gate(self, pctx, pattern, *, unfused, fused, build_unfused,
              build_fused, args, kernel_native: bool = True,
              probe=None, require_measured: bool = False) -> bool:
        """One gate decision.  Resolution order: persisted TuningProfile hit
        (zero candidate compiles, zero probes) -> cost estimates -> the
        subclass ``_decide`` policy (base: estimate-only strict-less-than;
        AutotunePass: probe-measure inside the uncertainty band).  Fresh
        decisions are recorded back into the profile."""
        be = pctx.backend
        desc = self._desc(pctx)
        prof = desc.profile
        opk = (pattern, fused, unfused)
        bd = None
        if prof is not None:
            bd = _backend_gate_digest(be)
            hit = prof.lookup(bd, opk, GATE_MAXQ)
            if hit is not None:
                pctx.counters["profile_hits"] += 1
                d = dict(hit)
                d["source"] = "profile"
                pctx.decisions.append(d)
                return bool(d["accepted"])
            pctx.counters["profile_misses"] += 1
        est_u, err_u = _estimate(be, desc, unfused, build_unfused, args,
                                 counters=pctx.counters)
        est_f, err_f = _estimate(be, desc, fused, build_fused, args,
                                 counters=pctx.counters)
        d = self._decide(pctx, desc, est_u, est_f, build_unfused,
                         build_fused, probe, require_measured)
        errors = [f"{side}: {e}" for side, e in
                  (("unfused", err_u), ("fused", err_f)) if e is not None]
        if d.get("error"):
            errors.append(d["error"])
        if errors:
            pctx.counters["gate_errors"] += len(errors)
            d["error"] = "; ".join(errors)
        d.update({
            "pattern": pattern, "kernel_native": kernel_native,
            "unfused_key": unfused, "fused_key": fused,
            "unfused_proxy_s": None if est_u is None else est_u["time_proxy_s"],
            "fused_proxy_s": None if est_f is None else est_f["time_proxy_s"],
            "unfused_flops": None if est_u is None else est_u["flops_per_chip"],
            "unfused_bytes": None if est_u is None else est_u["bytes_per_chip"],
            "fused_flops": None if est_f is None else est_f["flops_per_chip"],
            "fused_bytes": None if est_f is None else est_f["bytes_per_chip"],
        })
        pctx.decisions.append(d)
        if prof is not None and not errors:   # a failure is not replayed
            prof.record(bd, opk, GATE_MAXQ, d)
        return d["accepted"]

    def _decide(self, pctx, desc, est_u, est_f, build_unfused, build_fused,
                probe, require_measured: bool = False) -> dict:
        """Static policy: accept iff the fused estimate prices *strictly*
        cheaper (lowering failure on either side -> never fuse blind; the
        gate records the failure as the decision's ``error``).
        Semantics-affecting candidates (``require_measured``) are never
        taken on estimates alone, so the static gate rejects them."""
        accepted = (not require_measured
                    and est_u is not None and est_f is not None
                    and est_f["time_proxy_s"] < est_u["time_proxy_s"])
        return {"accepted": accepted, "source": "estimate",
                "unfused_measured_s": None, "fused_measured_s": None}


class AutotunePass(FusionPass):
    """Measurement-driven fusion gate (opt-in: ``descriptor.autotune``).

    Two extensions over the static gate.  (1) When the estimated margin
    between the candidates, ``|fused - unfused| / unfused`` over the proxy
    times, is within ``descriptor.autotune_band`` — the regime where the
    static roofline is least trustworthy — both lowerings are wall-clock
    measured on a small concrete probe batch and the *measured* winner is
    recorded.  (2) Mixed-k ``linear()`` combinations, which the equivalence
    rewriter must skip (per-model truncation depths differ), are lowered to
    a single MultiRetrieve when — and only when — measured faster.  Either
    way the decision lands in the TuningProfile exactly like the static
    gate's, so the next compile replays it with zero probes."""
    name = "autotune"

    def _decide(self, pctx, desc, est_u, est_f, build_unfused, build_fused,
                probe, require_measured: bool = False) -> dict:
        d = super()._decide(pctx, desc, est_u, est_f, build_unfused,
                            build_fused, probe, require_measured)
        measure = require_measured
        if not measure and est_u is not None and est_f is not None:
            pu, pf = est_u["time_proxy_s"], est_f["time_proxy_s"]
            measure = pu > 0 and abs(pf - pu) / pu <= desc.autotune_band
        if not measure or probe is None:
            return d
        try:
            static_args, batched_args = probe(desc.probe_queries)
            m_u = _measure_callable(build_unfused(), static_args,
                                    batched_args, desc.probe_repeats)
            m_f = _measure_callable(build_fused(), static_args,
                                    batched_args, desc.probe_repeats)
        except Exception as e:     # keep the estimate, record the failure
            d["error"] = f"probe: {_error_line(e)}"
            return d
        pctx.counters["probe_measurements"] += 2
        d.update({"accepted": bool(m_f < m_u), "source": "measured",
                  "unfused_measured_s": m_u, "fused_measured_s": m_f})
        return d

    # -- IVF knob search: nprobe (and PQ candidate block on TPU) ------------
    def _tune_dense_knobs(self, op: Op, pctx: PassContext) -> Op:
        """Measured ``nprobe`` search around the configured value, on an
        already accepted fused dense stage.  Speed alone would always shrink
        ``nprobe`` (fewer lists scanned is strictly less work) and silently
        trash recall, so a candidate is eligible only if its top-K overlap
        against the *widest* candidate stays within the descriptor's
        ``autotune_band``; the fastest eligible candidate wins.  For PQ on a
        TPU backend the candidate-block size of the streaming ADC kernel is
        probed the same way (on CPU the reference path ignores it)."""
        import jax
        desc = self._desc(pctx)
        be = pctx.backend
        params = dict(op.params)
        nprobe = params.get("nprobe")
        if not nprobe:
            return op
        from repro.index import dense as DN
        pq = bool(params.get("pq"))
        K = params["k"]
        if pq:
            index = be.ivfpq
            refine = be.pq_refine
            sl = params.get("pq_shortlist")
            fn_for = lambda c: (lambda ix, q: DN.ivfpq_retrieve_topk_fused(
                ix, q, k=K, nprobe=c, refine=refine, shortlist=sl))
        else:
            index = be.ivf
            refine = None
            fn_for = lambda c: (lambda ix, q: DN.ivf_retrieve_topk_fused(
                ix, q, k=K, nprobe=c))
        npb = min(int(nprobe), index.n_lists)
        cands = sorted({max(1, npb // 2), npb,
                        min(2 * npb, index.n_lists)})
        chosen = self._probe_knob(
            pctx, pattern="nprobe_tune", knob="nprobe", configured=npb,
            cands=cands, index=index, fn_for=fn_for,
            extra_key=(pq, K, refine))
        if chosen is not None and chosen != params["nprobe"]:
            params["nprobe"] = chosen
            op = leaf(S.FusedDenseRetrieve(**params))
        if pq and jax.default_backend() == "tpu":
            from repro.kernels.pq_scoring.pq_scoring import BLOCK_C
            npb = min(int(params["nprobe"]), index.n_lists)
            sl = params.get("pq_shortlist")
            blk_for = lambda c: (
                lambda ix, q: DN.ivfpq_retrieve_topk_fused(
                    ix, q, k=K, nprobe=npb, refine=refine, block=c,
                    shortlist=sl))
            chosen_b = self._probe_knob(
                pctx, pattern="pq_block_tune", knob="pq_block",
                configured=params.get("pq_block") or BLOCK_C,
                cands=[BLOCK_C // 2, BLOCK_C, BLOCK_C * 2],
                index=index, fn_for=blk_for,
                extra_key=(params["nprobe"], K, refine))
            if chosen_b is not None and chosen_b != params.get("pq_block"):
                params["pq_block"] = chosen_b
                op = leaf(S.FusedDenseRetrieve(**params))
        return op

    def _probe_knob(self, pctx, *, pattern, knob, configured, cands,
                    index, fn_for, extra_key):
        """Measure each knob candidate on the concrete probe batch; return
        the fastest whose top-K doc overlap vs the widest candidate is >=
        1 - autotune_band (None = keep the configured value).  Decisions are
        persisted in the TuningProfile and replayed like gate decisions."""
        import numpy as np
        desc = self._desc(pctx)
        be = pctx.backend
        prof = desc.profile
        opk = (pattern, knob, tuple(cands), extra_key)
        bd = None
        if prof is not None:
            bd = _backend_gate_digest(be)
            hit = prof.lookup(bd, opk, GATE_MAXQ)
            if hit is not None:
                pctx.counters["profile_hits"] += 1
                d = dict(hit)
                d["source"] = "profile"
                pctx.decisions.append(d)
                return d.get("chosen")
            pctx.counters["profile_misses"] += 1
        if len(cands) < 2:
            return None
        import jax
        try:
            qvecs = _probe_qvecs(be, desc.probe_queries)
            times, docs = {}, {}
            for c in cands:
                vf = jax.jit(jax.vmap(fn_for(c), in_axes=(None, 0)))
                out = vf(index, qvecs)
                jax.block_until_ready(out)
                docs[c] = np.asarray(out[0])
                best = float("inf")
                for _ in range(max(desc.probe_repeats, 1)):
                    t0 = time.perf_counter()
                    jax.block_until_ready(vf(index, qvecs))
                    best = min(best, time.perf_counter() - t0)
                times[c] = best
        except Exception as e:     # keep the configured knob, record why
            pctx.counters["gate_errors"] += 1
            pctx.decisions.append({
                "pattern": pattern, "knob": knob, "configured": configured,
                "candidates": list(cands), "chosen": None,
                "accepted": False, "source": "measured",
                "error": f"probe: {_error_line(e)}"})
            return None
        pctx.counters["probe_measurements"] += len(cands)
        ref = docs[cands[-1]]

        def overlap(a):
            tot = 0.0
            for i in range(ref.shape[0]):
                want = {int(x) for x in ref[i] if x >= 0}
                got = {int(x) for x in a[i] if x >= 0}
                tot += len(want & got) / max(len(want), 1)
            return tot / max(ref.shape[0], 1)

        ovl = {c: overlap(docs[c]) for c in cands}
        floor = 1.0 - desc.autotune_band
        eligible = [c for c in cands if ovl[c] >= floor]
        chosen = min(eligible, key=lambda c: times[c]) if eligible \
            else cands[-1]
        d = {"pattern": pattern, "knob": knob, "configured": configured,
             "candidates": list(cands), "chosen": chosen,
             "accepted": bool(chosen != configured), "source": "measured",
             "measured_knob_s": {str(c): times[c] for c in cands},
             "overlap_at_k": {str(c): ovl[c] for c in cands},
             "kernel_native": True,
             "unfused_proxy_s": None, "fused_proxy_s": None,
             "unfused_measured_s": None, "fused_measured_s": None}
        pctx.decisions.append(d)
        if prof is not None:
            prof.record(bd, opk, GATE_MAXQ, d)
        return chosen

    def _tune_mixed_linear(self, op: Op, pctx: PassContext) -> Op:
        """Σ wᵢ·Retrieve(mᵢ, kᵢ) with *differing* kᵢ -> MultiRetrieve at
        max(kᵢ) when measured faster.  ``retrieve_multi`` combines the full
        dense score vectors before the final top-k (no per-model
        truncation), so the fused program is identical whatever the
        children's ks — but it is NOT equivalent to the truncating unfused
        sum, hence measured-only."""
        desc = self._desc(pctx)
        be = pctx.backend
        if not desc.supports("multi_model"):
            return op
        ks, models = [], []
        for c in op.inputs:
            if c.kind != "retrieve":
                return op
            ks.append(min(c.params["k"] or be.default_k, be.index.n_docs))
            models.append(c.params["model"])
        if len(models) < 2 or len(set(ks)) == 1:
            return op
        import jax.numpy as jnp

        from repro.index import retrieve as RT
        mtuple = tuple(models)
        weights = tuple(op.params["weights"])
        kmax = max(ks)
        mp = be.max_postings
        mw = jnp.asarray(weights, jnp.float32)

        def build_fused():
            def f(ix, t, w):
                return RT.retrieve_multi(ix, t, w, mw, models=mtuple,
                                         k=kmax, max_postings=mp)
            return f

        def build_unfused():
            def f(ix, t, w):
                return tuple(
                    RT.retrieve_topk(ix, t, w, model=m, k=kc,
                                     max_postings=mp)
                    for m, kc in zip(mtuple, ks))
            return f

        fused = leaf(S.MultiRetrieve(models=mtuple, weights=weights, k=kmax))
        if self._gate(pctx, "multi_mixed", kernel_native=True,
                      args=_abstract_args(be),
                      unfused=("multi_mixed_unfused", mtuple, tuple(ks), mp),
                      fused=("multi_mixed_fused", mtuple, weights, kmax, mp),
                      build_unfused=build_unfused, build_fused=build_fused,
                      probe=lambda n: ((be.index,), _probe_queries(be, n)),
                      require_measured=True):
            pctx.trace.append(("tune_multi_mixed", op, fused))
            return fused
        return op


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def default_passes(descriptor: BackendDescriptor | None = None,
                   max_rewrite_iters: int = 20) -> list[Pass]:
    """The standard pass pipeline, parameterised by the backend descriptor
    (None = resolve from the PassContext per run, the legacy behaviour).
    ``descriptor.autotune`` selects the measurement-driven fusion gate."""
    fusion_cls = AutotunePass if (descriptor is not None
                                  and descriptor.autotune) else FusionPass
    return [CanonicalizePass(), SchemaPass("schema_inference"),
            RewritePass(descriptor, max_iters=max_rewrite_iters), CSEPass(),
            fusion_cls(descriptor), SchemaPass("schema_check")]


def compile_pipeline(node: Transformer | Op, backend, *,
                     optimize: bool = True, trace: list | None = None,
                     cse_table: dict | None = None,
                     report: dict | None = None,
                     keep_snapshots: bool = False,
                     max_rewrite_iters: int = 20,
                     pctx: PassContext | None = None) -> Op:
    """Lower a pipeline to IR and (optionally) run the pass pipeline.

    ``optimize=False`` lowers only — exactly the seed's unoptimised
    semantics.  ``report`` (a dict, filled in place) receives per-pass
    timings and the fusion gate's decisions; ``cse_table`` may be shared
    across calls to intern ops across pipelines.
    """
    op = node if isinstance(node, Op) else lower(node)
    if not optimize:
        return op
    pctx = pctx or PassContext(backend, trace=trace, cse_table=cse_table,
                               keep_snapshots=keep_snapshots)
    passes = default_passes(pctx.descriptor,
                            max_rewrite_iters=max_rewrite_iters)
    op = PassManager(passes).run(op, pctx)
    if report is not None:
        report["pass_timings_s"] = list(pctx.timings)
        report["fusion_decisions"] = list(pctx.decisions)
        report["snapshots"] = list(pctx.snapshots)
        report["tuning"] = dict(pctx.counters)
    return op


def explain_pipeline(node: Transformer, backend=None, *,
                     optimize: bool = True) -> str:
    """Render the IR before/after each pass (``pipeline.explain()``)."""
    op = lower(node)
    if backend is None or not optimize:
        return "== lowered IR ==\n" + pretty(op, _safe_annotate(op, backend))
    pctx = PassContext(backend, keep_snapshots=True)
    compile_pipeline(op, backend, pctx=pctx, keep_snapshots=True)
    out = []
    prev_key = None
    for name, snap in pctx.snapshots:
        if prev_key is not None and snap.key() == prev_key:
            out.append(f"== after {name}: (unchanged)")
            continue
        prev_key = snap.key()
        head = "lowered IR" if name == "lower" else f"after {name}"
        out.append(f"== {head} ==\n" + pretty(snap, _safe_annotate(snap,
                                                                   backend)))
    for d in pctx.decisions:
        fmt = lambda v: "n/a" if v is None else f"{v:.4e}s"
        if d.get("knob"):
            out.append(
                f"-- autotune knob [{d['pattern']}]: "
                f"{d['knob']}={d['chosen']} "
                f"(configured {d['configured']}, "
                f"candidates {d['candidates']}, "
                f"{d.get('source', 'measured')})")
            continue
        line = (f"-- fusion gate [{d['pattern']}]: "
                f"{'fused' if d['accepted'] else 'kept unfused'} "
                f"(predicted fused {fmt(d['fused_proxy_s'])} vs "
                f"unfused {fmt(d['unfused_proxy_s'])}")
        if d.get("fused_measured_s") is not None:
            line += (f"; measured fused {fmt(d['fused_measured_s'])} vs "
                     f"unfused {fmt(d['unfused_measured_s'])}")
        line += f", {d.get('source', 'estimate')})"
        out.append(line)
    return "\n".join(out)


def _safe_annotate(op: Op, backend):
    try:
        return annotate(op, backend)
    except SchemaError:
        return None
