"""Bring-up smoke test: the served retrieval path on one TPU chip.

One process drives the system through the entry points a user calls
(``JaxBackend``, ``compile_pipeline``, ``PipelineServer``, ``run_pipeline``)
at the scale of a Robust04-sized deployment, and checks every answer:

  a. device   JAX must find a TPU; prints its kind, count, JAX version.
  b. index    synthetic corpus with Robust04's shape (528,155 docs, 200k
              vocabulary, mean length 300), inverted index, 64-d dense
              store and IVF-PQ index, all on the device.
  c. compile  BM25 % 10, BM25 >> dense rerank % 10 and IVF-PQ dense
              retrieval % 10 through the fusion gate: no gate decision may
              carry an error, and each is a cost verdict over two compiled
              candidates (the gate may decline a kernel lowering on cost).
              Each fused stage (the Pallas kernel path) runs on the device
              against its reference, and its engine program must hold the
              kernel (``tpu_custom_call``).
  d. serve    one PipelineServer with the first two pipelines as tenants;
              64 single-query requests to each.  BM25 top-10 must equal a
              host numpy BM25 over the same postings; the rerank top-10
              must equal the unfused chain on the device; no recompiles
              after warm-up.
  e. RAG      ... >> Generate with the qwen2-1.5b architecture at its
              published widths (random weights from ``--seed``), 16
              requests through continuous-batched decode; served tokens
              must equal the offline ``run_pipeline`` oracle; no recompiles
              after warm-up.

``--chips 4`` runs only the multi-chip path: BM25 % 10 and dense rerank
% 10 through a 4-device engine against a 1-device engine, and
``engine.run_doc_sharded`` on a (2, 2) mesh against the single-shard run.

Times printed on the way are single cold runs (compilation included), not
benchmark numbers.  The last line of stdout is exactly one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.

    python chip_smoke.py                 # one TPU chip
    python chip_smoke.py --chips 4       # a four-chip host
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse      # small, on CPU
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (BackendDescriptor, DenseRerank, DenseRetrieve,
                        JaxBackend, Retrieve, ShardedQueryEngine,
                        compile_pipeline, ir, make_queries, run_pipeline)
from repro.core.descriptor import DEFAULT_CAPABILITIES
from repro.core.engine import StageProgram
from repro.core.stages import (FusedDenseRerank, FusedDenseRetrieve,
                               FusedTopKRetrieve, Generate)
from repro.index import build_index, synthesize_corpus, synthesize_topics
from repro.launch.cache import use_compile_cache
from repro.serve import PipelineServer, ServeConfig

#: Robust04-shaped deployment (benchmarks/ir_bench.py::build_robust_env)
FULL = dict(n_docs=528_155, vocab=200_000, mean_len=300, n_topics=64,
            rag_requests=16, max_prompt_len=512, max_new_tokens=32)
#: --chips 4 checks the mesh, not the scale: a quarter of the documents
#: keeps the index build out of most of a four-chip call
FOUR_CHIPS = dict(FULL, n_docs=132_000)
#: --rehearse: every size cut so the whole run fits a CPU in a minute
REHEARSE = dict(n_docs=20_000, vocab=40_000, mean_len=100, n_topics=16,
                rag_requests=4, max_prompt_len=64, max_new_tokens=8)
K = 10
K_IN = 1000
ALPHA = 0.3


class SmokeFailure(AssertionError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@contextlib.contextmanager
def phase(name: str):
    t0 = time.perf_counter()
    log(f"[{name}] start")
    yield
    log(f"[{name}] ok ({time.perf_counter() - t0:.1f} s, single cold run)")


# ---------------------------------------------------------------------------
# references
# ---------------------------------------------------------------------------

def numpy_bm25(post: dict, terms, weights):
    """BM25 of one query against every document, in float64, straight from
    the postings arrays on the host."""
    from repro.index.scoring import BM25_B, BM25_K1
    n, avg = post["n_docs"], post["avg_doclen"]
    s = np.zeros(n)
    for t, w in zip(terms, weights):
        if t < 0:
            continue
        lo, hi = post["term_start"][t], post["term_start"][t + 1]
        docs = post["doc_ids"][lo:hi]
        tf = post["tfs"][lo:hi].astype(np.float64)
        ok = docs >= 0
        docs, tf = docs[ok], tf[ok]
        df = float(post["df"][t])
        idf = np.log1p((n - df + 0.5) / (df + 0.5))
        norm = BM25_K1 * (1 - BM25_B + BM25_B * post["doc_len"][docs] / avg)
        s[docs] += w * idf * tf * (BM25_K1 + 1.0) / (tf + norm)
    return s


def check_topk(label, got_d, got_s, ref_d, ref_s, *, tol):
    """Same top-k up to ties: position-wise equal scores (within ``tol``),
    equal doc sets except documents tied with the k-th score."""
    got_d, got_s = np.asarray(got_d).ravel(), np.asarray(got_s).ravel()
    ref_d, ref_s = np.asarray(ref_d).ravel(), np.asarray(ref_s).ravel()
    require(got_d.shape == ref_d.shape, f"{label}: shape {got_d.shape} "
            f"vs {ref_d.shape}")
    require(np.all(np.isfinite(got_s)), f"{label}: non-finite scores")
    require(np.all(np.diff(got_s) <= tol), f"{label}: not sorted")
    bad = np.abs(got_s - ref_s) > tol
    require(not bad.any(), f"{label}: scores {got_s[bad]} vs {ref_s[bad]}")
    kth = ref_s[-1]
    for d in set(got_d.tolist()) ^ set(ref_d.tolist()):
        s = got_s[got_d == d] if d in got_d else ref_s[ref_d == d]
        require(abs(float(s[0]) - kth) <= tol,
                f"{label}: doc {d} (score {float(s[0])}) differs and is no "
                f"tie with the k-th score {kth}")


def check_bm25(label, got_d, got_s, post, terms, weights):
    s = numpy_bm25(post, terms, weights)
    order = np.lexsort((np.arange(s.size), -s))[:K]
    tol = 1e-5 * max(1.0, abs(float(s[order[0]])))
    got_d = np.asarray(got_d).ravel()
    require(np.all(np.abs(s[got_d] - np.asarray(got_s).ravel()) <= tol),
            f"{label}: served scores differ from numpy BM25 of the same docs")
    check_topk(label, got_d, got_s, order, s[order], tol=tol)


def tol_for(scores) -> float:
    return 1e-5 * max(1.0, float(np.max(np.abs(np.asarray(scores)))))


def rows(Q, idx):
    return {k: np.asarray(v)[idx] for k, v in Q.items()}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(args):
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    log(f"[a] platform={info['platform']} kind={info['kind']} "
        f"count={info['count']} jax={jax.__version__}")
    if not args.rehearse:
        require(dev.platform == "tpu",
                f"no TPU: JAX runs on {dev.platform!r} (use --rehearse for "
                f"a small run on the CPU)")
    require(info["count"] >= args.chips,
            f"--chips {args.chips} needs {args.chips} devices, JAX has "
            f"{info['count']}")
    return info


def phase_index(size, seed, *, with_pq=True):
    t0 = time.perf_counter()
    corpus = synthesize_corpus(n_docs=size["n_docs"], vocab=size["vocab"],
                               mean_len=size["mean_len"], seed=seed)
    topics = synthesize_topics(corpus, n_topics=size["n_topics"], q_len=3,
                               seed=seed + 1)
    index = build_index(corpus)
    del corpus
    # the default descriptor less the RQ1 pruned rewrite: it would take
    # Retrieve % K (approximately, by block-max pruning) before the kernel
    # lowering gate sees it
    desc = BackendDescriptor.default(DEFAULT_CAPABILITIES - {"pruned_topk"})
    be = JaxBackend(index, descriptor=desc, seed=seed)
    pq = be.ivfpq if with_pq else None
    jax.block_until_ready((index, be.dense.emb, pq))
    build_s = time.perf_counter() - t0
    nbytes = lambda tree: sum(x.nbytes for x in jax.tree.leaves(tree))
    log(f"[b] {index.n_docs} docs, vocab {index.vocab}, "
        f"{int(index.doc_ids.shape[0])} posting slots, max posting list "
        f"{be.max_postings}; built in {build_s:.1f} s")
    pq_bytes = ("not built" if pq is None else
                nbytes((pq.codes, pq.doc_ids, pq.list_start, pq.centroids)))
    log(f"[b] device bytes: inverted index {nbytes(index)}, dense store "
        f"{nbytes(be.dense.emb)}, IVF-PQ codes+lists {pq_bytes}")
    Q = make_queries(np.asarray(topics.terms), np.asarray(topics.weights),
                     np.asarray(topics.qids))
    return be, Q


def pipelines(be, names=("bm25", "rerank", "pq")):
    """name -> (pipeline, gate pattern, fused stage the gate must choose,
    unfused reference chain or None for the numpy BM25 reference)."""
    out = {}
    if "bm25" in names:
        out["bm25"] = (Retrieve("BM25") % K, "topk",
                       FusedTopKRetrieve(model="BM25", k=K), None)
    if "rerank" in names:
        chain = Retrieve("BM25", k=K_IN) >> DenseRerank(alpha=ALPHA) % K
        out["rerank"] = (chain, "dense_rerank",
                         FusedDenseRerank(model="BM25", k_in=K_IN, k=K,
                                          alpha=ALPHA), chain)
    if "pq" in names:
        # the gate keeps the unfused chain's ADC shortlist depth, so the
        # fused stage is an exact rewrite of DenseRetrieve(k=200) % 10
        pqi = be.ivfpq
        n_cand = min(8, pqi.n_lists) * pqi.max_list_len
        shortlist = max(200, min(be.pq_refine * 200, n_cand))
        chain = DenseRetrieve(k=200, nprobe=8, pq=True) % K
        out["pq"] = (chain, "pq_topk",
                     FusedDenseRetrieve(k=K, nprobe=8, pq=True,
                                        pq_shortlist=shortlist), chain)
    return out


def bm25_postings(be):
    post = {f: np.asarray(getattr(be.index, f))
            for f in ("term_start", "doc_ids", "tfs", "doc_len", "df")}
    post.update(n_docs=be.index.n_docs, avg_doclen=be.index.avg_doclen)
    return post


def phase_compile(be, Q, on_tpu):
    # one batch at the largest ladder rung
    n = min(be.engine.ladder[-1], int(np.asarray(Q["qid"]).shape[0]))
    Qn = rows(Q, slice(0, n))
    post = bm25_postings(be)
    for name, (pipe, pattern, fused, unfused) in pipelines(be).items():
        rep = {}
        op = compile_pipeline(pipe, be, report=rep)
        errors = [d["error"] for d in rep["fusion_decisions"]
                  if d.get("error")]
        require(not errors, f"{name}: gate errors {errors}")
        (d,) = [d for d in rep["fusion_decisions"] if d["pattern"] == pattern]
        kinds = [o.kind for o in ir.chain(op)]
        log(f"[c] {name}: gate {'accepted' if d['accepted'] else 'declined'}"
            f" {fused.kind} (proxy fused {d['fused_proxy_s']} s vs unfused "
            f"{d['unfused_proxy_s']} s); compiled chain {kinds}")
        # a decline must be a cost verdict, with both candidates compiled
        # and priced; an acceptance must leave exactly the fused stage
        require(d["fused_proxy_s"] is not None
                and d["unfused_proxy_s"] is not None,
                f"{name}: a gate candidate was not priced")
        require(not d["accepted"] or ir.raise_ir(op).key() == fused.key(),
                f"{name}: the gate accepted {fused.kind} but compiled {kinds}")
        # the fused stage on the device (the compiled pipeline itself when
        # the gate chose it), against its reference
        got = run_pipeline(fused, Qn, backend=be, optimize=False)
        if unfused is None:
            for i in range(n):
                check_bm25(f"{name} fused q{i}", got["docids"][i],
                           got["scores"][i], post, np.asarray(Qn["terms"][i]),
                           np.asarray(Qn["weights"][i]))
        else:
            ref = run_pipeline(unfused, Qn, backend=be, optimize=False)
            tol = tol_for(ref["scores"])
            for i in range(n):
                check_topk(f"{name} fused q{i}", got["docids"][i],
                           got["scores"][i], ref["docids"][i],
                           ref["scores"][i], tol=tol)
        if on_tpu:
            text = be.engine.compiled_text((be.uid, fused.key()),
                                           be.engine.select_bucket(n))
            require(text is not None and "tpu_custom_call" in text,
                    f"{name}: the fused stage's program holds no Pallas "
                    f"kernel")
        log(f"[c] {name}: {fused.kind} matches "
            f"{'numpy BM25' if unfused is None else 'its unfused chain'} on "
            f"{n} queries; kernel in program: "
            f"{'yes' if on_tpu else 'not checked (no TPU)'}")


def phase_serve(be, Q):
    pipes = pipelines(be)
    server = PipelineServer(pipes["bm25"][0], be, ServeConfig.default(),
                            name="bm25")
    server.add_pipeline(pipes["rerank"][0], name="rerank")
    warm = server.warmup(rows(Q, slice(0, 1)))
    log(f"[d] warm-up {warm['warmup_s']} s, {warm['compiles']} compiles, "
        f"tenants {warm['pipelines']}")
    n = int(np.asarray(Q["qid"]).shape[0])
    t0 = time.perf_counter()
    reqs = {name: [server.submit_one(rows(Q, slice(i, i + 1)),
                                     pipeline=name, timeout_ms=None)
                   for i in range(n)] for name in ("bm25", "rerank")}
    server.pump()
    out = {name: [r.wait(60.0) for r in rs] for name, rs in reqs.items()}
    wall = time.perf_counter() - t0
    st = server.stats()
    log(f"[d] served {st['served']} requests in {st['batches']} batches, "
        f"{wall:.2f} s wall (single cold run, not a benchmark)")
    require(st["recompiles_since_warmup"] == 0,
            f"{st['recompiles_since_warmup']} recompiles after warm-up")
    post = bm25_postings(be)
    terms, weights = np.asarray(Q["terms"]), np.asarray(Q["weights"])
    for i, r in enumerate(out["bm25"]):
        check_bm25(f"bm25 q{i}", r["docids"], r["scores"], post, terms[i],
                   weights[i])
    ref = run_pipeline(pipes["rerank"][3], Q, backend=be, optimize=False)
    tol = tol_for(ref["scores"])
    for i, r in enumerate(out["rerank"]):
        check_topk(f"rerank q{i}", r["docids"], r["scores"],
                   ref["docids"][i], ref["scores"][i], tol=tol)
    log(f"[d] bm25 top-{K} == numpy BM25 and rerank top-{K} == unfused "
        f"chain for {n} queries each; 0 recompiles after warm-up")


def phase_rag(be, Q, size, seed, rehearse):
    from repro.configs import qwen2_1_5b
    cfg = qwen2_1_5b.reduced()[0] if rehearse else qwen2_1_5b.model_cfg()
    t0 = time.perf_counter()
    be.register_lm(cfg.name, cfg, seed=seed)
    _, params = be.lm(cfg.name)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"[e] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"GQA {cfg.n_q}/{cfg.n_kv}, vocab {cfg.vocab}, {n_params} params "
        f"({sum(x.nbytes for x in jax.tree.leaves(params))} bytes), "
        f"initialised in {time.perf_counter() - t0:.1f} s")
    rag = (Retrieve("BM25", k=K_IN) >> DenseRerank(alpha=ALPHA) % 8
           >> Generate(cfg.name, max_new_tokens=size["max_new_tokens"],
                       max_prompt_len=size["max_prompt_len"], prompt_docs=3))
    server = PipelineServer(rag, be, ServeConfig.default().with_decode(8),
                            name="rag")
    warm = server.warmup(rows(Q, slice(0, 1)))
    log(f"[e] warm-up {warm['warmup_s']} s")
    n = size["rag_requests"]
    t0 = time.perf_counter()
    reqs = [server.submit_one(rows(Q, slice(i, i + 1)), timeout_ms=None)
            for i in range(n)]
    server.pump()
    served = [r.wait(120.0) for r in reqs]
    st = server.stats()
    log(f"[e] {st['decode']['requests']} requests, {st['decode']['tokens']} "
        f"tokens in {time.perf_counter() - t0:.2f} s wall (single cold run, "
        f"not a benchmark)")
    require(st["recompiles_since_warmup"] == 0,
            f"{st['recompiles_since_warmup']} recompiles after warm-up")
    oracle = run_pipeline(rag, rows(Q, slice(0, n)), backend=be)
    want = np.asarray(oracle["tokens"])
    for i, r in enumerate(served):
        got = np.asarray(r["tokens"]).ravel()
        require(np.array_equal(got, want[i]),
                f"rag q{i}: served tokens {got.tolist()} != oracle "
                f"{want[i].tolist()}")
    log(f"[e] served tokens == run_pipeline oracle for {n} requests "
        f"({want.shape[1]} tokens each); 0 recompiles after warm-up")


def phase_four_chips(be, Q):
    """BM25 and dense rerank through a 4-device engine vs a 1-device one,
    and the doc-sharded dense top-k on a (2, 2) mesh vs one shard."""
    from repro.index.dense import dense_retrieve_exact, shard_dense_index
    from repro.launch.mesh import make_query_mesh
    log(f"[4] query mesh {dict(be.engine.mesh.shape)}")
    eng4, eng1 = be.engine, ShardedQueryEngine(max_devices=1)
    for name, (pipe, *_rest) in pipelines(be, ("bm25", "rerank")).items():
        got = run_pipeline(pipe, Q, backend=be)
        # the same backend (and gate decisions) on a one-device engine
        be.engine = eng1
        ref = run_pipeline(pipe, Q, backend=be)
        be.engine = eng4
        tol = tol_for(ref["scores"])
        for i in range(int(np.asarray(Q["qid"]).shape[0])):
            check_topk(f"4-device {name} q{i}", got["docids"][i],
                       got["scores"][i], ref["docids"][i], ref["scores"][i],
                       tol=tol)
        log(f"[4] {name}: 4-device engine == 1-device engine")
    qvecs = be.embed_queries(Q)

    def programs(n_shards):
        progs = []
        for shard, off in shard_dense_index(be.dense, n_shards):
            def fn(qv, shard=shard, off=off):
                d, v = dense_retrieve_exact(shard, qv, k=K)
                return d + jnp.int32(off), v
            progs.append(StageProgram(key=("doc_shard", n_shards, off),
                                      fn=fn))
        return progs

    eng = ShardedQueryEngine(mesh=make_query_mesh(doc_shards=2))
    log(f"[4] doc-sharded mesh {dict(eng.mesh.shape)}")
    docs, vals = eng.run_doc_sharded(programs(2), None, qvecs, k=K)
    odocs, ovals = eng1.run_doc_sharded(programs(1), None, qvecs, k=K)
    reorder = 2 * be.dense.dim * 2.0 ** -24     # see tests/test_dense.py
    require(np.array_equal(docs, np.asarray(odocs)),
            "doc-sharded ids differ from the single-shard run")
    require(np.all(np.abs(vals - np.asarray(ovals)) <= reorder),
            "doc-sharded scores differ from the single-shard run")
    log("[4] run_doc_sharded on (2, 2) == single-shard run")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip path")
    ap.add_argument("--rehearse", action="store_true",
                    help="small sizes, any JAX device (CPU rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    size = REHEARSE if args.rehearse else FULL
    try:
        use_compile_cache()
        with phase("a. device"):
            info = phase_device(args)
        on_tpu = info["platform"] == "tpu"
        with phase("b. corpus and index"):
            if args.rehearse:
                log(f"[b] --rehearse: {size['n_docs']} docs instead of "
                    f"{FULL['n_docs']}")
            if args.chips == 4 and not args.rehearse:
                size = FOUR_CHIPS
                log(f"[b] --chips 4: {size['n_docs']} docs instead of "
                    f"{FULL['n_docs']}")
            be, Q = phase_index(size, args.seed, with_pq=args.chips == 1)
        if args.chips == 4:
            with phase("4. four-chip engine and doc sharding"):
                phase_four_chips(be, Q)
        else:
            with phase("c. compile"):
                phase_compile(be, Q, on_tpu)
            with phase("d. serve"):
                phase_serve(be, Q)
            with phase("e. RAG"):
                phase_rag(be, Q, size, args.seed, args.rehearse)
    except Exception:
        traceback.print_exc()
        log("FAILED")
        return 1
    log(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
