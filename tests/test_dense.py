"""Dense second-stage retrieval subsystem: fused-vs-unfused equivalence,
IVF recall against brute force, the cost gate's both branches, IR round-trip
key preservation for the dense ops, and engine==sequential equality."""
import numpy as np
import pytest

from repro.core import (BackendDescriptor, DenseRerank, DenseRetrieve,
                        FusedDenseRerank, FusedDenseRetrieve, JaxBackend,
                        Retrieve, ShardedQueryEngine, compile_pipeline,
                        lower, raise_ir)
from repro.core.transformer import Cutoff
from repro.index.dense import (build_ivf_index, build_ivfpq_index,
                               build_pq_codebook, dense_retrieve_exact,
                               ivf_retrieve_topk, ivfpq_retrieve_topk,
                               ivfpq_retrieve_topk_fused, pq_decode,
                               pq_encode, pq_store_bytes, shard_dense_index,
                               sharded_dense_topk)


def _dense_backend(env, default_k=60, extra=(), **kw):
    """Kernel-lowering-capable backend without dynamic pruning (keeps the
    sparse first stage exact, so dense equivalences are exact too)."""
    caps = frozenset({"fat", "fused_dense", "dense_topk"}) | set(extra)
    return JaxBackend(env["index"], default_k=default_k,
                      dense=env["backend"].dense,
                      descriptor=BackendDescriptor.default(caps), **kw)


# ---------------------------------------------------------------------------
# FusedDenseRerank == unfused retrieve >> dense_rerank % K (exact mode)
# ---------------------------------------------------------------------------

def test_fused_dense_rerank_matches_unfused(small_ir):
    be = _dense_backend(small_ir)
    pipe = (Retrieve("BM25", k=200) >> DenseRerank(alpha=0.3)) % 10
    rep = {}
    op = compile_pipeline(pipe, be, report=rep)
    assert op.kind == "fused_dense_rerank"
    assert isinstance(raise_ir(op), FusedDenseRerank)
    assert op.params == {"model": "BM25", "k_in": 200, "k": 10,
                         "alpha": 0.3}
    assert any(d["pattern"] == "dense_rerank" and d["accepted"]
               for d in rep["fusion_decisions"])
    Ro = pipe.transform(small_ir["Q"], backend=be, optimize=True)
    Ru = pipe.transform(small_ir["Q"], backend=be, optimize=False)
    np.testing.assert_array_equal(np.asarray(Ro["docids"]),
                                  np.asarray(Ru["docids"]))
    np.testing.assert_allclose(np.asarray(Ro["scores"]),
                               np.asarray(Ru["scores"]), rtol=1e-4,
                               atol=1e-5)


def test_dense_rerank_fusion_needs_capability(small_ir):
    """Without ``fused_dense`` the chain stays interpreted (and still agrees
    with itself under optimisation)."""
    be = JaxBackend(small_ir["index"], default_k=60,
                    dense=small_ir["backend"].dense,
                    descriptor=BackendDescriptor.default(frozenset({"fat"})))
    pipe = (Retrieve("BM25", k=200) >> DenseRerank(alpha=0.3)) % 10
    op = compile_pipeline(pipe, be)
    assert "fused_dense_rerank" not in _kinds(op)


def _kinds(op):
    out = [op.kind]
    for i in op.inputs:
        out.extend(_kinds(i))
    return out


# ---------------------------------------------------------------------------
# cost gate: both branches for the dense candidate-generation pattern
# ---------------------------------------------------------------------------

def test_dense_retrieve_gate_fuses_and_falls_back(small_ir):
    be = _dense_backend(small_ir, default_k=200)

    # deep dense retrieve + shallow cutoff: fused strictly cheaper
    rep1 = {}
    op1 = compile_pipeline(DenseRetrieve(k=200, nprobe=8) % 10, be,
                           report=rep1)
    assert op1.kind == "fused_dense_retrieve"
    assert isinstance(raise_ir(op1), FusedDenseRetrieve)

    # cutoff at the retrieve depth: the estimates tie and the gate keeps
    # the unfused interpreter path
    rep2 = {}
    op2 = compile_pipeline(DenseRetrieve(k=10, nprobe=8) % 10, be,
                           report=rep2)
    assert op2.kind == "cutoff"
    assert isinstance(raise_ir(op2), Cutoff)

    decided = [d["accepted"] for d in
               rep1["fusion_decisions"] + rep2["fusion_decisions"]]
    assert True in decided and False in decided

    for pipe in (DenseRetrieve(k=200, nprobe=8) % 10,
                 DenseRetrieve(k=10, nprobe=8) % 10,
                 DenseRetrieve(k=200, nprobe=0) % 10):
        Ro = pipe.transform(small_ir["Q"], backend=be, optimize=True)
        Ru = pipe.transform(small_ir["Q"], backend=be, optimize=False)
        np.testing.assert_array_equal(np.asarray(Ro["docids"]),
                                      np.asarray(Ru["docids"]))
        np.testing.assert_allclose(np.asarray(Ro["scores"]),
                                   np.asarray(Ru["scores"]), rtol=1e-5,
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# IVF recall vs brute force
# ---------------------------------------------------------------------------

def _recall(ivf_docs, brute_docs, k):
    hits = [len(set(a[a >= 0].tolist()) & set(b[b >= 0].tolist())) / k
            for a, b in zip(np.asarray(ivf_docs), np.asarray(brute_docs))]
    return float(np.mean(hits))


def test_ivf_recall_vs_brute_force(small_ir):
    be = small_ir["backend"]
    ivf = build_ivf_index(be.dense, n_lists=16, seed=0)
    qvecs = np.asarray(be.embed_queries(small_ir["Q"]))
    k = 10
    brute, full, half = [], [], []
    for qv in qvecs:
        brute.append(np.asarray(
            dense_retrieve_exact(be.dense, qv, k=k)[0]))
        full.append(np.asarray(
            ivf_retrieve_topk(ivf, qv, k=k, nprobe=ivf.n_lists)[0]))
        half.append(np.asarray(
            ivf_retrieve_topk(ivf, qv, k=k, nprobe=ivf.n_lists // 2)[0]))
    # probing every list scores every document: recall is exactly 1
    assert _recall(full, brute, k) >= 0.999
    # a half-width probe keeps most of the true top-k (loose floor: the
    # quantiser would have to be adversarially bad to miss half)
    assert _recall(half, brute, k) >= 0.5


def test_ivf_lists_partition_documents(small_ir):
    ivf = build_ivf_index(small_ir["backend"].dense, n_lists=16, seed=0)
    starts = np.asarray(ivf.list_start)
    assert starts[0] == 0 and starts[-1] == small_ir["index"].n_docs
    assert (np.diff(starts) >= 0).all()
    assert int(np.diff(starts).max()) == ivf.max_list_len
    assert sorted(np.asarray(ivf.doc_ids).tolist()) == \
        list(range(small_ir["index"].n_docs))


# ---------------------------------------------------------------------------
# IVF-PQ: reconstruction, ADC-vs-float parity, gate, doc-axis sharding
# ---------------------------------------------------------------------------

def test_pq_reconstruction_error_decreases_with_m(small_ir):
    """More subspaces -> finer quantisation -> lower reconstruction MSE
    (each subspace clusters a shorter slice with the same 256 codewords)."""
    emb = small_ir["backend"].dense.emb
    mses = []
    for m in (2, 4, 8, 16):
        cb = build_pq_codebook(emb, m=m, iters=8, seed=0)
        rec = np.asarray(pq_decode(cb, pq_encode(cb, emb)))
        mses.append(float(np.mean((np.asarray(emb) - rec) ** 2)))
    assert all(a > b for a, b in zip(mses, mses[1:])), mses


def test_ivfpq_adc_parity_and_recall(small_ir):
    """Two-level search contract: returned scores are *exact* float scores
    of the returned docs (the ADC stage only shortlists), full-probe
    recall@k clears the acceptance floor, and the fused kernel path is
    bit-identical to the unfused reference path."""
    be = small_ir["backend"]
    pqi = build_ivfpq_index(be.dense, n_lists=16, seed=0, m=8)
    emb = np.asarray(be.dense.emb)
    qvecs = np.asarray(be.embed_queries(small_ir["Q"]))
    k = 10
    recalls = []
    for qv in qvecs:
        docs, vals = ivfpq_retrieve_topk(pqi, qv, k=k, nprobe=pqi.n_lists)
        docs, vals = np.asarray(docs), np.asarray(vals)
        # ADC-vs-float parity: the final-K scores ARE the float scores
        np.testing.assert_allclose(vals, emb[docs] @ qv, rtol=1e-5,
                                   atol=1e-5)
        df, vf = ivfpq_retrieve_topk_fused(pqi, qv, k=k, nprobe=pqi.n_lists)
        np.testing.assert_array_equal(np.asarray(df), docs)
        np.testing.assert_array_equal(np.asarray(vf), vals)
        brute = np.asarray(dense_retrieve_exact(be.dense, qv, k=k)[0])
        recalls.append(len(set(docs.tolist()) & set(brute.tolist())) / k)
    assert float(np.mean(recalls)) >= 0.8, recalls


def test_ivfpq_store_compresses_4x(small_ir):
    be = small_ir["backend"]
    pqi = build_ivfpq_index(be.dense, n_lists=16, seed=0, m=8)
    flat = be.dense.emb.size * be.dense.emb.dtype.itemsize
    assert pq_store_bytes(pqi) * 4 <= flat


def test_ivf_keep_flat_false_drops_float_copy(small_ir):
    be = small_ir["backend"]
    ivf = build_ivf_index(be.dense, n_lists=16, seed=0, keep_flat=False)
    assert ivf.emb is None
    with pytest.raises(ValueError):
        ivf_retrieve_topk(ivf, np.zeros(be.dense.dim, np.float32), k=5,
                          nprobe=4)
    # the PQ index built over the skeleton shares the doc-order float
    # store by reference (no list-ordered duplicate is ever materialised)
    pqi = build_ivfpq_index(be.dense, n_lists=16, seed=0, m=8, ivf=ivf)
    assert pqi.emb is be.dense.emb


def test_pq_gate_both_branches(small_ir):
    """The pq_topk cost gate takes the fused kernel lowering for a deep
    retrieve + shallow cutoff and keeps the unfused chain when the
    estimates tie — and the fused rewrite is exact either way."""
    be = _dense_backend(small_ir, default_k=200, extra={"pq_topk"}, pq_m=8)

    rep1 = {}
    op1 = compile_pipeline(DenseRetrieve(k=200, nprobe=8, pq=True) % 10, be,
                           report=rep1)
    assert op1.kind == "fused_dense_retrieve"
    assert op1.params["pq"] is True
    assert op1.params["pq_shortlist"] is not None

    rep2 = {}
    op2 = compile_pipeline(DenseRetrieve(k=10, nprobe=8, pq=True) % 10, be,
                           report=rep2)
    assert op2.kind == "cutoff"

    pq_ds = [d for d in rep1["fusion_decisions"] + rep2["fusion_decisions"]
             if d["pattern"] == "pq_topk"]
    assert [d["accepted"] for d in pq_ds] == [True, False]

    for pipe in (DenseRetrieve(k=200, nprobe=8, pq=True) % 10,
                 DenseRetrieve(k=10, nprobe=8, pq=True) % 10):
        Ro = pipe.transform(small_ir["Q"], backend=be, optimize=True)
        Ru = pipe.transform(small_ir["Q"], backend=be, optimize=False)
        np.testing.assert_array_equal(np.asarray(Ro["docids"]),
                                      np.asarray(Ru["docids"]))
        np.testing.assert_allclose(np.asarray(Ro["scores"]),
                                   np.asarray(Ru["scores"]), rtol=1e-5,
                                   atol=1e-6)


def test_pq_fusion_needs_capability(small_ir):
    """Without ``pq_topk`` the pq chain stays interpreted even though
    ``dense_topk`` is on."""
    be = _dense_backend(small_ir, default_k=200, pq_m=8)
    op = compile_pipeline(DenseRetrieve(k=200, nprobe=8, pq=True) % 10, be)
    assert "fused_dense_retrieve" not in _kinds(op)


def test_nprobe_autotune_measures_then_replays(small_ir):
    """AutotunePass probes the nprobe candidates (wall-clock + overlap
    band) on the first compile and replays the persisted choice with zero
    probe measurements on the second."""
    from repro.core import BackendDescriptor, TuningProfile

    caps = frozenset({"fat", "fused_dense", "dense_topk", "pq_topk"})
    desc = (BackendDescriptor.default(caps)
            .with_autotune(True, probe_queries=2, probe_repeats=1)
            .with_profile(TuningProfile(path=None)))
    be = JaxBackend(small_ir["index"], default_k=200,
                    dense=small_ir["backend"].dense, descriptor=desc,
                    pq_m=8)
    pipe = DenseRetrieve(k=200, nprobe=8, pq=True) % 10
    rep1 = {}
    op1 = compile_pipeline(pipe, be, report=rep1)
    knobs = [d for d in rep1["fusion_decisions"] if d.get("knob") == "nprobe"]
    if not knobs:        # the gate kept the unfused chain: nothing to tune
        pytest.skip("pq fusion not taken on this host; no knob to tune")
    d = knobs[0]
    assert d["source"] == "measured"
    assert d["chosen"] in d["candidates"]
    assert set(d["overlap_at_k"]) == {str(c) for c in d["candidates"]}
    assert op1.params["nprobe"] == d["chosen"]
    rep2 = {}
    op2 = compile_pipeline(pipe, be, report=rep2)
    assert op2.params == op1.params
    knobs2 = [d2 for d2 in rep2["fusion_decisions"]
              if d2.get("knob") == "nprobe"]
    assert knobs2 and knobs2[0]["source"] == "profile"
    assert rep2["tuning"]["probe_measurements"] == 0


@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_doc_shard_merge_matches_single_shard_oracle(small_ir, n_shards):
    """Per-shard top-k + cross-shard merge through the engine returns the
    single-shard run's doc ids exactly, and its scores to within the
    float32 reordering bound of a dot product (the traced lax merge agrees
    the same way).  XLA blocks the ``[N, dim] @ [dim]`` product by the row
    count N, so a row's dot product can be summed in a different order in
    a shard than in the whole index (measured: 1.19e-7, one ULP at 1.0).
    Two summation orders of a length-d dot product of unit vectors differ
    by at most ``2 * d * 2**-24``."""
    import jax
    import jax.numpy as jnp

    from repro.core.engine import StageProgram
    from repro.launch.mesh import make_query_mesh

    be = small_ir["backend"]
    dense = be.dense
    qvecs = be.embed_queries(small_ir["Q"])
    k = 10
    eng = ShardedQueryEngine(mesh=make_query_mesh(doc_shards=1))

    def progs_for(s):
        out = []
        for shard, off in shard_dense_index(dense, s):
            ks = min(k, int(shard.emb.shape[0]))
            fn = (lambda sh, o, kk: (lambda qv: (
                (lambda dv: (dv[0] + jnp.int32(o), dv[1]))(
                    dense_retrieve_exact(sh, qv, k=kk)))))(shard, off, ks)
            out.append(StageProgram(key=("t_shard", s, off), fn=fn))
        return out

    oracle = eng.run_doc_sharded(progs_for(1), None, qvecs, k=k)
    docs, vals = eng.run_doc_sharded(progs_for(n_shards), None, qvecs, k=k)
    reorder = 2 * dense.dim * 2.0 ** -24
    np.testing.assert_array_equal(docs, oracle[0])
    np.testing.assert_allclose(vals, oracle[1], rtol=0, atol=reorder)

    shards = shard_dense_index(dense, n_shards)
    dt, vt = jax.jit(jax.vmap(
        lambda q: sharded_dense_topk(shards, q, k=k)))(qvecs)
    np.testing.assert_array_equal(np.asarray(dt), oracle[0])
    np.testing.assert_allclose(np.asarray(vt), oracle[1], rtol=0,
                               atol=reorder)


# ---------------------------------------------------------------------------
# IR round trip preserves key() for the dense ops
# ---------------------------------------------------------------------------

def _dense_pipelines():
    return [
        DenseRetrieve(k=20, nprobe=4),
        DenseRetrieve(k=30, nprobe=0) % 5,
        (Retrieve("BM25", k=30) >> DenseRerank(alpha=0.2)) % 10,
        FusedDenseRetrieve(k=5, nprobe=2),
        FusedDenseRerank(model="BM25", k_in=30, k=5, alpha=0.1),
    ]


@pytest.mark.parametrize("i", range(5))
def test_dense_lower_raise_preserves_key(i):
    pipe = _dense_pipelines()[i]
    op = lower(pipe)
    assert op.key() == pipe.key()
    raised = raise_ir(op)
    assert raised is pipe
    assert raised.key() == pipe.key()


# ---------------------------------------------------------------------------
# engine == sequential for dense pipelines
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make_pipe", [
    lambda: (Retrieve("BM25", k=60) >> DenseRerank(alpha=0.3)) % 10,
    lambda: DenseRetrieve(k=20, nprobe=4),
], ids=["fused_dense_rerank", "dense_retrieve"])
def test_dense_engine_matches_sequential(small_ir, make_pipe):
    env = small_ir
    ivf = build_ivf_index(env["backend"].dense, n_lists=16, seed=0)
    be_seq = _dense_backend(env, sharded=False, ivf=ivf)
    be_eng = _dense_backend(env, ivf=ivf)
    assert be_eng.engine is not None
    pipe = make_pipe()
    Rs = pipe.transform(env["Q"], backend=be_seq, optimize=True)
    Re = pipe.transform(env["Q"], backend=be_eng, optimize=True)
    np.testing.assert_array_equal(np.asarray(Rs["docids"]),
                                  np.asarray(Re["docids"]))
    np.testing.assert_allclose(np.asarray(Rs["scores"]),
                               np.asarray(Re["scores"]), rtol=1e-5,
                               atol=1e-6)
