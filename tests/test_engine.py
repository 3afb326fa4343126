"""Sharded query execution engine: sequential-equivalence, bucket-ladder
recompile bounds, chunk planning, async plan execution, rewrite soundness
under sharded execution."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:      # property tests skip; fallbacks below run
    HAVE_HYPOTHESIS = False

from repro.core import (BackendDescriptor, DenseRerank, Experiment, Extract,
                        ExperimentPlan, FusedTopKRetrieve, JaxBackend,
                        Retrieve, RM3Expand, SDMRewrite, ShardedQueryEngine,
                        default_bucket_ladder)
from repro.core.compiler import Context
from repro.core.data import make_queries
from repro.core.engine import StageProgram


def _seq_backend(env):
    return JaxBackend(env["index"], default_k=60, query_chunk=4,
                      dense=env["backend"].dense, sharded=False)


def _tiled_queries(env, nq):
    terms = np.tile(np.asarray(env["Q"]["terms"]), (nq // 8 + 1, 1))[:nq]
    weights = np.tile(np.asarray(env["Q"]["weights"]), (nq // 8 + 1, 1))[:nq]
    return make_queries(terms, weights)


PIPELINES = [
    Retrieve("BM25", k=20),
    Retrieve("BM25", k=20) >> Extract("QL"),
    Retrieve("BM25", k=20) >> RM3Expand(fb_terms=5, fb_docs=5)
    >> Retrieve("BM25", k=10),
    SDMRewrite() >> Retrieve("QL", k=15),
    Retrieve("BM25", k=20) >> DenseRerank(alpha=0.5),
]


# ---------------------------------------------------------------------------
# engine == sequential path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("i", range(len(PIPELINES)))
def test_engine_matches_sequential(small_ir, i):
    env = small_ir
    pipe = PIPELINES[i]
    Re = pipe.transform(env["Q"], backend=env["backend"], optimize=False)
    Rs = pipe.transform(env["Q"], backend=_seq_backend(env), optimize=False)
    np.testing.assert_array_equal(np.asarray(Re["docids"]),
                                  np.asarray(Rs["docids"]))
    np.testing.assert_allclose(np.asarray(Re["scores"]),
                               np.asarray(Rs["scores"]), rtol=1e-6)


def _check_engine_matches_sequential_at(env, nq):
    """Padding/bucketing must be invisible at every query-set size."""
    Q = _tiled_queries(env, nq)
    pipe = Retrieve("BM25", k=10) >> Extract("QL")
    Re = pipe.transform(Q, backend=env["backend"], optimize=False)
    Rs = pipe.transform(Q, backend=_seq_backend(env), optimize=False)
    assert np.asarray(Re["docids"]).shape[0] == nq
    np.testing.assert_array_equal(np.asarray(Re["docids"]),
                                  np.asarray(Rs["docids"]))
    np.testing.assert_allclose(np.asarray(Re["features"]),
                               np.asarray(Rs["features"]), rtol=1e-6)


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(st.integers(min_value=1, max_value=40))
    def test_engine_matches_sequential_any_size(small_ir, nq):
        _check_engine_matches_sequential_at(small_ir, nq)


# deterministic fallbacks: bucket boundaries, tails, multi-chunk sizes
@pytest.mark.parametrize("nq", [1, 7, 8, 9, 32, 33, 40])
def test_engine_matches_sequential_sizes_fixed(small_ir, nq):
    _check_engine_matches_sequential_at(small_ir, nq)


def test_optimized_pipelines_match_under_sharded_execution(small_ir):
    """The paper's core equivalence claim must survive the engine: rewritten
    and unrewritten pipelines agree when executed sharded (exact-equality
    pipelines only — pruning rewrites are approximate by design)."""
    env = small_ir
    be = JaxBackend(env["index"], default_k=60, dense=env["backend"].dense,
                    descriptor=BackendDescriptor.default(
                        frozenset({"fat", "multi_model"})))
    for pipe in [(Retrieve("BM25", k=30) >> SDMRewrite()) % 10,
                 Retrieve("BM25", k=20) >> Extract("QL") >> Extract("TF_IDF"),
                 (Retrieve("BM25", k=30) >> RM3Expand(fb_docs=5)) % 10]:
        Ro = pipe.transform(env["Q"], backend=be, optimize=True)
        Ru = pipe.transform(env["Q"], backend=_seq_backend(env),
                            optimize=False)
        np.testing.assert_array_equal(np.asarray(Ro["docids"]),
                                      np.asarray(Ru["docids"]))
        np.testing.assert_allclose(np.asarray(Ro["scores"]),
                                   np.asarray(Ru["scores"]), rtol=1e-6)


# ---------------------------------------------------------------------------
# bucket ladder bounds recompilation
# ---------------------------------------------------------------------------

def test_recompiles_bounded_by_ladder(small_ir):
    """Across many distinct query-set sizes, one stage may compile at most
    len(ladder) variants (the seed's loop recompiled per distinct size)."""
    env = small_ir
    be = JaxBackend(env["index"], default_k=60, dense=env["backend"].dense)
    eng = be.engine
    pipe = Retrieve("BM25", k=10)
    for nq in (1, 2, 3, 5, 8, 9, 13, 21, 33, 40, 64, 65):
        pipe.transform(_tiled_queries(env, nq), backend=be, optimize=False)
    assert eng.max_compiles_per_stage() <= len(eng.ladder)
    # and the jit cache is really shared across structurally-equal stages
    pipe2 = Retrieve("BM25", k=10)
    n = eng.max_compiles_per_stage()
    pipe2.transform(_tiled_queries(env, 17), backend=be, optimize=False)
    assert eng.max_compiles_per_stage() == n


def test_chunk_plan_covers_and_buckets(small_ir):
    eng = small_ir["backend"].engine
    for nq in range(1, 3 * eng.ladder[-1] + 2):
        plan = eng.chunk_plan(nq)
        assert sum(n for _, n, _ in plan) == nq
        assert all(b in eng.ladder for _, _, b in plan)
        assert all(n <= b for _, n, b in plan)
        starts = [s for s, _, _ in plan]
        assert starts == sorted(starts)
    with pytest.raises(ValueError):
        eng.chunk_plan(0)


def test_default_ladder_is_device_aligned():
    for nd in (1, 2, 3, 5, 8):
        ladder = default_bucket_ladder(nd)
        assert all(b % nd == 0 for b in ladder)
        assert ladder == tuple(sorted(ladder))


def test_explicit_ladder_honoured():
    eng = ShardedQueryEngine(ladder=(2, 6))
    assert eng.ladder == (2, 6)
    assert eng.chunk_plan(15) == ((0, 6, 6), (6, 6, 6), (12, 3, 6))
    assert eng.chunk_plan(1) == ((0, 1, 2),)


# ---------------------------------------------------------------------------
# plan execution through the engine
# ---------------------------------------------------------------------------

def test_plan_results_identical_with_and_without_engine(small_ir):
    env = small_ir
    be_seq = _seq_backend(env)
    for optimize in (False, True):
        pe = ExperimentPlan(PIPELINES, env["backend"], optimize=optimize)
        ps = ExperimentPlan(PIPELINES, be_seq, optimize=optimize)
        re_ = pe.execute(env["Q"], ctx=Context(env["backend"]), record=None)
        rs = ps.execute(env["Q"], ctx=Context(be_seq))
        for Ra, Rb in zip(re_, rs):
            np.testing.assert_array_equal(np.asarray(Ra["docids"]),
                                          np.asarray(Rb["docids"]))
            np.testing.assert_allclose(np.asarray(Ra["scores"]),
                                       np.asarray(Rb["scores"]), rtol=1e-6)


def test_untimed_plan_skips_barriers_and_stays_correct(small_ir):
    """record=None runs fully async (no per-stage block) yet returns the
    same results as the barriered timed pass."""
    env = small_ir
    plan = ExperimentPlan(PIPELINES[:3], env["backend"], optimize=False)
    r_async = plan.execute(env["Q"], ctx=Context(env["backend"]), record=None)
    r_timed = plan.execute(env["Q"], ctx=Context(env["backend"]),
                           record="cold")
    assert all(n.cold_s is not None for n in plan.nodes())
    for Ra, Rb in zip(r_async, r_timed):
        np.testing.assert_array_equal(np.asarray(Ra["docids"]),
                                      np.asarray(Rb["docids"]))


def test_experiment_through_engine_measures_time(small_ir):
    env = small_ir
    res = Experiment([Retrieve("BM25", k=30), Retrieve("QL", k=30)],
                     env["Q"], env["topics"].qrels, ["map"],
                     backend=env["backend"], measure_time=True)
    for row in res["table"]:
        assert row["mrt_ms"] > 0
        assert row["compile_ms"] >= 0


# ---------------------------------------------------------------------------
# bucket-ladder edge cases (parity with the sequential engine throughout)
# ---------------------------------------------------------------------------

def test_empty_query_batch_raises_on_both_paths(small_ir):
    """Neither path can infer output shapes from zero queries; both must
    fail loudly and identically instead of crashing deep in XLA."""
    env = small_ir
    Q0 = make_queries(np.zeros((0, 4), np.int32))
    pipe = Retrieve("BM25", k=10)
    with pytest.raises(ValueError, match="empty query batch"):
        pipe.transform(Q0, backend=env["backend"], optimize=False)
    with pytest.raises(ValueError, match="empty query batch"):
        pipe.transform(Q0, backend=_seq_backend(env), optimize=False)


def _fused_caps_backends(env):
    """Engine + sequential backends with identical capabilities and no
    dynamic pruning, so ``% K`` reaches the fused-topk lowering (gate
    permitting) instead of the RQ1 pushdown on both sides."""
    caps = frozenset({"fat", "multi_model", "fused_topk", "fused_scoring"})
    be = JaxBackend(env["index"], default_k=60, query_chunk=4,
                    dense=env["backend"].dense,
                    descriptor=BackendDescriptor.default(caps))
    be_seq = JaxBackend(env["index"], default_k=60, query_chunk=4,
                        dense=env["backend"].dense,
                        descriptor=BackendDescriptor.default(caps),
                        sharded=False)
    return be, be_seq


def test_single_query_parity_through_fused_topk(small_ir):
    env = small_ir
    be, be_seq = _fused_caps_backends(env)
    Q1 = _tiled_queries(env, 1)
    pipe = Retrieve("BM25") % 10
    Re = pipe.transform(Q1, backend=be, optimize=True)
    Rs = pipe.transform(Q1, backend=be_seq, optimize=True)
    assert np.asarray(Re["docids"]).shape[0] == 1
    np.testing.assert_array_equal(np.asarray(Re["docids"]),
                                  np.asarray(Rs["docids"]))
    np.testing.assert_allclose(np.asarray(Re["scores"]),
                               np.asarray(Rs["scores"]), rtol=1e-6)


def test_batch_exactly_at_every_bucket_boundary(small_ir):
    """nq == a ladder rung must take the exact-fit path (no tail trim) and
    stay identical to the sequential engine."""
    env = small_ir
    be, be_seq = _fused_caps_backends(env)
    eng = be.engine
    pipe = Retrieve("BM25") % 10
    for bucket in eng.ladder:
        Q = _tiled_queries(env, bucket)
        plan = eng.chunk_plan(bucket)
        assert plan[-1][1] == plan[-1][2]        # tail fills its bucket
        Re = pipe.transform(Q, backend=be, optimize=True)
        Rs = pipe.transform(Q, backend=be_seq, optimize=True)
        np.testing.assert_array_equal(np.asarray(Re["docids"]),
                                      np.asarray(Rs["docids"]))


def _tiny_env(n_docs=60):
    from repro.index import build_index, synthesize_corpus, synthesize_topics
    corpus = synthesize_corpus(n_docs=n_docs, vocab=500, mean_len=40, seed=3)
    topics = synthesize_topics(corpus, n_topics=4, q_len=3, rels_per_topic=5,
                               seed=4)
    index = build_index(corpus)
    Q = make_queries(np.asarray(topics.terms), np.asarray(topics.weights),
                     np.asarray(topics.qids))
    return index, Q


def test_k_exceeds_ndocs_through_fused_topk_path(small_ir):
    """k > n_docs clamps to the corpus size on every path (top-k cannot
    return more entries than documents exist) — fused kernel, optimised
    cutoff chain, and the sequential engine all agree."""
    index, Q = _tiny_env(n_docs=60)
    k = 96                                        # > n_docs
    be = JaxBackend(index, default_k=50, query_chunk=4)
    be_seq = JaxBackend(index, default_k=50, query_chunk=4, dense=be.dense,
                        sharded=False)
    ref = Retrieve("BM25", k=k).transform(Q, backend=be_seq, optimize=False)
    assert np.asarray(ref["docids"]).shape[1] == 60
    fused = FusedTopKRetrieve("BM25", k=k).transform(Q, backend=be,
                                                     optimize=False)
    np.testing.assert_array_equal(np.asarray(fused["docids"]),
                                  np.asarray(ref["docids"]))
    np.testing.assert_allclose(np.asarray(fused["scores"]),
                               np.asarray(ref["scores"]), rtol=1e-6)
    # the optimised cutoff chain survives compilation + gating at k > n_docs
    be_nopruning = JaxBackend(index, default_k=50, query_chunk=4,
                              dense=be.dense,
                              descriptor=BackendDescriptor.default(frozenset(
                                  {"fat", "multi_model", "fused_topk"})))
    Ro = (Retrieve("BM25", k=k) % k).transform(Q, backend=be_nopruning,
                                               optimize=True)
    np.testing.assert_array_equal(np.asarray(Ro["docids"]),
                                  np.asarray(ref["docids"]))


# ---------------------------------------------------------------------------
# serving API: bucket selection, single-chunk submission, bounded caches
# ---------------------------------------------------------------------------

def test_select_bucket_and_submit_chunk(small_ir):
    env = small_ir
    eng = ShardedQueryEngine(ladder=(4, 8))
    assert [eng.select_bucket(n) for n in (1, 4, 5, 8)] == [4, 4, 8, 8]
    with pytest.raises(ValueError):
        eng.select_bucket(0)
    with pytest.raises(ValueError):
        eng.select_bucket(9)                      # bigger than the ladder
    Q = _tiled_queries(env, 5)
    prog = StageProgram(key=("t", "sum"), fn=lambda t, w: w.sum())
    out = eng.submit_chunk(prog, Q)               # one padded chunk @ 8
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(Q["weights"]).sum(1), rtol=1e-6)
    assert eng.n_dispatches == 1
    with pytest.raises(ValueError):
        eng.submit_chunk(prog, _tiled_queries(env, 9))


def test_engine_caches_are_lru_bounded_with_cache_info(small_ir):
    env = small_ir
    eng = ShardedQueryEngine(ladder=(2, 4), max_jit_entries=2,
                             max_chunk_entries=2)
    Q = _tiled_queries(env, 4)
    for i in range(4):                            # 4 distinct stage keys
        eng.map_queries(lambda t, w: w.sum() + i, Q, key=("stage", i))
    info = eng.cache_info()
    assert set(info) == {"jit", "chunk"}
    assert info["jit"]["size"] <= 2
    assert info["jit"]["evictions"] >= 2
    assert info["chunk"]["size"] <= 2
    for part in info.values():
        assert {"size", "maxsize", "hits", "misses",
                "evictions"} <= set(part)
    # an evicted stage key recompiles on next use (bounded memory trumps
    # the ladder bound under cache pressure)
    eng.map_queries(lambda t, w: w.sum() + 0, Q, key=("stage", 0))
    assert eng.cache_info()["jit"]["size"] <= 2


def test_engine_chunk_cache_reused_across_stages(small_ir):
    """Stage-to-stage handoff must reuse sharded chunk pieces instead of
    re-slicing the concatenated output."""
    env = small_ir
    be = JaxBackend(env["index"], default_k=60, dense=env["backend"].dense)
    (Retrieve("BM25", k=20) >> Extract("QL") >> Extract("TF_IDF")) \
        .transform(env["Q"], backend=be, optimize=False)
    assert be.engine.n_chunk_cache_hits > 0


# ---------------------------------------------------------------------------
# device placement: meshes, hoisted index arrays, the compile cache
# ---------------------------------------------------------------------------

def test_query_mesh_axes_are_auto():
    """Auto axes: GSPMD may propagate the query sharding through a stage's
    gathers from replicated index arrays (explicit axes reject them)."""
    import jax
    from repro.launch.mesh import make_host_mesh, make_query_mesh
    for mesh in (make_query_mesh(), make_query_mesh(doc_shards=1),
                 make_host_mesh()):
        assert set(mesh.axis_types) == {jax.sharding.AxisType.Auto}


def test_closed_over_arrays_are_program_arguments_not_constants():
    """A stage's closed-over index enters its program as an argument: the
    lowered program stays small however large the index is, and one
    placement serves every program closing over the same array."""
    import jax.numpy as jnp
    big = jnp.arange(1 << 20, dtype=jnp.float32)        # 4 MiB "index"
    eng = ShardedQueryEngine(ladder=(8,))
    terms = np.arange(5, dtype=np.int32)
    for key in ("a", "b"):
        out = eng.run(StageProgram(key=key, fn=lambda t: big[t] * 2.0),
                      None, terms)
        np.testing.assert_array_equal(np.asarray(out), 2.0 * terms)
    vf = eng._jit_cache.values()[0]
    text = vf.lower(jnp.zeros((8,), jnp.int32)).as_text()
    assert len(text) < 100_000               # an embedded copy is ~20 MB
    assert len(eng._replicas) <= 1


def test_compile_cache_stays_where_the_environment_puts_it(monkeypatch,
                                                          tmp_path):
    import jax
    from repro.launch.cache import REPO_CACHE_DIR, use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert use_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
        assert REPO_CACHE_DIR.name == ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
