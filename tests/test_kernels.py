"""Per-kernel shape/dtype sweeps vs pure-jnp oracles (interpret mode)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.dense_scoring.ops import streaming_dense_topk
from repro.kernels.dense_scoring.ref import dense_topk_ref
from repro.kernels.pq_scoring.ops import streaming_pq_topk
from repro.kernels.pq_scoring.ref import pq_topk_ref
from repro.kernels.fused_scoring.ops import fused_scoring
from repro.kernels.fused_scoring.ref import fused_scoring_ref
from repro.kernels.topk.ops import streaming_topk
from repro.kernels.topk.topk import streaming_topk_pallas

STATS = {"n_docs": 8000.0, "avg_doclen": 200.0, "total_terms": 1.6e6}


@pytest.mark.parametrize("n", [512, 2048, 5000])
@pytest.mark.parametrize("models", [("BM25",), ("BM25", "QL", "TF_IDF"),
                                    ("BM25", "TF_IDF", "QL", "DPH", "Coord")])
def test_fused_scoring_sweep(n, models):
    rng = np.random.default_rng(n)
    tf = jnp.asarray(rng.integers(0, 30, n), jnp.int32)
    dl = jnp.asarray(rng.integers(20, 800, n), jnp.int32)
    df = jnp.asarray(rng.integers(1, 4000, n), jnp.int32)
    cf = jnp.asarray(rng.integers(1, 30000, n), jnp.int32)
    a = fused_scoring(tf, dl, df, cf, models=models, stats=STATS,
                      impl="pallas", interpret=True)
    b = fused_scoring_ref(tf, dl, df, cf, models=models,
                          n_docs=STATS["n_docs"], avg_dl=STATS["avg_doclen"],
                          total_terms=STATS["total_terms"])
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=1e-5)


@pytest.mark.parametrize("n,k,block", [(4096, 10, 1024), (8192, 32, 2048),
                                       (4096, 128, 4096), (20000, 7, 1024)])
def test_streaming_topk_sweep(n, k, block):
    rng = np.random.default_rng(n + k)
    scores = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    v1, i1 = streaming_topk(scores, k=k, block=block, impl="pallas",
                            interpret=True)
    v2, i2 = jax.lax.top_k(scores, k)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)
    assert set(np.asarray(i1).tolist()) == set(np.asarray(i2).tolist())


@pytest.mark.parametrize("n,dim,k,block,with_base",
                         [(2048, 64, 10, 1024, False),
                          (5000, 64, 32, 1024, True),
                          (700, 32, 16, 512, True),
                          (4096, 128, 128, 2048, False)])
def test_streaming_dense_topk_sweep(n, dim, k, block, with_base):
    rng = np.random.default_rng(n + k)
    emb = jnp.asarray(rng.standard_normal((n, dim)).astype(np.float32))
    q = jnp.asarray(rng.standard_normal(dim).astype(np.float32))
    base = (jnp.asarray(rng.standard_normal(n).astype(np.float32))
            if with_base else None)
    v1, i1 = streaming_dense_topk(emb, q, base, k=k, block=block,
                                  impl="pallas", interpret=True)
    v2, i2 = dense_topk_ref(emb, q, base, k=k)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-5,
                               atol=1e-5)
    assert set(np.asarray(i1).tolist()) == set(np.asarray(i2).tolist())


@pytest.mark.parametrize("n,m,k,block,with_base",
                         [(2048, 8, 10, 512, False),
                          (5000, 8, 32, 512, True),
                          (700, 4, 16, 256, True),
                          (4096, 16, 128, 1024, False)])
def test_streaming_pq_topk_sweep(n, m, k, block, with_base):
    rng = np.random.default_rng(n + m + k)
    codes = jnp.asarray(rng.integers(0, 256, (n, m)).astype(np.uint8))
    table = jnp.asarray(rng.standard_normal((m, 256)).astype(np.float32))
    base = (jnp.asarray(rng.standard_normal(n).astype(np.float32))
            if with_base else None)
    v1, i1 = streaming_pq_topk(codes, table, base, k=k, block=block,
                               impl="pallas", interpret=True)
    v2, i2 = pq_topk_ref(codes, table, base, k=k)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-5,
                               atol=1e-5)
    # the kernel's lexsort finish orders equal-value survivors by lowest
    # index (lax.top_k's rule), so with distinct scores indices match
    # position-for-position
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_streaming_pq_topk_vmapped_over_a_trailing_query_axis():
    # the engine vmaps the IVF-PQ stage over queries; the per-query ADC
    # table comes out of an einsum with the query axis *last*, which the
    # kernel must move to the front of its blocks
    rng = np.random.default_rng(3)
    n, m, nq = 1000, 8, 3
    codes = jnp.asarray(rng.integers(0, 256, (n, m)).astype(np.uint8))
    cents = jnp.asarray(rng.standard_normal((m, 256, 4)).astype(np.float32))
    qs = jnp.asarray(rng.standard_normal((nq, m, 4)).astype(np.float32))

    def one(q, impl):
        table = jnp.einsum("mcd,md->mc", cents, q)
        return streaming_pq_topk(codes, table, None, k=16, block=256,
                                 impl=impl, interpret=True)

    v1, i1 = jax.vmap(lambda q: one(q, "pallas"))(qs)
    v2, i2 = jax.vmap(lambda q: one(q, "ref"))(qs)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_streaming_pq_topk_duplicate_codes():
    # every doc in {0,1} code space: massive score ties.  Like the other
    # streaming kernels, ties deeper than k admit any valid top-k set —
    # the contract is equal top-k *values* and every returned index
    # actually scoring its reported value
    rng = np.random.default_rng(7)
    codes = jnp.asarray(rng.integers(0, 2, (3000, 8)).astype(np.uint8))
    table = jnp.asarray(rng.standard_normal((8, 256)).astype(np.float32))
    v1, i1 = streaming_pq_topk(codes, table, None, k=16, block=512,
                               impl="pallas", interpret=True)
    v2, _ = pq_topk_ref(codes, table, None, k=16)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-5,
                               atol=1e-5)
    full = np.asarray(table)[np.arange(8), np.asarray(codes)].sum(axis=1)
    np.testing.assert_allclose(full[np.asarray(i1)], np.asarray(v1),
                               rtol=1e-5, atol=1e-5)


def test_streaming_topk_duplicate_values():
    scores = jnp.asarray(np.array([1.0, 3.0, 3.0, 3.0, 0.5] * 300, np.float32))
    v1, _ = streaming_topk(scores, k=5, block=500, impl="pallas", interpret=True)
    assert (np.asarray(v1) == 3.0).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,HKV,D,bq,bkv",
                         [(1, 128, 2, 2, 64, 64, 64),     # MHA
                          (2, 256, 4, 2, 64, 128, 64),    # GQA
                          (1, 256, 8, 1, 128, 64, 128)])  # MQA
def test_flash_attention_sweep(dtype, B, S, H, HKV, D, bq, bkv):
    rng = np.random.default_rng(S + H)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, HKV, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, HKV, D)), dtype)
    o1 = flash_attention_pallas(q, k, v, causal=True, bq=bq, bkv=bkv,
                                interpret=True)
    o2 = flash_attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-6
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=tol)


@pytest.mark.parametrize("chunk", [32, 128])
def test_flash_attention_chunked(chunk):
    rng = np.random.default_rng(chunk)
    q = jnp.asarray(rng.standard_normal((1, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 256, 2, 64)), jnp.float32)
    o1 = flash_attention_pallas(q, k, v, causal=True, chunk=chunk,
                                bq=64, bkv=64, interpret=True)
    o2 = flash_attention_ref(q, k, v, causal=True, chunk=chunk)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-6)


def test_flash_vjp_matches_naive_grads():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 64, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 64, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 64, 2, 32)), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, impl="remat_ref") ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(flash_attention_ref(q, k, v) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
