"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and refuses what the chip would refuse
(block shapes off the (8, 128) tiling, primitives Mosaic cannot lower, too
much VMEM).  Interpret-mode tests cannot see any of that.  Each retrieval
kernel is compiled the way the query engine calls it — ``jit(vmap(fn))``
over the query axis at every default ladder rung — at the widths of a
Robust-scale deployment (528,155 documents, 64-d embeddings, IVF-PQ with
m=8 and an 800-deep ADC shortlist), plus the LM's flash attention at the
qwen2-1.5b head layout.  The three fused stages of the served pipelines
compile whole, per query as the engine maps them.  Nothing runs; the
compiled text must hold the kernel as a ``tpu_custom_call``.  A last case
compiles the engine's data-parallel map over all four chips of the
described host.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.common import round_up
from repro.core.data import MAXQ
from repro.core.engine import data_parallel
from repro.kernels.dense_scoring.dense_scoring import dense_topk_pallas
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.fused_scoring.fused_scoring import (BLOCK_P,
                                                       fused_scoring_pallas)
from repro.kernels.pq_scoring.pq_scoring import BLOCK_C, pq_topk_pallas
from repro.kernels.topk.topk import BLOCK_S, streaming_topk_pallas

RUNGS = (8, 16, 32)                     # core.engine.default_bucket_ladder(1)
N_DOCS = round_up(528_155, BLOCK_S)     # Robust-scale score vector
K_IN, DIM = 1024, 64                    # rerank candidates (k_in=1000 padded)
PQ_M, PQ_CODES = 8, 256
PQ_CAND = round_up(8 * 862, BLOCK_C)    # nprobe 8 x longest IVF list
PQ_SHORTLIST = 800                      # refine 4 x first-stage k 200
POSTINGS = round_up(MAXQ * 52_736, BLOCK_P)   # term slots x longest list


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache but
    # can never be read back without one: keep the cache out of it
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prior)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, shapes, sharding, batch=None):
    """Compile ``jit(vmap(fn))`` (or ``jit(fn)`` when ``batch`` is None)
    for the described chip and return the optimised HLO text."""
    lead = () if batch is None else (batch,)
    args = [jax.ShapeDtypeStruct(lead + s, d, sharding=sharding)
            for s, d in shapes]
    g = fn if batch is None else jax.vmap(fn)
    return jax.jit(g).lower(*args).compile().as_text()


@pytest.mark.parametrize("rung", RUNGS)
def test_streaming_topk_compiles_under_engine_vmap(one_chip, rung):
    text = _compile_text(lambda s: streaming_topk_pallas(s, k=10),
                         [((N_DOCS,), jnp.float32)], one_chip, rung)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rung", RUNGS)
def test_dense_topk_compiles_under_engine_vmap(one_chip, rung):
    text = _compile_text(
        lambda e, q, b: dense_topk_pallas(e, q, b, k=10),
        [((K_IN, DIM), jnp.float32), ((DIM,), jnp.float32),
         ((K_IN,), jnp.float32)], one_chip, rung)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rung", RUNGS)
def test_pq_topk_compiles_under_engine_vmap(one_chip, rung):
    text = _compile_text(
        lambda c, t, b: pq_topk_pallas(c, t, b, k=PQ_SHORTLIST),
        [((PQ_M, PQ_CAND), jnp.uint8), ((PQ_M, PQ_CODES), jnp.float32),
         ((PQ_CAND,), jnp.float32)], one_chip, rung)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rung", RUNGS)
def test_fused_scoring_compiles_under_engine_vmap(one_chip, rung):
    def fn(tf, dl, df, cf):
        return fused_scoring_pallas(
            tf, dl, df, cf, models=("BM25", "TF_IDF", "QL"),
            n_docs=528_155.0, avg_dl=300.0, total_terms=1.58e8)
    text = _compile_text(fn, [((POSTINGS,), jnp.int32)] * 4, one_chip, rung)
    assert "tpu_custom_call" in text


def test_topk_compiles_at_the_fusion_gates_batch_of_one(one_chip):
    # the fusion gate prices each candidate vmapped over one query
    text = _compile_text(lambda s: streaming_topk_pallas(s, k=10),
                         [((N_DOCS,), jnp.float32)], one_chip, batch=1)
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_at_qwen2_heads(one_chip):
    text = _compile_text(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
        [((1, 512, 12, 128), jnp.bfloat16), ((1, 512, 2, 128), jnp.bfloat16),
         ((1, 512, 2, 128), jnp.bfloat16)], one_chip)
    assert "tpu_custom_call" in text


# -- the fused stages, per query as the engine vmaps them ---------------------

@pytest.fixture(scope="module")
def tpu_ops(topo):
    """The kernels' ``impl="auto"`` picks Pallas (not the reference, not
    interpret mode) as on the chip: the fused stages compile as served."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        yield


def _robust_index(sharding):
    """Abstract Robust-scale indexes (shapes of the built deployment)."""
    from repro.index.dense import IVFPQIndex, PQCodebook
    from repro.index.inverted import InvertedIndex
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    i32, f32 = jnp.int32, jnp.float32
    D, V, NP, F = 528_155, 200_000, 85_958_144, 73_449_317
    inv = InvertedIndex(S((V + 1,), i32), S((NP,), i32), S((NP,), i32),
                        S((NP // 128,), i32), S((NP // 128,), i32),
                        S((D,), i32), S((V,), i32), S((V,), i32),
                        S((D + 1,), i32), S((F,), i32), S((F,), i32),
                        D, V, 300.0, D * 300, 1220)
    emb = S((D, DIM), f32)
    n_lists = 727
    pq = IVFPQIndex(S((n_lists, DIM), f32), S((D, PQ_M), jnp.uint8),
                    S((D,), i32), S((n_lists + 1,), i32),
                    PQCodebook(S((PQ_M, PQ_CODES, DIM // PQ_M), f32), PQ_M,
                               DIM // PQ_M, PQ_CODES),
                    emb, DIM, n_lists, 862)
    return inv, emb, pq


def _stage_text(fn, index, queries, sharding, rung=RUNGS[0]):
    """Compile ``fn(index, *query)`` vmapped over the query rows only, the
    index unbatched — how the engine runs a stage whose index it hoists."""
    args = [jax.ShapeDtypeStruct((rung,) + s, d, sharding=sharding)
            for s, d in queries]
    g = jax.vmap(fn, in_axes=(None,) + (0,) * len(queries))
    return jax.jit(g).lower(index, *args).compile().as_text()


def test_fused_bm25_stage_compiles_with_its_kernel(one_chip, tpu_ops):
    from repro.index.retrieve import retrieve_topk_fused
    inv, _, _ = _robust_index(one_chip)
    text = _stage_text(
        lambda ix, t, w: retrieve_topk_fused(ix, t, w, model="BM25", k=10,
                                             max_postings=52_736),
        inv, [((MAXQ,), jnp.int32), ((MAXQ,), jnp.float32)], one_chip)
    assert "tpu_custom_call" in text


def test_fused_dense_rerank_stage_compiles_with_its_kernel(one_chip, tpu_ops):
    from repro.index.retrieve import retrieve_dense_rerank_fused
    inv, emb, _ = _robust_index(one_chip)
    text = _stage_text(
        lambda ix, t, w, q: retrieve_dense_rerank_fused(
            ix[0], ix[1], t, w, q, model="BM25", k_in=1000, k=10, alpha=0.3,
            max_postings=52_736),
        (inv, emb), [((MAXQ,), jnp.int32), ((MAXQ,), jnp.float32),
                     ((DIM,), jnp.float32)], one_chip)
    assert "tpu_custom_call" in text


def test_fused_ivfpq_stage_compiles_with_its_kernel(one_chip, tpu_ops):
    # the ADC table is an einsum of the query: its query axis comes out
    # last, and the kernel must still tile it
    from repro.index.dense import ivfpq_retrieve_topk_fused
    _, _, pq = _robust_index(one_chip)
    text = _stage_text(
        lambda ix, q: ivfpq_retrieve_topk_fused(ix, q, k=10, nprobe=8,
                                                shortlist=PQ_SHORTLIST),
        pq, [((DIM,), jnp.float32)], one_chip)
    assert "tpu_custom_call" in text


def test_four_chip_query_mesh_runs_kernels_per_chip(topo):
    """On a four-chip host the engine splits the query axis over every
    device.  GSPMD cannot partition a Mosaic kernel, so the engine maps
    each device over its own rows: the program holds the kernel and no
    collective."""
    mesh = Mesh(np.array(topo.devices), ("data",),
                axis_types=(AxisType.Auto,))
    arg = jax.ShapeDtypeStruct((RUNGS[-1], N_DOCS), jnp.float32,
                               sharding=NamedSharding(mesh, P("data")))
    fn = data_parallel(lambda s: streaming_topk_pallas(s, k=10), mesh)
    text = fn.lower(arg).compile().as_text()
    assert "tpu_custom_call" in text
    assert not any(c in text for c in ("all-gather", "all-reduce",
                                       "all-to-all", "collective-permute"))
