"""Pallas TPU kernel: blocked query x doc-embedding matmul fused with
streaming top-k (the dense second-stage hot path).

Candidate generation / re-scoring over a dense index is a matrix-vector
product ``emb @ qvec`` followed by a rank cutoff.  Unfused, the [N] score
vector round-trips through HBM and is then fully sorted; this kernel streams
embedding blocks through VMEM, scores each [BLOCK_D, dim] tile on the MXU,
adds a per-row ``base`` score (the sparse first-stage contribution of a
fused rerank, doubling as the validity mask: padded / invalid rows carry
``NEG``), and merges the block into a running ``[k_rows, 128]`` top-k
scratch with the ``streaming_merge`` accumulator shared with
``kernels/topk``.  A block whose best fused score is <= the running k-th
score is skipped entirely (``@pl.when``) — block-max pruning at
dense-scoring granularity.  Scores, base and query are lane-dense
``[1, ...]`` rows, so every block keeps (8, 128)-legal trailing dims also
under the engine's vmap.

Intended for k <= 128 (the rank-cutoff regime); larger k falls back to the
``lax.top_k`` oracle in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dense_scoring.ref import PRECISION
from repro.kernels.topk.topk import (LANES, finish_topk, init_topk,
                                     leading_batch, streaming_merge,
                                     topk_rows)

BLOCK_D = 1024


def _kernel(emb_ref, q_ref, base_ref, vals_ref, idxs_ref, *, k, block):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        init_topk(vals_ref, idxs_ref, k=k)

    emb = emb_ref[...].astype(jnp.float32)               # [block, dim]
    q = q_ref[...].astype(jnp.float32)                   # [1, dim]
    # q @ emb.T: one lane-dense [1, block] score row on the MXU
    scores = jax.lax.dot_general(
        q, emb, (((1,), (1,)), ((), ())), precision=PRECISION,
        preferred_element_type=jnp.float32) \
        + base_ref[...].astype(jnp.float32)              # [1, block]
    gidx = b * block + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)

    @pl.when(jnp.max(scores) > jnp.min(vals_ref[...]))   # block-max skip
    def _merge():
        vals, idxs = streaming_merge(scores, gidx, vals_ref[...],
                                     idxs_ref[...])
        vals_ref[...] = vals
        idxs_ref[...] = idxs


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def dense_topk_pallas(emb, qvec, base, *, k: int, block: int = BLOCK_D,
                      interpret: bool = False):
    """emb [N, dim] (N % block == 0, block % 128 == 0), qvec [dim],
    base [N] -> (values [k], indices [k]) of ``emb @ qvec + base``, sorted
    descending with ties to the lowest index."""
    n, dim = emb.shape
    assert n % block == 0 and block % LANES == 0, (n, block)
    kr = topk_rows(k)
    vals, idxs = leading_batch(pl.pallas_call(
        functools.partial(_kernel, k=k, block=block),
        grid=(n // block,),
        in_specs=[pl.BlockSpec((block, dim), lambda i: (i, 0)),
                  pl.BlockSpec((1, dim), lambda i: (0, 0)),
                  pl.BlockSpec((1, block), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((kr, LANES), lambda i: (0, 0)),
                   pl.BlockSpec((kr, LANES), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((kr, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((kr, LANES), jnp.int32)],
        interpret=interpret,
        name="dense_topk",
    ))(emb, qvec.reshape(1, dim), base.reshape(1, n))
    return finish_topk(vals, idxs, k)
