"""Pallas TPU kernel: PQ code gathers + ADC table adds fused with
streaming top-k (the compressed dense second-stage hot path).

ADC scoring of an IVF-PQ candidate block is ``m`` table lookups per row:
``score[i] = sum_s table[s, codes[i, s]] + base[i]``.  Unfused, the [N]
score vector round-trips through HBM and is then fully sorted; this kernel
streams subspace-major uint8 code blocks ``[m, block]`` through VMEM (``m``
bytes per candidate instead of ``dim * 4`` — the memory axis the PQ layout
buys), materialises each subspace lookup as a ``[1, n_codes] x [n_codes,
block]`` one-hot matmul against the table row (the standard MXU-friendly
small-vocab gather, lane-dense in the candidates), adds the per-row
``base`` (validity mask: padded rows carry ``NEG``), and merges the block
into a running ``[k_rows, 128]`` top-k scratch with the
``streaming_merge`` accumulator shared with ``kernels/topk``.  A block whose best score is <= the running k-th
score is skipped entirely (``@pl.when``) — block-max pruning at ADC
granularity.

The merge keeps ``lax.top_k``'s order — descending value, ties to the
lowest candidate row — and the final ordering is ``lexsort((idxs,
-vals))``, so the fused and ref ADC stages produce bit-identical
shortlists even when distinct documents share a code word (ties are
*expected* under quantisation, unlike in float scoring).

Intended for shortlists up to 1024 (``refine * k`` of a first stage at
k <= 256); deeper shortlists fall back to the ``lax.top_k`` oracle in
ops.py.
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

BLOCK_C = 512


def _kernel(codes_ref, table_ref, base_ref, vals_ref, idxs_ref, *, k, block,
            m):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        init_topk(vals_ref, idxs_ref, k=k)

    codes = codes_ref[...].astype(jnp.int32)             # [m, block]
    table = table_ref[...].astype(jnp.float32)           # [m, n_codes]
    n_codes = table.shape[1]
    code_id = jax.lax.broadcasted_iota(jnp.int32, (n_codes, block), 0)
    scores = base_ref[...].astype(jnp.float32)           # [1, block]
    for s in range(m):                                   # static unroll
        onehot = (codes[s:s + 1, :] == code_id).astype(jnp.float32)
        scores = scores + jnp.dot(table[s:s + 1, :], onehot,
                                  precision=PRECISION,
                                  preferred_element_type=jnp.float32)
    gidx = b * block + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)

    @pl.when(jnp.max(scores) > jnp.min(vals_ref[...]))   # block-max skip
    def _merge():
        vals, idxs = streaming_merge(scores, gidx, vals_ref[...],
                                     idxs_ref[...])
        vals_ref[...] = vals
        idxs_ref[...] = idxs


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def pq_topk_pallas(codes_t, table, base, *, k: int, block: int = BLOCK_C,
                   interpret: bool = False):
    """codes_t [m, N] uint8 (subspace-major; N % block == 0, block % 128
    == 0), table [m, n_codes], base [N] -> (values [k], indices [k]) of the
    ADC scores, sorted descending with ties broken to the lowest index
    (``lax.top_k`` order)."""
    m, n = codes_t.shape
    assert n % block == 0 and block % LANES == 0, (n, block)
    n_codes = table.shape[1]
    kr = topk_rows(k)
    vals, idxs = leading_batch(pl.pallas_call(
        functools.partial(_kernel, k=k, block=block, m=m),
        grid=(n // block,),
        in_specs=[pl.BlockSpec((m, block), lambda i: (0, i)),
                  pl.BlockSpec((m, n_codes), lambda i: (0, 0)),
                  pl.BlockSpec((1, block), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((kr, LANES), lambda i: (0, 0)),
                   pl.BlockSpec((kr, LANES), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((kr, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((kr, LANES), jnp.int32)],
        interpret=interpret,
        name="pq_topk",
    ))(codes_t, table, base.reshape(1, n))
    return finish_topk(vals, idxs, k)
