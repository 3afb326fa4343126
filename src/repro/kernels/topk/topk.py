"""Pallas TPU kernel: streaming top-k with block-max skipping.

The TPU-idiomatic BlockMaxWAND (DESIGN.md §2): scores stream through VMEM in
``[rows, 128]`` tiles; a ``[k_rows, 128]`` scratch holds the running top-k.
A tile whose max is ≤ the running k-th score (θ) is *skipped entirely*
(``@pl.when``) — the dynamic-pruning threshold exactly as in WAND, at block
granularity.  The grid is sequential on TPU so the scratch carries across
blocks.

Every block is 2-D with its last two dims multiples of (8, 128) or equal to
the array's, so the kernel also lowers when the engine vmaps it over the
query axis (:func:`leading_batch` prepends a squeezed batch dim to each
block).

Merge step: (select the block max, evict the scratch minimum) until the
block has nothing better than θ — pure VPU masks/maxes (``iota == j``
selects, ``jnp.where`` replaces), no sort and no dynamic indexing.
Intended for k ≤ 128 (rank-cutoff regime of RQ1); larger k falls back to
``lax.top_k`` in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.common import cdiv

BLOCK_S = 4096
LANES = 128
NEG = -3.0e38  # python float: jnp scalars would be captured as consts
#: value of scratch slots past k: never the minimum, so never evicted
_PAD = 3.0e38
_IMAX = 2 ** 31 - 1


def leading_batch(call):
    """``call`` (a ``pallas_call``) with the vmap axis of every batched
    operand moved to the front.  Pallas turns a vmap axis into a squeezed
    block dim at the axis's own position, so an axis an upstream op left
    trailing (an einsum puts it last) would land in a block's last two dims
    and break the (8, 128) tiling rule.  One level of vmap — the engine's
    query axis."""
    f = jax.custom_batching.custom_vmap(call)

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        out = jax.vmap(call, in_axes=[0 if b else None for b in in_batched],
                       axis_size=axis_size)(*args)
        return out, jax.tree.map(lambda _: True, out)

    return f


def topk_rows(k: int) -> int:
    """Sublane rows of the ``[rows, 128]`` running top-k scratch for k."""
    return cdiv(k, LANES)


def init_topk(vals_ref, idxs_ref, *, k):
    """Empty running top-k: the first ``k`` slots hold NEG (any real score
    replaces them), the padding slots hold ``_PAD`` (never replaced)."""
    slot = jax.lax.broadcasted_iota(jnp.int32, vals_ref.shape, 0) * LANES \
        + jax.lax.broadcasted_iota(jnp.int32, vals_ref.shape, 1)
    vals_ref[...] = jnp.where(slot < k, NEG, _PAD).astype(jnp.float32)
    idxs_ref[...] = jnp.full(idxs_ref.shape, -1, jnp.int32)


def streaming_merge(cand, gidx, vals, idxs):
    """Merge a candidate tile into the running top-k scratch.

    ``cand``/``gidx`` are the tile's scores and global indices (any 2-D
    shape); ``vals``/``idxs`` the ``[rows, 128]`` scratch.  Each step takes
    the tile's best candidate (ties: lowest index) and, if it beats the
    scratch minimum, evicts that minimum (ties: highest index), so the
    scratch always holds the top-k of everything seen under ``lax.top_k``'s
    order — descending value, lowest index first.  The loop stops as soon
    as the tile's best no longer beats the minimum.  Shared by the
    score-stream kernel here and the dense/PQ scoring kernels."""

    def cond(carry):
        cand, vals, _ = carry
        return jnp.max(cand) > jnp.min(vals)

    def body(carry):
        cand, vals, idxs = carry
        m = jnp.max(cand)
        j = jnp.min(jnp.where(cand == m, gidx, _IMAX))
        vmin = jnp.min(vals)
        at_min = vals == vmin
        worst = jnp.max(jnp.where(at_min, idxs, -1))
        hit = at_min & (idxs == worst)
        # several empty slots tie (NEG, -1): fill the first one only
        slot = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 0) * LANES \
            + jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
        first = jnp.min(jnp.where(hit, slot, _IMAX))
        hit = slot == first
        vals = jnp.where(hit, m, vals)
        idxs = jnp.where(hit, j, idxs)
        cand = jnp.where(gidx == j, NEG, cand)
        return cand, vals, idxs

    _, vals, idxs = jax.lax.while_loop(cond, body, (cand, vals, idxs))
    return vals, idxs


def _kernel(scores_ref, vals_ref, idxs_ref, *, k, rows):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        init_topk(vals_ref, idxs_ref, k=k)

    blk = scores_ref[...].astype(jnp.float32)            # [rows, 128]
    gidx = b * (rows * LANES) \
        + jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0) * LANES \
        + jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)

    @pl.when(jnp.max(blk) > jnp.min(vals_ref[...]))      # block-max skip
    def _merge():
        vals, idxs = streaming_merge(blk, gidx, vals_ref[...], idxs_ref[...])
        vals_ref[...] = vals
        idxs_ref[...] = idxs


def finish_topk(vals, idxs, k: int):
    """Flatten the ``[rows, 128]`` scratch to its first ``k`` slots and
    order them as ``lax.top_k`` does: descending value, ties to the lowest
    index."""
    vals = vals.reshape(-1)[:k]
    idxs = idxs.reshape(-1)[:k]
    order = jnp.lexsort((idxs, -vals))
    return vals[order], idxs[order]


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def streaming_topk_pallas(scores, *, k: int, block: int = BLOCK_S,
                          interpret: bool = False):
    """scores [N] (N % block == 0, block % 1024 == 0) -> (values [k],
    indices [k]), sorted descending with ties to the lowest index."""
    n = scores.shape[0]
    assert n % block == 0 and block % (8 * LANES) == 0, (n, block)
    rows = block // LANES
    kr = topk_rows(k)
    vals, idxs = leading_batch(pl.pallas_call(
        functools.partial(_kernel, k=k, rows=rows),
        grid=(n // block,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((kr, LANES), lambda i: (0, 0)),
                   pl.BlockSpec((kr, LANES), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((kr, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((kr, LANES), jnp.int32)],
        interpret=interpret,
        name="streaming_topk",
    ))(scores.reshape(n // LANES, LANES))
    return finish_topk(vals, idxs, k)
