"""Pure-jnp oracle for streaming_dense_topk.

Shape of the computation matters beyond correctness: the IR fusion pass
cost-gates the kernel lowering by comparing optimized-HLO proxies, and the
*unfused* dense paths (``index/dense.py``) score candidates with exactly the
expression below — so on hosts where the kernel falls back to this oracle, a
fused candidate at the same ``k`` prices identical to its unfused twin and
the strictly-cheaper gate correctly declines the rewrite.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


#: every dense score is contracted at full f32 precision — the TPU's default
#: f32 matmul rounds its operands to bf16 — so the kernel, the unfused
#: chains and the serving path rank candidates identically
PRECISION = jax.lax.Precision.HIGHEST


def dense_scores(emb, qvec):
    """``emb @ qvec`` in f32 at :data:`PRECISION`."""
    return jnp.dot(emb.astype(jnp.float32), qvec.astype(jnp.float32),
                   precision=PRECISION)


def dense_topk_ref(emb, qvec, base=None, *, k: int):
    scores = dense_scores(emb, qvec)
    if base is not None:
        scores = scores + base
    vals, idxs = jax.lax.top_k(scores, k)
    return vals, idxs.astype(jnp.int32)
