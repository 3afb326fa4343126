"""Jitted wrapper for the dense-scoring kernel (pads the doc axis, falls
back to the ``lax.top_k`` oracle for large k / non-TPU backends)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common import round_up
from repro.kernels.dense_scoring.dense_scoring import (BLOCK_D,
                                                       dense_topk_pallas)
from repro.kernels.dense_scoring.ref import dense_topk_ref
from repro.kernels.topk.topk import LANES, NEG

MAX_KERNEL_K = 128


def kernel_native(k: int) -> bool:
    """Whether the Pallas kernel itself serves this ``k`` on TPU (larger k
    falls back to the oracle).  The IR fusion pass (core/passes.py) records
    this so gate decisions distinguish kernel-native lowerings from
    oracle-served ones."""
    return k <= MAX_KERNEL_K


def streaming_dense_topk(emb, qvec, base=None, *, k: int,
                         block: int = BLOCK_D, impl: str = "auto",
                         interpret: bool = False):
    """Top-k of ``emb @ qvec + base`` (base defaults to 0) without ever
    materialising + sorting the full score vector on the kernel path.
    Returns values sorted descending (ties to the lowest index) + their
    row indices into ``emb``; padded rows score ``NEG`` and can never enter
    the top-k of real candidates.  ``block`` is rounded up to whole
    128-lane rows."""
    if impl == "auto":
        impl = "pallas" if (jax.default_backend() == "tpu" and
                            k <= MAX_KERNEL_K) else "ref"
    if impl == "ref" or k > MAX_KERNEL_K:
        return dense_topk_ref(emb, qvec, base, k=k)
    block = round_up(block, LANES)
    n, dim = emb.shape
    n_pad = round_up(max(n, block), block)
    if base is None:
        base = jnp.zeros((n,), jnp.float32)
    emb_p = jnp.pad(emb.astype(jnp.float32), ((0, n_pad - n), (0, 0)))
    base_p = jnp.pad(base.astype(jnp.float32), (0, n_pad - n),
                     constant_values=NEG)
    return dense_topk_pallas(
        emb_p, qvec.astype(jnp.float32), base_p, k=k, block=block,
        interpret=interpret or jax.default_backend() != "tpu")
