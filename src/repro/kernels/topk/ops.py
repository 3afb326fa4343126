"""Jitted wrapper for streaming_topk (pads, falls back for large k)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common import round_up
from repro.kernels.topk.ref import streaming_topk_ref
from repro.kernels.topk.topk import (BLOCK_S, LANES, NEG,
                                     streaming_topk_pallas)

MAX_KERNEL_K = 128


def kernel_native(k: int) -> bool:
    """Whether the Pallas kernel itself serves this ``k`` on TPU (larger k
    falls back to the ``lax.top_k`` oracle).  The IR fusion pass
    (core/passes.py) records this so gate decisions distinguish
    kernel-native lowerings from oracle-served ones."""
    return k <= MAX_KERNEL_K


def streaming_topk(scores, *, k: int, block: int = BLOCK_S,
                   impl: str = "auto", interpret: bool = False):
    """Top-k of a score vector with block-max skipping. Returns values
    sorted descending (ties to the lowest index) + their indices.
    ``block`` is rounded up to whole ``[8, 128]`` tiles."""
    if impl == "auto":
        impl = "pallas" if (jax.default_backend() == "tpu" and
                            k <= MAX_KERNEL_K) else "ref"
    if impl == "ref" or k > MAX_KERNEL_K:
        return streaming_topk_ref(scores, k=k)
    block = round_up(block, 8 * LANES)
    n = scores.shape[0]
    n_pad = round_up(max(n, block), block)
    padded = jnp.pad(scores.astype(jnp.float32), (0, n_pad - n),
                     constant_values=NEG)
    return streaming_topk_pallas(
        padded, k=k, block=block,
        interpret=interpret or jax.default_backend() != "tpu")
