"""Pallas TPU kernel: causal GQA flash attention (+ chunked-local masking).

The LM hot spot (train_4k / prefill_32k cells).  Grid =
(batch, q_heads, q_blocks, kv_blocks) with the kv dim innermost/sequential;
VMEM scratch carries the online-softmax state (m, l, acc) across kv blocks.
Causal + Llama-4 chunked-local masks are computed from global indices; fully
masked kv blocks are skipped before their compute issues (``@pl.when``),
so chunked layers cost O(S·chunk), not O(S²).

Memory: O(bq·bkv + bq·D) VMEM per step vs the O(S·T) HLO scores tensor of
the xla path — the §Perf memory-term fix.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 512
DEFAULT_BKV = 512
NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq, bkv, n_kv_blocks, causal, chunk, scale):
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_idx = qb * bq + jax.lax.iota(jnp.int32, bq)
    k_idx = kb * bkv + jax.lax.iota(jnp.int32, bkv)

    # block-level skip: causal (kv block entirely in the future) and
    # chunked-local (kv block entirely outside the q block's chunk range)
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, (kb * bkv) <= (qb * bq + bq - 1))
    if chunk:
        lo_chunk = (qb * bq) // chunk
        hi_chunk = (qb * bq + bq - 1) // chunk
        run = jnp.logical_and(run, (kb * bkv + bkv - 1) // chunk >= lo_chunk)
        run = jnp.logical_and(run, (kb * bkv) // chunk <= hi_chunk)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                # [bkv, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bq,bkv]
        ok = jnp.ones((bq, bkv), jnp.bool_)
        if causal:
            ok = ok & (k_idx[None, :] <= q_idx[:, None])
        if chunk:
            ok = ok & ((k_idx[None, :] // chunk) == (q_idx[:, None] // chunk))
        s = jnp.where(ok, s, NEG)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(kb == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "chunk", "bq", "bkv", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True, chunk: int = 0,
                           bq: int = DEFAULT_BQ, bkv: int = DEFAULT_BKV,
                           interpret: bool = False):
    """q [B,S,H,D]; k/v [B,T,Hkv,D] -> out [B,S,H,D] (GQA folded via the
    kv-head index map h -> h // G).  Requires S % bq == 0, T % bkv == 0."""
    B, S, H, D = q.shape
    T, HKV = k.shape[1], k.shape[2]
    G = H // HKV
    assert S % bq == 0 and T % bkv == 0, (S, T, bq, bkv)
    grid = (B, H, S // bq, T // bkv)
    scale = D ** -0.5

    # [B, H, S, D] layout so blocks are [1, 1, bq, D]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    kernel = functools.partial(_kernel, bq=bq, bkv=bkv,
                               n_kv_blocks=T // bkv, causal=causal,
                               chunk=chunk, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bkv, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # m: running max
            pltpu.VMEM((bq,), jnp.float32),      # l: running denominator
            pltpu.VMEM((bq, D), jnp.float32),    # acc: running numerator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2)
