"""Pallas TPU kernel: single-pass multi-model postings scoring (fat, RQ2).

One VMEM-resident postings tile (tf, doc_len, df, cf) produces F weighting-
model scores — the fat-postings insight as arithmetic-intensity: postings are
read from HBM once and every model's math runs on the registers/VMEM tile.

Grid: postings blocks of ``BLOCK_P`` postings, laid out as ``[BLOCK_P/128,
128]`` tiles; per block the kernel emits an ``[F, BLOCK_P/128, 128]`` score
tile, which the wrapper transposes back to ``[N, F]``.  Every block keeps
(8, 128)-legal trailing dims, also under the engine's vmap.  Pure VPU math
(no MXU), bf16-safe in fp32 compute.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.index.scoring import BM25_B, BM25_K1, QL_MU
from repro.kernels.topk.topk import LANES, leading_batch

BLOCK_P = 2048

#: model id order used by the kernel (a static tuple per call)
SUPPORTED = ("BM25", "TF_IDF", "QL", "DPH", "Coord")


def _model_scores(model, tf, dl, df, cf, n_docs, avg_dl, total_terms):
    """fp32 elementwise math for one model over a postings tile."""
    if model == "BM25":
        idf = jnp.log1p((n_docs - df + 0.5) / (df + 0.5))
        denom = tf + BM25_K1 * (1 - BM25_B + BM25_B * dl / avg_dl)
        return idf * tf * (BM25_K1 + 1.0) / jnp.maximum(denom, 1e-9)
    if model == "TF_IDF":
        idf = jnp.log(n_docs / jnp.maximum(df, 1.0))
        k = 1.2 * (0.25 + 0.75 * dl / avg_dl)
        return idf * tf / (tf + k)
    if model == "QL":
        p_c = cf / total_terms
        num = tf + QL_MU * p_c
        den = dl + QL_MU
        base = QL_MU * p_c / jnp.maximum(den, 1.0)
        return jnp.log(jnp.maximum(num, 1e-20) / jnp.maximum(den, 1.0)) - \
            jnp.log(jnp.maximum(base, 1e-20))
    if model == "DPH":
        dl1 = jnp.maximum(dl, 1.0)
        f = jnp.clip(tf / dl1, 1e-9, 1.0 - 1e-9)
        norm = (1.0 - f) ** 2 / (tf + 1.0)
        avg = total_terms / n_docs
        info = tf * jnp.log2(jnp.maximum(
            tf * avg / dl1 * n_docs / jnp.maximum(cf, 1.0), 1e-9))
        bonus = 0.5 * jnp.log2(2.0 * jnp.pi * tf * (1.0 - f) + 1e-9)
        return jnp.maximum(norm * (info + bonus), 0.0)
    if model == "Coord":
        return (tf > 0).astype(jnp.float32)
    raise ValueError(model)


def _kernel(tf_ref, dl_ref, df_ref, cf_ref, out_ref, *, models, n_docs,
            avg_dl, total_terms):
    tf = tf_ref[...].astype(jnp.float32)                 # [rows, 128]
    dl = dl_ref[...].astype(jnp.float32)
    df = df_ref[...].astype(jnp.float32)
    cf = cf_ref[...].astype(jnp.float32)
    for j, m in enumerate(models):
        s = _model_scores(m, tf, dl, df, cf, n_docs, avg_dl, total_terms)
        out_ref[j] = jnp.where(tf > 0, s, 0.0)


@functools.partial(jax.jit, static_argnames=("models", "n_docs", "avg_dl",
                                             "total_terms", "interpret"))
def fused_scoring_pallas(tf, dl, df, cf, *, models: tuple[str, ...],
                         n_docs: float, avg_dl: float, total_terms: float,
                         interpret: bool = False):
    """tf/dl/df/cf: [N] (N % BLOCK_P == 0) -> scores [N, F] fp32."""
    n = tf.shape[0]
    assert n % BLOCK_P == 0, n
    rows = BLOCK_P // LANES
    n_models = len(models)
    kernel = functools.partial(_kernel, models=models, n_docs=float(n_docs),
                               avg_dl=float(avg_dl),
                               total_terms=float(total_terms))
    tile = lambda x: x.reshape(n // LANES, LANES)
    in_spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    out = leading_batch(pl.pallas_call(
        kernel,
        grid=(n // BLOCK_P,),
        in_specs=[in_spec] * 4,
        out_specs=pl.BlockSpec((n_models, rows, LANES), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_models, n // LANES, LANES),
                                       jnp.float32),
        interpret=interpret,
        name="fused_scoring",
    ))(tile(tf), tile(dl), tile(df), tile(cf))
    return out.reshape(n_models, n).T
