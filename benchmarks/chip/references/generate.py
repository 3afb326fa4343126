"""Plain dense retrieval >> Generate: a first-stage top ``k``, a prompt
assembled from it, and a decoder-only GQA transformer that holds the
served tokens to its logits.

Imports nothing of the program and takes nothing it made.  The ranking,
the prompts and the weights are all made here from the benchmark's own
collection and the run's seed:

* ranking: every document scored by ``bm25_rerank.py``'s encoders, the
  dot product of the unit document and query vectors, top ``k`` (the
  first stage ``DenseRetrieve(nprobe=0)`` serves);
* prompt: the query's terms, then the forward terms (distinct non-stop
  terms, ascending) of the first ``prompt_docs`` served documents, each
  term ``t`` mapped to ``2 + t % (vocab - 2)``, the first
  ``max_prompt_len`` kept and repeated cyclically to fill
  ``max_prompt_len`` (the rule ``repro.core.stages.assemble_prompt_fn``
  documents; a prompt with no term is all 0);
* weights: ``chipbench/weights.py``'s rule, regenerated one layer at a
  time in the dtype they are served in and widened to float32;
* decoder (the ``lm`` sizes, as ``system.lm_spec`` gives them): token
  embedding; per layer a pre-norm block, RMSNorm (``x * rsqrt(mean(x^2)
  + eps) * gain``), attention with ``n_q`` query heads over ``n_kv`` key
  and value heads (query head ``h`` reads key/value head ``h // (n_q /
  n_kv)``), rotary positions on both halves of each head (``theta``,
  frequencies ``theta ** (-2i / d_head)``), causal softmax of
  ``q . k / sqrt(d_head)``, output projection added to the residual; then
  RMSNorm and the SwiGLU MLP ``(silu(h Wg) * (h Wu)) Wd`` added to the
  residual; a final RMSNorm and the unembedding (the embedding's
  transpose where tied).  Mixture-of-experts layers and chunked attention
  are not written yet: an ``lm`` with either is refused.

The reference runs teacher-forced: one forward pass over each prompt and
its served tokens, in float32 at ``highest`` matmul precision, with no
cache and no kernel, one layer at a time on the run's device.
``logit_gap`` is the largest, over the sample and the generated
positions, of (best reference logit - reference logit of the served
token) / max(1, |best|): a near-tie that rounding flips costs about the
rounding, a wrong block a whole logit.  A missing, short or out-of-range
token reads ``inf``.

``control`` is the same reference a step below the served precision and
put in the program's place: the ranking in bfloat16, the decoder's
weights and matmul inputs in float8 (e4m3, a scale per weight matrix and
per activation row), the rest in bfloat16; it decodes greedily, one full
pass per token.
"""
from __future__ import annotations

import importlib.util
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench import weights  # noqa: E402


def _load(name: str):
    s = importlib.util.spec_from_file_location(
        f"chipbench_reference_{name}_base", HERE / f"{name}.py")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


bm25 = _load("bm25")
rerank = _load("bm25_rerank")

Collection = bm25.Collection

#: documents whose vectors are made at once (bounds the host's memory)
DOC_BLOCK = 1 << 16


# -- ranking ---------------------------------------------------------------

def doc_store(coll, params: dict, dtype) -> np.ndarray:
    """Every document's unit vector, in blocks of documents."""
    proj = rerank.projection(int(params["doc_proj_seed"]), coll.vocab,
                             int(params["dim"]))
    return np.concatenate([
        rerank.doc_vectors(coll, np.arange(s, min(s + DOC_BLOCK,
                                                  coll.n_docs)),
                           proj, dtype)
        for s in range(0, coll.n_docs, DOC_BLOCK)])


def run(coll, Q: dict, params: dict, *, seed: int, dtype=np.float64) -> list:
    """The dense first stage's top ``k`` (doc ids, scores) of every query,
    and for the comparison a function giving any document's score."""
    k = int(params["k"])
    emb = doc_store(coll, params, dtype)
    qproj = rerank.projection(seed, coll.vocab, int(params["dim"]))
    out = []
    for terms, w in zip(Q["terms"], Q["weights"]):
        s = (emb @ rerank.query_vector(terms, w, qproj, dtype)).astype(dtype)
        docs, top = bm25.top_k(s, k)
        out.append({"docids": docs, "scores": top.astype(np.float64),
                    "score_of": (lambda d, s=s: s[d].astype(np.float64))})
    return out


# -- prompts ---------------------------------------------------------------

def prompt(coll, terms, docids, params: dict, vocab: int) -> np.ndarray:
    P = int(params["max_prompt_len"])
    parts = [np.asarray(terms)[np.asarray(terms) >= 0]]
    for d in np.asarray(docids)[:int(params["prompt_docs"])]:
        if d < 0:
            continue
        own = np.unique(coll.doc_terms[coll.doc_start[d]:coll.doc_start[d + 1]])
        parts.append(own[~coll.stop[own]])
    toks = (2 + np.concatenate(parts).astype(np.int64) % (vocab - 2))[:P]
    if toks.size == 0:
        return np.zeros(P, np.int32)
    return toks[np.arange(P) % toks.size].astype(np.int32)


# -- the decoder -----------------------------------------------------------

def _layer_leaves(lm: dict) -> dict:
    """name -> (shape of one layer, dtype) of the stacked leaves."""
    d, h, g, k, f = (lm["d_model"], lm["n_q"], lm["n_kv"], lm["d_head"],
                     lm["d_ff"])
    dt = np.dtype(lm["dtype"])
    out = {"layers/attn/wq": ((d, h, k), dt), "layers/attn/wk": ((d, g, k), dt),
           "layers/attn/wv": ((d, g, k), dt), "layers/attn/wo": ((h, k, d), dt),
           "layers/ln_attn": ((d,), np.float32),
           "layers/ln_mlp": ((d,), np.float32),
           "layers/mlp/w_gate": ((d, f), dt), "layers/mlp/w_up": ((d, f), dt),
           "layers/mlp/w_down": ((f, d), dt)}
    if lm["qkv_bias"]:
        out.update({"layers/attn/bq": ((h, k), dt),
                    "layers/attn/bk": ((g, k), dt),
                    "layers/attn/bv": ((g, k), dt)})
    return out


def leaves(lm: dict) -> dict:
    """name -> (shape, dtype) of every leaf, stacked ones whole."""
    _supported(lm)
    d, V, L = lm["d_model"], lm["vocab"], lm["n_layers"]
    dt = np.dtype(lm["dtype"])
    out = {"embed": ((V, d), dt), "ln_final": ((d,), np.float32)}
    if not lm["tie_embeddings"]:
        out["unembed"] = ((d, V), dt)
    for name, (shape, t) in _layer_leaves(lm).items():
        out[name] = ((L,) + shape, t)
    return out


def _supported(lm: dict) -> None:
    if lm.get("moe") or lm.get("attn_chunk"):
        raise NotImplementedError(
            "the reference decoder has no mixture-of-experts layer and no "
            "chunked attention yet")


def layer_weights(lm: dict, seed: int, l: int) -> dict:
    """Layer ``l``'s leaves, by the weights' rule, widened to float32."""
    return {name.split("/")[-1]: weights.layer(seed, name, l, shape, dt)
            .astype(jnp.float32)
            for name, (shape, dt) in _layer_leaves(lm).items()}


def _outer(lm: dict, seed: int, name: str) -> jax.Array:
    shape, dt = leaves(lm)[name]
    return weights.whole(seed, name, shape, dt)


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """Rotary positions 0.. on ``x`` [S, H, D] (float32)."""
    D = x.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mm(spec, a, w, low):
    """One matmul; ``low`` rounds both inputs to float8 first (``w`` is
    already rounded: ``(values, scale)``) and accumulates in float32."""
    if not low:
        return jnp.einsum(spec, a, w)
    qa, sa = _fp8(a, axes=(-2, -1) if spec.startswith("bshk") else (-1,))
    y = jnp.einsum(spec, qa, w[0], preferred_element_type=jnp.float32)
    return (y * sa.reshape(sa.shape[:2] + (1,) * (y.ndim - 2))
            * w[1]).astype(jnp.bfloat16)


def _fp8(x, axes):
    """``x`` rounded to float8 e4m3 under a scale that maps the absolute
    maximum over ``axes`` to its largest value: (values in bfloat16,
    scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axes, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / 448.0
    q = (x.astype(jnp.float32) / s).astype(ml_dtypes.float8_e4m3fn)
    return q.astype(jnp.bfloat16), s


def _block(w, x, *, n_q, n_kv, theta, eps, bias, low=False):
    """One pre-norm decoder layer over ``x`` [B, S, d]."""
    act = jnp.bfloat16 if low else jnp.float32
    h = _rms(x, w["ln_attn"], eps).astype(act)
    q = _mm("bsd,dhk->bshk", h, w["wq"], low)
    k = _mm("bsd,dhk->bshk", h, w["wk"], low)
    v = _mm("bsd,dhk->bshk", h, w["wv"], low)
    if bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    G, D = n_q // n_kv, q.shape[-1]

    def attend(qkv):
        q, k, v = qkv
        q, k = _rope(q, theta).astype(act), _rope(k, theta).astype(act)
        k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
        s = jnp.einsum("shd,thd->hst", q, k,
                       preferred_element_type=jnp.float32) * D ** -0.5
        S = s.shape[-1]
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1).astype(act)
        return jnp.einsum("hst,thd->shd", p, v).astype(act)

    o = jax.lax.map(attend, (q, k, v))
    x = (x + _mm("bshk,hkd->bsd", o, w["wo"], low)).astype(act)
    h = _rms(x, w["ln_mlp"], eps).astype(act)
    up = jax.nn.silu(_mm("bsd,df->bsf", h, w["w_gate"], low)) \
        * _mm("bsd,df->bsf", h, w["w_up"], low)
    return (x + _mm("bsf,fd->bsd", up.astype(act), w["w_down"], low)
            ).astype(act)


def _hyper(lm: dict) -> dict:
    return dict(n_q=int(lm["n_q"]), n_kv=int(lm["n_kv"]),
                theta=float(lm["rope_theta"]), eps=float(lm["norm_eps"]),
                bias=bool(lm["qkv_bias"]))


@partial(jax.jit, static_argnames=("n_q", "n_kv", "theta", "eps", "bias",
                                   "low"))
def _block_jit(w, x, **kw):
    return _block(w, x, **kw)


@partial(jax.jit, static_argnames=("eps",))
def _logits(x, ln, unembed, *, eps):
    return jnp.einsum("bsd,dv->bsv", _rms(x, ln, eps), unembed)


def logits(lm: dict, seed: int, seqs: np.ndarray, first: int) -> np.ndarray:
    """Reference logits [B, S - first, V] (float32) at positions ``first``
    .. S-1 of the token sequences ``seqs`` [B, S], one layer at a time."""
    _supported(lm)
    with jax.default_matmul_precision("highest"):
        embed = _outer(lm, seed, "embed")
        x = embed[jnp.asarray(seqs)].astype(jnp.float32)
        del embed
        for l in range(lm["n_layers"]):
            x = _block_jit(layer_weights(lm, seed, l), x, **_hyper(lm))
        unembed = (_outer(lm, seed, "embed").T if lm["tie_embeddings"]
                   else _outer(lm, seed, "unembed"))
        out = _logits(x[:, first:], _outer(lm, seed, "ln_final"),
                      unembed.astype(jnp.float32), eps=float(lm["norm_eps"]))
        return np.asarray(out)


def logit_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """(best - logit of ``tokens``) / max(1, |best|) at each position of
    ``ref_logits`` [..., T, V], ``tokens`` [..., T]."""
    best = ref_logits.max(-1)
    got = np.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return (best - got) / np.maximum(1.0, np.abs(best))


def numbers(coll, Q: dict, answers: list, refs: list, params: dict, *,
            seed: int, lm: dict) -> dict:
    """``logit_gap`` of the served tokens of ``answers`` (one per row of
    ``Q``), and the number of tokens it was read over."""
    T, V = int(params["max_new_tokens"]), int(lm["vocab"])
    if not answers:
        return {"logit_gap": float("inf"), "tokens_checked": 0}
    toks = []
    for a in answers:
        t = np.asarray(a.get("tokens", []), np.int64).reshape(-1)
        if t.size != T or t.min() < 0 or t.max() >= V:
            return {"logit_gap": float("inf"), "tokens_checked": 0}
        toks.append(t)
    toks = np.stack(toks)
    P = int(params["max_prompt_len"])
    seqs = np.stack([np.concatenate([prompt(coll, Q["terms"][i], a["docids"],
                                            params, V), toks[i, :-1]])
                     for i, a in enumerate(answers)])
    gaps = logit_gap(logits(lm, seed, seqs, P - 1), toks)
    return {"logit_gap": float(gaps.max()), "tokens_checked": int(toks.size)}


# -- the control -----------------------------------------------------------

@partial(jax.jit, static_argnames=("hyper",))
def _next_token(layers, embed, ln_final, unembed, seqs, pos, *, hyper):
    """The low-precision decoder's greedy token at position ``pos`` of
    ``seqs`` (one full pass; later positions are masked by causality)."""
    def body(x, w):
        return _block(w, x, **dict(hyper)), None

    x, _ = jax.lax.scan(body, embed[seqs], layers)
    h = _rms(jax.lax.dynamic_slice_in_dim(x, pos, 1, 1), ln_final,
             dict(hyper)["eps"]).astype(jnp.bfloat16)
    logit = _mm("bsd,dv->bsv", h, unembed, True)
    return jnp.argmax(logit[:, 0].astype(jnp.float32), -1).astype(jnp.int32)


def _low_layer(lm: dict, seed: int, l: int) -> dict:
    w = {}
    for name, (shape, dt) in _layer_leaves(lm).items():
        leaf = weights.layer(seed, name, l, shape, dt)
        key = name.split("/")[-1]
        if key.startswith("ln"):
            w[key] = leaf
        elif key.startswith("b"):
            w[key] = leaf.astype(jnp.bfloat16)
        else:
            w[key] = _fp8(leaf, axes=tuple(range(leaf.ndim)))
    return w


def control(coll, Q: dict, params: dict, *, seed: int, lm: dict) -> list:
    """The reference a step below the served precision, in the program's
    place: its answers (doc ids, scores, greedy tokens) to ``Q``."""
    _supported(lm)
    ranked = run(coll, Q, params, seed=seed, dtype=ml_dtypes.bfloat16)
    P, T, V = (int(params["max_prompt_len"]), int(params["max_new_tokens"]),
               int(lm["vocab"]))
    seqs = np.zeros((len(ranked), P + T - 1), np.int32)
    for i, r in enumerate(ranked):
        seqs[i, :P] = prompt(coll, Q["terms"][i], r["docids"], params, V)
    layers = jax.tree.map(lambda *w: jnp.stack(w),
                          *[_low_layer(lm, seed, l)
                            for l in range(lm["n_layers"])])
    embed = _outer(lm, seed, "embed").astype(jnp.bfloat16)
    unembed = _fp8(embed.T if lm["tie_embeddings"]
                   else _outer(lm, seed, "unembed"), axes=(0, 1))
    ln_final = _outer(lm, seed, "ln_final")
    step = partial(_next_token,
                   hyper=tuple(dict(_hyper(lm), low=True).items()))
    seqs = jnp.asarray(seqs)
    out = []
    for t in range(T):
        tok = step(layers, embed, ln_final, unembed, seqs, P - 1 + t)
        out.append(tok)
        if t < T - 1:
            seqs = seqs.at[:, P + t].set(tok)
    toks = np.asarray(jnp.stack(out, 1))
    return [{"docids": r["docids"], "scores": r["scores"], "tokens": toks[i]}
            for i, r in enumerate(ranked)]
