"""Collections, topics and arrivals, all drawn from the run's seed.

The collection follows the shape of the program's own generator
(``repro.index.corpus.synthesize_corpus``: Zipf-distributed terms,
lognormal document lengths with median ``median_len`` and sigma 0.5, at
least 8 terms a document), vectorised on the device: the numpy original
takes about half a minute at Robust04's 528,155 documents.

Every seed serves the same amount of work.  The collection's sizes (the
multiset of document lengths and of term counts, hence every posting-list
length and every array shape the program compiles for) come from the
configuration's fixed ``collection_key``; the run's seed then renames the
terms and reorders the documents, both by a random permutation.  A seed
that drew its own sizes would move the longest posting list across the
stop-word cut, change the shapes the served programs are compiled for
and miss the compile cache in every run.  Topics and arrivals likewise
take a fixed multiset of lengths and gaps, in the seed's order.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: padded query width of the program's Q relation (``repro.core.data.MAXQ``)
MAXQ = 48


def key_of(seed: int):
    """A JAX key from a seed of any size (the low and high 32 bits)."""
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def host_rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([seed & (2 ** 64 - 1), salt])


@dataclasses.dataclass
class Collection:
    """Documents as one flat term stream (doc-major) on the host."""
    doc_terms: np.ndarray      # [T] int32
    doc_start: np.ndarray      # [D + 1] int64
    vocab: int
    rank_to_term: np.ndarray   # [V] int32: frequency rank -> term id

    @property
    def n_docs(self) -> int:
        return len(self.doc_start) - 1


@partial(jax.jit, static_argnames=("n_docs",))
def _doc_lengths(key, median, sigma, min_len, *, n_docs):
    z = jax.random.normal(key, (n_docs,), jnp.float32)
    return jnp.maximum(jnp.floor(median * jnp.exp(sigma * z)),
                       min_len).astype(jnp.int32)


@partial(jax.jit, static_argnames=("n_tokens", "vocab"))
def _zipf_ranks(key, zipf_s, *, n_tokens, vocab):
    """Frequency ranks (0 = most frequent) by the inverse CDF of a power
    law with exponent ``zipf_s`` over ``[1, vocab + 1)``, floored."""
    u = jax.random.uniform(key, (n_tokens,), jnp.float32)
    a = 1.0 - zipf_s
    c = (vocab + 1.0) ** a
    x = (1.0 + u * (c - 1.0)) ** (1.0 / a)
    return jnp.clip(jnp.floor(x).astype(jnp.int32) - 1, 0, vocab - 1)


@jax.jit
def _permute(key, lens, ranks):
    """Rename terms and reorder documents by the seed's permutations."""
    n_docs, n_tokens = lens.shape[0], ranks.shape[0]
    vocab_perm_key, doc_perm_key = jax.random.split(key)
    doc_perm = jax.random.permutation(doc_perm_key, n_docs)
    new_lens = lens[doc_perm]
    old_start = jnp.cumsum(lens) - lens
    new_start = jnp.cumsum(new_lens) - new_lens
    # document of every position of the reordered stream (no empty docs)
    seg = jnp.cumsum(jnp.zeros(n_tokens, jnp.int32)
                     .at[new_start[1:]].add(1))
    pos = jnp.arange(n_tokens, dtype=jnp.int32)
    old_pos = old_start[doc_perm[seg]] + (pos - new_start[seg])
    return ranks[old_pos], new_lens, doc_perm_key, vocab_perm_key


@partial(jax.jit, static_argnames=("vocab",))
def _rename(key, ranks, *, vocab):
    rank_to_term = jax.random.permutation(key, vocab).astype(jnp.int32)
    return rank_to_term[ranks], rank_to_term


def collection(coll: dict, seed: int) -> Collection:
    """The configuration's collection under the seed's renaming.  Three
    jitted device calls, then one copy of the term stream to the host
    (the program's index build is host code)."""
    n_docs, vocab = int(coll["n_docs"]), int(coll["vocab"])
    base = jax.random.key(int(coll["collection_key"]))
    k_len, k_tok = jax.random.split(base)
    lens = _doc_lengths(k_len, float(coll["median_len"]),
                        float(coll["len_sigma"]), float(coll["min_len"]),
                        n_docs=n_docs)
    n_tokens = int(jnp.sum(lens))
    ranks = _zipf_ranks(k_tok, float(coll["zipf_s"]), n_tokens=n_tokens,
                        vocab=vocab)
    ranks, lens, _, k_vocab = _permute(key_of(seed), lens, ranks)
    terms, rank_to_term = _rename(k_vocab, ranks, vocab=vocab)
    doc_terms = np.asarray(terms)
    doc_start = np.zeros(n_docs + 1, np.int64)
    np.cumsum(np.asarray(lens), out=doc_start[1:])
    return Collection(doc_terms, doc_start, vocab, np.asarray(rank_to_term))


# ---------------------------------------------------------------------------
# topics
# ---------------------------------------------------------------------------

def _distinct_rows(rng, lo: int, hi: int, width: int, n: int) -> np.ndarray:
    """``n`` rows of ``width`` distinct integers in ``[lo, hi)``."""
    out = rng.integers(lo, hi, (n, width))
    while True:
        s = np.sort(out, axis=1)
        dup = (s[:, 1:] == s[:, :-1]).any(axis=1)
        if not dup.any():
            return out
        out[dup] = rng.integers(lo, hi, (int(dup.sum()), width))


def topics(query: dict, n: int, seed: int, rank_to_term: np.ndarray,
           salt: int = 1) -> dict:
    """``n`` topics as a Q relation on the host (``qid``, ``terms`` [n, 48]
    padded with -1, ``weights``).

    A topic has ``title_terms`` terms of weight 1 drawn from the ranks in
    ``title_band`` (fractions of the vocabulary: the program's own
    generator draws titles from ``[V/200, V/4)``), then enough terms of
    weight ``extra_weight`` from ``extra_band`` to reach ``total_terms``
    (TD and TDN formulations; weight 0.5 as ``expand_topics`` gives).
    All of a topic's terms are distinct.  Lengths are a fixed multiset
    (an equal share of each allowed length) in the seed's order."""
    rng = host_rng(seed, salt)
    V = len(rank_to_term)
    t_lo, t_hi = (int(f * V) for f in query["title_band"])
    t_min, t_max = query["title_terms"]
    n_title = rng.permutation(np.resize(np.arange(t_min, t_max + 1), n))
    tot_min, tot_max = query.get("total_terms", [t_min, t_max])
    if tot_max > MAXQ:
        raise ValueError(f"topics of {tot_max} terms exceed {MAXQ} slots")
    terms = np.full((n, MAXQ), -1, np.int32)
    weights = np.zeros((n, MAXQ), np.float32)
    title = _distinct_rows(rng, t_lo, t_hi, t_max, n)
    if tot_max > t_max:
        n_total = rng.permutation(np.resize(np.arange(tot_min, tot_max + 1),
                                            n))
        e_lo, e_hi = (int(f * V) for f in query["extra_band"])
        pool = np.concatenate(
            [title, _distinct_rows(rng, e_lo, e_hi, tot_max, n)], axis=1)
        # extra terms that repeat a title term are drawn again
        for i in range(n):
            seen = list(dict.fromkeys(pool[i, :n_title[i]]))
            for r in pool[i, t_max:]:
                if len(seen) == n_total[i]:
                    break
                if r not in seen:
                    seen.append(r)
            while len(seen) < n_total[i]:
                r = int(rng.integers(e_lo, e_hi))
                if r not in seen:
                    seen.append(r)
            terms[i, :n_total[i]] = rank_to_term[np.asarray(seen)]
            weights[i, :n_title[i]] = 1.0
            weights[i, n_title[i]:n_total[i]] = query["extra_weight"]
    else:
        for i in range(n):
            terms[i, :n_title[i]] = rank_to_term[title[i, :n_title[i]]]
            weights[i, :n_title[i]] = 1.0
    return {"qid": np.arange(n, dtype=np.int32), "terms": terms,
            "weights": weights}


#: salt of block b >= 1 of a topic pool is ``BLOCK_SALT + b``: far from
#: the salts of ``topics`` (1), ``arrivals`` (2) and ``knee.py`` (50, 60+)
BLOCK_SALT = 1 << 20


def topic_blocks(query: dict, pool: int, n_blocks: int, seed: int,
                 rank_to_term: np.ndarray) -> dict:
    """``n_blocks`` blocks of ``pool`` topics, one after the other.
    Block 0 is ``topics(query, pool, seed, rank_to_term)`` bit for bit;
    block b >= 1 is drawn alike with salt ``BLOCK_SALT + b``.  ``qid``s
    run on across blocks: block b holds ``b * pool + arange(pool)``."""
    blocks = [topics(query, pool, seed, rank_to_term, salt=1 if b == 0
                     else BLOCK_SALT + b) for b in range(n_blocks)]
    return {"qid": np.arange(n_blocks * pool, dtype=np.int32),
            **{k: np.concatenate([blk[k] for blk in blocks])
               for k in ("terms", "weights")}}


def empty_topics(n: int) -> dict:
    """``n`` topics without terms: the shapes of a batch, for warming up."""
    return {"qid": np.arange(n, dtype=np.int32),
            "terms": np.full((n, MAXQ), -1, np.int32),
            "weights": np.zeros((n, MAXQ), np.float32)}


def rows(Q: dict, start: int, stop: int) -> dict:
    return {k: v[start:stop] for k, v in Q.items()}


def arrivals(rate_qps: float, seconds: float, seed: int,
             salt: int = 2, block: int | None = None) -> np.ndarray:
    """Send times in ``[0, seconds)`` of an open Poisson stream: the
    ``rate * seconds`` quantiles of the exponential gap distribution,
    shuffled by the seed and scaled to fill the window exactly.

    With ``block`` = m the shuffle is stratified: the sorted gaps are cut
    into m strata of neighbouring quantiles, and every run of m
    consecutive arrivals takes one gap of each stratum, both choices in
    the seed's order.  The gaps stay the same multiset, but every stretch
    of m arrivals offers the same load, so no seed draws a slow build-up
    of the queue that another seed does not."""
    n = max(1, int(round(rate_qps * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    rng = host_rng(seed, salt)
    if block is None or block <= 1 or block >= n:
        gaps = rng.permutation(gaps)
    else:
        n_blocks = -(-n // block)
        grid = np.full((n_blocks, block), np.nan)
        for s, stratum in enumerate(np.array_split(gaps, block)):
            grid[:len(stratum), s] = rng.permutation(stratum)
        grid = rng.permuted(grid, axis=1)
        gaps = grid[~np.isnan(grid)]
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
