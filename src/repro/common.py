"""Shared utilities: dtype policy, tree helpers, simple registries."""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# dtype policy
# ---------------------------------------------------------------------------

#: Default parameter / activation dtype for large-scale runs. fp32 is used for
#: softmax, layernorm statistics, router logits and the optimizer state.
DEFAULT_DTYPE = jnp.bfloat16


def cast_tree(tree: Any, dtype) -> Any:
    """Cast every floating leaf of ``tree`` to ``dtype``."""

    def _cast(x):
        if isinstance(x, (jax.Array, np.ndarray)) and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x

    return jax.tree.map(_cast, tree)


def tree_size_bytes(tree: Any) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree) if hasattr(x, "size"))


def tree_param_count(tree: Any) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree) if hasattr(x, "shape"))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class Registry:
    """Minimal name → factory registry (used for archs, weighting models, ...)."""

    def __init__(self, kind: str):
        self.kind = kind
        self._entries: dict[str, Any] = {}

    def register(self, name: str, obj: Any = None):
        if obj is not None:
            self._entries[name] = obj
            return obj

        def deco(fn):
            self._entries[name] = fn
            return fn

        return deco

    def __getitem__(self, name: str) -> Any:
        if name not in self._entries:
            raise KeyError(
                f"unknown {self.kind} {name!r}; known: {sorted(self._entries)}")
        return self._entries[name]

    def __contains__(self, name: str) -> bool:
        return name in self._entries

    def names(self) -> list[str]:
        return sorted(self._entries)


# ---------------------------------------------------------------------------
# bounded LRU mapping (engine jit/chunk caches, serve-layer stage cache)
# ---------------------------------------------------------------------------


class LRU:
    """Bounded insertion/access-ordered mapping with eviction + hit counters.

    ``maxsize=None`` disables the bound (plain dict semantics).  A long-lived
    server touches arbitrarily many (stage, bucket, signature) cache keys, so
    every cache on that path must be bounded or it leaks; the counters feed
    ``cache_info()``-style accessors.

    Thread-safe: the serving layer explicitly supports one cache shared by
    several running servers, and both ``get`` (pop + re-insert) and ``put``
    (insert + evict-oldest) are compound — two racing evictions would pop
    the same oldest key and the loser would KeyError without the lock.
    The lock is reentrant because weakref death callbacks (the engine's
    chunk cache evicts entries when their source array dies) may fire from
    GC triggered *inside* a locked method on the same thread.
    """

    def __init__(self, maxsize: int | None = None):
        import threading
        self.maxsize = maxsize
        self._d: dict = {}
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key, default=None):
        with self._lock:
            try:
                v = self._d.pop(key)
            except KeyError:
                self.misses += 1
                return default
            self._d[key] = v      # re-insert = move to most-recent
            self.hits += 1
            return v

    def put(self, key, value) -> None:
        with self._lock:
            self._d.pop(key, None)
            self._d[key] = value
            if self.maxsize is not None:
                while len(self._d) > self.maxsize:
                    self._d.pop(next(iter(self._d)), None)
                    self.evictions += 1

    def pop(self, key, default=None):
        with self._lock:
            return self._d.pop(key, default)

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)

    def __contains__(self, key) -> bool:   # no LRU touch, no counter bump
        with self._lock:
            return key in self._d

    def values(self) -> list:
        """Snapshot copy — a live dict view would raise if another thread
        inserts mid-iteration (stats readers race the serving thread)."""
        with self._lock:
            return list(self._d.values())

    def items(self) -> list:
        """Snapshot copy of the (key, value) pairs, like :meth:`values`."""
        with self._lock:
            return list(self._d.items())

    def clear(self) -> None:
        with self._lock:
            self._d.clear()

    def info(self) -> dict:
        with self._lock:
            return {"size": len(self._d), "maxsize": self.maxsize,
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions}


# ---------------------------------------------------------------------------
# bucket ladder policy (shared by the engine and the serving scheduler)
# ---------------------------------------------------------------------------


def select_ladder_bucket(ladder, n: int, *, clamp: bool = False) -> int:
    """Smallest rung of a sorted bucket ``ladder`` covering an ``n``-query
    micro-batch.  This is THE ladder policy — the engine's padding rule and
    the serving scheduler's batch-closure rule are the same function, so
    the two can never drift.  ``clamp=True`` returns the largest rung for
    oversized ``n`` (schedulers report a bucket for any batch they could
    close); ``clamp=False`` raises (the engine chunk-plans big batches
    instead of silently truncating them)."""
    if n <= 0:
        raise ValueError("empty query batch")
    for b in ladder:
        if b >= n:
            return int(b)
    if clamp:
        return int(ladder[-1])
    raise ValueError(
        f"micro-batch of {n} exceeds largest bucket {ladder[-1]}; "
        f"split it (run() chunk-plans big batches automatically)")


# ---------------------------------------------------------------------------
# misc
# ---------------------------------------------------------------------------


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def frozen(cls):
    """Shorthand for a frozen dataclass with keyword-only fields."""
    return dataclasses.dataclass(frozen=True, kw_only=True)(cls)
