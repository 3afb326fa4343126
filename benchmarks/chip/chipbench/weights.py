"""Seeded weights of a generating leaf's LM, drawn by one rule.

The benchmark draws the weights a cell serves (``system.build``), and a
plain reference regenerates any one layer of any leaf on its own, by the
same rule and nothing the program made:

* a leaf is named by its path in the parameter tree, the keys joined by
  ``/`` (``layers/attn/wq``); a leaf under a top-level key that ends in
  ``layers`` is stacked over the layers on its first axis, and each layer
  is a draw of its own;
* the key of a leaf is ``fold_in(fold_in(key(seed), SALT), crc32(path))``,
  and of its layer ``l`` one more ``fold_in(., l)``;
* a leaf whose last key starts with ``ln`` (an RMSNorm gain) is ones;
* ``embed`` is a standard normal (the token embedding of a pre-norm
  decoder: its rows enter the residual stream unscaled);
* every other leaf is a normal of standard deviation ``fan_in ** -0.5``
  over the shape of one draw, where ``fan_in`` is the product of the axes
  the leaf's matmul contracts: the first axis, the first two for ``wo``
  (heads x head size), the second for an expert stack (a leaf of three
  axes under ``moe`` and not under ``shared``);
* each draw is made in float32 and cast to the leaf's dtype, the one the
  program's ``init_params`` gives it.

:func:`draw` makes the whole tree on the device in one jitted call;
:func:`layer` one layer of one leaf.  Both give the same bits: a stacked
leaf is the layer draws, stacked.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.datagen import key_of

#: separates the weights' keys from every other draw of the run's seed
SALT = 0x57E1


def path_name(path) -> str:
    """``layers/attn/wq`` from a ``jax.tree_util`` key path."""
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def stacked(name: str) -> bool:
    return name.split("/")[0].endswith("layers")


def scale(name: str, shape) -> float | None:
    """Standard deviation of one draw of leaf ``name`` with ``shape``;
    None for a leaf of ones."""
    keys = name.split("/")
    if keys[-1].startswith("ln"):
        return None
    if name == "embed":
        return 1.0
    if keys[-1] == "wo":
        fan_in = shape[0] * shape[1]
    elif "moe" in keys and "shared" not in keys and len(shape) == 3:
        fan_in = shape[1]
    else:
        fan_in = shape[0]
    return fan_in ** -0.5


def leaf_key(seed: int, name: str):
    k = jax.random.fold_in(key_of(seed), SALT)
    return jax.random.fold_in(k, zlib.crc32(name.encode()))


def _one(key, name: str, shape, dtype):
    std = scale(name, shape)
    if std is None:
        return jnp.ones(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@partial(jax.jit, static_argnames=("name", "shape", "dtype"))
def _layer(key, l, *, name, shape, dtype):
    return _one(jax.random.fold_in(key, l), name, shape, dtype)


_whole = jax.jit(_one, static_argnums=(1, 2, 3))


def layer(seed: int, name: str, l: int, shape, dtype) -> jax.Array:
    """Layer ``l`` of stacked leaf ``name``: ``shape`` and ``dtype`` are
    one layer's."""
    return _layer(leaf_key(seed, name), l, name=name, shape=tuple(shape),
                  dtype=np.dtype(dtype))


def whole(seed: int, name: str, shape, dtype) -> jax.Array:
    """Leaf ``name`` that is not stacked."""
    return _whole(leaf_key(seed, name), name, tuple(shape), np.dtype(dtype))


def draw(shapes, seed: int):
    """Every leaf of ``shapes`` (a tree of ``jax.ShapeDtypeStruct``, as
    ``jax.eval_shape`` gives the program's parameters), drawn on the
    device in one call."""
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = [(path_name(p), tuple(s.shape), np.dtype(s.dtype))
              for p, s in flat]

    def make(keys):
        out = []
        for k, (name, shape, dtype) in zip(keys, leaves):
            if stacked(name):
                out.append(jax.vmap(
                    lambda l, k=k, name=name, shape=shape, dtype=dtype:
                    _one(jax.random.fold_in(k, l), name, shape[1:], dtype))(
                        jnp.arange(shape[0])))
            else:
                out.append(_one(k, name, shape, dtype))
        return out

    keys = [leaf_key(seed, name) for name, _, _ in leaves]
    return jax.tree_util.tree_unflatten(tree, jax.jit(make)(keys))
