"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run overrides the host
platform device count while tests/benches must see one device.

Every mesh is built with ``AxisType.Auto`` axes: ``jax.make_mesh`` defaults
to explicit axis types, under which a per-query gather from a replicated
index array with ``data``-sharded indices is a sharding-type error.  Auto
axes let GSPMD propagate the query sharding through those gathers, so the
engine keeps its single per-query code path.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh over the real local device (smoke tests)."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_query_mesh(*, max_devices: int | None = None,
                    doc_shards: int | None = None):
    """``("data",)`` mesh over the local devices — the serving-side
    counterpart of the training meshes above, used by the sharded query
    execution engine (core/engine.py) to data-parallel the query axis.
    ``max_devices`` restricts the mesh (device-scaling benchmarks).

    ``doc_shards`` selects the 2-D ``(query x doc-shard)`` layout
    ``("data", "docs")``: the query axis data-parallels over the first
    axis while each ``docs`` group owns one contiguous slice of the
    document axis (``index.dense.shard_dense_index``), merged across
    shards by ``core.engine.merge_shard_topk``.  The device count must be
    divisible by ``doc_shards``."""
    devices = jax.local_devices()
    if max_devices is not None:
        devices = devices[:max(1, min(max_devices, len(devices)))]
    if doc_shards is None:
        return _auto_mesh((len(devices),), ("data",), devices)
    doc_shards = int(doc_shards)
    if doc_shards < 1 or len(devices) % doc_shards:
        raise ValueError(
            f"doc_shards={doc_shards} must divide the device count "
            f"{len(devices)}")
    return _auto_mesh((len(devices) // doc_shards, doc_shards),
                      ("data", "docs"), devices)
