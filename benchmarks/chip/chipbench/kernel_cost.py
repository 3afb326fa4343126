"""Operations and bytes that one kernel call needs, from its shapes.

``streaming_topk`` (``repro/kernels/topk``): the score vector, padded to
whole ``[block/128, 128]`` tiles, streams once from HBM through VMEM; the
running top-k (values f32 and indices i32, ``ceil(k/128)`` rows of 128)
is written once.  The least work is one comparison per score (the block
maximum that decides the skip); merges come on top and depend on the
data, so they are not counted.  The roofline share is then

    least time = max(ops / peak FLOP/s, bytes / peak HBM bytes/s)
    share      = least time / measured kernel time.
"""
from __future__ import annotations

import re

#: score block the kernel streams (``repro.kernels.topk.topk.BLOCK_S``)
TOPK_BLOCK = 4096
LANES = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def streaming_topk(n_scores: int, k: int, rows: int = 1) -> dict:
    """ops and bytes of one call over ``rows`` score vectors of
    ``n_scores`` each, at cutoff ``k``."""
    n_pad = _round_up(max(n_scores, TOPK_BLOCK), TOPK_BLOCK)
    out_rows = _round_up(k, LANES) // LANES
    read = 4 * n_pad
    write = 2 * 4 * out_rows * LANES
    return {"ops": float(rows * n_pad), "bytes": float(rows * (read + write))}


def least_time_s(cost: dict, peaks: dict) -> tuple:
    """(seconds, bound) of the roofline: the larger of the two limits."""
    t_ops = cost["ops"] / peaks["flops_per_s"]
    t_mem = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")


#: ``%vmap_streaming_topk_.1 = (f32[B,kr,128]..., s32[B,kr,128]...)
#: custom-call(f32[B,R,128]...`` as the device trace names the kernel
_TOPK_EVENT = re.compile(
    r"= \(f32\[(\d+),(\d+),(\d+)\].*?custom-call\(f32\[(\d+),(\d+),(\d+)\]")


def streaming_topk_event(name: str) -> dict:
    """ops and bytes of one ``streaming_topk`` event, from the operand and
    result shapes in its name: B rows of R x 128 scores streamed in, B
    rows of kr x 128 values and as many indices written out."""
    m = _TOPK_EVENT.search(name)
    if m is None:
        raise ValueError(f"no streaming_topk shapes in event {name[:200]!r}")
    b_out, kr, lanes_out, b, r, lanes = (int(x) for x in m.groups())
    if b_out != b:
        raise ValueError(f"rows in {b} != rows out {b_out}: {name[:200]!r}")
    return {"ops": float(b * r * lanes),
            "bytes": float(b * (4 * r * lanes + 2 * 4 * kr * lanes_out))}
