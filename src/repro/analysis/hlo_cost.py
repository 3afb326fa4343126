"""Trip-count-aware cost model over post-SPMD optimized HLO text.

``compiled.cost_analysis()`` counts while-loop bodies ONCE (verified
empirically: a 10-iteration scan reports 1/10th the flops of its unrolled
twin), which silently destroys roofline numbers for scan-over-layers models.
This walker parses the optimized per-device HLO, multiplies loop bodies by
their ``known_trip_count`` backend config, and accounts:

* flops        — dots (2·result·K from contracting dims), elementwise/reduce
                 ops at 1 flop/output element,
* bytes        — HBM traffic proxy: operands+result at fusion/op granularity;
                 gathers/scatters/dynamic-slices count touched bytes, not the
                 whole operand buffer,
* collectives  — per-kind per-chip ring traffic (all-reduce 2·b, others ~b),
                 inside loops correctly multiplied.

Post-SPMD shapes are per-shard, so every figure is PER CHIP.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
from typing import Any

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3": 1, "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4,
    "u16": 2, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0, "s4": 1,
    "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
#: a shape with its layout; a TPU layout tagged ``S(1)`` places the buffer
#: in on-chip VMEM (memory-space assignment), so it is no HBM traffic
_LAID_OUT_RE = re.compile(r"(\w+)\[([\d,]*)\](\{[^}]*\})?")
_INSTR_HEAD_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OP_RE = re.compile(r"\s*([\w\-]+)\(")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\((.*?)\)\s*->")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_PARAM_RE = re.compile(r"([\w.\-]+):\s*((?:\([^)]*\)|[\w\[\],]+))")

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "ragged-all-to-all")

#: zero-traffic bookkeeping ops
_FREE = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
         "after-all", "partition-id", "replica-id", "iota", "copy-start",
         "copy-done", "domain", "opt-barrier", "slice-done"}

#: custom calls that move no data (TPU layout bookkeeping)
_FREE_CUSTOM_CALLS = ("ConcatBitcast", "AssumeGatherIndicesInBound")
_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')


def shape_elems(type_str: str) -> int:
    n = 0
    for m in _SHAPE_RE.finditer(type_str):
        if m.group(1) not in _DTYPE_BYTES:
            continue
        k = 1
        for d in m.group(2).split(","):
            if d:
                k *= int(d)
        n += k
    return n


def shape_bytes(type_str: str) -> int:
    """HBM bytes of a (tuple) type: buffers laid out in VMEM (``S(1)``)
    count zero."""
    total = 0
    for m in _LAID_OUT_RE.finditer(type_str):
        dt = m.group(1)
        if dt not in _DTYPE_BYTES or "S(1)" in (m.group(3) or ""):
            continue
        k = 1
        for d in m.group(2).split(","):
            if d:
                k *= int(d)
        total += k * _DTYPE_BYTES[dt]
    return total


def shape_dims(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Instr:
    name: str
    rtype: str
    op: str
    rest: str           # the raw tail of the line (operands + attrs)
    operands: list[str]


@dataclasses.dataclass
class Computation:
    name: str
    instrs: list[Instr]
    shapes: dict[str, str]  # instr name -> result type string


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0
    collectives: dict[str, float] = dataclasses.field(default_factory=dict)
    collective_counts: dict[str, float] = dataclasses.field(default_factory=dict)

    def add(self, other: "Cost", mult: float = 1.0):
        self.flops += mult * other.flops
        self.bytes += mult * other.bytes
        self.collective_bytes += mult * other.collective_bytes
        for k, v in other.collectives.items():
            self.collectives[k] = self.collectives.get(k, 0.0) + mult * v
        for k, v in other.collective_counts.items():
            self.collective_counts[k] = self.collective_counts.get(k, 0.0) + mult * v


def _match_paren(s: str, start: int) -> int:
    """Index just past the ')' matching the '(' at ``start``."""
    depth = 0
    for i in range(start, len(s)):
        if s[i] == "(":
            depth += 1
        elif s[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(s)


def _parse_instr(line: str) -> Instr | None:
    m = _INSTR_HEAD_RE.match(line)
    if not m:
        return None
    name, rest = m.group(1), m.group(2).strip()
    # result type: either a tuple "(...)" (may contain /*index=N*/ comments)
    # or a plain "dtype[dims]{layout}" token
    if rest.startswith("("):
        end = _match_paren(rest, 0)
        rtype, tail = rest[:end], rest[end:]
    else:
        sp = rest.find(" ")
        if sp < 0:
            return None
        rtype, tail = rest[:sp], rest[sp:]
    mo = _OP_RE.match(tail)
    if not mo:
        return None
    op = mo.group(1)
    open_idx = mo.end() - 1
    close = _match_paren(tail, open_idx)
    operand_str = tail[open_idx:close]
    attrs = tail[close:]
    operands = _OPERAND_RE.findall(operand_str)
    return Instr(name, rtype, op, attrs, operands)


def parse_module(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for raw in text.splitlines():
        line = raw.rstrip()
        stripped = line.strip()
        if stripped.endswith("{"):
            hdr = _COMP_HDR_RE.match(stripped)
            if hdr:
                cur = Computation(hdr.group(1), [], {})
                comps[cur.name] = cur
                # header params give shapes of %param names
                for pm in _PARAM_RE.finditer(hdr.group(2)):
                    cur.shapes[pm.group(1)] = pm.group(2)
                continue
        if cur is None:
            continue
        if stripped == "}":
            cur = None
            continue
        inst = _parse_instr(line)
        if inst is None:
            continue
        cur.instrs.append(inst)
        cur.shapes[inst.name] = inst.rtype
    return comps


_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_CALLED_RE = {
    "body": re.compile(r"body=%([\w.\-]+)"),
    "cond": re.compile(r"condition=%([\w.\-]+)"),
    "calls": re.compile(r"calls=%([\w.\-]+)"),
    "to_apply": re.compile(r"to_apply=%([\w.\-]+)"),
    "branches": re.compile(r"branch_computations=\{([^}]*)\}"),
}
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_LHS_BATCH_RE = re.compile(r"lhs_batch_dims=\{([\d,]*)\}")


def _dot_flops(inst: Instr, shapes: dict[str, str]) -> float:
    out_elems = shape_elems(inst.rtype)
    lhs = shapes.get(inst.operands[0]) if inst.operands else None
    k = 1
    if lhs:
        dims = shape_dims(lhs)
        mc = _LHS_CONTRACT_RE.search(inst.rest)
        if mc and mc.group(1):
            for i in mc.group(1).split(","):
                idx = int(i)
                if idx < len(dims):
                    k *= dims[idx]
    return 2.0 * out_elems * k


def _instr_bytes(inst: Instr, shapes: dict[str, str]) -> float:
    """HBM-traffic proxy for one top-level instruction."""
    op = inst.op
    rb = shape_bytes(inst.rtype)
    if op == "gather":
        idx = shape_bytes(shapes.get(inst.operands[1], "")) if len(inst.operands) > 1 else 0
        return 2.0 * rb + idx
    if op == "scatter":
        upd = shape_bytes(shapes.get(inst.operands[-1], ""))
        return rb + 3.0 * upd
    if op == "dynamic-slice":
        return 2.0 * rb
    if op == "slice-start":
        # async slice: the result tuple aliases the whole operand; only the
        # slice itself is read and written
        ob = sum(shape_bytes(shapes.get(o, "")) for o in inst.operands)
        return 2.0 * max(rb - ob, 0)
    if op == "custom-call":
        mt = _TARGET_RE.search(inst.rest)
        if mt and mt.group(1) in _FREE_CUSTOM_CALLS:
            return 0.0
    if op == "dynamic-update-slice":
        upd = shape_bytes(shapes.get(inst.operands[1], "")) if len(inst.operands) > 1 else 0
        return 2.0 * upd
    ob = sum(shape_bytes(shapes.get(o, "")) for o in inst.operands)
    return rb + ob


class CostModel:
    def __init__(self, text: str):
        self.comps = parse_module(text)
        self._memo: dict[str, Cost] = {}
        entry = None
        for raw in text.splitlines():
            if raw.startswith("ENTRY"):
                m = _COMP_HDR_RE.match(raw.strip())
                entry = m.group(1) if m else None
        self.entry = entry

    def cost(self, comp_name: str | None = None, _depth: int = 0) -> Cost:
        comp_name = comp_name or self.entry
        if comp_name in self._memo:
            return self._memo[comp_name]
        comp = self.comps.get(comp_name)
        total = Cost()
        if comp is None or _depth > 64:
            return total
        for inst in comp.instrs:
            op = inst.op
            if op in _FREE:
                continue
            if op == "while":
                trips = 1.0
                mt = _TRIP_RE.search(inst.rest)
                if mt:
                    trips = float(mt.group(1))
                inner = Cost()
                for key in ("body", "cond"):
                    mm = _CALLED_RE[key].search(inst.rest)
                    if mm:
                        inner.add(self.cost(mm.group(1), _depth + 1))
                total.add(inner, trips)
                continue
            if op == "fusion":
                mm = _CALLED_RE["calls"].search(inst.rest)
                if mm:
                    sub = self.cost(mm.group(1), _depth + 1)
                    total.flops += sub.flops          # internal dots count
                total.bytes += shape_bytes(inst.rtype) + sum(
                    shape_bytes(comp.shapes.get(o, "")) for o in inst.operands)
                continue
            if op in ("call", "conditional", "async-start"):
                for key in ("calls", "to_apply", "branches"):
                    mm = _CALLED_RE[key].search(inst.rest)
                    if mm:
                        for sub in _OPERAND_RE.findall("%" + mm.group(1)):
                            total.add(self.cost(sub, _depth + 1))
                continue
            base = op.removesuffix("-start").removesuffix("-done")
            if base in COLLECTIVES:
                if op.endswith("-done"):
                    continue
                opb = sum(shape_bytes(comp.shapes.get(o, "")) for o in inst.operands)
                if base == "all-reduce":
                    vol = 2.0 * opb
                elif base == "all-gather":
                    vol = float(shape_bytes(inst.rtype))   # gathered result
                else:
                    vol = float(max(opb, shape_bytes(inst.rtype)))
                total.collectives[base] = total.collectives.get(base, 0.0) + vol
                total.collective_counts[base] = total.collective_counts.get(base, 0.0) + 1
                total.collective_bytes += vol
                total.bytes += _instr_bytes(inst, comp.shapes)
                continue
            if op == "dot":
                total.flops += _dot_flops(inst, comp.shapes)
                total.bytes += _instr_bytes(inst, comp.shapes)
                continue
            if op == "convolution":
                # rare here; approximate via output elems × window product
                total.flops += 2.0 * shape_elems(inst.rtype)
                total.bytes += _instr_bytes(inst, comp.shapes)
                continue
            if op == "custom-call" and _instr_bytes(inst, comp.shapes) == 0:
                continue                          # free bookkeeping call
            # elementwise / reduce / misc: 1 flop per output element
            total.flops += float(shape_elems(inst.rtype))
            total.bytes += _instr_bytes(inst, comp.shapes)
        self._memo[comp_name] = total
        return total


def analyze(hlo_text: str) -> dict[str, Any]:
    cm = CostModel(hlo_text)
    c = cm.cost()
    return {
        "flops_per_chip": c.flops,
        "bytes_per_chip": c.bytes,
        "collective_bytes_per_chip": c.collective_bytes,
        "collectives": dict(sorted(c.collectives.items())),
        "collective_counts": dict(sorted(c.collective_counts.items())),
    }


# ---------------------------------------------------------------------------
# callable estimation — the pipeline compiler's cost gate (core/passes.py)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Per-chip roofline peaks of one device kind, with their source."""
    flops_per_s: float
    bytes_per_s: float
    source: str
    ici_bytes_per_s: float | None = None     # per link


#: roofline peaks keyed by ``jax.Device.device_kind``.  A kind that is not
#: listed is an error, never a default: pricing a chip with another chip's
#: peaks would silently skew every gate decision.
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(
        197e12, 819e9,
        "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, 16 GB "
        "HBM at 819 GB/s; 1,600 Gbit/s ICI over 4 links",
        ici_bytes_per_s=50e9),
    "cpu": DevicePeaks(
        1.0e14, 1.0e12,
        "uncalibrated proxy, not a property of any CPU: nominal TPU-class "
        "constants kept so CPU gate decisions only order candidates by a "
        "plausible flops:bytes weighting (calibrate with fit_peaks)"),
}


def device_peaks(device_kind: str) -> DevicePeaks:
    """The :data:`DEVICE_PEAKS` row for ``device_kind``; raises for a kind
    with no row."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no roofline peaks for device kind {device_kind!r}; add a row "
            f"with its source to DEVICE_PEAKS (have "
            f"{sorted(DEVICE_PEAKS)})") from None


def host_fingerprint() -> str:
    """Short identity digest of this host for scoping calibration data and
    cached estimates (peak constants are host properties, not code
    properties)."""
    import platform
    raw = f"{platform.node()}:{platform.machine()}:{os.cpu_count()}"
    return hashlib.sha256(raw.encode()).hexdigest()[:16]


def estimate_callable(fn, *args, peaks: tuple[float, float]
                      ) -> dict[str, Any]:
    """Lower ``fn(*args)`` (args may be ``jax.ShapeDtypeStruct`` pytrees) to
    post-optimisation HLO and run the trip-count-aware cost model over it.

    Adds ``time_proxy_s`` — flops/peak + bytes/peak, an additive roofline
    proxy: comparing two candidates' proxies orders them by modelled cost
    even when one resource dominates.  ``peaks`` is ``(flop/s, byte/s)``:
    a :data:`DEVICE_PEAKS` row, or a calibrated descriptor's fitted
    constants.  Used by the fusion pass's cost gate; callers should cache
    per content key (compilation is the expensive part).
    """
    import jax
    pf, pb = peaks
    text = jax.jit(fn).lower(*args).compile().as_text()
    out = analyze(text)
    out["time_proxy_s"] = (out["flops_per_chip"] / pf
                           + out["bytes_per_chip"] / pb)
    return out


# ---------------------------------------------------------------------------
# peak calibration from measured gate records (bench artifacts)
# ---------------------------------------------------------------------------

def _ratio(rec: dict, gamma: float) -> float | None:
    """Predicted fused/unfused time ratio at flops:bytes weight ``gamma``
    (gamma = peak_flops / peak_bytes — the byte premium in flop units)."""
    try:
        fu = rec["unfused"]["flops"] + gamma * rec["unfused"]["bytes"]
        ff = rec["fused"]["flops"] + gamma * rec["fused"]["bytes"]
    except (KeyError, TypeError):
        return None
    if fu <= 0 or ff <= 0:
        return None
    return ff / fu


def fit_peaks(records: list[dict]) -> dict | None:
    """Fit per-host roofline peaks from measured gate-calibration records.

    Each record carries, per candidate (``unfused`` / ``fused``), the HLO
    counts and a measured wall-clock: ``{"flops", "bytes", "measured_s"}``.
    The proxy is ``t = (F + gamma*B) / Pf`` with ``gamma = Pf/Pb``, so the
    *ratio* of two candidates depends only on gamma: step 1 grid-searches
    gamma to minimise the squared log-ratio error against the measured
    ratios; step 2 anchors the absolute scale by the median of
    ``(F + gamma*B) / measured_s`` over every candidate.  Returns None when
    no record is usable (the caller keeps the nominal constants)."""
    import math

    usable = []
    for rec in records or ():
        ok = True
        for side in ("unfused", "fused"):
            c = rec.get(side) or {}
            if not all(isinstance(c.get(f), (int, float)) and c.get(f) > 0
                       for f in ("flops", "bytes", "measured_s")):
                ok = False
        if ok:
            usable.append(rec)
    if not usable:
        return None

    def log_err(gamma: float) -> float:
        total = 0.0
        for rec in usable:
            pred = _ratio(rec, gamma)
            meas = rec["fused"]["measured_s"] / rec["unfused"]["measured_s"]
            total += (math.log(pred) - math.log(meas)) ** 2
        return total

    # gamma grid: 1 (pure-flops pricing) .. 1e4 (extreme byte premium);
    # the nominal constants sit at gamma = 100
    grid = [10 ** (e / 8.0) for e in range(0, 33)]
    gamma = min(grid, key=log_err)
    scales = []
    for rec in usable:
        for side in ("unfused", "fused"):
            c = rec[side]
            scales.append((c["flops"] + gamma * c["bytes"]) / c["measured_s"])
    scales.sort()
    pf = scales[len(scales) // 2]          # median: robust to one bad probe
    err = math.sqrt(log_err(gamma) / len(usable))
    return {"peak_flops_per_s": pf, "peak_bytes_per_s": pf / gamma,
            "gamma": gamma, "n_records": len(usable),
            "rms_log_ratio_error": err}


def calibration_records(summary: dict) -> list[dict]:
    """Extract usable calibration records from a bench ``summary.json``
    (the ``calibration`` blocks the fusion/dense/autotune sections emit per
    workload).  Tolerant of older artifacts that lack the per-candidate
    counts — those records are simply skipped by ``fit_peaks``."""
    out = []
    for section in ("fusion", "dense", "autotune"):
        sec = summary.get(section) or {}
        for w in (sec.get("workloads") or {}).values():
            cal = w.get("calibration")
            if cal:
                out.append(cal)
    return out


if __name__ == "__main__":
    import sys
    print(json.dumps(analyze(open(sys.argv[1]).read()), indent=1))
