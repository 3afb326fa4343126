"""One run of one cell: set up, warm up, drive the window, reduce, check.

``run.py`` is the command; ``knee.py`` and ``control.py`` reuse the parts.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import sys
import tempfile
import time

import jax
import numpy as np

from chipbench import correctness, datagen, devtrace, loadgen, spec, stats
from chipbench.peaks import peaks as device_peaks

GRACE_S = loadgen.GRACE_S


def log(msg: str) -> None:
    print(msg, flush=True)


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program is
    cached, however fast it compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(spec.ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class NoChip(RuntimeError):
    pass


def device_info(chips: int, rehearse: bool) -> dict:
    devs = jax.devices()
    d = devs[0]
    if not rehearse:
        if d.platform != "tpu":
            raise NoChip(f"no TPU: JAX runs on {d.platform!r}")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX has {len(devs)}")
        device_peaks(d.device_kind)          # an unknown kind is an error
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    peaks = []
    for d in jax.devices():
        st = d.memory_stats()
        if st:
            peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


class CompileCounter:
    """XLA backend compilations, counted from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


@dataclasses.dataclass
class Traffic:
    """The window's inputs, made from the seed before the window."""
    Q: dict | None = None             # served: one topic per request
    due: np.ndarray | None = None     # open loop send times
    warm: dict | None = None          # warm-up topics (no terms)


def pool_blocks(traffic: dict, seconds: float) -> int:
    """Blocks of ``pool`` topics a closed loop needs to keep its clients
    sending fresh topics at ``rate_ceiling_qps`` for ``seconds``; one
    where the traffic states no ceiling."""
    ceiling = traffic.get("rate_ceiling_qps")
    if ceiling is None:
        return 1
    return math.ceil((int(traffic["clients"]) + float(ceiling) * seconds)
                     / int(traffic["pool"]))


def make_traffic(traffic: dict, seconds: float, seed: int,
                 rank_to_term: np.ndarray) -> Traffic:
    q, mode = traffic["query"], traffic["mode"]
    out = Traffic()
    if mode == "open":
        out.due = datagen.arrivals(float(traffic["rate_qps"]), seconds, seed,
                                   block=traffic.get("arrival_block"))
        out.Q = datagen.topics(q, len(out.due), seed, rank_to_term)
        out.warm = datagen.empty_topics(1)
    elif mode == "closed":
        out.Q = datagen.topic_blocks(q, int(traffic["pool"]),
                                     pool_blocks(traffic, seconds), seed,
                                     rank_to_term)
        out.warm = datagen.empty_topics(1)
    else:
        raise spec.SpecError(f"unknown traffic mode {mode!r}")
    return out


def warm_up(sys_, tr: Traffic) -> dict:
    """Compile and run every shape the window uses: each ladder rung of
    the server.  The warm-up topics have no terms: only their shapes
    matter, and they serve no request."""
    t0 = time.monotonic()
    info = sys_.server.warmup(datagen.rows(tr.warm, 0, 1))
    return {"warmup_s": time.monotonic() - t0,
            "engine_compiles": sys_.backend.engine.total_compiles(),
            **{k: info[k] for k in ("buckets",) if k in info}}


def drive(sys_, traffic: dict, tr: Traffic, seconds: float,
          ann: loadgen.Annotator) -> loadgen.Window:
    if traffic["mode"] == "open":
        return loadgen.open_loop(sys_.server, tr.Q, tr.due, seconds, ann)
    return loadgen.closed_loop(sys_.server, tr.Q, int(traffic["clients"]),
                               seconds, ann,
                               traffic.get("rate_ceiling_qps"))


@dataclasses.dataclass
class RunRecord:
    """What the per-layer readers (``metrics/*.py``) read."""
    window: loadgen.Window
    requests: list            # served answers in the window, one dict each
    trace: devtrace.Summary | None
    peaks: dict | None        # the chip's peak rates (None in rehearsals)


def served_requests(win: loadgen.Window) -> tuple:
    """(records of the answers, number attempted, number failed)."""
    recs, failed = [], 0
    for i, due, req in win.sent:
        if (req is None or not req.done.is_set() or req.error is not None
                or req.trace.timed_out or req.result is None):
            failed += 1
            continue
        tr = req.trace
        rec = {"row": i, "due": due, "t_done": tr.t_done,
               "latency_ms": 1000.0 * (tr.t_done - due),
               "queue_wait_ms": tr.queue_wait_ms,
               "service_ms": tr.service_ms,
               "batch_size": tr.batch_size, "bucket": tr.bucket,
               "stage_ms": tr.stage_ms,
               "docids": np.asarray(req.result["docids"])[0],
               "scores": np.asarray(req.result["scores"])[0]}
        if "tokens" in req.result:
            # ttft_ms and n_tokens stay 0 for an answer the stage cache
            # served whole: it decoded nothing
            rec.update(tokens=np.asarray(req.result["tokens"])[0],
                       ttft_ms=tr.ttft_ms, n_tokens=tr.n_tokens)
        recs.append(rec)
    return recs, len(win.sent), failed


def end_to_end(cell: spec.Cell, win: loadgen.Window, recs: list,
               setup_s: float) -> dict:
    mode = cell.traffic["mode"]
    vals = {"setup_s": setup_s}
    if mode == "open" and recs:
        lat = [r["latency_ms"] for r in recs]
        vals["latency_p50_ms"] = stats.percentile(lat, 50)
        vals["latency_p95_ms"] = stats.percentile(lat, 95)
    elif mode == "closed":
        vals["served_qps"] = len(recs) / (win.t_end - win.t0)
    out = {}
    for m in cell.end_to_end:
        if m["name"] not in vals:
            raise RuntimeError(f"cell {cell.name} reports {m['name']} but "
                               f"its {mode} window cannot measure it")
        out[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
    return out


def per_layer(cell: spec.Cell, rec: RunRecord) -> dict:
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"])(rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def answered(recs: list, tr: Traffic) -> tuple:
    """(queries, answers): one query row per answer the window gave."""
    rows = [r["row"] for r in recs]
    return {k: v[rows] for k, v in tr.Q.items()}, recs


def check(cell: spec.Cell, coll: datagen.Collection, queries: dict,
          answered: list, seed: int, ref_dtype=np.float64,
          lm: dict | None = None) -> dict:
    """Reference rankings of a sample of the answers, and the numbers
    compared.  ``queries`` holds one row per answer, in order.  A
    reference module that defines ``numbers`` adds what it returns (a
    generating chain's ``logit_gap``); ``lm`` is the generating leaf's
    LM as ``system.lm_spec`` gives it."""
    ref_spec = cell.config["reference"]
    ref = spec.reference_module(ref_spec["name"])
    idx = correctness.sample(len(answered), int(cell.traffic["check_sample"]),
                             seed)
    Qs = {k: np.asarray(v)[idx] for k, v in queries.items()}
    rcoll = ref.Collection(coll.doc_terms, coll.doc_start, coll.vocab,
                           float(cell.config["collection"]
                                 ["stop_df_fraction"]))
    refs = ref.run(rcoll, Qs, ref_spec, seed=seed, dtype=ref_dtype)
    sample = [answered[i] for i in idx]
    k = int(ref_spec["k"])
    nums = {"score_gap": correctness.score_gap(sample, refs, k, coll.n_docs),
            "abs_score_gap": correctness.score_gap(sample, refs, k,
                                                   coll.n_docs, divide=False),
            "n_checked": len(idx)}
    if hasattr(ref, "numbers"):
        nums.update(ref.numbers(rcoll, Qs, sample, refs, ref_spec,
                                seed=seed, lm=lm))
    return nums


def trace_dir() -> str:
    return tempfile.mkdtemp(prefix="chipbench-trace-")


def start_trace(path: str) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(path, profiler_options=opts)


def stop_trace() -> None:
    jax.profiler.stop_trace()


def remove(path: str) -> None:
    shutil.rmtree(path, ignore_errors=True)


def free(sys_) -> None:
    sys_.close()
    sys_.server = sys_.backend = None
    gc.collect()


def eprint(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
