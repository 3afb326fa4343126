"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up (``setup_s``, from process start to the window's start) makes the
cell's collection on the device from the seed, builds the program's index
and backend, draws and registers the configuration's LM where it has one,
compiles the cell's one pipeline and warms up every shape its traffic
uses.  Then the window is driven for ``--seconds``, answers are awaited,
the program is freed, and a sample of the answers is compared with the
configuration's plain reference.  A number that ``checks/<cell>.json``
names and the reference does not give ends the run with exit code 1.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones read from a profiler trace of the
window), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared with its limit.  The same numbers are
the last lines of standard error.  With no TPU, or fewer chips than the
cell needs, it exits 3 and prints no result.

``--rehearse`` runs the cell at the configuration's tiny ``rehearse``
sizes on any JAX device, without the compilation cache: for the tests.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import spec  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any device (tests only)")
    return ap.parse_args(argv)


def main(argv=None, t_start: float | None = None) -> int:
    args = parse(argv)
    t_start = T_START if t_start is None else t_start
    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    import jax
    from chipbench import harness as H
    try:
        device = H.device_info(cell.chips, args.rehearse)
    except (H.NoChip, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    if not args.rehearse:
        H.use_compile_cache()
    try:
        from chipbench import system
    except ImportError as e:
        print(f"error: the program under test cannot be imported: {e}",
              file=sys.stderr)
        return 1
    return run(cell, args, device, t_start, H, system, jax)


def run(cell, args, device, t_start, H, system, jax) -> int:
    from chipbench import devtrace, loadgen, stats
    from chipbench.peaks import peaks as device_peaks
    trace = bool(args.trace)
    seconds = float(args.seconds)
    counter = H.CompileCounter()
    stage_timing = trace and any(m["name"].startswith("stage_ms")
                                 for m in cell.per_layer)
    t = time.monotonic()
    sys_ = system.build(cell.config, cell.traffic, args.seed,
                       rehearse=args.rehearse, stage_timing=stage_timing)
    H.log(f"[setup] {time.monotonic() - t:.3f} s: "
          + ", ".join(f"{k} {v:.3f} s" for k, v in sys_.timings.items())
          + f"; {sys_.coll.n_docs} docs, "
          f"{sys_.coll.doc_terms.size} tokens, max posting list "
          f"{sys_.backend.max_postings}")
    if sys_.lm is not None:
        lm = sys_.lm
        H.log(f"[setup] lm {lm['name']}: {lm['n_layers']} layers, d_model "
              f"{lm['d_model']}, GQA {lm['n_q']}/{lm['n_kv']} x "
              f"{lm['d_head']}, d_ff {lm['d_ff']}, vocab {lm['vocab']}, "
              f"{lm['dtype']}")
    H.log(f"[setup] compiled chain {system.compiled_chain(sys_)}; gate "
          f"{system.gate_decisions(sys_)}")
    tr = H.make_traffic(cell.traffic, seconds, args.seed,
                        sys_.coll.rank_to_term)
    if cell.traffic["mode"] == "closed":
        H.log(f"[setup] topic pool {len(tr.Q['qid'])} "
              f"({H.pool_blocks(cell.traffic, seconds)} blocks of "
              f"{cell.traffic['pool']}), rate ceiling "
              f"{cell.traffic.get('rate_ceiling_qps')} q/s")
    warm = H.warm_up(sys_, tr)
    H.log(f"[setup] warm-up {warm}")
    ann = loadgen.Annotator(trace)
    loadgen.instrument(sys_, ann)
    sys_.server.start()
    tdir = H.trace_dir() if trace else None
    if trace:
        H.start_trace(tdir)
    compiles0, xla0 = system.engine_compiles(sys_), counter.n
    setup_s = time.monotonic() - t_start
    win = H.drive(sys_, cell.traffic, tr, seconds, ann)
    xla_in_window = counter.n - xla0
    if trace:
        H.stop_trace()
    loadgen.wait_all(win, H.GRACE_S)
    sys_.server.stop()
    engine_in_window = system.engine_compiles(sys_) - compiles0
    mem_peak = H.memory_peak_bytes()

    recs, attempted, failed = H.served_requests(win)
    late = win.lateness_s
    H.log(f"[window] requests sent {attempted}, answered {len(recs)}, "
          f"failed {failed} (rejected at the door {win.rejected}); "
          f"generator lateness p50 {stats.percentile(late, 50):.6f} s, "
          f"p95 {stats.percentile(late, 95):.6f} s, max {max(late):.6f} s")
    H.log(f"[window] compiles inside the window: engine_compiles_total "
          f"+{engine_in_window}, XLA backend compiles +{xla_in_window}")
    gen = [r for r in recs if "tokens" in r]
    if gen:
        # an answer the stage cache served whole decoded nothing and has
        # no time to its first token
        ttft = [r["ttft_ms"] for r in gen if r["n_tokens"]]
        p50 = (f"{stats.percentile(ttft, 50):.3f}" if ttft else "none")
        H.log(f"[window] generated answers {len(gen)}, {len(ttft)} "
              f"decoded: ttft_ms p50 {p50}; tokens "
              f"{sum(r['n_tokens'] for r in gen)}")
    queries, answers = H.answered(recs, tr)

    out = {"correct": False, "attempted": attempted, "failed": failed}
    device = dict(device, memory_peak_bytes=mem_peak)
    if trace:
        t = time.monotonic()
        raw = devtrace.read(tdir)
        t_read = time.monotonic() - t
        t = time.monotonic()
        summary = devtrace.summarize(raw,
                                     kernels=spec.kernels(cell.per_layer))
        t_sum = time.monotonic() - t
        H.remove(tdir)
        H.log(f"[trace] read {t_read:.3f} s, summarize {t_sum:.3f} s; "
              f"{sum(map(len, raw.device_ops.values()))} device operations, "
              f"{len(raw.host_spans)} host spans, {summary.n_gaps} gaps")
        rec = H.RunRecord(window=win, requests=recs, trace=summary,
                          peaks=(device_peaks(device["kind"])
                                 if not args.rehearse else None))
        out["metrics"] = H.per_layer(cell, rec)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        out["device"] = device
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    else:
        out["metrics"] = H.end_to_end(cell, win, recs, setup_s)
        out["device"] = device

    coll, lm = sys_.coll, sys_.lm
    H.free(sys_)
    del sys_
    t = time.monotonic()
    nums = H.check(cell, coll, queries, answers, args.seed, lm=lm)
    H.log(f"[check] reference over {nums['n_checked']} answers in "
          f"{time.monotonic() - t:.3f} s; abs_score_gap "
          f"{nums['abs_score_gap']!r} (not compared)")
    missing = [name for name in cell.checks if name not in nums]
    if missing:
        H.eprint(f"error: checks/{cell.name}.json names {missing}, which "
                 f"the reference does not give; it gives {sorted(nums)}")
        return 1
    checks = {name: {"value": min(nums[name], sys.float_info.max),
                     "limit": float(lim["limit"])}
              for name, lim in cell.checks.items()}
    out["correct"] = bool(failed == 0 and answers and all(
        c["value"] <= c["limit"] for c in checks.values()))
    out["checks"] = checks
    for name, c in checks.items():
        H.eprint(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
