"""Fail loudly when the bench run silently dropped a section.

The bench-smoke CI job uploads ``summary.json`` as the per-push trajectory
artifact; a section that vanishes (e.g. the fusion bench was skipped) used
to pass silently and poison the trajectory.  This gate requires the
sections the trajectory tracks to be present AND non-empty.

    python scripts/check_bench.py [experiments/bench/summary.json]
"""
from __future__ import annotations

import json
import sys

REQUIRED = ("fusion", "rq1", "rq2", "dense", "serve", "autotune", "obs")

#: every serve workload must report at least this many offered-load levels
#: (p50/p95/p99 batched vs naive at light/mid/sat/overload)
SERVE_WORKLOADS = ("bm25_topk", "bm25_dense_rerank")
SERVE_MIN_LEVELS = 4

#: at saturation the deadline-aware scheduler must keep goodput tracking
#: throughput on the heavy workload (pre-shedding it collapsed to ~0: the
#: unbounded backlog blew every SLO)
SERVE_GOODPUT_WORKLOAD = "bm25_dense_rerank"
SERVE_MIN_GOODPUT_FRAC = 0.5

#: the IVF-PQ scan store must compress to at most 1/4 of the flat float
#: store, while full-probe recall (every list scanned; only the
#: exact-re-scored ADC shortlist bounds it) stays above the floor
PQ_MAX_BYTES_FRACTION_DEN = 4
PQ_MIN_FULL_PROBE_RECALL = 0.8


def main() -> int:
    path = sys.argv[1] if len(sys.argv) > 1 else "experiments/bench/summary.json"
    try:
        summary = json.load(open(path))
    except (OSError, json.JSONDecodeError) as e:
        print(f"FAIL: cannot read bench summary {path}: {e}", file=sys.stderr)
        return 1
    missing = [k for k in REQUIRED if not summary.get(k)]
    if missing:
        print(f"FAIL: bench summary {path} is missing sections: {missing} "
              f"(present: {sorted(summary)})", file=sys.stderr)
        return 1
    fus = summary["fusion"].get("workloads", {})
    if not fus:
        print("FAIL: fusion section has no workloads", file=sys.stderr)
        return 1
    dense = summary["dense"]
    if not dense.get("workloads"):
        print("FAIL: dense section has no workloads", file=sys.stderr)
        return 1
    if not dense.get("ivf"):
        print("FAIL: dense section has no ivf report", file=sys.stderr)
        return 1
    pq = dense.get("dense_pq")
    if not pq:
        print("FAIL: dense section has no dense_pq report", file=sys.stderr)
        return 1
    if not pq.get("pq_bytes_per_doc", 1e18) <= \
            pq.get("flat_bytes_per_doc", 0) / PQ_MAX_BYTES_FRACTION_DEN:
        print(f"FAIL: IVF-PQ store not <= 1/{PQ_MAX_BYTES_FRACTION_DEN} of "
              f"the flat store: {pq.get('pq_bytes_per_doc')} vs "
              f"{pq.get('flat_bytes_per_doc')} bytes/doc", file=sys.stderr)
        return 1
    if not pq.get("recall_at_k_full_probe", 0.0) >= PQ_MIN_FULL_PROBE_RECALL:
        print(f"FAIL: IVF-PQ full-probe recall@k "
              f"{pq.get('recall_at_k_full_probe')} < "
              f"{PQ_MIN_FULL_PROBE_RECALL}", file=sys.stderr)
        return 1
    shard_rows = {r.get("shards"): r for r in pq.get("doc_shards", [])}
    missing_shards = [s for s in (2, 4) if s not in shard_rows]
    if missing_shards:
        print(f"FAIL: dense_pq doc-shard scaling lacks shard counts "
              f"{missing_shards} (present: {sorted(shard_rows)})",
              file=sys.stderr)
        return 1
    bad_merge = [s for s, r in shard_rows.items()
                 if not r.get("merge_matches_oracle")]
    if bad_merge:
        print(f"FAIL: doc-shard merge diverged from the single-shard "
              f"oracle at shard counts {bad_merge}", file=sys.stderr)
        return 1
    serve = summary["serve"]
    sw = serve.get("workloads", {})
    missing_wl = [w for w in SERVE_WORKLOADS if w not in sw]
    if missing_wl:
        print(f"FAIL: serve section is missing workloads {missing_wl} "
              f"(present: {sorted(sw)})", file=sys.stderr)
        return 1
    for name in SERVE_WORKLOADS:
        levels = sw[name].get("levels", [])
        if len(levels) < SERVE_MIN_LEVELS:
            print(f"FAIL: serve workload {name!r} reports {len(levels)} "
                  f"offered-load levels (< {SERVE_MIN_LEVELS})",
                  file=sys.stderr)
            return 1
        for lvl in levels:
            for side in ("batched", "naive"):
                if "p95_ms" not in lvl.get(side, {}):
                    print(f"FAIL: serve workload {name!r} level "
                          f"{lvl.get('level')!r} lacks {side} p95_ms",
                          file=sys.stderr)
                    return 1
        if not sw[name].get("batched_beats_naive_at_saturation"):
            print(f"FAIL: serve workload {name!r}: continuous batching did "
                  "not beat naive per-request throughput at saturation",
                  file=sys.stderr)
            return 1
        by_level = {lvl.get("level"): lvl for lvl in levels}
        for lname in ("sat", "overload"):
            b = by_level.get(lname, {}).get("batched", {})
            missing_keys = [k for k in ("goodput_qps", "shed", "shed_door",
                                        "shed_queue") if k not in b]
            if lname not in by_level or missing_keys:
                print(f"FAIL: serve workload {name!r} lacks a deadline-"
                      f"aware {lname!r} level with goodput + shed counts "
                      f"(missing: {missing_keys or 'level'})",
                      file=sys.stderr)
                return 1
        sat_b = by_level["sat"]["batched"]
        if sat_b["goodput_qps"] < SERVE_MIN_GOODPUT_FRAC * \
                sat_b["throughput_qps"] and name == SERVE_GOODPUT_WORKLOAD:
            print(f"FAIL: serve workload {name!r} saturation goodput "
                  f"{sat_b['goodput_qps']} < {SERVE_MIN_GOODPUT_FRAC}x "
                  f"throughput {sat_b['throughput_qps']} (deadline-aware "
                  "shedding is not holding the SLO)", file=sys.stderr)
            return 1
    if not serve.get("gated"):
        print("FAIL: serve section has no gated trajectory metrics",
              file=sys.stderr)
        return 1
    missing_gate = [f"{w}.sat.goodput_qps" for w in SERVE_WORKLOADS
                    if f"{w}.sat.goodput_qps" not in serve["gated"]]
    if missing_gate:
        print(f"FAIL: serve gated block lacks saturation goodput metrics: "
              f"{missing_gate}", file=sys.stderr)
        return 1
    tt = serve.get("two_tenant")
    if not tt:
        print("FAIL: serve section has no two_tenant workload",
              file=sys.stderr)
        return 1
    if not tt.get("cross_pipeline_hits", 0) > 0:
        print(f"FAIL: two-tenant serve workload recorded no cross-pipeline "
              f"prefix hits: {tt}", file=sys.stderr)
        return 1
    if tt.get("recompiles_since_warmup") != 0:
        print(f"FAIL: two-tenant serve workload recompiled after warmup "
              f"({tt.get('recompiles_since_warmup')})", file=sys.stderr)
        return 1
    starved = [n for n, p in tt.get("per_pipeline", {}).items()
               if not p.get("served")]
    if len(tt.get("per_pipeline", {})) < 2 or starved:
        print(f"FAIL: two-tenant serve workload did not serve every "
              f"pipeline (starved: {starved})", file=sys.stderr)
        return 1
    rag = serve.get("rag")
    if not rag:
        print("FAIL: serve section has no rag workload", file=sys.stderr)
        return 1
    if not rag.get("continuous_beats_sequential_at_saturation"):
        print("FAIL: rag serve workload: continuous-batched decode did not "
              f"beat the sequential one-slot baseline at saturation "
              f"({(rag.get('continuous') or {}).get('decode_tokens_per_s')} "
              f"vs {(rag.get('sequential') or {}).get('decode_tokens_per_s')}"
              " tokens/s)", file=sys.stderr)
        return 1
    cont = rag.get("continuous") or {}
    for field in ("ttft_ms", "per_token_ms"):
        if "p95_ms" not in (cont.get(field) or {}):
            print(f"FAIL: rag serve workload lacks {field} p95 in its "
                  "continuous-decode traces", file=sys.stderr)
            return 1
    if cont.get("recompiles_since_warmup") != 0:
        print("FAIL: rag serve workload recompiled after warmup "
              f"({cont.get('recompiles_since_warmup')}) — decode "
              "prefill/step must ride the pinned jit-cache entries",
              file=sys.stderr)
        return 1
    if "rag.sat.decode_tokens_per_s" not in serve["gated"]:
        print("FAIL: serve gated block lacks rag.sat.decode_tokens_per_s",
              file=sys.stderr)
        return 1
    # overload post-mortems must ship the scheduler's decision log
    for name in SERVE_WORKLOADS:
        over = {lvl.get("level"): lvl
                for lvl in sw[name]["levels"]}.get("overload", {})
        fr = over.get("flight_record")
        if not fr:
            print(f"FAIL: serve workload {name!r} overload level lacks a "
                  "flight_record dump", file=sys.stderr)
            return 1
        bad_ev = [e for e in fr if "kind" not in e or "t" not in e]
        if bad_ev:
            print(f"FAIL: serve workload {name!r} flight_record has "
                  f"malformed events: {bad_ev[:3]}", file=sys.stderr)
            return 1
    obs = summary["obs"]
    for field in ("disabled_qps", "enabled_qps", "enabled_over_disabled_qps"):
        if obs.get(field) is None:
            print(f"FAIL: obs section lacks {field!r}", file=sys.stderr)
            return 1
    if "enabled_over_disabled_qps" not in obs.get("gated", {}):
        print("FAIL: obs gated block lacks enabled_over_disabled_qps",
              file=sys.stderr)
        return 1
    trace = obs.get("trace") or {}
    evs = trace.get("traceEvents")
    if not isinstance(evs, list) or not evs:
        print("FAIL: obs section lacks a Chrome trace export "
              "(trace.traceEvents)", file=sys.stderr)
        return 1
    malformed = [e for e in evs
                 if not {"name", "ph", "ts", "pid", "tid"} <= set(e)]
    if malformed:
        print(f"FAIL: obs trace has malformed trace events: "
              f"{malformed[:3]}", file=sys.stderr)
        return 1
    span_ids = {e["args"].get("span_id") for e in evs if "args" in e}
    n_nested = sum(1 for e in evs
                   if e.get("cat") == "serve"
                   and e.get("args", {}).get("parent_id") in span_ids)
    if n_nested < 1:
        print("FAIL: obs trace export contains no nested serve span "
              "(no event's parent_id matches another's span_id)",
              file=sys.stderr)
        return 1
    at = summary["autotune"]
    for field in ("cold_tune_s", "warm_compile_s", "warm_profile_reuse"):
        if not at.get(field):
            print(f"FAIL: autotune section lacks {field!r}", file=sys.stderr)
            return 1
    reuse = at["warm_profile_reuse"]
    if reuse.get("probe_measurements", 1) != 0 or \
            reuse.get("gate_estimates", 1) != 0:
        print("FAIL: warm profile-reuse compile performed probe "
              f"measurements / gate compiles: {reuse}", file=sys.stderr)
        return 1
    if not at["warm_compile_s"] < at["cold_tune_s"]:
        print(f"FAIL: warm profile-reuse compile ({at['warm_compile_s']}s) "
              f"not faster than cold tune ({at['cold_tune_s']}s)",
              file=sys.stderr)
        return 1
    at_wl = at.get("workloads", {})
    bad = [n for n, w in at_wl.items()
           if not any(d.get("predicted_ratio") is not None
                      and d.get("measured_ratio") is not None
                      for d in w.get("decisions", []))]
    if not at_wl or bad:
        print("FAIL: autotune workloads lack per-decision measured/"
              f"predicted ratios: {bad or 'no workloads'}", file=sys.stderr)
        return 1
    print(f"bench summary OK: sections {list(REQUIRED)} all present; "
          f"fusion workloads: {sorted(fus)}; "
          f"dense workloads: {sorted(dense['workloads'])}; "
          f"serve workloads: {sorted(sw)} "
          f"({len(sw[SERVE_WORKLOADS[0]]['levels'])} load levels)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
