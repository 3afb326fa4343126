"""Benchmark harness — one section per paper table/figure.

  RQ1   rank-cutoff optimisation (paper Table 3 top)    [ir_bench]
  RQ2   fat feature extraction  (paper Table 3 bottom)  [ir_bench]
  ROOF  roofline terms per (arch x shape x mesh)        [roofline]
  KERN  kernel micro-benches                            [kernel_bench]

Prints ``name,us_per_call,derived`` CSV rows per the harness contract, plus
the full tables; writes JSON artifacts under experiments/bench/.  Runs in
one process, which holds the accelerator; the simulated-device engine
scaling bench is a separate CPU tool (``benchmarks/engine_bench.py``).

  PYTHONPATH=src python -m benchmarks.run [--scale robust|small] [--skip-ir]
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

from benchmarks import ir_bench, kernel_bench, roofline, serve_bench
from repro.launch.cache import use_compile_cache

OUT = Path("experiments/bench")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="robust", choices=["robust", "small"])
    ap.add_argument("--skip-ir", action="store_true")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()
    use_compile_cache()
    OUT.mkdir(parents=True, exist_ok=True)
    # clear stale section files: summary.json is merged from OUT/*.json, so
    # a leftover section from a previous run would mask exactly the
    # missing-section failures scripts/check_bench.py exists to catch
    for stale in OUT.glob("*.json"):
        stale.unlink()
    csv_rows: list[dict] = []

    # --- KERN ---------------------------------------------------------------
    kern = kernel_bench.bench_fused_scoring() + kernel_bench.bench_topk()
    csv_rows += kern
    (OUT / "kernels.json").write_text(json.dumps(kern, indent=1))

    # --- RQ1 / RQ2 ----------------------------------------------------------
    if not args.skip_ir:
        if args.scale == "robust":
            # 50 topics per formulation keeps the unoptimised doc-vectors
            # baseline tractable on this 1-core host; MRT is per query.
            env = ir_bench.build_robust_env(n_topics=50)
        else:
            env = ir_bench.build_robust_env(n_docs=20000, n_topics=32,
                                            vocab=40000)
        print(f"# corpus: {env['index'].n_docs} docs, "
              f"built in {env['build_s']:.0f}s")
        rq1 = ir_bench.bench_rq1(env, repeats=args.repeats)
        rq2 = ir_bench.bench_rq2(env, repeats=args.repeats)
        cw = ir_bench.clueweb_extrapolation(env, rq1, rq2)
        (OUT / "rq1.json").write_text(json.dumps(rq1, indent=1))
        (OUT / "rq2.json").write_text(json.dumps(rq2, indent=1))
        (OUT / "clueweb_extrapolation.json").write_text(json.dumps(cw, indent=1))
        print("\n== RQ1: rank-cutoff optimisation (MRT ms/query) ==")
        for r in rq1:
            print(r)
            csv_rows.append({
                "name": f"rq1_{r['formulation']}_opt",
                "us_per_call": r["opt_mrt_ms"] * 1000,
                "derived": f"delta={r['delta_pct']}%,overlap={r['topk_overlap']}"})
            csv_rows.append({
                "name": f"rq1_{r['formulation']}_orig",
                "us_per_call": r["orig_mrt_ms"] * 1000, "derived": ""})
        print("\n== RQ2: fat feature extraction (MRT ms/query) ==")
        for r in rq2:
            print(r)
            csv_rows.append({
                "name": f"rq2_{r['formulation']}_opt",
                "us_per_call": r["opt_mrt_ms"] * 1000,
                "derived": f"delta={r['delta_pct']}%"})
            csv_rows.append({
                "name": f"rq2_{r['formulation']}_orig",
                "us_per_call": r["orig_mrt_ms"] * 1000, "derived": ""})
        print("\n== ClueWeb09 extrapolation ==")
        print(cw)

        # --- planner: amortised shared-prefix speedup --------------------
        pl = ir_bench.bench_planner(env, repeats=args.repeats)
        (OUT / "planner.json").write_text(json.dumps(pl, indent=1))
        print("\n== Planner: shared-prefix amortisation ==")
        print(pl)
        csv_rows.append({
            "name": "planner_shared_prefix",
            "us_per_call": pl["planned_mrt_ms"] * 1000,
            "derived": (f"speedup={pl['amortised_speedup']}x,"
                        f"stages={pl['stage_executions']}/"
                        f"{pl['stage_requests']}")})

        # --- fusion: cost-gated kernel lowering --------------------------
        fus = ir_bench.bench_fusion(env, repeats=args.repeats)
        (OUT / "fusion.json").write_text(json.dumps(fus, indent=1))
        print("\n== Fusion: cost-gated kernel lowering (MRT ms/query) ==")
        print(f"compile breakdown (ms/pass): {fus['compile_breakdown_ms']}")
        for name, w in fus["workloads"].items():
            print(f"[{name}] {w}")
            csv_rows.append({
                "name": f"fusion_{name}_fused",
                "us_per_call": w["fused_mrt_ms"] * 1000,
                "derived": (f"speedup={w['speedup']}x,"
                            f"fused_stage={w['fused_stage']},"
                            f"overlap={w['topk_overlap']}")})
            csv_rows.append({
                "name": f"fusion_{name}_unfused",
                "us_per_call": w["unfused_mrt_ms"] * 1000, "derived": ""})

        # --- autotune: measured gating + persisted tuning profiles -------
        at = ir_bench.bench_autotune(env)
        (OUT / "autotune.json").write_text(json.dumps(at, indent=1))
        print("\n== Autotune: measured gating + persisted tuning profile ==")
        print(f"cold tune {at['cold_tune_s']}s vs warm profile-reuse "
              f"compile {at['warm_compile_s']}s ({at['warm_speedup']}x); "
              f"warm reuse counters: {at['warm_profile_reuse']}")
        print(f"calibration fit: {at['calibration_fit']}")
        print(f"seed 0.41x fused-gather case: {at['seed_fused_gather_case']}")
        for name, w in at["workloads"].items():
            print(f"[{name}] {w['decisions']}")
        n_dec = sum(len(w["decisions"]) for w in at["workloads"].values())
        csv_rows.append({
            "name": "autotune_cold_tune",
            "us_per_call": round(at["cold_tune_s"] * 1e6, 1),
            "derived": f"decisions={n_dec}"})
        csv_rows.append({
            "name": "autotune_warm_compile",
            "us_per_call": round(at["warm_compile_s"] * 1e6, 1),
            "derived": (
                f"speedup={at['warm_speedup']}x,"
                f"probes={at['warm_profile_reuse']['probe_measurements']},"
                f"gate_compiles={at['warm_profile_reuse']['gate_estimates']},"
                f"hits={at['warm_profile_reuse']['profile_hits']}")})

        # --- dense second stage: fused rerank + IVF candidate gen --------
        dn = ir_bench.bench_dense(env, repeats=args.repeats)
        (OUT / "dense.json").write_text(json.dumps(dn, indent=1))
        print("\n== Dense: fused second-stage rerank + IVF (MRT ms/query) ==")
        for name, w in dn["workloads"].items():
            print(f"[{name}] {w}")
            csv_rows.append({
                "name": f"dense_{name}_fused",
                "us_per_call": w["fused_mrt_ms"] * 1000,
                "derived": (f"speedup={w['speedup']}x,"
                            f"fused_stage={w['fused_stage']},"
                            f"overlap={w['topk_overlap']}")})
            csv_rows.append({
                "name": f"dense_{name}_unfused",
                "us_per_call": w["unfused_mrt_ms"] * 1000, "derived": ""})
        print(f"[ivf] {dn['ivf']}")
        csv_rows.append({
            "name": "dense_ivf_retrieve",
            "us_per_call": dn["ivf"]["ivf_mrt_ms"] * 1000,
            "derived": (f"speedup={dn['ivf']['speedup']}x,"
                        f"recall={dn['ivf']['recall_at_k']},"
                        f"nprobe={dn['ivf']['nprobe']}/"
                        f"{dn['ivf']['n_lists']}")})
        csv_rows.append({
            "name": "dense_brute_retrieve",
            "us_per_call": dn["ivf"]["brute_mrt_ms"] * 1000, "derived": ""})

        # --- serving: continuous micro-batching vs naive per-request -----
        sv = serve_bench.bench_serving(env)
        (OUT / "serve.json").write_text(json.dumps(sv, indent=1))
        print("\n== Serve: continuous micro-batching (open-loop Poisson) ==")
        for name, wl in sv["workloads"].items():
            print(f"[{name}] capacity {wl['capacity_qps']} "
                  f"recompiles_after_warmup={wl['recompiles_since_warmup']} "
                  f"beats_naive_at_saturation="
                  f"{wl['batched_beats_naive_at_saturation']}")
            for lvl in wl["levels"]:
                b, nv = lvl["batched"], lvl["naive"]
                shed = (f" shed={b['shed']}"
                        if lvl.get("deadline_ms") is not None else "")
                print(f"  [{lvl['level']}] {b['offered_qps']} q/s offered: "
                      f"batched p95={b['p95_ms']}ms "
                      f"tput={b['throughput_qps']} "
                      f"goodput={b['goodput_qps']}{shed} "
                      f"| naive p95={nv['p95_ms']}ms "
                      f"tput={nv['throughput_qps']}")
                csv_rows.append({
                    "name": f"serve_{name}_{lvl['level']}_batched",
                    "us_per_call": round(b["p95_ms"] * 1000, 1),
                    "derived": (f"tput={b['throughput_qps']}q/s,"
                                f"goodput={b['goodput_qps']}q/s,"
                                f"shed={b['shed']},"
                                f"batch={b['mean_batch_size']},"
                                f"offered={b['offered_qps']}q/s")})
                csv_rows.append({
                    "name": f"serve_{name}_{lvl['level']}_naive",
                    "us_per_call": round(nv["p95_ms"] * 1000, 1),
                    "derived": f"tput={nv['throughput_qps']}q/s"})
        # --- observability: enabled-vs-disabled serve overhead -----------
        ob = serve_bench.bench_obs(env)
        (OUT / "obs.json").write_text(json.dumps(ob, indent=1))
        print("\n== Observability: enabled-vs-disabled serve overhead ==")
        print(f"disabled {ob['disabled_qps']} q/s vs enabled "
              f"{ob['enabled_qps']} q/s "
              f"(ratio {ob['enabled_over_disabled_qps']}, overhead "
              f"{ob['overhead_pct']}%); trace events={ob['trace_events']} "
              f"nested_serve_spans={ob['nested_serve_spans']} "
              f"recorder={ob['flight_record_kinds']}")
        csv_rows.append({
            "name": "obs_enabled_serve",
            "us_per_call": round(1e6 / max(ob["enabled_qps"], 1e-9), 1),
            "derived": (f"ratio={ob['enabled_over_disabled_qps']},"
                        f"overhead={ob['overhead_pct']}%,"
                        f"spans={ob['nested_serve_spans']}")})
        csv_rows.append({
            "name": "obs_disabled_serve",
            "us_per_call": round(1e6 / max(ob["disabled_qps"], 1e-9), 1),
            "derived": ""})

        tt = sv.get("two_tenant")
        if tt:
            print(f"[two_tenant] pipelines={tt['pipelines']} "
                  f"served={tt['served']}/{tt['n_requests']} "
                  f"cross_prefix_hits={tt['cross_pipeline_hits']} "
                  f"lanes={tt['lane_served']} "
                  f"recompiles_after_warmup={tt['recompiles_since_warmup']}")
            csv_rows.append({
                "name": "serve_two_tenant",
                "us_per_call": round(1e6 / max(tt["throughput_qps"], 1e-9),
                                     1),
                "derived": (f"cross_hits={tt['cross_pipeline_hits']},"
                            f"served={tt['served']},"
                            f"recompiles={tt['recompiles_since_warmup']}")})

    # --- ROOF ---------------------------------------------------------------
    recs = roofline.load_records()
    for mesh in ["16x16", "2x16x16"]:
        rows = roofline.roofline_rows(recs, mesh=mesh)
        if rows:
            print(f"\n== Roofline ({mesh}, {len(rows)} cells) ==")
            print(roofline.format_csv(rows))
            (OUT / f"roofline_{mesh.replace('x','_')}.json").write_text(
                json.dumps(rows, indent=1))

    print("\n== CSV ==")
    print("name,us_per_call,derived")
    for r in csv_rows:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")

    # one merged artifact for CI's per-push bench trajectory (BENCH_<sha>)
    summary = {"scale": args.scale, "rows": csv_rows}
    for f in OUT.glob("*.json"):
        if f.name != "summary.json":
            summary[f.stem] = json.loads(f.read_text())
    (OUT / "summary.json").write_text(json.dumps(summary, indent=1))


if __name__ == "__main__":
    main()
