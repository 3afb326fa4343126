import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: lower + compile every (arch × shape) on the production
# meshes and extract roofline terms from the compiled artifact.
#
# MUST be invoked as its own process (the device-count flag above is locked at
# first jax init):
#   PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
#       [--multi-pod | --both-meshes] [--out experiments/dryrun]

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax

from repro.analysis import hlo_cost
from repro.configs.registry import all_arch_ids, get_arch
from repro.launch import mesh as mesh_lib
from repro.launch.steps import build_bundle

#: the chip the production meshes are priced on
_V5E = hlo_cost.device_peaks("TPU v5 lite")

# ---------------------------------------------------------------------------
# dry-run of one cell
# ---------------------------------------------------------------------------

def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
             verbose: bool = True,
             overrides: dict[str, str] | None = None) -> dict:
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    rec: dict = {"arch": arch_id, "shape": shape_name,
                 "mesh": "2x16x16" if multi_pod else "16x16",
                 "n_chips": mesh.size, "overrides": overrides or {}}
    t0 = time.time()
    bundle = build_bundle(arch_id, shape_name, mesh, overrides=overrides)
    with mesh:
        jitted = jax.jit(bundle.fn,
                         in_shardings=bundle.in_shardings,
                         out_shardings=bundle.out_shardings,
                         donate_argnums=bundle.donate_argnums)
        lowered = jitted.lower(*bundle.args)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    mem = compiled.memory_analysis()
    try:
        rec["memory"] = {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
        }
        rec["bytes_per_device"] = (
            rec["memory"]["argument_bytes"] + rec["memory"]["temp_bytes"]
            + rec["memory"]["output_bytes"] - rec["memory"]["alias_bytes"])
    except AttributeError:
        rec["memory"] = {"repr": str(mem)}

    cost = compiled.cost_analysis() or {}
    # raw XLA numbers (NOTE: count while bodies once — kept for reference)
    rec["xla_flops_raw"] = float(cost.get("flops", 0.0))
    rec["xla_bytes_raw"] = float(cost.get("bytes accessed", 0.0))

    # trip-count-aware per-chip cost (see repro.analysis.hlo_cost)
    hlo = compiled.as_text()
    walk = hlo_cost.analyze(hlo)
    rec["hlo_flops_per_chip"] = walk["flops_per_chip"]
    rec["hlo_bytes_per_chip"] = walk["bytes_per_chip"]
    rec["collectives"] = walk["collectives"]
    rec["collective_bytes_per_chip"] = walk["collective_bytes_per_chip"]
    rec["collective_counts"] = walk["collective_counts"]

    # roofline terms (seconds); cost_analysis FLOPs/bytes are per-chip
    rec["model_flops"] = bundle.model_flops_per_step
    rec["t_compute"] = rec["hlo_flops_per_chip"] / _V5E.flops_per_s
    rec["t_memory"] = rec["hlo_bytes_per_chip"] / _V5E.bytes_per_s
    rec["t_collective"] = rec["collective_bytes_per_chip"] / _V5E.ici_bytes_per_s
    terms = {"compute": rec["t_compute"], "memory": rec["t_memory"],
             "collective": rec["t_collective"]}
    rec["bottleneck"] = max(terms, key=terms.get)
    total_chip_flops = rec["hlo_flops_per_chip"] * mesh.size
    rec["useful_flops_ratio"] = (
        rec["model_flops"] / total_chip_flops if total_chip_flops else 0.0)

    if verbose:
        print(f"[{rec['mesh']}] {arch_id} × {shape_name}: "
              f"lower {rec['lower_s']}s compile {rec['compile_s']}s | "
              f"flops/chip {rec['hlo_flops_per_chip']:.3g} "
              f"bytes/chip {rec['hlo_bytes_per_chip']:.3g} "
              f"coll/chip {rec['collective_bytes_per_chip']:.3g} | "
              f"t=(c {rec['t_compute']:.2e}, m {rec['t_memory']:.2e}, "
              f"x {rec['t_collective']:.2e}) -> {rec['bottleneck']}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg override key=value (e.g. attn_impl=flash); "
                         "results tagged with --tag")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    overrides = dict(kv.split("=", 1) for kv in args.override)
    archs = [args.arch] if args.arch else all_arch_ids()
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch_id in archs:
        shapes = [args.shape] if args.shape else sorted(get_arch(arch_id).shapes)
        for shape_name in shapes:
            for mp in meshes:
                tag = f"{arch_id}__{shape_name}__{'mp' if mp else 'sp'}"
                if args.tag:
                    tag += f"__{args.tag}"
                try:
                    rec = run_cell(arch_id, shape_name, multi_pod=mp,
                                   overrides=overrides or None)
                    (outdir / f"{tag}.json").write_text(json.dumps(rec, indent=1))
                except Exception as e:  # noqa: BLE001 — record and continue
                    failures.append(tag)
                    print(f"FAILED {tag}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES: {failures}")
        raise SystemExit(1)
    print("\nDRY-RUN PASS")


if __name__ == "__main__":
    main()
