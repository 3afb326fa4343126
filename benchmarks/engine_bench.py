"""Device-scaling benchmark for the sharded query execution engine on
simulated CPU devices — a standalone CPU tool, not part of
``benchmarks/run.py`` (that process holds the accelerator, and a child
that needs it would fail or hang).

The ``--xla_force_host_platform_device_count`` flag must reach XLA before
jax initialises, so this module sets the flag (and pins JAX to the CPU)
and only then imports the benchmark stack.  Its numbers are CPU numbers
and say nothing about a chip.

    PYTHONPATH=src python -m benchmarks.engine_bench --devices 8 \
        --scale small --out experiments/bench/engine_scaling.json
"""
from __future__ import annotations

import argparse
import json
import os
from pathlib import Path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8,
                    help="simulated host devices (data-parallel width)")
    ap.add_argument("--scale", default="small", choices=["robust", "small"])
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--n-queries", type=int, default=256)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={args.devices}"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    from benchmarks import ir_bench         # imports jax with the flag set

    if args.scale == "robust":
        env = ir_bench.build_robust_env(n_topics=50)
    else:
        env = ir_bench.build_robust_env(n_docs=20000, n_topics=32,
                                        vocab=40000)
    rec = ir_bench.bench_engine_scaling(
        env, device_counts=(1, 2, 4, args.devices), repeats=args.repeats,
        n_queries=args.n_queries)

    print("\n== Engine: device-sharded query throughput ==")
    print(f"simulated devices: {rec['simulated_devices']}, "
          f"host cpus: {rec['host_cpus']} "
          f"(device speedup saturates at host cores)")
    for name, wl in rec["workloads"].items():
        print(f"[{name}] sequential (1 device, chunked loop + stage "
              f"barriers): {wl['sequential_qps']} q/s")
        for row in wl["rows"]:
            print(f"  {row}")
    if args.out:
        out = Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
