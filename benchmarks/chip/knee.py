"""Find the knee of an open-loop cell: the highest offered rate at which
completions keep pace with arrivals over a window.

    python3 benchmarks/chip/knee.py --workload bm25.title.open --seed 11 \
        [--seconds 51] [--rates 2,3,4]

One process sets the cell up once, times one burst at each ladder rung
(``capacity = top rung / its service time``), then offers each rate (by
default fractions of that capacity) for ``--seconds`` with fresh topics
and reports, per rate: requests sent, answered inside the window, the
backlog at the window's close, latency p50 and p95 from the intended send
time, and the mean batch size.  The knee is the highest rate whose backlog
at the close is at most one top-rung batch; the cell offers 0.8 x the
knee, written into its traffic file as a number.  Results go to standard
output and ``<--out>/knee-<cell>.json`` (default ``bench_out/`` in the
checkout).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import datagen, harness as H, loadgen, spec, stats  # noqa: E402

FRACTIONS = (0.3, 0.5, 0.7, 0.8, 0.9, 1.0, 1.15)


def burst_service_s(server, Q, bucket: int, repeats: int = 3) -> float:
    """Median wall time of one full batch of ``bucket`` requests."""
    times = []
    for r in range(repeats):
        lo = (r + 1) * 1000
        t0 = time.monotonic()
        reqs = server.submit(datagen.rows(Q, lo, lo + bucket),
                             timeout_ms=None)
        server.pump()
        for q in reqs:
            q.wait(60.0)
        times.append(time.monotonic() - t0)
    return stats.median(times)


def level(server, query, rank_to_term, rate: float, seconds: float,
          seed: int, salt: int, block: int | None = None) -> dict:
    due = datagen.arrivals(rate, seconds, seed, block=block)
    Q = datagen.topics(query, len(due), seed, rank_to_term, salt=salt)
    server.start()
    win = loadgen.open_loop(server, Q, due, seconds, loadgen.Annotator(False))
    loadgen.wait_all(win, H.GRACE_S)
    server.stop()
    recs, sent, failed = H.served_requests(win)
    close = win.t0 + seconds
    lat = [r["latency_ms"] for r in recs]
    return {"offered_qps": rate, "sent": sent, "failed": failed,
            "answered_in_window": sum(r["t_done"] <= close for r in recs),
            "backlog_at_close": sum(r["t_done"] > close for r in recs)
            + failed,
            "latency_p50_ms": stats.percentile(lat, 50) if lat else None,
            "latency_p95_ms": stats.percentile(lat, 95) if lat else None,
            "mean_batch": (sum(r["batch_size"] for r in recs) / len(recs)
                           if recs else None),
            "lateness_max_s": max(win.lateness_s)}


def knee(levels: list, top_rung: int) -> float | None:
    """The highest offered rate below which every level kept pace."""
    best = None
    for lv in sorted(levels, key=lambda lv: lv["offered_qps"]):
        if lv["failed"] or lv["backlog_at_close"] > top_rung:
            break
        best = lv["offered_qps"]
    return best


def main(argv=None) -> int:
    t_start = time.monotonic()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rates", default="")
    ap.add_argument("--out", default=str(spec.ROOT / "bench_out"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    block = cell.traffic.get("arrival_block")
    if args.seconds is None:
        args.seconds = float(spec.benchmark()["run_seconds"])
    if not args.rehearse:
        H.use_compile_cache()
    device = H.device_info(cell.chips, args.rehearse)
    from chipbench import system
    t = time.monotonic()
    sys_ = system.build(cell.config, cell.traffic, args.seed,
                       rehearse=args.rehearse)
    H.log(f"[setup] build {time.monotonic() - t:.3f} s; chain "
          f"{system.compiled_chain(sys_)}; gate "
          f"{system.gate_decisions(sys_)}")
    Q = datagen.topics(cell.traffic["query"], 4000, args.seed,
                       sys_.coll.rank_to_term, salt=50)
    warm = sys_.server.warmup(datagen.rows(Q, 0, 1))
    H.log(f"[setup] warm-up {warm['warmup_s']} s, compiles "
          f"{warm['compiles']}; set-up {time.monotonic() - t_start:.3f} s")
    service = {b: burst_service_s(sys_.server, Q, b)
               for b in sys_.server.scheduler.ladder}
    top = max(service)
    capacity = top / service[top]
    H.log(f"[burst] service seconds per rung {service}; capacity "
          f"{capacity:.3f} queries/s")
    rates = ([float(r) for r in args.rates.split(",")] if args.rates
             else [round(f * capacity, 3) for f in FRACTIONS])
    levels = []
    for j, rate in enumerate(rates):
        lv = level(sys_.server, cell.traffic["query"],
                   sys_.coll.rank_to_term, rate, args.seconds, args.seed + j,
                   salt=60 + j, block=block)
        H.log(f"[level] {json.dumps(lv)}")
        levels.append(lv)
    k = knee(levels, top)
    H.log(f"[knee] {k} queries/s; the cell offers 0.8 x the knee = "
          f"{None if k is None else round(0.8 * k, 3)}")
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    result = {"cell": cell.name, "device": device, "service_s": service,
              "capacity_qps": capacity, "seconds": args.seconds,
              "arrival_block": block,
              "levels": levels, "knee_qps": k}
    (out_dir / f"knee-{cell.name}.json").write_text(
        json.dumps(result, indent=1))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
