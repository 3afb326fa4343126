"""The control a cell's limit is set against.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3

For each seed, in one process: the cell's collection and topics as a run
makes them, the sample of queries a run compares, and the configuration's
reference computed in bfloat16 and put in the program's place, read by the
same comparison against the float64 reference (every number a run
compares, and ``abs_score_gap``).  With ``--dense-only`` only the dense
vectors and their contraction are bfloat16 (for a reference with a dense
stage).  A reference that defines ``control`` (a generating chain's) is
called instead: it answers in its own lower precision, and its answers
carry the tokens it decodes.  Each number's smallest reading over the
seeds is the upper end its limit must stay under; the program's own
readings (the lower end) are the ``checks`` of the cell's runs.  The
benchmark's own runs never run this.  Prints one JSON line per seed and
writes them to ``<--out>/control-<cell>.json`` (default ``bench_out/`` in
the checkout).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import correctness, datagen, harness as H, spec  # noqa: E402


def control_answers(cell, coll, queries: dict, seed: int,
                    dense_only: bool = False, lm: dict | None = None) -> list:
    """The bfloat16 reference's own top-k, as the program would answer;
    or the answers of the reference's own ``control``."""
    import ml_dtypes
    ref_spec = cell.config["reference"]
    ref = spec.reference_module(ref_spec["name"])
    rcoll = ref.Collection(coll.doc_terms, coll.doc_start, coll.vocab,
                           float(cell.config["collection"]
                                 ["stop_df_fraction"]))
    if hasattr(ref, "control"):
        if dense_only:
            raise SystemExit(f"reference {ref_spec['name']!r} has a control "
                             f"of its own; --dense-only does not apply")
        return ref.control(rcoll, queries, ref_spec, seed=seed, lm=lm)
    if dense_only:
        low = ref.run(rcoll, queries, ref_spec, seed=seed,
                      dense_dtype=ml_dtypes.bfloat16)
    else:
        low = ref.run(rcoll, queries, ref_spec, seed=seed,
                      dtype=ml_dtypes.bfloat16)
    return [{"docids": r["docids"], "scores": r["scores"]} for r in low]


def reading(cell, seed: int, seconds: float, rehearse: bool,
            dense_only: bool = False) -> dict:
    from chipbench import system
    t = time.monotonic()
    coll = datagen.collection(
        system.collection_spec(cell.config, rehearse), seed)
    queries = H.make_traffic(cell.traffic, seconds, seed,
                             coll.rank_to_term).Q
    idx = correctness.sample(len(queries["qid"]),
                             int(cell.traffic["check_sample"]), seed)
    sample = {k: v[idx] for k, v in queries.items()}
    cfg_lm = system.lm_config(cell.config, rehearse)
    lm = None if cfg_lm is None else system.lm_spec(cfg_lm)
    low = control_answers(cell, coll, sample, seed, dense_only, lm)
    # the comparison a run makes, with the control's answers in place of
    # the program's: the same sample of the same queries
    answers = [None] * len(queries["qid"])
    for j, i in enumerate(idx):
        answers[i] = low[j]
    nums = H.check(cell, coll, queries, answers, seed, lm=lm)
    return {"seed": seed, **{n: nums[n] for n in cell.checks},
            "abs_score_gap": nums["abs_score_gap"],
            "seconds": time.monotonic() - t}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window the topics are drawn for (default: "
                    "the benchmark's run_seconds)")
    ap.add_argument("--dense-only", action="store_true",
                    help="bfloat16 in the dense stage alone")
    ap.add_argument("--out", default=str(spec.ROOT / "bench_out"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    if args.seconds is None:
        args.seconds = float(spec.benchmark()["run_seconds"])
    H.device_info(cell.chips, args.rehearse)
    if not args.rehearse:
        H.use_compile_cache()
    rows = []
    for s in args.seeds.split(","):
        r = reading(cell, int(s), args.seconds, args.rehearse,
                    args.dense_only)
        print(json.dumps(r), flush=True)
        rows.append(r)
    if not args.rehearse:
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        tag = "-dense" if args.dense_only else ""
        (out / f"control-{cell.name}{tag}.json").write_text(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
