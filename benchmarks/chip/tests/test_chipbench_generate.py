"""A generating cell added by files alone (a configuration with ``lm``, a
reference with ``numbers``, a checks file naming ``logit_gap``),
rehearsed through ``run.run`` at a tiny size on the CPU."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import generating as G  # noqa: E402
from chipbench import spec  # noqa: E402


@pytest.mark.parametrize("trace", [0, 1])
def test_a_generating_cell_reads_correct(capsys, trace):
    c = G.cell()
    rc, out, err = G.run_cell(capsys, c, trace)
    assert rc == 0
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert list(res)[-1] == "checks"
    gap = res["checks"]["logit_gap"]
    assert 0 <= gap["value"] <= gap["limit"]
    assert any(line.startswith("check logit_gap = ") for line in err[-2:])
    names = {m["name"] for m in (c.per_layer if trace else c.end_to_end)}
    assert set(res["metrics"]) <= names
    if not trace:
        assert set(res["metrics"]) == names
        assert all(m["value"] > 0 for m in res["metrics"].values())
    log = "\n".join(out)
    assert "[setup]" in log and ", lm " in log
    assert "[setup] lm internlm2-smoke: 2 layers" in log and "vocab 512" in log
    assert "generated answers" in log and "ttft_ms p50 " in log


def _answer(rid, tokens=None, ttft_ms=0.0, n_tokens=0):
    from repro.serve.request import RequestTrace, ServeRequest
    result = {"docids": np.array([[3, 1]]), "scores": np.array([[2.0, 1.0]])}
    if tokens is not None:
        result["tokens"] = np.asarray([tokens])
    req = ServeRequest(rid, Q=None, deadline=None, result=result,
                       trace=RequestTrace(rid, t_arrival=10.0, t_done=12.5,
                                          ttft_ms=ttft_ms,
                                          n_tokens=n_tokens))
    req.done.set()
    return req


def test_served_records_keep_the_tokens_of_a_generated_answer():
    from chipbench import harness as H, loadgen
    sent = [(0, 10.0, _answer(0)),
            (1, 10.0, _answer(1, [5, 6, 7], ttft_ms=800.0, n_tokens=3)),
            # served whole from the stage cache: tokens, nothing decoded
            (2, 10.0, _answer(2, [5, 6, 7]))]
    recs, attempted, failed = H.served_requests(
        loadgen.Window(10.0, 2.0, sent, [0.0] * 3))
    assert (attempted, failed) == (3, 0)
    assert not {"tokens", "ttft_ms", "n_tokens"} & set(recs[0])
    assert recs[1]["tokens"].tolist() == [5, 6, 7]
    assert (recs[1]["ttft_ms"], recs[1]["n_tokens"]) == (800.0, 3)
    assert recs[2]["tokens"].tolist() == [5, 6, 7]
    assert (recs[2]["ttft_ms"], recs[2]["n_tokens"]) == (0.0, 0)
    assert all(r["latency_ms"] == 2500.0 for r in recs)


def test_a_check_no_number_matches_ends_the_run(capsys):
    c = G.cell(checks={"score_gap": {"limit": 1e-3},
                       "logit_gapp": {"limit": 0.02}})
    rc, out, err = G.run_cell(capsys, c, 0, seconds=1.0)
    assert rc == 1
    assert not any(line.startswith("{") for line in out)
    assert any("logit_gapp" in line for line in err)


def test_metric_files_name_the_kernels_a_traced_run_times():
    bench = spec.benchmark()
    per_layer = {w["name"]: spec.load_cell(w["name"]).per_layer
                 for w in bench["workloads"]}
    assert spec.kernels(per_layer["bm25.title.open"]) == ("streaming_topk",)
    assert spec.kernels(per_layer["rerank.title.closed"]) == ()
    assert spec.kernels(per_layer["bm25.title.open"]
                        + per_layer["bm25.title.open"]) == ("streaming_topk",)
