"""Trace reduction, roofline arithmetic and the refusals, on hand-made
events and shapes (no device needed)."""
import importlib.util
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from chipbench import devtrace, kernel_cost, peaks, spec, stats  # noqa: E402

E = devtrace.Event


def _run_module():
    s = importlib.util.spec_from_file_location("chipbench_run", HERE / "run.py")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def _trace():
    # window [0, 100] ns; two overlapping ops, a kernel, one op past the end
    dev = {"/device:TPU:0": [E("fusion.1", 10, 20), E("fusion.2", 20, 20),
                             E("streaming_topk", 60, 10),
                             E("fusion.1", 95, 25)]}
    host = [E("bench.window", 0, 100), E("bench.sleep", 0, 100),
            E("bench.execute_batch", 35, 30), E("bench.dispatch", 50, 8)]
    return devtrace.Trace(dev, host)


def test_busy_is_the_union_clipped_to_the_window():
    s = devtrace.summarize(_trace(), kernels=("streaming_topk",))
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx((30 + 10 + 5) * 1e-9)
    assert s.idle_share == pytest.approx(0.55)


def test_gaps_are_named_by_the_working_span_that_covers_them():
    s = devtrace.summarize(_trace())
    got = [(n, round(t * 1e9)) for n, t in s.idle_gaps]
    assert got == [("sleep", 25), ("execute_batch", 20), ("sleep", 10)]


def test_kernel_events_and_op_ranking():
    s = devtrace.summarize(_trace(), kernels=("streaming_topk",))
    assert [e.name for e in s.kernel_events["streaming_topk"]] == \
        ["streaming_topk"]
    names = [n for n, _ in s.device_ops]
    assert names[0] == "fusion.1"          # 20 + 5 ns inside the window
    assert dict(s.device_ops)["fusion.2"] == pytest.approx(20e-9)


def test_busy_averages_over_devices():
    t = devtrace.Trace({"/device:TPU:0": [E("a", 0, 50)],
                        "/device:TPU:1": [E("a", 0, 100)]},
                       [E("bench.window", 0, 100)])
    assert devtrace.summarize(t).busy_s == pytest.approx(75e-9)


def test_a_trace_without_its_window_or_device_ops_is_refused():
    with pytest.raises(ValueError):
        devtrace.summarize(devtrace.Trace({"/device:TPU:0": []}, []))
    with pytest.raises(ValueError):
        devtrace.summarize(devtrace.Trace({}, [E("bench.window", 0, 9)]))


@pytest.mark.parametrize("rows", [8, 16, 32])
def test_streaming_topk_cost_at_the_cell_shapes(rows):
    # 528,155 scores pad to 129 blocks of 4096; k=10 keeps one 128-lane row
    c = kernel_cost.streaming_topk(528_155, 10, rows)
    assert c["bytes"] == rows * (4 * 528_384 + 2 * 4 * 128)
    assert c["ops"] == rows * 528_384
    t, bound = kernel_cost.least_time_s(c, peaks.peaks("TPU v5 lite"))
    assert bound == "memory"
    assert t == pytest.approx(c["bytes"] / 819e9)


#: a streaming_topk event as the v5e trace names it (rung 8)
TOPK_EVENT = ("%vmap_streaming_topk_.1 = (f32[8,1,128]{2,1,0:T(1,128)S(1)}, "
              "s32[8,1,128]{2,1,0:T(1,128)S(1)}) custom-call(f32[8,4128,128]"
              "{2,1,0:T(8,128)S(1)} %copy_bitcast_fusion), custom_call_target="
              "\"tpu_custom_call\"")


def test_streaming_topk_event_cost_matches_the_shape_arithmetic():
    assert kernel_cost.streaming_topk_event(TOPK_EVENT) == \
        kernel_cost.streaming_topk(528_155, 10, 8)
    with pytest.raises(ValueError):
        kernel_cost.streaming_topk_event("%fusion.3 = s32[8] fusion()")


def test_op_names_lose_layouts_and_attributes():
    assert devtrace.op_name(TOPK_EVENT) == (
        "vmap_streaming_topk_.1 = (f32[8,1,128], s32[8,1,128]) "
        "custom-call(f32[8,4128,128] %copy_bitcast_fusion)")


def test_unknown_device_kind_is_refused():
    with pytest.raises(ValueError, match="no peak rates"):
        peaks.peaks("TPU v99")


def test_unknown_cell_is_refused(capsys):
    with pytest.raises(spec.SpecError):
        spec.load_cell("no.such.cell")
    assert _run_module().main(["--workload", "no.such.cell", "--seed", "1",
                               "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


def test_a_run_without_a_tpu_prints_no_result(capsys):
    rc = _run_module().main(["--workload", "bm25.title.open", "--seed", "1",
                             "--seconds", "1"])
    assert rc == 3
    cap = capsys.readouterr()
    assert cap.out == "" and "no TPU" in cap.err


def test_every_cell_finds_its_files():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.checks and cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))
    for c in bench["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        spec.reference_module(cfg["reference"]["name"])


def test_percentile_is_nearest_rank():
    xs = list(range(1, 21))
    assert stats.percentile(xs, 50) == 10
    assert stats.percentile(xs, 95) == 19
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.spread([1, 2, 3, 4, 5]) == pytest.approx((4.5 - 1.5) / 3)
