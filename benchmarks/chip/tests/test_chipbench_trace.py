"""Trace reduction, roofline arithmetic and the refusals, on hand-made
events and shapes (no device needed)."""
import importlib.util
import json
import random
import sys
import time
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


def _summarize_naming_every_gap(trace, kernels=(), top=10):
    """The reduction as it was before only the longest gaps were named:
    every gap named, then sorted and cut to ``top``."""
    lo, hi = devtrace.window_of(trace)
    busy = [devtrace.busy_ns(evs, lo, hi)
            for evs in trace.device_ops.values()]
    evs = trace.device_ops[sorted(trace.device_ops)[0]]
    idle = sorted(((devtrace.name_gap(g, trace.host_spans),
                    (g[1] - g[0]) * 1e-9)
                   for g in devtrace.gaps(evs, lo, hi)), key=lambda x: -x[1])
    per_op: dict = {}
    for dev in trace.device_ops.values():
        for ev in dev:
            d = devtrace._overlap(lo, hi, ev.start_ns, ev.end_ns)
            if d > 0:
                key = devtrace.op_name(ev.name)
                per_op[key] = per_op.get(key, 0.0) + d * 1e-9
    n_dev = len(trace.device_ops)
    ops = sorted(((k, v / n_dev) for k, v in per_op.items()),
                 key=lambda x: -x[1])
    kev = {k: [ev for dev in trace.device_ops.values() for ev in dev
               if k in ev.name and lo <= ev.start_ns and ev.end_ns <= hi]
           for k in kernels}
    return ((hi - lo) * 1e-9, sum(busy) / n_dev * 1e-9, idle[:top],
            ops[:top], kev)


def _random_trace(seed: int):
    """Ops and spans on a 10 ns grid, so that many gaps share a length
    and many spans share an overlap and a length; spans nest, some only
    wait, and some are copies of another under a different name."""
    rng = random.Random(seed)
    lo, hi = 1000, 1000 + 10 * rng.randrange(150, 400)
    names = ["fusion.3", "fusion.4", "sort.1", "vmap_streaming_topk_.1",
             TOPK_EVENT]
    dev = {}
    for d in range(rng.choice([1, 1, 2])):
        dev[f"/device:TPU:{d}"] = [
            E(rng.choice(names), lo + 10 * rng.randrange(-5, 420),
              10 * rng.randrange(1, 4)) for _ in range(rng.randrange(20, 90))]
    host = [E("bench.window", lo, hi - lo)]
    for _ in range(rng.randrange(1, 4)):
        host.append(E("bench.sleep", lo + 10 * rng.randrange(-5, 300),
                      10 * rng.randrange(20, 200)))
    working = ("bench.submit", "bench.batch_wait", "bench.result_wait",
               "bench.execute_batch")
    for _ in range(rng.randrange(10, 60)):
        outer = E(rng.choice(working), lo + 10 * rng.randrange(-5, 400),
                  10 * rng.randrange(1, 30))
        host.append(outer)
        if rng.random() < 0.4:        # nested inside it
            host.append(E("bench.dispatch", outer.start_ns + 10,
                          max(10, outer.dur_ns - 20)))
        if rng.random() < 0.3:        # equal in overlap and length
            host.append(E(rng.choice(working), outer.start_ns,
                          outer.dur_ns))
    rng.shuffle(host)
    return devtrace.Trace(dev, host)


@pytest.mark.parametrize("seed", range(8))
def test_naming_only_the_longest_gaps_changes_nothing(seed):
    t = _random_trace(seed)
    for top in (1, 3, 10, 10_000):
        s = devtrace.summarize(t, kernels=("streaming_topk", "sort"),
                               top=top)
        want = _summarize_naming_every_gap(
            t, kernels=("streaming_topk", "sort"), top=top)
        assert (s.window_s, s.busy_s, s.idle_gaps, s.device_ops,
                s.kernel_events) == want
        assert s.n_gaps == len(devtrace.gaps(
            t.device_ops[sorted(t.device_ops)[0]], *devtrace.window_of(t)))


def test_the_random_traces_hold_ties():
    # the case the stable sort has to keep: gaps of one length, and ties
    # among them at the cut
    tied = at_cut = 0
    for seed in range(8):
        s = devtrace.summarize(_random_trace(seed), top=10_000)
        lengths = [x for _, x in s.idle_gaps]
        tied += len(lengths) - len(set(lengths))
        at_cut += sum(lengths[top - 1:top + 1].count(lengths[top - 1]) == 2
                      for top in (1, 3, 10) if top < len(lengths))
    assert tied >= 20 and at_cut >= 3


def test_summarize_names_at_most_top_gaps(monkeypatch):
    calls = []
    name_gap = devtrace.name_gap
    monkeypatch.setattr(devtrace, "name_gap",
                        lambda g, spans: calls.append(g) or name_gap(g, spans))
    t = _random_trace(3)
    s = devtrace.summarize(t, top=4)
    assert s.n_gaps > 4 and len(calls) == 4
    assert [round(x * 1e9) for _, x in s.idle_gaps] == \
        [round(g1 - g0) for g0, g1 in calls]


def test_a_fast_servers_window_reduces_in_seconds():
    # one 51 s window at about 490 q/s: 25,000 submits, 800 rung-32
    # batches of four spans each and 100 device operations with a gap
    # after each; naming every gap would pair 2.3e9 gaps and spans
    lo, hi = 0, 51e9
    host = [E("bench.window", lo, hi - lo)]
    host += [E("bench.submit", i * 2.04e6, 0.05e6) for i in range(25_000)]
    ops, batch = [], (hi - lo) / 800
    for b in range(800):
        t0 = lo + b * batch
        host += [E("bench.batch_wait", t0, 0.2e6),
                 E("bench.execute_batch", t0 + 0.2e6, batch - 0.4e6),
                 E("bench.dispatch", t0 + 0.3e6, 1e6),
                 E("bench.result_wait", t0 + 1.3e6, batch - 1.6e6)]
        step = (batch - 0.4e6) / 100
        ops += [E(f"fusion.{k}", t0 + 0.2e6 + k * step, step - 3e3 - 7 * k)
                for k in range(100)]
    t = devtrace.Trace({"/device:TPU:0": ops}, host)
    t0 = time.monotonic()
    s = devtrace.summarize(t, kernels=("streaming_topk",))
    took = time.monotonic() - t0
    assert s.n_gaps == 80_000 + 1 and len(s.idle_gaps) == 10
    # the 799 gaps between batches tie at 403,693 ns; the first ten win
    assert s.idle_gaps == [("batch_wait", pytest.approx(403_693e-9))] * 10
    assert took < 20, f"summarize took {took:.1f} s"


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
