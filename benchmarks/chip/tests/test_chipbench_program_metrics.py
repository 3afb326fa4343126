"""The readers of the program's own per-batch numbers (phases, padded
work, collector pauses), on hand-made run records: each reader's value,
and nothing from a program that does not stamp them."""
import sys
import threading
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from chipbench import harness, loadgen, spec  # noqa: E402

sys.path.insert(0, str(spec.ROOT / "src"))

from repro.obs import hostpause  # noqa: E402


def _req(service_ms, phases=None, work=None, answered=True):
    done = threading.Event()
    done.set()
    tr = types.SimpleNamespace(service_ms=service_ms, timed_out=False)
    if phases is not None:
        tr.phase_ms = tuple(phases)
    if work is not None:
        tr.work = work
    return types.SimpleNamespace(done=done, error=None, trace=tr,
                                 result={} if answered else None)


def _run(reqs, t0=100.0, seconds=51.0, t_end=None):
    win = loadgen.Window(t0=t0, seconds=seconds,
                         sent=[(i, t0, r) for i, r in enumerate(reqs)],
                         lateness_s=[0.0] * len(reqs))
    win.t_end = t0 + seconds if t_end is None else t_end
    return harness.RunRecord(window=win, requests=[], trace=None, peaks=None)


def _program_run():
    # two batches: the first of three requests (one of them never answered)
    # padded to rung 8, the second of one request; each batch's work dict
    # is shared by its requests
    w1 = {"rows_live": 3, "rows_pad": 5, "slots_live": 900,
          "slots_pad": 8 * 48 * 1000 - 900}
    w2 = {"rows_live": 1, "rows_pad": 7, "slots_live": 100,
          "slots_pad": 8 * 48 * 1000 - 100}
    ph = lambda wait, host: [("cache_lookup", host / 2), ("stage:Retrieve", 0),
                             ("device_wait:Retrieve", wait),
                             ("reply", host / 2)]
    return _run([_req(103.0, ph(100.0, 3.0), w1),
                 _req(105.0, ph(100.0, 5.0), w1),
                 _req(109.0, ph(100.0, 9.0), w1, answered=False),
                 _req(54.0, ph(50.0, 4.0), w2)])


def _parent_run():
    return _run([_req(103.0), _req(105.0)])


@pytest.mark.parametrize("cell", ["open", "closed"])
def test_batch_host_ms_is_service_less_device_waits(cell):
    read = spec.metric_reader(f"batch_host_ms.{cell}")
    assert read(_program_run()) == pytest.approx(4.0)      # median of 3, 5, 4
    assert read(_parent_run()) is None


@pytest.mark.parametrize("cell", ["open", "closed"])
def test_posting_fill_counts_each_batch_once(cell):
    read = spec.metric_reader(f"posting_fill.{cell}")
    assert read(_program_run()) == pytest.approx(
        100.0 * 1000 / (2 * 8 * 48 * 1000))
    assert read(_parent_run()) is None
    no_slots = {"rows_live": 3, "rows_pad": 5, "slots_live": 0,
                "slots_pad": 0}
    assert read(_run([_req(1.0, [("reply", 1.0)], no_slots)])) is None


def test_batch_fill_open_is_live_rows_over_dispatched_rows():
    read = spec.metric_reader("batch_fill.open")
    assert read(_program_run()) == pytest.approx(100.0 * 4 / 16)
    assert read(_parent_run()) is None


def test_gc_pause_ms_open_sums_the_pauses_inside_the_window(monkeypatch):
    read = spec.metric_reader("gc_pause_ms.open")
    ring = [(90.0, 0.5, 2), (99.9, 0.2, 0), (120.0, 0.004, 0),
            (150.9, 0.3, 1), (200.0, 1.0, 2)]
    monkeypatch.setattr(hostpause, "RING", ring)
    # window [100, 151]: 0.1 s of the pause that began at 99.9, the whole
    # 4 ms one, 0.1 s of the one that ran past the close
    assert read(_run([])) == pytest.approx(1000.0 * (0.1 + 0.004 + 0.1))
    # a closed loop's window ends at its last answer
    assert read(_run([], t_end=130.0)) == pytest.approx(1000.0 * 0.104)
    assert read(_run([], t0=300.0)) is None
    monkeypatch.setattr(hostpause, "RING", [])
    assert read(_run([])) is None
