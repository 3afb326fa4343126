"""Drive run.py in this process at the configurations' tiny sizes."""
import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from chipbench import spec  # noqa: E402


def load_cell(name: str):
    return spec.load_cell(name)


def run_cell(capsys, name: str, trace: int, seed: int = 2 ** 31 + 5,
             seconds: float = 2.0) -> tuple:
    """(exit code, last stdout line as JSON, stderr lines)."""
    s = importlib.util.spec_from_file_location("chipbench_run",
                                               HERE / "run.py")
    run = importlib.util.module_from_spec(s)
    s.loader.exec_module(run)
    rc = run.main(["--workload", name, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace), "--rehearse"])
    cap = capsys.readouterr()
    lines = cap.out.strip().splitlines()
    return rc, json.loads(lines[-1]), cap.err.strip().splitlines()


def check_line(cell, out: dict, err: list, trace: int) -> None:
    """The result line has the contract's keys, the cell's metrics and
    the numbers compared, last, beside their limits."""
    assert list(out)[:3] == ["correct", "attempted", "failed"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    dev = out["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        names = {m["name"] for m in cell.per_layer}
        assert set(out["metrics"]) <= names
        assert dev["busy_s"] > 0 and dev["window_s"] > 0
        for key in ("device_ops", "idle_gaps"):
            assert 0 < len(out["breakdown"][key]) <= 10
    else:
        assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
        assert "breakdown" not in out
    for m in out["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    for name, c in out["checks"].items():
        assert c["value"] <= c["limit"]
        assert any(line.startswith(f"check {name} = ") for line in err[-3:])
