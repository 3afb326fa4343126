"""A generating cell built in the test from ``tests/fixtures/`` (it is not
in ``BENCHMARK.json``), driven through ``run.run`` at its tiny sizes."""
import argparse
import importlib.util
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
sys.path.insert(0, str(HERE))

from chipbench import spec  # noqa: E402

#: the fixture cell's name
NAME = "rag.tiny.closed8"


def _fixture(name: str) -> dict:
    return json.loads((FIXTURES / name).read_text())


def cell(checks: dict | None = None) -> spec.Cell:
    """The fixture cell: configuration ``rag.tiny`` under traffic
    ``rag.closed8``, reporting ``served_qps`` and two per-layer
    metrics."""
    bench = spec.benchmark()
    return spec.Cell(
        name=NAME, chips=1, config=_fixture("rag.tiny.json"),
        traffic=_fixture("rag.closed8.json"),
        checks=(_fixture(f"{NAME}.checks.json") if checks is None
                else checks),
        end_to_end=[m for m in bench["end_to_end"]
                    if m["name"] in ("setup_s", "served_qps")],
        per_layer=[m for m in bench["per_layer"]
                   if m["name"] in ("batch_fill.closed",
                                    "device_idle.closed")])


def run_module():
    s = importlib.util.spec_from_file_location("chipbench_run",
                                               HERE / "run.py")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def run_cell(capsys, c: spec.Cell, trace: int, seed: int = 2 ** 33 + 11,
             seconds: float = 2.0) -> tuple:
    """(exit code, stdout lines, stderr lines) of one rehearsal of ``c``."""
    import jax
    from chipbench import harness as H, system
    run = run_module()
    args = argparse.Namespace(workload=c.name, seed=seed, seconds=seconds,
                              trace=trace, rehearse=True)
    rc = run.run(c, args, H.device_info(c.chips, True), time.monotonic(),
                 H, system, jax)
    cap = capsys.readouterr()
    return rc, cap.out.strip().splitlines(), cap.err.strip().splitlines()
