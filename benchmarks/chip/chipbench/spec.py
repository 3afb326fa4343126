"""Cells, configurations, traffic mixes and limits, found by name.

``BENCHMARK.json`` at the checkout root lists the cells.  Everything that
belongs to one configuration, one traffic mix, one cell's limits or one
per-layer metric is a file of its own under ``benchmarks/chip``:

    configs/<config>.json     sizes, pipeline, reference (data)
    traffic/<traffic>.json    arrivals, query shape, serving policy (data)
    checks/<cell>.json        the limit of each number compared (data)
    metrics/<metric>.py       a reader: ``read(run) -> float | None``, and
                              ``KERNELS``, the kernels a traced run times
    references/<r>.py         a plain reference: ``run`` ranks, and
                              ``numbers``, where defined, adds numbers

so a later cell, configuration or metric is added by adding files.

A cell whose pipeline ends in ``Generate`` adds:

* ``configs/<c>.json`` with an ``lm`` section: ``name`` (the model the
  pipeline's ``Generate`` names), ``arch`` (an id of
  ``repro.configs.registry``), ``overrides`` (applied to the arch's
  published config, a dict nesting into a dataclass field such as
  ``moe``) and ``rehearse`` (applied to its ``reduced()`` config under
  ``--rehearse``).  ``system.build`` draws the weights by
  ``chipbench/weights.py``'s rule and registers the LM before the server
  is built;
* ``references/<r>.py`` with ``numbers``, which holds the served tokens
  to a plain decoder (``references/generate.py``: ``logit_gap``), and
  with ``control``, the same reference in a lower precision, for
  ``control.py``;
* ``checks/<cell>.json`` naming any number the reference returns
  (``logit_gap``); a name no number matches ends the run with exit 1;
* ``metrics/<m>.py`` with ``KERNELS`` naming the kernels it reads from the
  trace.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path

#: benchmarks/chip
HERE = Path(__file__).resolve().parents[1]
#: the checkout root (BENCHMARK.json, src/)
ROOT = HERE.parents[1]


class SpecError(ValueError):
    """A cell, configuration, traffic mix or metric that is not defined."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    checks: dict
    end_to_end: list       # BENCHMARK.json entries this cell reports
    per_layer: list


def _load_json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise SpecError(f"no {what} file {path.relative_to(ROOT)}")
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json", "benchmark")


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Cell ``name`` of ``BENCHMARK.json`` with its configuration, traffic
    and limits."""
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown cell {name!r}; BENCHMARK.json has "
                        f"{sorted(cells)}")
    w = cells[name]
    config = _load_json(HERE / "configs" / f"{w['config']}.json",
                        "configuration")
    traffic = _load_json(HERE / "traffic" / f"{w['traffic']}.json",
                         "traffic")
    checks = _load_json(HERE / "checks" / f"{name}.json", "checks")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, checks=checks,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


@functools.cache
def _metric_module(name: str):
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader for per-layer metric {name!r} "
                        f"(expected metrics/{name}.py)")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    return _metric_module(name).read


def kernels(metrics: list) -> tuple:
    """The kernels a traced run times: the union, in order, of the
    ``KERNELS`` that the metrics' readers declare."""
    out = {}
    for m in metrics:
        out.update(dict.fromkeys(getattr(_metric_module(m["name"]),
                                         "KERNELS", ())))
    return tuple(out)


def reference_module(name: str):
    """``references/<name>.py``: a plain reference of one pipeline."""
    path = HERE / "references" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reference {name!r} (expected "
                        f"references/{name}.py)")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_reference_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
