"""The system under test, built through the entry points a user calls:
``build_index``, ``JaxBackend``, ``register_lm``, the pipeline operators
and ``PipelineServer``."""
from __future__ import annotations

import dataclasses
import sys
import time

import jax

from chipbench import datagen, weights
from chipbench.spec import ROOT

sys.path.insert(0, str(ROOT / "src"))

from repro import core  # noqa: E402
from repro.core import BackendDescriptor, JaxBackend  # noqa: E402
from repro.configs.registry import get_arch  # noqa: E402
from repro.core.descriptor import DEFAULT_CAPABILITIES  # noqa: E402
from repro.index import build_index  # noqa: E402
from repro.index.corpus import Corpus  # noqa: E402
from repro.serve import PipelineServer, ServeConfig  # noqa: E402


def collection_spec(config: dict, rehearse: bool) -> dict:
    coll = dict(config["collection"])
    if rehearse:
        coll.update(config["rehearse"])
    return coll


def pipeline(config: dict):
    """``stage >> stage % cutoff ...`` from the configuration's list."""
    out = None
    for st in config["pipeline"]:
        t = getattr(core, st["stage"])(*st.get("args", []),
                                       **st.get("kwargs", {}))
        if st.get("cutoff") is not None:
            t = t % int(st["cutoff"])
        out = t if out is None else out >> t
    return out


def _replace(obj, overrides: dict):
    """``obj`` with ``overrides`` applied by ``dataclasses.replace``; a
    dict given for a dataclass field nests into it, a string for a type
    field (``dtype``) names a NumPy dtype."""
    kw = {}
    for k, v in overrides.items():
        cur = getattr(obj, k)
        if isinstance(v, dict) and dataclasses.is_dataclass(cur):
            v = _replace(cur, v)
        elif isinstance(v, str) and isinstance(cur, type):
            v = jax.numpy.dtype(v).type
        kw[k] = v
    return dataclasses.replace(obj, **kw)


def lm_config(config: dict, rehearse: bool):
    """The configuration's LM: ``lm.arch``'s published config with
    ``lm.overrides``, or under ``rehearse`` its ``reduced()`` config with
    ``lm.rehearse``; None for a configuration without ``lm``."""
    lm = config.get("lm")
    if lm is None:
        return None
    arch = get_arch(lm["arch"])
    if rehearse:
        return _replace(arch.reduced()[0], lm.get("rehearse", {}))
    return _replace(arch.model_cfg(), lm.get("overrides", {}))


def lm_spec(cfg) -> dict:
    """The LM's sizes as plain numbers, for a reference: every field of
    its config, the dtype by name, a nested config as a dict."""
    out = {}
    for f in dataclasses.fields(cfg):
        v = getattr(cfg, f.name)
        if dataclasses.is_dataclass(v):
            v = lm_spec(v)
        elif isinstance(v, type):
            v = jax.numpy.dtype(v).name
        out[f.name] = v
    return out


def lm_weights(config: dict, cfg, seed: int):
    """The LM's weights by ``chipbench/weights.py``'s rule, over the tree
    of the program's parameters."""
    init = get_arch(config["lm"]["arch"]).module.init_params
    shapes = jax.eval_shape(lambda: init(cfg, jax.random.key(0)))
    return weights.draw(shapes, seed)


@dataclasses.dataclass
class System:
    coll: datagen.Collection
    backend: JaxBackend
    server: PipelineServer | None = None
    compile_report: dict | None = None
    #: seconds of each set-up step, in order
    timings: dict = dataclasses.field(default_factory=dict)
    #: the generating leaf's LM (``lm_spec``), None without one
    lm: dict | None = None

    def close(self) -> None:
        if self.server is not None:
            self.server.stop()


def build(config: dict, traffic: dict, seed: int, *, rehearse: bool,
          stage_timing: bool = False) -> System:
    """Collection, index, backend, the configuration's LM where it has
    one, and the cell's one compiled pipeline.  Warm-up is the caller's
    (it needs the cell's own queries)."""
    t, times = time.monotonic(), {}

    def step(name):
        nonlocal t
        now = time.monotonic()
        times[name] = now - t
        t = now

    spec = collection_spec(config, rehearse)
    coll = datagen.collection(spec, seed)
    step("collection")
    index = build_index(Corpus(coll.doc_terms, coll.doc_start, coll.vocab),
                        stop_df_fraction=float(spec["stop_df_fraction"]))
    jax.block_until_ready(index)
    step("build_index")
    caps = DEFAULT_CAPABILITIES - frozenset(config["capabilities_removed"])
    backend = JaxBackend(index, descriptor=BackendDescriptor.default(caps),
                         seed=seed)
    jax.block_until_ready(backend.dense.emb)
    step("backend")
    sys_ = System(coll, backend, timings=times)
    cfg_lm = lm_config(config, rehearse)
    if cfg_lm is not None:
        params = lm_weights(config, cfg_lm, seed)
        jax.block_until_ready(params)
        backend.register_lm(config["lm"]["name"], cfg_lm, params=params)
        sys_.lm = lm_spec(cfg_lm)
        step("lm")
    pipe = pipeline(config)
    cfg = ServeConfig.default(**traffic.get("serve", {}))
    if stage_timing:
        cfg = cfg.with_tracing(stages=True)
    sys_.server = PipelineServer(pipe, backend, cfg, name="cell")
    sys_.compile_report = sys_.server.compile_report
    step("compile_pipeline")
    return sys_


def gate_decisions(sys_: System) -> list:
    rep = sys_.compile_report or {}
    return [{k: d.get(k) for k in ("pattern", "accepted", "error")}
            for d in rep.get("fusion_decisions", [])]


def compiled_chain(sys_: System) -> list:
    return [op.kind for op in sys_.server.chain]


def engine_compiles(sys_: System) -> int:
    return int(sys_.backend.engine.total_compiles())
