"""Backend descriptors, calibrated cost model, autotune + tuning-profile
persistence (the measurement-driven compiler layer).

Covers the ISSUE-6 acceptance property explicitly: compiling against a
backend whose descriptor carries a *persisted* TuningProfile performs zero
probe measurements and zero gate-candidate compiles (decision-record
counters), plus profile corruption recovery and backend-digest
invalidation."""
import dataclasses
import json

import numpy as np
import pytest

from repro.analysis.hlo_cost import fit_peaks
from repro.core import BackendDescriptor, JaxBackend, Retrieve, TuningProfile
from repro.core.descriptor import as_descriptor
from repro.core.passes import compile_pipeline, explain_pipeline
from repro.index import build_index, synthesize_corpus

#: fusion-visible capability set (pruned_topk off: the pushdown rewrite
#: would otherwise consume the cutoff before the gate ever sees it)
FUSE_CAPS = frozenset({"fat", "fused_topk", "fused_scoring", "multi_model"})


@pytest.fixture(scope="module")
def env():
    corpus = synthesize_corpus(n_docs=600, vocab=2500, mean_len=60, seed=11)
    return {"index": build_index(corpus)}


def _backend(env, profile=None, *, autotune=True, band=10.0, default_k=50):
    desc = BackendDescriptor.default(FUSE_CAPS).with_profile(profile)
    if autotune:
        desc = desc.with_autotune(True, band=band, probe_queries=2,
                                  probe_repeats=1)
    return JaxBackend(env["index"], default_k=default_k, descriptor=desc)


def _compile(backend, pipe=None):
    rep = {}
    op = compile_pipeline(pipe if pipe is not None
                          else Retrieve("BM25", k=50) % 10,
                          backend, report=rep)
    return op, rep


# ---------------------------------------------------------------------------
# descriptor basics
# ---------------------------------------------------------------------------

def test_default_descriptor_fields():
    d = BackendDescriptor.default()
    assert d.supports("fused_topk") and not d.supports("nope")
    assert d.native_limit("topk") is not None
    assert d.kernel_native("topk", d.native_limit("topk"))
    assert not d.kernel_native("topk", d.native_limit("topk") + 1)
    assert d.kernel_native("fat", 10 ** 9)     # no ceiling for fat
    assert d.host and len(d.peak_digest) == 16


def test_peak_digest_tracks_calibration():
    d = BackendDescriptor.default()
    d2 = d.calibrated({"peak_flops_per_s": 2.0e13,
                       "peak_bytes_per_s": 4.0e11})
    assert d2.peak_flops_per_s == 2.0e13
    assert d.peak_digest != d2.peak_digest


def test_device_peak_table_keyed_by_device_kind():
    from repro.analysis.hlo_cost import device_peaks
    v5e = device_peaks("TPU v5 lite")
    assert (v5e.flops_per_s, v5e.bytes_per_s) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    assert "uncalibrated proxy" in device_peaks("cpu").source
    # the CPU row keeps the constants the CPU gate decisions were made with
    d = BackendDescriptor.default()
    assert (d.peak_flops_per_s, d.peak_bytes_per_s) == (1.0e14, 1.0e12)


def test_unknown_tpu_device_kind_raises():
    from repro.analysis.hlo_cost import device_peaks
    with pytest.raises(ValueError, match="TPU v99"):
        device_peaks("TPU v99")


def test_fused_candidate_lowering_error_is_recorded(env, monkeypatch):
    """A fused candidate that cannot lower is declined with the failure on
    the decision (``error``) and the pass counters — never passed off as a
    cost verdict for the unfused chain."""
    from repro.index import retrieve as RT

    def broken(*a, **kw):
        raise NotImplementedError("no lowering for this kernel\nsecond line")

    monkeypatch.setattr(RT, "retrieve_topk_fused", broken)
    op, rep = _compile(_backend(env, autotune=False))
    assert op.kind != "fused_topk_retrieve"
    (d,) = [d for d in rep["fusion_decisions"] if d["pattern"] == "topk"]
    assert d["accepted"] is False
    assert d["error"] == ("fused: NotImplementedError: no lowering for "
                          "this kernel")
    assert rep["tuning"]["gate_errors"] == 1


def test_capabilities_kwarg_removed(env):
    """The pre-descriptor ``capabilities=`` ctor kwarg finished its
    deprecation cycle: it now fails like any unknown kwarg, and the
    descriptor is the only capability surface."""
    with pytest.raises(TypeError):
        JaxBackend(env["index"], capabilities=frozenset({"fat"}))
    be = JaxBackend(env["index"],
                    descriptor=BackendDescriptor.default(frozenset({"fat"})))
    assert be.capabilities == frozenset({"fat"})     # read-only alias stays
    assert be.descriptor.capabilities == frozenset({"fat"})
    assert as_descriptor(be) is be.descriptor


# ---------------------------------------------------------------------------
# satellite bugfix: estimate cache scoped by host/peak digest
# ---------------------------------------------------------------------------

def test_estimate_cache_scoped_by_peak_digest(env):
    be = _backend(env, autotune=False)
    _, rep1 = _compile(be)
    assert rep1["tuning"]["gate_estimates"] > 0
    assert set(be._cost_estimates) == {be.descriptor.peak_digest}
    # same backend re-priced under different peak constants: the cached
    # estimates must NOT answer — a fresh scope appears and the candidates
    # are re-priced
    be.descriptor = be.descriptor.calibrated(
        {"peak_flops_per_s": 3.3e13, "peak_bytes_per_s": 1.1e11})
    _, rep2 = _compile(be)
    assert rep2["tuning"]["gate_estimates"] > 0
    assert len(be._cost_estimates) == 2
    # ...and the old scope still answers for the old descriptor
    be.descriptor = _backend(env, autotune=False).descriptor
    _, rep3 = _compile(be)
    assert rep3["tuning"]["gate_estimates"] == 0


# ---------------------------------------------------------------------------
# tuning-profile persistence (tentpole acceptance)
# ---------------------------------------------------------------------------

def test_profile_roundtrip_zero_probe_measurements(env, tmp_path):
    path = tmp_path / "profile.json"
    be = _backend(env, TuningProfile(path))
    _, cold = _compile(be)
    assert cold["tuning"]["probe_measurements"] > 0
    assert cold["tuning"]["gate_estimates"] > 0
    assert path.exists()
    # fresh backend + fresh profile object loading the persisted file:
    # the decision replays with ZERO candidate compiles and ZERO probes
    be2 = _backend(env, TuningProfile(path))
    _, warm = _compile(be2)
    assert warm["tuning"]["probe_measurements"] == 0
    assert warm["tuning"]["gate_estimates"] == 0
    assert warm["tuning"]["profile_hits"] > 0
    assert warm["tuning"]["profile_misses"] == 0
    # the replayed decision is the persisted one, marked as such
    srcs = [d["source"] for d in warm["fusion_decisions"]]
    assert srcs and all(s == "profile" for s in srcs)
    accepted = [d["accepted"] for d in cold["fusion_decisions"]]
    assert [d["accepted"] for d in warm["fusion_decisions"]] == accepted


def test_profile_corrupt_file_recovery(tmp_path):
    path = tmp_path / "profile.json"
    path.write_text('{"version": 1, "entries": {"x": ')   # truncated
    prof = TuningProfile(path)
    assert prof.entries == {} and not path.exists()
    # wrong version: also recovered (stale schema never half-parses)
    path.write_text(json.dumps({"version": 999, "entries": {}}))
    assert TuningProfile(path).entries == {}
    # non-dict entries
    path.write_text(json.dumps({"version": 1, "entries": [1, 2]}))
    assert TuningProfile(path).entries == {}


def test_profile_save_roundtrips_entries(tmp_path):
    path = tmp_path / "p.json"
    prof = TuningProfile(path)
    prof.record("digest", ("topk", ("f",), ("u",)), 8,
                {"accepted": True, "source": "measured"})
    assert prof.dirty
    prof.save()
    assert not prof.dirty and path.exists()
    again = TuningProfile(path)
    hit = again.lookup("digest", ("topk", ("f",), ("u",)), 8)
    assert hit == {"accepted": True, "source": "measured"}
    assert again.lookup("digest", ("other",), 8) is None
    assert again.hits == 1 and again.misses == 1


def test_profile_invalidated_by_backend_digest_change(env, tmp_path):
    path = tmp_path / "profile.json"
    _compile(_backend(env, TuningProfile(path)))
    # different default_k -> different backend content digest -> the
    # persisted entries must miss and the gate re-tunes
    be2 = _backend(env, TuningProfile(path), default_k=40)
    _, rep = _compile(be2, Retrieve("BM25", k=40) % 10)
    assert rep["tuning"]["profile_hits"] == 0
    assert rep["tuning"]["profile_misses"] > 0
    assert rep["tuning"]["gate_estimates"] > 0


# ---------------------------------------------------------------------------
# autotune policy
# ---------------------------------------------------------------------------

def test_autotune_band_zero_measures_nothing(env):
    be = _backend(env, band=0.0)
    _, rep = _compile(be)
    assert rep["tuning"]["probe_measurements"] == 0
    assert all(d["source"] == "estimate" for d in rep["fusion_decisions"])


def test_autotune_wide_band_measures_and_records(env):
    be = _backend(env, band=10.0)
    _, rep = _compile(be)
    assert rep["tuning"]["probe_measurements"] > 0
    d = rep["fusion_decisions"][0]
    assert d["source"] == "measured"
    assert d["fused_measured_s"] > 0 and d["unfused_measured_s"] > 0
    assert d["accepted"] == (d["fused_measured_s"] < d["unfused_measured_s"])
    # HLO counts ride along for calibration
    assert d["fused_flops"] > 0 and d["unfused_bytes"] > 0


def test_mixed_k_linear_fusion_is_measured_only(env):
    pipe = 0.5 * Retrieve("BM25", k=30) + 0.5 * Retrieve("QL", k=50)
    # static gate: mixed-k must NOT fuse (semantics-affecting)
    op_static, rep_static = _compile(_backend(env, autotune=False), pipe)
    assert op_static.kind == "linear"
    assert all(d["pattern"] != "multi_mixed"
               for d in rep_static["fusion_decisions"])
    # autotune: taken only on a measured win, at k = max(k_i)
    op, rep = _compile(_backend(env), pipe)
    ds = [d for d in rep["fusion_decisions"] if d["pattern"] == "multi_mixed"]
    assert len(ds) == 1 and ds[0]["source"] == "measured"
    if ds[0]["accepted"]:
        assert op.kind == "multi_retrieve" and op.params["k"] == 50
    else:
        assert op.kind == "linear"


def test_explain_shows_measured_vs_predicted(env):
    text = explain_pipeline(Retrieve("BM25", k=50) % 10, _backend(env))
    assert "fusion gate" in text
    assert "predicted" in text and "measured" in text


# ---------------------------------------------------------------------------
# auto-refit: profile-carried calibration applied by with_profile
# ---------------------------------------------------------------------------

FIT = {"peak_flops_per_s": 2.0e13, "peak_bytes_per_s": 4.0e11,
       "gamma": 50.0, "n_records": 6, "rms_log_ratio_error": 0.01}


def test_with_profile_auto_refits_from_calibration(tmp_path):
    path = tmp_path / "p.json"
    prof = TuningProfile(path)
    prof.note_calibration(FIT)
    prof.save()
    # a fresh descriptor attaching the persisted profile re-prices its
    # roofline peaks from the stored fit, once
    prof2 = TuningProfile(path)
    d = BackendDescriptor.default().with_profile(prof2)
    assert d.peak_flops_per_s == FIT["peak_flops_per_s"]
    assert d.peak_bytes_per_s == FIT["peak_bytes_per_s"]
    assert prof2.pending_fit(d.peak_digest) is None    # marked applied
    # a second attach of the same (marked) profile is a no-op refit
    d2 = BackendDescriptor.default().with_profile(prof2)
    assert d2.peak_digest == d.peak_digest
    # the applied marker survives persistence
    prof2.save()
    prof3 = TuningProfile(path)
    assert prof3.pending_fit(d.peak_digest) is None
    assert prof3.info()["calibrated"]


def test_with_profile_auto_refit_opt_out():
    prof = TuningProfile(path=None)
    prof.note_calibration(FIT)
    d = BackendDescriptor.default().with_profile(prof, auto_refit=False)
    assert d.peak_flops_per_s != FIT["peak_flops_per_s"]
    # the fit stays pending for a future auto-refit attach
    assert prof.pending_fit(d.peak_digest) == {
        k: float(v) for k, v in FIT.items()}


def test_note_calibration_ignores_malformed_fit():
    prof = TuningProfile(path=None)
    prof.note_calibration(None)
    prof.note_calibration({"peak_flops_per_s": 1.0})   # missing bytes peak
    assert prof.calibration is None and not prof.dirty


# ---------------------------------------------------------------------------
# calibration fit
# ---------------------------------------------------------------------------

def test_fit_peaks_recovers_synthetic_roofline():
    g_true, pf_true = 100.0, 2.0e13
    rng = np.random.default_rng(0)
    recs = []
    for _ in range(6):
        rec = {}
        for side in ("unfused", "fused"):
            F = float(rng.uniform(1e6, 1e9))
            B = float(rng.uniform(1e5, 1e8))
            rec[side] = {"flops": F, "bytes": B,
                         "measured_s": (F + g_true * B) / pf_true}
        recs.append(rec)
    fit = fit_peaks(recs)
    assert fit is not None and fit["n_records"] == 6
    assert abs(np.log10(fit["gamma"] / g_true)) < 1e-6   # grid hits 100
    assert abs(fit["peak_flops_per_s"] / pf_true - 1) < 1e-6
    assert fit["rms_log_ratio_error"] < 1e-9


def test_fit_peaks_rejects_unusable_records():
    assert fit_peaks([]) is None
    assert fit_peaks([{"unfused": {"flops": 0, "bytes": 1,
                                   "measured_s": 1},
                       "fused": {"flops": 1, "bytes": 1,
                                 "measured_s": 1}}]) is None


# ---------------------------------------------------------------------------
# server restart: warm profile skips tuning at compile time
# ---------------------------------------------------------------------------

def test_server_warmup_persists_and_restart_is_profile_warm(env, tmp_path):
    from repro.core.data import make_queries
    from repro.serve.server import PipelineServer

    path = tmp_path / "serve_profile.json"
    pipe = Retrieve("BM25", k=50) % 10
    srv = PipelineServer(pipe, _backend(env, TuningProfile(path)))
    assert srv.compile_report["tuning"]["probe_measurements"] > 0
    terms = np.zeros((1, 3), np.int32)
    weights = np.ones((1, 3), np.float32)
    info = srv.warmup(make_queries(terms, weights, np.array([0])))
    assert path.exists()
    assert info["tuning_profile"]["entries"] > 0
    # "restart": a fresh server process compiles the same pipeline against
    # the persisted profile with zero probes and zero gate compiles
    srv2 = PipelineServer(pipe, _backend(env, TuningProfile(path)))
    t = srv2.compile_report["tuning"]
    assert t["probe_measurements"] == 0 and t["gate_estimates"] == 0
    assert t["profile_hits"] > 0
    assert srv2.stats()["tuning_profile"]["hits"] > 0
