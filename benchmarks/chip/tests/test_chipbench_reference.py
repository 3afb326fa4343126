"""The plain references equal the program at a small size, and their
bfloat16 control reads far above each cell's limit."""
import sys
from pathlib import Path

import ml_dtypes
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import correctness, datagen, spec, system  # noqa: E402
from rehearse import load_cell  # noqa: E402
from repro.core import (BackendDescriptor, JaxBackend, make_queries,  # noqa: E402
                        run_pipeline)
from repro.core.descriptor import DEFAULT_CAPABILITIES  # noqa: E402
from repro.index import build_index  # noqa: E402
from repro.index.corpus import Corpus  # noqa: E402

SEED = 2 ** 31 + 17
CELLS = ("bm25.title.open", "rerank.title.closed")
#: a TDN formulation: 2-4 title terms of weight 1, then terms of weight 0.5
#: up to 20-32 in all
TDN = {"title_terms": [2, 4], "title_band": [0.005, 0.25],
       "total_terms": [20, 32], "extra_band": [0.001, 0.5],
       "extra_weight": 0.5}


@pytest.fixture(scope="module")
def world():
    cfg = load_cell(CELLS[0]).config
    coll = datagen.collection(system.collection_spec(cfg, True), SEED)
    index = build_index(Corpus(coll.doc_terms, coll.doc_start, coll.vocab),
                        stop_df_fraction=0.1)
    caps = DEFAULT_CAPABILITIES - {"pruned_topk"}
    be = JaxBackend(index, descriptor=BackendDescriptor.default(caps),
                    seed=SEED)
    return coll, be


def _compare(cell, coll, be, n=24):
    """(program's, bfloat16 control's) ``score_gap``."""
    Q = datagen.topics(cell.traffic["query"], n, SEED, coll.rank_to_term)
    R = run_pipeline(system.pipeline(cell.config),
                     make_queries(Q["terms"], Q["weights"], Q["qid"]),
                     backend=be)
    ref_spec = cell.config["reference"]
    ref = spec.reference_module(ref_spec["name"])
    rcoll = ref.Collection(coll.doc_terms, coll.doc_start, coll.vocab, 0.1)
    refs = ref.run(rcoll, Q, ref_spec, seed=SEED)
    k = int(ref_spec["k"])
    prog = [{"docids": d, "scores": s}
            for d, s in zip(np.asarray(R["docids"]), np.asarray(R["scores"]))]
    low = [{"docids": r["docids"], "scores": r["scores"]}
           for r in ref.run(rcoll, Q, ref_spec, seed=SEED,
                            dtype=ml_dtypes.bfloat16)]
    return (correctness.score_gap(prog, refs, k, coll.n_docs),
            correctness.score_gap(low, refs, k, coll.n_docs))


@pytest.mark.parametrize("name", CELLS)
def test_reference_equals_program_and_control_fails(world, name):
    coll, be = world
    cell = load_cell(name)
    gap, control = _compare(cell, coll, be)
    limit = cell.checks["score_gap"]["limit"]
    assert gap < limit / 10, (gap, limit)
    assert control > 3 * limit, (control, limit)


def test_the_seed_renames_terms_but_keeps_every_size():
    cfg = load_cell(CELLS[0]).config
    spec_ = system.collection_spec(cfg, True)
    a = datagen.collection(spec_, 1)
    b = datagen.collection(spec_, 2)
    assert not np.array_equal(a.doc_terms, b.doc_terms)
    assert np.array_equal(np.sort(np.diff(a.doc_start)),
                          np.sort(np.diff(b.doc_start)))
    ca = np.sort(np.bincount(a.doc_terms, minlength=a.vocab))
    cb = np.sort(np.bincount(b.doc_terms, minlength=b.vocab))
    assert np.array_equal(ca, cb)
    again = datagen.collection(spec_, 1)
    assert np.array_equal(a.doc_terms, again.doc_terms)


def test_topics_and_arrivals_are_a_fixed_multiset_in_the_seeds_order():
    q = TDN
    r2t = np.arange(12000, dtype=np.int32)
    a = datagen.topics(q, 60, 1, r2t)
    b = datagen.topics(q, 60, 2, r2t)
    na, nb = (a["terms"] >= 0).sum(1), (b["terms"] >= 0).sum(1)
    assert np.array_equal(np.sort(na), np.sort(nb))
    assert na.min() >= 20 and na.max() <= 32
    for row in a["terms"]:
        row = row[row >= 0]
        assert len(set(row.tolist())) == len(row)
    assert set(np.unique(a["weights"])) <= {0.0, 0.5, 1.0}
    x, y = datagen.arrivals(8.0, 30.0, 1), datagen.arrivals(8.0, 30.0, 2)
    assert len(x) == len(y) == 240
    assert np.allclose(np.sort(np.diff(np.r_[x, 30.0])),
                       np.sort(np.diff(np.r_[y, 30.0])))
    assert x[0] == 0.0 and x[-1] < 30.0


@pytest.mark.parametrize("block", [4, 8, 16])
def test_stratified_arrivals_keep_the_gaps_and_even_out_the_load(block):
    rate, seconds = 2.8, 51.0
    plain = datagen.arrivals(rate, seconds, 5)
    x = datagen.arrivals(rate, seconds, 5, block=block)
    y = datagen.arrivals(rate, seconds, 6, block=block)
    gaps = [np.sort(np.diff(np.r_[a, seconds])) for a in (plain, x, y)]
    assert len(x) == len(y) == len(plain)
    assert np.allclose(gaps[0], gaps[1]) and np.allclose(gaps[1], gaps[2])
    assert not np.allclose(x, y)
    assert np.array_equal(x, datagen.arrivals(rate, seconds, 5, block=block))
    # every run of ``block`` arrivals holds one gap of each stratum
    strata = np.array_split(gaps[0], block)
    order = np.diff(np.r_[x, seconds])
    which = [int(np.searchsorted([s[-1] for s in strata], g - 1e-12))
             for g in order]
    for b in range(len(order) // block):
        assert sorted(which[b * block:(b + 1) * block]) == list(range(block))


def test_dense_only_control_rounds_the_dense_stage_alone(world):
    coll, _ = world
    cell = load_cell("rerank.title.closed")
    ref_spec = cell.config["reference"]
    ref = spec.reference_module(ref_spec["name"])
    rcoll = ref.Collection(coll.doc_terms, coll.doc_start, coll.vocab, 0.1)
    Q = datagen.topics(cell.traffic["query"], 12, SEED, coll.rank_to_term)
    k = int(ref_spec["k"])
    refs = ref.run(rcoll, Q, ref_spec, seed=SEED)
    same = ref.run(rcoll, Q, ref_spec, seed=SEED, dense_dtype=np.float64)
    dense = ref.run(rcoll, Q, ref_spec, seed=SEED,
                    dense_dtype=ml_dtypes.bfloat16)
    low = ref.run(rcoll, Q, ref_spec, seed=SEED, dtype=ml_dtypes.bfloat16)
    gap = lambda xs, **kw: correctness.score_gap(xs, refs, k, coll.n_docs,
                                                 **kw)
    assert gap(same) < 1e-12
    assert 0.0 < gap(dense, divide=False) < 1.0
    assert gap(dense) < gap(low)
    assert gap(dense) > cell.checks["score_gap"]["limit"]
