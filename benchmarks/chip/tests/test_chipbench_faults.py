"""With the timed path broken underneath, a run's ``correct`` comes out
false: an answer altered where it is produced, and half of each batch
left out (its rows answered with the other half's answers).  A served
batch of one has no half to leave out, so the open cell, whose tiny
rehearsal forms batches of one, is checked for the altered answer."""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from rehearse import run_cell  # noqa: E402
from repro.core.engine import ShardedQueryEngine  # noqa: E402
from repro.index import retrieve as RT  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_traces():
    """A jitted program traced while a fault was patched in keeps the
    fault in JAX's trace cache: drop every trace once the test is done."""
    yield
    jax.clear_caches()


def _alter_first_doc(fn):
    def altered(index, *a, **kw):
        docs, scores = fn(index, *a, **kw)
        return docs.at[0].set((docs[0] + 1) % index.n_docs), scores
    return altered


#: every retrieval entry the cells' stages call, fused or not: the fusion
#: gate prices the candidates through them and may pick either
RETRIEVERS = ("retrieve_topk", "retrieve_topk_fused",
              "retrieve_dense_rerank_fused")


@pytest.mark.parametrize("name", ["bm25.title.open", "rerank.title.closed"])
def test_an_altered_answer_is_not_correct(capsys, monkeypatch, name):
    for target in RETRIEVERS:
        monkeypatch.setattr(RT, target,
                            _alter_first_doc(getattr(RT, target)))
    rc, out, _ = run_cell(capsys, name, 0)
    assert rc == 0 and out["correct"] is False
    assert out["checks"]["score_gap"]["value"] > \
        out["checks"]["score_gap"]["limit"]


@pytest.mark.parametrize("name", ["rerank.title.closed"])
def test_half_of_each_batch_left_out_is_not_correct(capsys, monkeypatch,
                                                    name):
    run_plan = ShardedQueryEngine._run_plan

    def half(self, program, args, plan):
        args = list(args)
        for start, n, _ in plan:
            h = n // 2
            for j, a in enumerate(args):
                a = np.array(a)
                a[start + n - h:start + n] = a[start:start + h]
                args[j] = a
        return run_plan(self, program, tuple(args), plan)

    monkeypatch.setattr(ShardedQueryEngine, "_run_plan", half)
    rc, out, _ = run_cell(capsys, name, 0)
    assert rc == 0 and out["correct"] is False
