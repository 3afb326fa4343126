"""With the generating leaf broken underneath, the fixture generating
cell's ``correct`` comes out false: one layer's weights perturbed after
they are drawn, and a token altered where it is produced."""
import json
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import generating as G  # noqa: E402
from repro.core import JaxBackend  # noqa: E402
from repro.serve import batching  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_traces():
    """A jitted program traced while a fault was patched in keeps the
    fault in JAX's trace cache: drop every trace once the test is done."""
    yield
    jax.clear_caches()


def _halve_first_mlp(monkeypatch):
    register = JaxBackend.register_lm

    def perturbed(self, name, cfg, params=None, **kw):
        mlp = dict(params["layers"]["mlp"])
        mlp["w_down"] = mlp["w_down"].at[0].multiply(0.5)
        layers = dict(params["layers"], mlp=mlp)
        return register(self, name, cfg, params=dict(params, layers=layers),
                        **kw)

    monkeypatch.setattr(JaxBackend, "register_lm", perturbed)


def _alter_first_token(monkeypatch):
    prefill = batching.ContinuousBatcher.prefill_request

    def altered(self, req):
        s = prefill(self, req)
        req.generated[0] = (req.generated[0] + 1) % self.cfg.vocab
        return s

    monkeypatch.setattr(batching.ContinuousBatcher, "prefill_request",
                        altered)


@pytest.mark.parametrize("fault", [_halve_first_mlp, _alter_first_token])
def test_a_broken_generating_leaf_is_not_correct(capsys, monkeypatch, fault):
    fault(monkeypatch)
    rc, out, _ = G.run_cell(capsys, G.cell(), 0, seconds=1.0)
    assert rc == 0
    res = json.loads(out[-1])
    assert res["correct"] is False
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
    assert res["checks"]["score_gap"]["value"] <= \
        res["checks"]["score_gap"]["limit"]
