"""The weights' rule and the plain decoder reference against the program,
at a tiny size on the CPU: the rule gives the program's tree, the
reference regenerates any leaf bit for bit, its prompts and logits are
the program's, and its control reads above the fixture cell's limit."""
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import generating as G  # noqa: E402
from chipbench import datagen, spec, system, weights  # noqa: E402
from repro.core.stages import assemble_prompt_fn  # noqa: E402
from repro.index import build_index  # noqa: E402
from repro.index.corpus import Corpus  # noqa: E402
from repro.models import transformer_lm as tlm  # noqa: E402

SEED = 2 ** 36 + 5
REF = spec.reference_module("generate")


def _lm(**overrides):
    config = dict(G.cell().config)
    config["lm"] = dict(config["lm"], rehearse=overrides)
    return config, system.lm_config(config, True)


@pytest.mark.parametrize("overrides", [{}, {"qkv_bias": True,
                                            "tie_embeddings": True}])
def test_the_rule_gives_the_programs_tree_and_the_reference_each_leaf(
        overrides):
    config, cfg = _lm(**overrides)
    params = system.lm_weights(config, cfg, SEED)
    shapes = jax.eval_shape(lambda: tlm.init_params(cfg, jax.random.key(0)))
    assert jax.tree.structure(params) == jax.tree.structure(shapes)
    for p, s in zip(jax.tree.leaves(params), jax.tree.leaves(shapes)):
        assert p.shape == s.shape and p.dtype == s.dtype
    lm = system.lm_spec(cfg)
    want = REF.leaves(lm)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    assert {weights.path_name(k) for k, _ in flat} == set(want)
    for k, leaf in flat:
        name = weights.path_name(k)
        assert (tuple(leaf.shape), np.dtype(leaf.dtype)) == \
            (want[name][0], np.dtype(want[name][1]))
        if weights.stacked(name):
            for l in range(leaf.shape[0]):
                one = weights.layer(SEED, name, l, leaf.shape[1:], leaf.dtype)
                assert np.array_equal(np.asarray(one), np.asarray(leaf[l]))
        else:
            one = weights.whole(SEED, name, leaf.shape, leaf.dtype)
            assert np.array_equal(np.asarray(one), np.asarray(leaf))
    ln = np.asarray(params["layers"]["ln_attn"])
    assert np.all(ln == 1.0)
    wq = np.asarray(params["layers"]["attn"]["wq"], np.float32)
    assert np.std(wq) == pytest.approx(cfg.d_model ** -0.5, rel=0.1)
    other = system.lm_weights(config, cfg, SEED + 1)
    assert not np.array_equal(np.asarray(other["embed"]),
                              np.asarray(params["embed"]))


def test_reference_logits_are_the_programs_in_float32():
    config, cfg = _lm(dtype="float32")
    lm = system.lm_spec(cfg)
    params = system.lm_weights(config, cfg, SEED)
    toks = np.random.default_rng(3).integers(0, cfg.vocab, (2, 24),
                                             dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = tlm.forward(cfg, params, jnp.asarray(toks))
    got = REF.logits(lm, SEED, toks, 5)
    np.testing.assert_allclose(got, np.asarray(want)[:, 5:], rtol=1e-4,
                               atol=1e-4)


def test_reference_prompts_are_the_programs():
    c = G.cell()
    coll = datagen.collection(system.collection_spec(c.config, True), SEED)
    index = build_index(Corpus(coll.doc_terms, coll.doc_start, coll.vocab),
                        stop_df_fraction=0.1)
    rcoll = REF.Collection(coll.doc_terms, coll.doc_start, coll.vocab, 0.1)
    Q = datagen.topics(c.traffic["query"], 6, SEED, coll.rank_to_term)
    params = c.config["reference"]
    rng = np.random.default_rng(4)
    for P in (8, 32, 400):
        one = jax.jit(assemble_prompt_fn(
            index, vocab=512, max_prompt_len=P,
            prompt_docs=int(params["prompt_docs"])))
        for i in range(6):
            docs = rng.integers(0, coll.n_docs, 4).astype(np.int32)
            docs[3 - 2 * (i % 2)] = -1
            got = one(jnp.asarray(Q["terms"][i]), jnp.asarray(Q["weights"][i]),
                      jnp.asarray(docs))
            want = REF.prompt(rcoll, Q["terms"][i], docs,
                              dict(params, max_prompt_len=P), 512)
            assert np.array_equal(np.asarray(got), want), (P, i)


def test_the_control_reads_above_the_fixture_limit():
    s = importlib.util.spec_from_file_location(
        "chipbench_control", Path(G.HERE) / "control.py")
    control = importlib.util.module_from_spec(s)
    s.loader.exec_module(control)
    c = G.cell()
    readings = [control.reading(c, seed, 2.0, True)
                for seed in (SEED, SEED + 1, SEED + 2)]
    limit = c.checks["logit_gap"]["limit"]
    assert min(r["logit_gap"] for r in readings) > limit, readings
