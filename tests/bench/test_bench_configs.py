"""Each configuration file holds its published model: parameter counts by
``jax.eval_shape`` (nothing is allocated), the program's parameter tree,
and the planted agreement at a tiny size."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchfix import ROOT, tiny_config
from bench import run as br
from bench import weights as wt
from bench.reference import qwen2_dense as ref

CONFIGS = {p.stem: json.loads(p.read_text())
           for p in sorted((ROOT / "bench/configs").glob("*.json"))}
MEMBERS = {m["name"]: (m, c["planting"]) for c in CONFIGS.values()
           for m in c["members"]}
# published parameter counts (Qwen1.5 model cards: 0.5B, 1.8B, 4B), as
# the architecture gives them from each config.json
PUBLISHED = {"qwen1.5-0.5b": 0.4639e9, "qwen1.5-1.8b": 1.8366e9,
             "qwen1.5-4b": 3.9502e9}


def _abstract(member, planting):
    return jax.eval_shape(
        lambda k: wt.make_weights(member["config"], planting,
                                  member["planted"], k),
        jax.random.PRNGKey(0))


@pytest.mark.parametrize("name", sorted(MEMBERS))
def test_parameter_count_is_the_published_models(name):
    member, planting = MEMBERS[name]
    shapes = _abstract(member, planting)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == pytest.approx(PUBLISHED[name], rel=2e-3)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(shapes))


@pytest.mark.parametrize("name", sorted(MEMBERS))
def test_weights_match_the_programs_parameter_tree(name):
    from repro.models.model import LanguageModel
    member, planting = MEMBERS[name]
    ours = wt.to_program(_abstract(member, planting))
    theirs = LanguageModel(br.program_config(member)).abstract_params()
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_configs_state_published_values():
    for c in CONFIGS.values():
        assert c["reduced"] == []
        for m in c["members"]:
            hf = m["config"]
            assert hf["vocab_size"] == 151936
            assert hf["hidden_size"] // hf["num_attention_heads"] in (64, 128)
    four = MEMBERS["qwen1.5-4b"][0]["config"]
    assert (four["rope_theta"], four["rms_norm_eps"]) == (5000000.0, 1e-06)
    assert MEMBERS["qwen1.5-0.5b"][0]["config"]["tie_word_embeddings"]


def _greedy_target(w, hf, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        h = ref.hidden(w, hf, jnp.asarray([toks], jnp.int32))
        lg = ref.logits(w["head"], h[0, -1:], False)
        toks.append(int(jnp.argmax(lg[0])))
    return toks


def test_planted_agreement_is_a_property_of_the_class():
    """Draft and target agree on every easy token, on no medium or hard
    one; the target stays inside each prompt's class and repeats the last
    token only where it is planted to."""
    cfg = tiny_config()
    draft, target = cfg["members"]
    wd = wt.make_weights(draft["config"], cfg["planting"], draft["planted"],
                         wt.member_key(11, 0))
    wtg = wt.make_weights(target["config"], cfg["planting"],
                          target["planted"], wt.member_key(11, 1))
    rng = np.random.default_rng(0)
    for cls, (lo, n) in cfg["planting"]["classes"].items():
        prompt = rng.integers(lo, lo + n, 10)
        seq = _greedy_target(wtg, target["config"], prompt, 6)
        out = np.asarray(seq[10:])
        assert ((lo <= out) & (out < lo + n)).all()
        assert (out == prompt[-1]).all() == (cls != "hard")
        h = ref.hidden(wd, draft["config"], jnp.asarray([seq], jnp.int32))
        pred = np.asarray(jnp.argmax(ref.logits(wd["embed"], h[0], True),
                                     -1))[9:-1]
        agree = float(np.mean(pred == out))
        assert agree == (1.0 if cls == "easy" else 0.0), (cls, agree)
