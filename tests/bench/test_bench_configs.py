"""Each configuration file holds its published model: parameter counts by
``jax.eval_shape`` (nothing is allocated), the program's parameter tree,
each from the member's architecture module, the keys cut from the source,
and the planted agreement at a tiny size."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchfix import ROOT, tiny_config
from bench import run as br
from bench import weights as wt

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {p.stem: json.loads(p.read_text())
           for p in sorted((ROOT / "bench/configs").glob("*.json"))}
MEMBERS = {m["name"]: (m, c["planting"]) for c in CONFIGS.values()
           for m in c["members"]}
# published parameter counts (Qwen1.5 model cards: 0.5B, 1.8B, 4B), as
# the architecture gives them from each config.json
PUBLISHED = {"qwen1.5-0.5b": 0.4639e9, "qwen1.5-1.8b": 1.8366e9,
             "qwen1.5-4b": 3.9502e9}


def _arch(member):
    return br.arch_module(member["arch"])


def _abstract(member, planting):
    return jax.eval_shape(
        lambda k: _arch(member).make_weights(member["config"], planting,
                                             member["planted"], k),
        jax.random.PRNGKey(0))


@pytest.mark.parametrize("name", sorted(MEMBERS))
def test_parameter_count_is_the_published_models(name):
    """The layout holds what the architecture counts for the config as
    run; the config with its published values restored counts as the
    model card says."""
    member, planting = MEMBERS[name]
    arch = _arch(member)
    shapes = _abstract(member, planting)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == arch.published_params(member["config"])
    if name in PUBLISHED:
        published = dict(member["config"], **member.get("published", {}))
        assert arch.published_params(published) == pytest.approx(
            PUBLISHED[name], rel=2e-3)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(shapes))


@pytest.mark.parametrize("name", sorted(MEMBERS))
def test_weights_match_the_programs_parameter_tree(name):
    from repro.models.model import LanguageModel
    member, planting = MEMBERS[name]
    arch = _arch(member)
    ours = arch.to_program(_abstract(member, planting))
    theirs = LanguageModel(arch.program_config(member)).abstract_params()
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_configs_state_published_values():
    """Every key cut from the source is named in ``reduced``, here and in
    the manifest, and a member that runs it states its published value
    under ``published``; numbers that a file repeats at its top level (a
    catalog entry's form) are its target's as run."""
    entries = {e["file"]: e for e in MANIFEST["configs"]}
    for stem, c in CONFIGS.items():
        entry = entries.get(f"bench/configs/{stem}.json")
        if entry is not None:
            assert entry["reduced"] == c["reduced"]
        for key in c["reduced"]:
            cut = [m for m in c["members"] if key in m.get("published", {})]
            assert cut, f"{stem}: no member states {key!r} as published"
            for m in cut:
                assert key in m["config"]
                assert m["config"][key] != m["published"][key]
        for m in c["members"]:
            assert set(m.get("published", {})) <= set(c["reduced"])
        target = c["members"][-1]["config"]
        for key, v in c.items():
            if isinstance(v, (int, float)) and key in target:
                assert v == target[key], (stem, key)
    # Qwen1.5: one 151,936-id tokenizer, head sizes 64 and 128, as published
    qwen = CONFIGS["qwen1.5-0.5b-1.8b-4b"]
    assert qwen["reduced"] == []
    for m in qwen["members"]:
        hf = m["config"]
        assert hf["vocab_size"] == 151936
        assert hf["hidden_size"] // hf["num_attention_heads"] in (64, 128)
    four = MEMBERS["qwen1.5-4b"][0]["config"]
    assert (four["rope_theta"], four["rms_norm_eps"]) == (5000000.0, 1e-06)
    assert MEMBERS["qwen1.5-0.5b"][0]["config"]["tie_word_embeddings"]


def _greedy_target(ref, w, hf, prompt, n):
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
    wd = _arch(draft).make_weights(draft["config"], cfg["planting"],
                                   draft["planted"], wt.member_key(11, 0))
    wtg = _arch(target).make_weights(target["config"], cfg["planting"],
                                     target["planted"], wt.member_key(11, 1))
    ref, ref_d = br.reference_module(cfg), _arch(draft).REFERENCE
    rng = np.random.default_rng(0)
    for cls, (lo, n) in cfg["planting"]["classes"].items():
        prompt = rng.integers(lo, lo + n, 10)
        seq = _greedy_target(ref, wtg, target["config"], prompt, 6)
        out = np.asarray(seq[10:])
        assert ((lo <= out) & (out < lo + n)).all()
        assert (out == prompt[-1]).all() == (cls != "hard")
        h = ref_d.hidden(wd, draft["config"], jnp.asarray([seq], jnp.int32))
        pred = np.asarray(jnp.argmax(ref_d.logits(wd["embed"], h[0], True),
                                     -1))[9:-1]
        agree = float(np.mean(pred == out))
        assert agree == (1.0 if cls == "easy" else 0.0), (cls, agree)
