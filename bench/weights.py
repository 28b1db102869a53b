"""Seeded weights with planted draft agreement, made on the device.

Random weights accept nothing, so agreement is planted, and it is planted
in the embedding and the output head only: every layer keeps dense random
weights at full width and full cost.  The vocabulary holds one disjoint
token range per difficulty class (the configuration's ``planting``).  For
each member, with ``x`` the last hidden state and ``rms`` its RMS:

* every class token ``v`` of class ``c`` embeds as
  ``kappa_c + beta + s_c * a_v``: a class direction, an anchor shared by
  all classes, and a random identity code ``a_v`` scaled by the member's
  ``code_scale`` for that class;
* an untied head column is
  ``gain * (khat_c - bhat) + copy_gain * a_v / sqrt(d) + noise``, and a
  column outside every class is ``-gain * bhat + noise``.  In a class-``c``
  context the class's logits sit near 0 and every other logit near
  ``-gain * sqrt(d) / rms``: the stream stays in its class, and the logits
  that compete are small, so bf16 rounds them finely;
* ``copy_gain`` on a class makes the member repeat the last token there
  (its code outweighs the noise).  Without it the class's logits are the
  noise term read through all the layers: a random, context-dependent
  competition among thousands of near-equal logits, which is where a
  lower precision shows;
* a tied member's head is its embedding, so it repeats the last token
  wherever its code scale is large and does not where it is small.

So a class is easy where the draft repeats the token as the target does,
and hard where the target does not repeat.  Noise columns are made
orthogonal to the class directions and the anchor, so no token of a class
gets a fixed advantage.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# per-layer leaves of the benchmark's own layout, stacked over layers
LAYER_LEAVES = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
                "ln2", "wg", "wu", "wd")


def dims(hf: Dict) -> Dict[str, int]:
    """Shape numbers of one member, from its published config keys."""
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    return dict(d=d, L=hf["num_hidden_layers"], H=h,
                Hkv=hf["num_key_value_heads"], hd=d // h,
                ff=hf["intermediate_size"], V=hf["vocab_size"],
                tied=bool(hf["tie_word_embeddings"]))


def member_key(seed: int, index: int) -> jax.Array:
    """Per-member PRNG key from a seed of any size."""
    s = np.random.default_rng([int(seed) % 2**63, 7, index])
    return jax.random.PRNGKey(int(s.integers(0, 2**31 - 1)))


def _layers(key, n: Dict[str, int], p: Dict, dt):
    L, d, H, Hkv, hd, ff = (n[k] for k in ("L", "d", "H", "Hkv", "hd", "ff"))
    shapes = {
        "ln1": (L, d), "ln2": (L, d),
        "wq": (L, d, H * hd), "wk": (L, d, Hkv * hd), "wv": (L, d, Hkv * hd),
        "bq": (L, H * hd), "bk": (L, Hkv * hd), "bv": (L, Hkv * hd),
        "wo": (L, H * hd, d), "wg": (L, d, ff), "wu": (L, d, ff),
        "wd": (L, ff, d),
    }
    keys = dict(zip(LAYER_LEAVES, jax.random.split(key, len(LAYER_LEAVES))))
    out = {}
    for name, shape in shapes.items():
        z = jax.random.normal(keys[name], shape, dt)
        if name.startswith("ln"):
            out[name] = (1.0 + p["norm_jitter"] * z).astype(dt)
        elif name.startswith("b"):
            out[name] = (p["bias_scale"] * z).astype(dt)
        else:                       # fan-in scaled, as a fresh init
            out[name] = (z / math.sqrt(shape[1])).astype(dt)
    return out


def _embed_and_head(key, n: Dict[str, int], planting: Dict, member: Dict,
                    dt):
    d, V = n["d"], n["V"]
    classes = planting["classes"]
    kc, kb, kr, kh, ka = jax.random.split(key, 5)
    kap = jax.random.normal(kc, (len(classes), d)) * planting["class_norm"]
    beta = jax.random.normal(kb, (d,)) * planting["anchor_norm"]
    emb = jax.random.normal(kr, (V, d)) * planting["rest_norm"]
    codes = {}
    for i, (cls, (start, count)) in enumerate(classes.items()):
        a = jax.random.normal(jax.random.fold_in(ka, i), (count, d))
        codes[cls] = a
        s = member["code_scale"][cls]
        emb = emb.at[start:start + count].set(kap[i] + beta + s * a)
    if n["tied"]:
        return emb.astype(dt), None
    # noise columns, orthogonal to every class direction and the anchor
    basis, _ = jnp.linalg.qr(jnp.concatenate([kap, beta[None]]).T)
    rho = jax.random.normal(kh, (d, V)) * (planting["head_noise"]
                                           / math.sqrt(d))
    rho = rho - basis @ (basis.T @ rho)
    gain = planting["class_gain"]
    bhat = beta / jnp.linalg.norm(beta)
    head = rho - gain * bhat[:, None]
    for i, (cls, (start, count)) in enumerate(classes.items()):
        col = gain * kap[i] / jnp.linalg.norm(kap[i])
        if cls in member["copies"]:
            col = col[None, :] + (planting["copy_gain"] / math.sqrt(d)
                                  * codes[cls])
        head = head.at[:, start:start + count].add(
            jnp.broadcast_to(col, (count, d)).T)
    return emb.astype(dt), head.astype(dt)


@partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, n_items, planting_items, member_items):
    n = dict(n_items)
    planting = _thaw(planting_items)
    member = _thaw(member_items)
    dt = jnp.bfloat16
    k_layers, k_io = jax.random.split(key)
    w = _layers(k_layers, n, planting, dt)
    w["embed"], head = _embed_and_head(k_io, n, planting, member, dt)
    if head is not None:
        w["head"] = head
    w["final_norm"] = jnp.ones((n["d"],), dt)
    return w


def _freeze(x):
    """Hashable form of a JSON value (jit's static arguments)."""
    if isinstance(x, dict):
        return ("__dict__",) + tuple((k, _freeze(v)) for k, v in x.items())
    if isinstance(x, list):
        return ("__list__",) + tuple(_freeze(v) for v in x)
    return x


def _thaw(x):
    if isinstance(x, tuple) and x and x[0] == "__dict__":
        return {k: _thaw(v) for k, v in x[1:]}
    if isinstance(x, tuple) and x and x[0] == "__list__":
        return [_thaw(v) for v in x[1:]]
    return x


def make_weights(hf: Dict, planting: Dict, member: Dict, key) -> Dict:
    """One member's bf16 weights in the benchmark's own layout, made in one
    jitted call on the default device."""
    return _make(key, tuple(sorted(dims(hf).items())), _freeze(planting),
                 _freeze(member))


def to_program(w: Dict) -> Dict:
    """The same arrays, nested as the serving program's parameter tree
    (``repro.models.transformer``); nothing is copied."""
    tree = {
        "embed": w["embed"],
        "blocks": {
            "ln1": {"scale": w["ln1"]}, "ln2": {"scale": w["ln2"]},
            "attn": {"q": {"w": w["wq"], "b": w["bq"]},
                     "k": {"w": w["wk"], "b": w["bk"]},
                     "v": {"w": w["wv"], "b": w["bv"]},
                     "o": {"w": w["wo"]}},
            "mlp": {"gate": {"w": w["wg"]}, "up": {"w": w["wu"]},
                    "down": {"w": w["wd"]}},
        },
        "final_norm": {"scale": w["final_norm"]},
    }
    if "head" in w:
        tree["lm_head"] = {"w": w["head"]}
    return tree
