"""Seeded weights with planted draft agreement, made on the device.

Random weights accept nothing, so agreement is planted, and it is planted
in the embedding and the output head only: every layer keeps random
weights at full width and full cost, laid out by the member's architecture
module (``bench/arch/<arch>.py``), which calls ``make``.  The vocabulary
holds one disjoint token range per difficulty class (the configuration's
``planting``).  For each member, with ``x`` the last hidden state and
``rms`` its RMS:

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


def member_key(seed: int, index: int) -> jax.Array:
    """Per-member PRNG key from a seed of any size."""
    s = np.random.default_rng([int(seed) % 2**63, 7, index])
    return jax.random.PRNGKey(int(s.integers(0, 2**31 - 1)))


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


@partial(jax.jit, static_argnums=(0, 2, 3, 4))
def _make(layers, key, n_items, planting_items, member_items):
    n = dict(n_items)
    planting = _thaw(planting_items)
    member = _thaw(member_items)
    dt = jnp.bfloat16
    k_layers, k_io = jax.random.split(key)
    w = layers(k_layers, n, planting, dt)
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


def make(layers, n: Dict, planting: Dict, member: Dict, key) -> Dict:
    """One member's bf16 weights, made in one jitted call on the default
    device: ``layers(key, n, planting, dtype)`` gives the architecture's
    own leaves, and the embedding, the head (untied) and the final norm
    are planted here.  ``n`` holds the architecture's shape numbers, among
    them ``d`` (hidden width), ``V`` (vocabulary) and ``tied``."""
    return _make(layers, key, tuple(sorted(n.items())), _freeze(planting),
                 _freeze(member))
