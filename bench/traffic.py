"""The one traffic generator: turns a mix file (``bench/traffic/<mix>.json``)
and ``--seed`` into request specs.

The offered work of a cell does not depend on the seed.  A mix names a
length distribution for prompts and for outputs (lognormal, median and
sigma, clipped to a deployment's context and ``max_tokens`` limits) and
cuts each into ``strata`` equal-probability strata, each stratum served at
its midpoint quantile.  One *block* holds, for every difficulty class, its
weight times one request per stratum pair (prompt stratum ``k`` with
output stratum ``pairing[k]``).  The seed only draws token ids and orders
requests among equals.

The one mode, ``closed_batches``, serves back-to-back closed batches, each
one block, longest output first (ties ordered by the seed).
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class RequestSpec:
    request_id: str
    arrival_s: float
    prompt: np.ndarray          # (Lp,) int64 token ids
    max_new_tokens: int
    cls: str                    # difficulty class, as the config names it


def strata_lengths(spec: Dict) -> List[int]:
    """Midpoint quantiles of a clipped lognormal, one per stratum."""
    k = int(spec["strata"])
    nd = NormalDist()
    out = []
    for i in range(k):
        z = nd.inv_cdf((i + 0.5) / k)
        v = spec["median"] * np.exp(spec["sigma"] * z)
        out.append(int(np.clip(round(v), spec["min"], spec["max"])))
    return out


def block_shapes(mix: Dict) -> List[tuple]:
    """One block's (prompt_len, output_len, class) triples, in a fixed
    order.  Every seed serves exactly this multiset per block."""
    prompts = strata_lengths(mix["prompt"])
    outputs = strata_lengths(mix["output"])
    pairing = mix["pairing"]
    if sorted(pairing) != list(range(len(outputs))) or \
            len(prompts) != len(outputs):
        raise ValueError("pairing must permute the output strata, and "
                         "prompt and output need the same strata count")
    shapes = []
    for cls, weight in mix["classes"].items():
        for _ in range(int(weight)):
            for k, p in enumerate(prompts):
                shapes.append((p, outputs[pairing[k]], cls))
    return shapes


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63, *stream])


def _prompt(rng, length: int, token_range) -> np.ndarray:
    start, count = token_range
    return rng.integers(start, start + count, size=length).astype(np.int64)


def closed_batch(mix: Dict, classes: Dict, seed: int, index: int,
                 prefix: str = "b") -> List[RequestSpec]:
    """Batch ``index`` of a ``closed_batches`` mix: one block, all due at
    0, longest output first with seed-ordered ties."""
    rng = _rng(seed, 1, index)
    shapes = block_shapes(mix)
    tie = rng.permutation(len(shapes))
    order = sorted(range(len(shapes)), key=lambda i: (-shapes[i][1], tie[i]))
    return [RequestSpec(f"{prefix}{index}.{j}", 0.0,
                        _prompt(rng, shapes[i][0], classes[shapes[i][2]]),
                        shapes[i][1], shapes[i][2])
            for j, i in enumerate(order)]


def row_need(specs) -> int:
    """Longest ``prompt + 2 * budget + 2`` of a set of requests: the
    per-row footprint the serving engine sizes its rows from."""
    return max(len(r.prompt) + 2 * r.max_new_tokens + 2 for r in specs)
