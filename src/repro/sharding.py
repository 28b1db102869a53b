"""Logical-axis sharding rules with divisibility fallback (DESIGN §6).

Every model init returns an ``axes`` pytree mirroring its params, with
tuples of logical axis names per dimension.  ``build_sharding`` maps each
logical axis onto mesh axes by RULES, degrading to replication whenever the
tensor dim does not divide the mesh axis size — this is what lets every
(arch × shape × mesh) combination lower (qwen1.5's 20 heads, whisper's
51865 vocab, kimi's 8 KV heads all simply stay replicated on that dim while
everything else shards).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import (AbstractMesh, Mesh, NamedSharding,
                          PartitionSpec as P)


# Priority-ordered mesh-axis candidates per logical axis.  Each entry is a
# tuple of mesh axes to try to use TOGETHER (e.g. batch over pod AND data).
RULES: Dict[str, Sequence[Tuple[str, ...]]] = {
    "vocab":      (("model",),),
    "heads":      (("model",),),
    "kv_heads":   (("model",),),
    "mlp":        (("model",),),
    "expert_mlp": (tuple(),),            # experts already take the model axis
    "experts":    (("model",),),
    "ssm_inner":  (("model",),),
    "ssm_dk":     (("model",),),
    "embed":      (("pod", "data"), ("data",)),   # FSDP
    "enc_embed":  (tuple(),),
    "batch":      (("pod", "data"), ("data",)),
    "seq":        (tuple(),),            # overridden for long-context decode
    "enc_seq":    (tuple(),),
    "layers":     (tuple(),),
    "layers2":    (tuple(),),
    "head_dim":   (tuple(),),
    "conv":       (tuple(),),
    "ssm_state":  (tuple(),),
}


def _axis_assignment(logical: Optional[str], dim: int, mesh: Mesh,
                     used: set, rules: Dict) -> Optional[Tuple[str, ...]]:
    """Pick mesh axes for one tensor dim, honoring divisibility and not
    reusing a mesh axis already consumed by another dim of this tensor."""
    if logical is None or logical not in rules:
        return None
    for cand in rules[logical]:
        cand = tuple(a for a in cand if a in mesh.axis_names)
        if not cand or any(a in used for a in cand):
            continue
        size = int(np.prod([mesh.shape[a] for a in cand]))
        if size > 1 and dim % size == 0:
            used.update(cand)
            return cand
        # try single axes of a multi-axis candidate (e.g. just "data")
        for a in cand:
            if a not in used and mesh.shape[a] > 1 and dim % mesh.shape[a] == 0:
                used.add(a)
                return (a,)
    return None


# dims are ASSIGNED in this priority order (first match wins the mesh axis);
# "seq" is deliberately last: it only takes an axis nothing else could use
# (context-parallel fallback for unshardable head counts).
_PRIORITY = ("experts", "vocab", "heads", "kv_heads", "mlp", "ssm_inner",
             "ssm_dk", "embed", "batch", "seq")


def spec_for(axes: Tuple[Optional[str], ...], shape: Tuple[int, ...],
             mesh: Mesh, rules: Dict = RULES) -> P:
    used: set = set()
    order = sorted(
        range(len(axes)),
        key=lambda i: _PRIORITY.index(axes[i]) if axes[i] in _PRIORITY
        else len(_PRIORITY))
    assignment: Dict[int, Optional[Tuple[str, ...]]] = {}
    for i in order:
        assignment[i] = _axis_assignment(axes[i], shape[i], mesh, used, rules)
    parts = []
    for i in range(len(axes)):
        a = assignment[i]
        parts.append(a if a is None else (a[0] if len(a) == 1 else a))
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def build_sharding(axes_tree: Any, shape_tree: Any, mesh: Mesh,
                   rules: Dict = RULES) -> Any:
    """axes_tree: pytree of per-dim logical-name tuples (leaves).
    shape_tree: matching pytree of arrays or ShapeDtypeStructs."""
    def one(ax, arr):
        if ax is None:
            return NamedSharding(mesh, P())
        ax = tuple(ax) + (None,) * (len(arr.shape) - len(ax))
        return NamedSharding(mesh, spec_for(ax[:len(arr.shape)], arr.shape,
                                            mesh, rules))
    return jax.tree.map(one, axes_tree, shape_tree,
                        is_leaf=lambda x: x is None or (
                            isinstance(x, tuple)
                            and all(isinstance(e, (str, type(None)))
                                    for e in x)))


def context_mesh() -> Optional[AbstractMesh]:
    """The multi-device mesh set by ``jax.set_mesh`` around the current
    trace (abstract: it is read while tracing), or None when there is no
    mesh or a single device that shards nothing."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty or mesh.size == 1 else mesh


def shape_tree_of(tree: Any) -> Any:
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def with_long_context_rules(rules: Dict = RULES) -> Dict:
    """long_500k (batch=1): shard the KV-cache sequence axis over data
    instead of the unshardable batch axis (context parallelism)."""
    r = dict(rules)
    r["seq"] = (("data", "model"), ("data",), ("model",))
    r["batch"] = (tuple(),)
    return r


def with_decode_rules(rules: Dict = RULES) -> Dict:
    """Serving shapes: the KV cache dominates memory; when kv_heads cannot
    take the model axis (e.g. qwen1.5's 20 heads, granite's MQA kv=1), fall
    back to sharding the cache SEQUENCE axis over whatever mesh axis is
    left (context parallelism — attention reduces over seq, XLA inserts the
    partial-softmax collectives)."""
    r = dict(rules)
    r["seq"] = (("model",),)
    return r
