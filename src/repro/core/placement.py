"""Placement (paper §4.5, DESIGN §3): per-pool-member mesh slices and
NamedSharding trees — a chain is a *placed* object.

The paper places whole models on single GPUs; the TPU/mesh adaptation
instead gives every pool member a *placement kind* over one shared mesh:

  * ``replicated`` — the member's params/KV live whole on every mesh
    device (the natural choice for small drafts: no collectives on the
    latency-critical draft scan);
  * ``tensor``     — tensor-parallel via ``sharding.py``'s decode rules
    (heads/kv_heads/mlp/vocab over the ``"model"`` axis, with the
    divisibility fallback to replication per dim) — the target's kind;
  * ``data``       — batch rows over the ``"data"`` axis (throughput
    serving of mid-chain verifiers).

``Placement.single()`` (the default everywhere) is the TRIVIAL placement:
no mesh, no shardings, ``qualify`` is the identity — every code path that
threads a trivial placement is byte-identical to the pre-placement code.
An explicit 1x1 mesh exercises the full mesh path (device_put with
NamedShardings, with_sharding_constraint resharding inside the fused
cycle) while remaining mathematically identical to the trivial path —
that A/B is the refactor's bit-exactness anchor
(``tests/test_mesh_serving.py``).

Memory accounting: ``charge``/``discharge`` store the EXACT per-device
byte charges taken when a member's params are placed, so ``discharge``
reverses precisely what ``charge`` added — repeated load/unload cycles
return ``usage`` to zero by construction (the old ``DeviceManager``
recomputed byte counts at free time and clamped at zero, silently
masking any mismatch).

Scheduler interaction: ``qualify`` maps a model name to its
placement-qualified profiling key (``"m7b@tensor:2x4"``), so the
scheduler's T_i model is placement-keyed — the same model on a different
slice is a different cost.
"""
from __future__ import annotations

import contextlib
import re
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..sharding import RULES, build_sharding, with_decode_rules

KINDS = ("replicated", "tensor", "data")


def parse_mesh(spec: str, devices=None) -> Mesh:
    """``"dxm"`` (e.g. ``"2x4"``) -> a ``("data", "model")`` mesh over the
    first d*m local devices.  ``"8"`` means ``"1x8"``."""
    m = re.fullmatch(r"(?:(\d+)x)?(\d+)", spec.strip())
    if not m:
        raise ValueError(f"bad mesh spec {spec!r} (expected 'dxm')")
    d, mm = int(m.group(1) or 1), int(m.group(2))
    devices = list(devices if devices is not None else jax.devices())
    if d * mm > len(devices):
        platform = devices[0].platform if devices else "none"
        hint = ("spawn virtual CPU devices with XLA_FLAGS="
                "--xla_force_host_platform_device_count=N"
                if platform == "cpu" else
                f"this host has {len(devices)} {platform} device(s); "
                "choose a mesh that fits them")
        raise ValueError(
            f"mesh {d}x{mm} needs {d * mm} devices, have {len(devices)} "
            f"({hint})")
    return Mesh(np.array(devices[:d * mm]).reshape(d, mm),
                ("data", "model"))


class Placement:
    """Per-pool-member mesh placement + NamedSharding factory + exact
    per-device memory accounting.  ``mesh=None`` is the trivial placement
    (single implicit device, no shardings — the legacy serving path)."""

    def __init__(self, mesh: Optional[Mesh] = None,
                 default_kind: str = "replicated"):
        self.mesh = mesh
        self.default_kind = default_kind
        self.kinds: Dict[str, str] = {}
        # exact charges taken per member: name -> {device: bytes}
        self._charges: Dict[str, Dict[Any, int]] = {}
        self.usage: Dict[Any, int] = {}

    # ---- constructors --------------------------------------------------
    @classmethod
    def single(cls) -> "Placement":
        """The trivial placement: every threading site degenerates to the
        unmeshed code path (no device_put, qualify = identity)."""
        return cls(mesh=None)

    @classmethod
    def from_spec(cls, spec, devices=None) -> "Placement":
        """Build from a ``"dxm"`` string, an existing Mesh, or a
        Placement (returned as-is)."""
        if isinstance(spec, Placement):
            return spec
        if isinstance(spec, Mesh):
            return cls(mesh=spec)
        return cls(mesh=parse_mesh(str(spec), devices))

    # ---- basic properties ----------------------------------------------
    @property
    def is_trivial(self) -> bool:
        return self.mesh is None

    @property
    def size(self) -> int:
        return 1 if self.mesh is None else self.mesh.size

    def describe(self) -> str:
        if self.mesh is None:
            return "single"
        return "x".join(str(self.mesh.shape[a])
                        for a in self.mesh.axis_names)

    def __repr__(self) -> str:
        return f"Placement({self.describe()}, kinds={self.kinds})"

    # ---- member assignment ---------------------------------------------
    def assign(self, name: str, kind: str) -> None:
        if kind not in KINDS:
            raise ValueError(f"unknown placement kind {kind!r} "
                             f"(expected one of {KINDS})")
        self.kinds[name] = kind

    def kind(self, name: str) -> str:
        return self.kinds.get(name, self.default_kind)

    def auto_assign(self, capability: Dict[str, float],
                    target: str) -> None:
        """The paper-shaped default: the TARGET is tensor-parallel across
        the mesh (its verify pass dominates FLOPs and memory), every
        draft/intermediate member is replicated (the draft scan is
        latency-critical and small — no collectives on it)."""
        for n in capability:
            self.assign(n, "tensor" if n == target else "replicated")

    # ---- profiling keys --------------------------------------------------
    def qualify(self, name: str) -> str:
        """Placement-qualified profiling/scheduler key.  Identity on the
        trivial placement so every existing EMA key is unchanged."""
        if self.mesh is None:
            return name
        return f"{name}@{self.kind(name)}:{self.describe()}"

    # ---- sharding factories ---------------------------------------------
    def rules_for(self, name: str, cfg: Any = None) -> Dict:
        kind = self.kind(name)
        if kind == "replicated":
            return {}                     # no rule matches -> all P()
        if kind == "data":
            return {"batch": RULES["batch"], "embed": RULES["embed"]}
        r = with_decode_rules(RULES)      # tensor
        # Param q/k/v projections store a FUSED (heads x head_dim) output
        # dim under the "heads"/"kv_heads" label.  Sharding it is only
        # layout-equivalent to head-parallelism when every shard holds
        # WHOLE heads; a partial-head shard splits head_dim, and RoPE's
        # rotate-half then crosses shard boundaries (miscompiled by the
        # CPU SPMD partitioner, and the wrong layout for the attention
        # kernels regardless).  The divisibility fallback cannot see the
        # fusion — the fused dim divides even when the head count does
        # not — so gate on the member's config here.  (State KV caches
        # carry kv_heads UNFUSED, where plain divisibility suffices.)
        if cfg is not None and self.mesh is not None:
            msize = int(dict(self.mesh.shape).get("model", 1))
            if msize > 1:
                nh = getattr(cfg, "num_heads", 0)
                nkv = getattr(cfg, "num_kv_heads", 0)
                if nh and nh % msize:
                    r["heads"] = (tuple(),)
                if nkv and nkv % msize:
                    r["kv_heads"] = (tuple(),)
        return r

    def param_sharding(self, name: str, axes_tree: Any, tree: Any,
                       cfg: Any = None) -> Optional[Any]:
        """NamedSharding tree for a member's params (None when trivial)."""
        if self.mesh is None:
            return None
        return build_sharding(axes_tree, tree, self.mesh,
                              self.rules_for(name, cfg))

    def state_sharding(self, name: str, state_axes: Any,
                       state: Any) -> Optional[Any]:
        """NamedSharding tree for a member's KV/session state.  The state
        axes pytree mirrors the state exactly (kv_cache.make_state /
        paged_state_axes), so the same rule engine shards the KV block
        pools that shards the params."""
        if self.mesh is None:
            return None
        return build_sharding(state_axes, state, self.mesh,
                              self.rules_for(name))

    def replicated_sharding(self) -> Optional[NamedSharding]:
        """Sharding for the shared session buffers (seq/seq_len/active…):
        replicated — every member's slice reads them."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P())

    def mesh_context(self):
        """Trace-time mesh scope.  The Executor traces every program
        inside this context; the Pallas kernel wrappers (kernels/ops.py)
        key their defensive operand replication off the active mesh —
        GSPMD cannot partition an opaque kernel correctly, so its inputs
        must be gathered whole.  nullcontext (no lowering change at all)
        on the trivial placement AND on single-device meshes: a 1-device
        mesh cannot shard anything, so the 1x1 anchor lowers through the
        byte-identical unmeshed kernel path."""
        if self.mesh is None or self.mesh.size == 1:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def reshard_between_levels(self) -> Optional[Callable[[Any], Any]]:
        """The fused-cycle level-boundary reshard: candidate tokens/probs
        produced under the draft's placement are constrained back to
        replicated before the next level's verify consumes them — the
        slab moves DEVICE-to-device (an XLA collective inside the one
        program), never through the host.  None on the trivial placement
        (byte-identical lowering to the unmeshed program)."""
        rep = self.replicated_sharding()
        if rep is None:
            return None

        def reshard(x):
            return jax.lax.with_sharding_constraint(x, rep)

        return reshard

    # ---- memory accounting ----------------------------------------------
    def _leaf_bytes(self, leaf, sharding) -> Tuple[Tuple[Any, int], ...]:
        if self.mesh is None or sharding is None:
            dev = jax.devices()[0]
            return ((dev, int(leaf.size) * leaf.dtype.itemsize),)
        shp = sharding.shard_shape(tuple(leaf.shape))
        nb = int(np.prod(shp, dtype=np.int64)) * leaf.dtype.itemsize
        return tuple((d, int(nb)) for d in self.mesh.devices.flat)

    def charge(self, name: str, tree: Any,
               shardings: Optional[Any] = None) -> Dict[Any, int]:
        """Record the exact per-device bytes ``tree`` occupies under
        ``shardings`` and add them to ``usage``.  Re-charging a name
        first discharges the stale entry (idempotent placement)."""
        if name in self._charges:
            self.discharge(name)
        leaves = jax.tree.leaves(tree)
        slvs = (jax.tree.leaves(
                    shardings,
                    is_leaf=lambda s: isinstance(s, NamedSharding))
                if shardings is not None else [None] * len(leaves))
        charges: Dict[Any, int] = {}
        for leaf, s in zip(leaves, slvs):
            for dev, nb in self._leaf_bytes(leaf, s):
                charges[dev] = charges.get(dev, 0) + nb
        self._charges[name] = charges
        for dev, nb in charges.items():
            self.usage[dev] = self.usage.get(dev, 0) + nb
        return charges

    def discharge(self, name: str) -> None:
        """Reverse EXACTLY what ``charge(name, …)`` added (no recompute,
        no clamping — a mismatch would surface as nonzero usage in the
        load/unload invariant test instead of being masked)."""
        for dev, nb in self._charges.pop(name, {}).items():
            self.usage[dev] = self.usage.get(dev, 0) - nb

    def total_usage(self) -> int:
        return sum(self.usage.values())
