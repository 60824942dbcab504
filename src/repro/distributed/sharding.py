"""Logical-axis → mesh-axis sharding rules (the GCP kernel layer for LMs).

Every param/cache leaf carries logical axis names (models/common.py).
``Rules`` maps those names onto mesh axes with conflict resolution (a
mesh axis is used at most once per leaf, first logical dim wins), giving
per-leaf ``PartitionSpec``s for pjit.

Parallelism expressed purely through these rules:
  TP      heads/kv_heads/ff/experts/inner/vocab → "model"
  DP      batch → ("pod", "data")                  (pod optional)
  ZeRO-1  optimizer moments inherit param axes + "embed" → "data"
  ZeRO-3  params themselves also shard "embed" → "data"
  SP      cache/activation "seq" → ("pod","data") when batch can't use them
  EP      experts → "model"
"""

from __future__ import annotations

import dataclasses
import math

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import ParamSpec, logical_axes


@dataclasses.dataclass(frozen=True)
class Rules:
    """logical name → tuple of candidate mesh axes (in priority order)."""

    table: dict

    def spec_for(
        self, logical: tuple, mesh_axes: dict, shape: tuple | None = None
    ) -> P:
        """Resolve one leaf. A mesh axis is used at most once per leaf, and
        (when ``shape`` is given) only if it divides the dim — non-dividing
        axes are dropped so every sharding is exact, never padded."""
        used: set[str] = set()
        dims = []
        for i, name in enumerate(logical):
            axes = self.table.get(name) if name else None
            if not axes:
                dims.append(None)
                continue
            picked = []
            rem = shape[i] if shape is not None else None
            for a in axes:
                if a not in mesh_axes or a in used:
                    continue
                if rem is not None and rem % mesh_axes[a] != 0:
                    continue
                picked.append(a)
                used.add(a)
                if rem is not None:
                    rem //= mesh_axes[a]
            if not picked:
                dims.append(None)
            elif len(picked) == 1:
                dims.append(picked[0])
            else:
                dims.append(tuple(picked))
        return P(*dims)


def param_rules(zero: int = 1, layout: str = "tp") -> Rules:
    """layout="tp": tensor-parallel over "model" (+ ZeRO over "data").
    layout="dp": no tensor parallelism — params fully sharded over
    (data, model) jointly (FSDP/ZeRO-3 style); right for models whose
    per-layer dims are small relative to the mesh (smollm, mamba2-130m),
    where TP only manufactures collectives."""
    if layout == "dp":
        flat = ("data", "model")
        t = {
            "vocab": flat,
            "heads": flat,
            "kv_heads": flat,
            "ff": flat,
            "experts": flat,
            "inner": flat,
            "embed": ("model", "data"),
            "seq": None,
            "layers": None,
            "conv": None,
            "batch": None,
        }
        return Rules(t)
    t = {
        "vocab": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),
        "ff": ("model",),
        "experts": ("model",),
        "inner": ("model",),
        "embed": ("data",) if zero >= 3 else None,
        "seq": None,
        "layers": None,
        "conv": None,
        "batch": None,
    }
    return Rules(t)


def opt_rules(zero: int = 1, layout: str = "tp") -> Rules:
    """Optimizer moments: always at least ZeRO-1 (shard embed over data)."""
    if layout == "dp":
        return param_rules(zero=zero, layout="dp")
    t = dict(param_rules(zero=3 if zero >= 1 else 0).table)
    return Rules(t)


def activation_rules(batch: int, mesh: Mesh, layout: str = "tp") -> Rules:
    """Input batches: shard the batch dim over whichever of (pod, data)
    divide it; under layout="dp" the model axis joins data parallelism."""
    axes = dict(mesh.shape)
    cands = ("pod", "data", "model") if layout == "dp" else ("pod", "data")
    batch_axes = []
    rem = batch
    for cand in cands:
        if cand in axes and rem % axes[cand] == 0:
            batch_axes.append(cand)
            rem //= axes[cand]
    t = {
        "batch": tuple(batch_axes) or None,
        "seq": None,
        "embed": None,
        "layers": None,
    }
    return Rules(t)


def cache_rules(batch: int, mesh: Mesh) -> Rules:
    """KV/SSM caches: batch over (pod,data) when divisible; the sequence
    axis shards over "model" (SP — even for few-KV-head archs where head
    sharding would pad); leftover DP axes reinforce seq when the batch
    can't use them (long_500k batch=1). Mamba state heads shard over
    "model" when divisible (jamba 128 ✓, mamba2-130m 24 ✗→replicated)."""
    axes = dict(mesh.shape)
    batch_axes = []
    rem = batch
    for cand in ("pod", "data"):
        if cand in axes and rem % axes[cand] == 0:
            batch_axes.append(cand)
            rem //= axes[cand]
    leftover = tuple(a for a in ("pod", "data") if a in axes and a not in batch_axes)
    t = {
        "batch": tuple(batch_axes) or None,
        "seq": ("model",) + leftover,
        "heads": ("model",),
        "kv_heads": None,
        "inner": ("model",),
        "embed": None,
        "layers": None,
        "conv": None,
    }
    return Rules(t)


def cache_rules_dp(batch: int, mesh: Mesh) -> Rules:
    """DP layout caches: batch takes every axis it divides (incl. model);
    the sequence axis soaks up the leftovers."""
    axes = dict(mesh.shape)
    batch_axes = []
    rem = batch
    for cand in ("pod", "data", "model"):
        if cand in axes and rem % axes[cand] == 0:
            batch_axes.append(cand)
            rem //= axes[cand]
    leftover = tuple(
        a for a in ("model", "pod", "data") if a in axes and a not in batch_axes
    )
    t = {
        "batch": tuple(batch_axes) or None,
        "seq": leftover or None,
        "heads": None,
        "kv_heads": None,
        "inner": None,
        "embed": None,
        "layers": None,
        "conv": None,
    }
    return Rules(t)


# ---------------------------------------------------------------------------
def tree_specs(schema: dict, rules: Rules, mesh: Mesh) -> dict:
    axes = dict(mesh.shape)
    return jax.tree_util.tree_map(
        lambda s: rules.spec_for(s.logical, axes, s.shape),
        schema,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def tree_shardings(schema: dict, rules: Rules, mesh: Mesh) -> dict:
    return jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, p),
        tree_specs(schema, rules, mesh),
        is_leaf=lambda x: isinstance(x, P),
    )


def validate_divisibility(schema: dict, specs: dict, mesh: Mesh) -> list[str]:
    """Return human-readable problems where dims don't divide mesh axes."""
    axes = dict(mesh.shape)
    problems = []

    def check(path, s: ParamSpec, spec: P):
        for dim, assignment in zip(s.shape, tuple(spec) + (None,) * 8):
            if assignment is None:
                continue
            names = assignment if isinstance(assignment, tuple) else (assignment,)
            k = math.prod(axes[a] for a in names)
            if dim % k != 0:
                problems.append(f"{path}: dim {dim} % {k} ({names}) != 0")

    def walk(path, sch, sp):
        if isinstance(sch, ParamSpec):
            check(path, sch, sp)
            return
        if isinstance(sch, dict):
            for k in sch:
                walk(f"{path}/{k}", sch[k], sp[k])
        elif isinstance(sch, (list, tuple)):
            for i, (a, b) in enumerate(zip(sch, sp)):
                walk(f"{path}[{i}]", a, b)

    walk("", schema, specs)
    return problems
