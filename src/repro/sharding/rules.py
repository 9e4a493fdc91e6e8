"""Logical-axis sharding rules.

Every parameter / activation in the model stack is annotated with *logical* axis
names (strings). A rule table maps logical names to mesh axes. This is the single
point of control for the distribution strategy, and the knob the §Perf hillclimbs
turn (e.g. moving FSDP from `data` to `(pod, data)`, or turning FSDP off for
serving).

Logical axes used by the model stack:

  batch     activation batch dim                     -> data (+ pod)
  fsdp      weight "long" dim, gathered per-use      -> data (FSDP / ZeRO-3)
  tp        weight sharded dim kept sharded in use   -> model (tensor parallel)
  expert    MoE expert dim                           -> data when divisible
  seq_kv    decode-time KV-cache sequence dim        -> model (flash-decode shards)
  seq       training-time sequence dim               -> None (or model for CP)
  vocab     logits vocabulary dim                    -> model
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Logical = Sequence[Any]  # tuple of logical axis names (str | None), one per dim


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """Mapping logical axis -> tuple of mesh axes (or () for replicated)."""

    table: Mapping[str, tuple]

    def get(self, name) -> tuple:
        if name is None:
            return ()
        got = self.table.get(name, ())
        if got is None:
            return ()
        if isinstance(got, str):
            return (got,)
        return tuple(got)

    def replace(self, **kw) -> "AxisRules":
        t = dict(self.table)
        for k, v in kw.items():
            t[k] = v
        return AxisRules(t)


DEFAULT_RULES = AxisRules(
    {
        "batch": ("data",),
        "fsdp": ("data",),
        "tp": ("model",),
        "expert": ("data",),
        "seq_kv": ("model",),
        "seq": (),
        "vocab": ("model",),
    }
)

# Multi-pod: batch is data-parallel across pods as well; FSDP stays intra-pod
# (cross-pod weight gathers over DCI would dominate; see DESIGN.md §4).
MULTIPOD_RULES = DEFAULT_RULES.replace(batch=("pod", "data"))

# Serving variant for small models: keep weights tensor-sharded only (no FSDP
# all-gathers per token). §Perf iteration uses this.
SERVE_TP_ONLY_RULES = DEFAULT_RULES.replace(fsdp=(), expert=())
REPLICATED_RULES = AxisRules({})


def _mesh_axis_size(mesh: Mesh | None, axes: tuple) -> int:
    if mesh is None:
        return int(np.prod([1]))
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def logical_to_spec(
    logical: Logical,
    rules: AxisRules,
    mesh: Mesh | None = None,
    shape: Sequence[int] | None = None,
) -> P:
    """Resolve logical axis names to a PartitionSpec.

    If `mesh` and `shape` are given, any dim whose size does not divide evenly by
    the product of its mesh axes is left replicated (e.g. grok's 8 experts on a
    16-way data axis). This keeps every (arch x mesh) combination lowerable
    without per-arch special cases.
    """
    spec = []
    used: set = set()
    for i, name in enumerate(logical):
        axes = tuple(a for a in rules.get(name) if mesh is None or a in mesh.shape)
        axes = tuple(a for a in axes if a not in used)
        if axes and mesh is not None and shape is not None:
            if shape[i] % _mesh_axis_size(mesh, axes) != 0:
                # try a prefix of the axes that still divides
                while axes and shape[i] % _mesh_axis_size(mesh, axes) != 0:
                    axes = axes[:-1]
        if not axes:
            spec.append(None)
        elif len(axes) == 1:
            spec.append(axes[0])
            used.add(axes[0])
        else:
            spec.append(tuple(axes))
            used.update(axes)
    return P(*spec)


def shardings_for(logical_tree, value_tree, mesh: Mesh, rules: AxisRules):
    """NamedSharding tree from a logical-annotation tree mirroring value_tree."""

    def one(logical, val):
        return NamedSharding(mesh, logical_to_spec(logical, rules, mesh, val.shape))

    return jax.tree.map(one, logical_tree, value_tree, is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x))


def mirror_params(sub, params, per_param, other):
    """A tree shaped like `sub` (a piece of train state) that carries
    `per_param` — a tree shaped like `params` — wherever a subtree of `sub`
    has the params' structure (w_stale, optimizer accumulators), and `other`
    at every other leaf (step counters, scalars)."""
    ptree = jax.tree.structure(params)

    def go(s):
        if jax.tree.structure(s) == ptree:
            return per_param
        if isinstance(s, dict):
            return {k: go(v) for k, v in s.items()}
        return jax.tree.map(lambda _: other, s)

    return go(sub)


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Runtime distribution context threaded through the model stack.

    mesh=None means single-device execution (unit tests / smoke tests): all
    shard_map wrappers degrade to plain function calls.
    """

    mesh: Mesh | None = None
    rules: AxisRules = DEFAULT_RULES
    # names of the mesh axes playing each role (for collectives inside shard_map)
    data_axes: tuple = ("data",)
    model_axis: str = "model"
    # MoE distributed dispatch: "gather" (baseline) | "alltoall" (GShard EP)
    moe_impl: str = "gather"

    @property
    def distributed(self) -> bool:
        return self.mesh is not None

    @property
    def n_workers(self) -> int:
        """Number of data-parallel workers (the paper's `c`)."""
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in self.data_axes if a in self.mesh.shape]))

    def spec(self, *logical, shape=None) -> P:
        return logical_to_spec(logical, self.rules, self.mesh, shape)

    def sharding(self, *logical, shape=None):
        assert self.mesh is not None
        return NamedSharding(self.mesh, self.spec(*logical, shape=shape))


LOCAL_CTX = ShardCtx()
