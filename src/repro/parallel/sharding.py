"""Logical-axis sharding: params and activations carry *logical* axis names;
rules map them onto mesh axes (MaxText-style), so the same model code runs on
1 CPU device, the 256-chip single-pod mesh, and the 512-chip multi-pod mesh.

Param logical axes
  vocab   embedding rows / unembed cols          -> TP ("model")
  embed   d_model                                -> FSDP ("data")
  heads   flattened q-projection out dim         -> TP
  kv      flattened kv-projection out dim        -> TP
  ffn     MLP hidden / mamba d_inner             -> TP
  expert  MoE expert dim                         -> EP ("model")
  layers  stacked-scan layer dim                 -> never sharded

Activation logical axes
  act_batch  -> ("pod", "data") when the batch is shardable
  act_seq    -> "data" only for long-context batch=1 shapes (SP)
  act_ffn / act_heads -> "model" (TP interior)

Cross-pod policy (DESIGN.md §4): parameters are *not* sharded over "pod";
FSDP gathers stay on intra-pod ICI and the only DCN collective is the
gradient/step all-reduce over "pod".
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@jax.tree_util.register_pytree_node_class
class Param:
    """A parameter bundled with its logical axis names.

    Registered as a pytree *node* whose only child is ``value`` and whose
    aux data is ``axes`` — so vmap/eval_shape/scan treat the axes as static
    metadata (stacking a Param under vmap batches the value and keeps axes).
    """
    __slots__ = ("value", "axes")

    def __init__(self, value, axes: tuple):
        self.value = value
        self.axes = tuple(axes)

    def tree_flatten(self):
        return (self.value,), self.axes

    @classmethod
    def tree_unflatten(cls, axes, children):
        return cls(children[0], axes)

    def __repr__(self):
        shape = getattr(self.value, "shape", None)
        return f"Param(shape={shape}, axes={self.axes})"


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Logical-name -> mesh-axis mapping. None = replicated."""
    vocab: Any = "model"
    embed: Any = "data"          # FSDP; set None to replicate params over data
    heads: Any = "model"
    kv: Any = "model"
    ffn: Any = "model"
    expert: Any = "model"
    layers: Any = None
    conv: Any = None
    state: Any = None
    act_batch: Any = ("pod", "data")
    act_seq: Any = None
    act_ffn: Any = "model"
    act_heads: Any = "model"
    act_embed: Any = None
    act_vocab: Any = "model"
    act_expert: Any = "model"

    def resolve(self, name, mesh_axes) -> Any:
        """Logical name -> mesh axis (dropping axes absent from the mesh)."""
        if name is None:
            return None
        target = getattr(self, name)
        if target is None:
            return None
        if isinstance(target, (tuple, list)):
            kept = tuple(t for t in target if t in mesh_axes)
            return kept if kept else None
        return target if target in mesh_axes else None


#: Rules for long-context batch=1 decode: shard along sequence instead.
LONG_CONTEXT_OVERRIDES = dict(act_batch=None, act_seq="data")

_CTX: dict = {"mesh": None, "rules": ShardingRules()}


def active_mesh() -> Optional[Mesh]:
    """The mesh of the enclosing ``use_mesh``/``shard_ctx``, or None."""
    return _CTX["mesh"]


def set_mesh_and_rules(mesh: Optional[Mesh], rules: Optional[ShardingRules]):
    _CTX["mesh"] = mesh
    _CTX["rules"] = rules or ShardingRules()


class use_mesh:
    """Context manager installing (mesh, rules) for logical constraints."""

    def __init__(self, mesh, rules=None):
        self.new = (mesh, rules or ShardingRules())

    def __enter__(self):
        self.old = (_CTX["mesh"], _CTX["rules"])
        _CTX["mesh"], _CTX["rules"] = self.new
        return self

    def __exit__(self, *exc):
        _CTX["mesh"], _CTX["rules"] = self.old
        return False


def shard_ctx(shard):
    """``use_mesh`` for an optional ``(mesh, rules)`` pair.

    The serving stack keys its shared jit caches on such a pair (both
    halves are hashable) and enters this context INSIDE the traced
    function body, so logical ``constrain`` calls bake the mesh at
    trace time — a sharded engine and a single-device engine can never
    alias one trace.  ``None`` is a true no-op: the single-device path
    traces byte-identical jaxprs to the pre-mesh code.
    """
    if shard is None:
        return contextlib.nullcontext()
    return use_mesh(shard[0], shard[1])


def logical_to_spec(axes, mesh=None, rules=None) -> P:
    mesh = mesh or _CTX["mesh"]
    rules = rules or _CTX["rules"]
    if mesh is None:
        return P()
    mesh_axes = set(mesh.axis_names)
    return P(*(rules.resolve(a, mesh_axes) for a in axes))


def constrain(x, *axes):
    """Apply a logical sharding constraint; no-op without an active mesh."""
    mesh = _CTX["mesh"]
    if mesh is None:
        return x
    spec = logical_to_spec(axes, mesh)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Param-tree utilities
# ---------------------------------------------------------------------------

def pcast_varying(x, axis_name: str):
    """Mark ``x`` varying over ``axis_name`` for shard_map's vma type
    system."""
    return jax.lax.pcast(x, (axis_name,), to="varying")


def is_param(x) -> bool:
    return isinstance(x, Param)


def tree_values(params):
    """Strip Param wrappers -> plain value pytree (idempotent)."""
    return jax.tree.map(lambda p: p.value if is_param(p) else p, params,
                        is_leaf=is_param)


def tree_axes(params):
    """Param tree -> logical-axes pytree (leaves are tuples)."""
    return jax.tree.map(lambda p: p.axes, params, is_leaf=is_param)


def spec_for_shape(shape, axes, mesh, rules=None) -> P:
    """Shape-aware spec: jit in_shardings demand exact divisibility, so for
    each dim keep the greedy prefix of mesh axes that divides it (e.g. a
    4-head xlstm param under a 16-way 'model' axis falls back to replicated;
    a batch of 2 under ('pod','data') keeps just 'pod')."""
    rules = rules or _CTX["rules"] or ShardingRules()
    mesh_axes = set(mesh.axis_names)
    entries = []
    for dim, name in zip(shape, axes):
        t = rules.resolve(name, mesh_axes)
        if t is None:
            entries.append(None)
            continue
        axs = t if isinstance(t, tuple) else (t,)
        chosen, size = [], 1
        for a in axs:
            if dim % (size * mesh.shape[a]) == 0:
                chosen.append(a)
                size *= mesh.shape[a]
            else:
                break
        entries.append(tuple(chosen) if len(chosen) > 1
                       else (chosen[0] if chosen else None))
    return P(*entries)


def tree_shardings(params, mesh, rules=None):
    """Param tree (or axes tree) -> NamedSharding pytree for pjit
    (shape-aware when the leaf carries a shape)."""
    rules = rules or _CTX["rules"] or ShardingRules()

    def _one(p):
        axes = p.axes if is_param(p) else p
        shape = getattr(getattr(p, "value", None), "shape", None)
        if shape is not None:
            spec = spec_for_shape(shape, axes, mesh, rules)
        else:
            spec = logical_to_spec(axes, mesh, rules)
        return NamedSharding(mesh, spec)

    return jax.tree.map(_one, params,
                        is_leaf=lambda x: is_param(x) or isinstance(x, tuple))


def constrain_tree(values, axes_tree, mesh=None, rules=None):
    """Constrain every leaf of a plain-value pytree to its logical axes
    (shape-aware, the in-jit counterpart of ``tree_shardings`` +
    ``device_put``).  ``axes_tree`` is a congruent pytree of logical-axis
    tuples (``tree_axes``).  No-op without a mesh, so an unsharded trace
    is untouched.  The serving engine constrains its jit outputs (the
    pooled cache) with this so every step's output sharding equals its
    input sharding — decode bursts, forks and eviction scatters chain
    with zero per-step resharding."""
    mesh = mesh or _CTX["mesh"]
    if mesh is None:
        return values
    rules = rules or _CTX["rules"] or ShardingRules()

    def _one(v, a):
        spec = spec_for_shape(v.shape, a, mesh, rules)
        return jax.lax.with_sharding_constraint(v, NamedSharding(mesh, spec))

    return jax.tree.map(_one, values, axes_tree)


def rejoin(values, axes):
    """Zip a value pytree with an axes pytree back into Params."""
    return jax.tree.map(lambda v, a: Param(v, a), values, axes,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, (str, type(None))) for e in x))


def validate_divisibility(params, mesh, rules=None, warn=print):
    """Report param dims not divisible by their mesh-axis size (GSPMD pads
    these; they surface as wasted FLOPs in the roofline table)."""
    rules = rules or ShardingRules()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    bad = []

    def _check(path, p):
        if not is_param(p):
            return
        shape = getattr(p.value, "shape", None)
        if shape is None:
            return
        for dim, name in zip(shape, p.axes):
            tgt = rules.resolve(name, set(mesh.axis_names))
            if tgt is None:
                continue
            n = (np.prod([sizes[t] for t in tgt])
                 if isinstance(tgt, tuple) else sizes[tgt])
            if dim % n:
                bad.append((jax.tree_util.keystr(path), dim, name, int(n)))

    jax.tree_util.tree_map_with_path(_check, params, is_leaf=is_param)
    for b in bad:
        warn(f"[sharding] non-divisible: {b[0]} dim={b[1]} "
             f"logical={b[2]} shards={b[3]}")
    return bad
