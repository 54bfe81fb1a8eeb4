"""Logical-axis sharding rules (MaxText-style) with divisibility fallback.

Every parameter / activation dimension carries a *logical* axis name; rules
map logical names onto physical mesh axes. ``spec_for`` degrades gracefully:
a dimension that is not divisible by its mapped mesh axes is replicated
rather than erroring, which is what lets one rule table serve ten
architectures (e.g. 8 KV heads on a 16-way model axis -> replicate).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

# logical axis -> ordered tuple of physical mesh axes it may shard over.
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    # activations
    "batch": ("pod", "data"),
    "seq": (),          # sequence parallelism is opt-in (see sp_rules)
    "embed": (),
    "act_heads": ("model",),
    "act_ff": ("model",),
    # params: FSDP over data, TP over model; replicated over pod
    "fsdp": ("data",),
    "tensor": ("model",),
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "expert": ("model",),
    "layers": (),
    "conv": (),
    "state": (),
    # serving pools
    "kv_pages": ("data",),
    "page": (),
    "requests": ("data",),
}


def sp_rules(base: Optional[Dict[str, Tuple[str, ...]]] = None) -> Dict[str, Tuple[str, ...]]:
    """Rules with sequence parallelism enabled (long-prefill shapes)."""
    rules = dict(base or DEFAULT_RULES)
    rules["seq"] = ("model",)
    return rules


def fsdp2d_rules() -> Dict[str, Tuple[str, ...]]:
    """Pure-FSDP (ZeRO-3) strategy: batch and parameters shard over the
    in-pod axes (data, model); the pod axis stays pure DP (params
    replicated across pods, gradients all-reduced over DCI). No tensor
    parallelism: for dense-model training this trades per-layer activation
    psums (O(tokens·d_model) each) for per-layer weight all-gathers
    (O(params/layer)) — a 6.6x collective win for phi3-class models at 4k
    context (EXPERIMENTS §Perf hillclimb #3). MoE keeps fsdp_tp (experts
    need the model axis)."""
    rules = dict(DEFAULT_RULES)
    rules["batch"] = ("data", "model", "pod")
    rules["fsdp"] = ("data", "model")
    rules["tensor"] = ()
    rules["act_heads"] = ()
    rules["act_ff"] = ()
    rules["vocab"] = ()
    rules["expert"] = ()
    return rules


STRATEGIES = {
    "fsdp_tp": lambda: dict(DEFAULT_RULES),
    "fsdp2d": fsdp2d_rules,
}

# module-level active rules: model code calls constrain() without plumbing
# rules through every layer; the launcher scopes a strategy per cell.
_ACTIVE_RULES: Dict[str, Tuple[str, ...]] = dict(DEFAULT_RULES)


class use_rules:
    def __init__(self, rules: Dict[str, Tuple[str, ...]]):
        self.rules = rules
        self._prev = None

    def __enter__(self):
        global _ACTIVE_RULES
        self._prev = _ACTIVE_RULES
        _ACTIVE_RULES = self.rules
        return self

    def __exit__(self, *a):
        global _ACTIVE_RULES
        _ACTIVE_RULES = self._prev
        return False


def _mesh_axis_sizes(mesh) -> Dict[str, int]:
    # works for both Mesh and AbstractMesh (no .devices on the latter)
    return dict(mesh.shape)


def spec_for(
    shape: Sequence[int],
    logical: Sequence[Optional[str]],
    mesh: Mesh,
    rules: Optional[Dict[str, Tuple[str, ...]]] = None,
) -> P:
    """Resolve logical axes for a concrete shape into a PartitionSpec.

    A mesh axis is used at most once across the whole spec (XLA requirement);
    axes are claimed greedily left-to-right. Non-divisible dims replicate.
    """
    rules = rules or DEFAULT_RULES
    sizes = _mesh_axis_sizes(mesh)
    assert len(shape) == len(logical), (shape, logical)
    used: set = set()
    out = []
    for dim, name in zip(shape, logical):
        if name is None:
            out.append(None)
            continue
        mapped = [a for a in rules.get(name, ()) if a in sizes and a not in used]
        # claim the largest divisible prefix of the mapped axes
        claimed = []
        prod = 1
        for a in mapped:
            if dim % (prod * sizes[a]) == 0:
                claimed.append(a)
                prod *= sizes[a]
        if not claimed:
            out.append(None)
        elif len(claimed) == 1:
            out.append(claimed[0])
            used.add(claimed[0])
        else:
            out.append(tuple(claimed))
            used.update(claimed)
    return P(*out)


def sharding_for(
    shape: Sequence[int],
    logical: Sequence[Optional[str]],
    mesh: Mesh,
    rules: Optional[Dict[str, Tuple[str, ...]]] = None,
) -> NamedSharding:
    return NamedSharding(mesh, spec_for(shape, logical, mesh, rules))


def tree_specs(abstract_tree, axes_tree, mesh: Mesh, rules=None):
    """Map a pytree of ShapeDtypeStructs + matching logical-axes pytree to
    a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda x, ax: spec_for(x.shape, ax, mesh, rules),
        abstract_tree,
        axes_tree,
        is_leaf=lambda x: x is None,
    )


def tree_shardings(abstract_tree, axes_tree, mesh: Mesh, rules=None):
    return jax.tree.map(
        lambda x, ax: sharding_for(x.shape, ax, mesh, rules),
        abstract_tree,
        axes_tree,
        is_leaf=lambda x: x is None,
    )


def constrain(x, logical: Sequence[Optional[str]], mesh: Optional[Mesh] = None, rules=None):
    """``with_sharding_constraint`` via logical names; no-op outside jit/mesh."""
    mesh = mesh or _current_mesh()
    if mesh is None or mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(
        x, sharding_for(x.shape, logical, mesh, rules or _ACTIVE_RULES)
    )


def _current_mesh() -> Optional[Mesh]:
    try:
        from jax._src import mesh as mesh_lib

        m = mesh_lib.get_concrete_mesh()
        if m is not None and not m.empty:
            return m
        m = mesh_lib.thread_resources.env.physical_mesh
        return None if m.empty else m
    except Exception:
        return None
