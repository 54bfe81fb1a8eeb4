# libra: waive[IMPORT001] launch entry point driven via subprocess in test_dryrun_launch (invisible to the static graph)
"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (module import never touches jax
device state). Single pod: (data=16, model=16) = 256 chips of TPU v5e;
multi-pod: (pod=2, data=16, model=16) = 512 chips, the 'pod' axis mapping
to the DCI-connected pod dimension (params replicated across pods, DP
gradient reduction over it).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh

from repro.common.types import MULTI_POD, SINGLE_POD, MeshSpec


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh_from_spec(spec: MeshSpec) -> Mesh:
    return jax.make_mesh(spec.shape, spec.axes,
                         axis_types=(AxisType.Auto,) * len(spec.axes))


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over however many (host) devices exist — tests/examples."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def mesh_spec_for(mesh: Mesh) -> MeshSpec:
    return MeshSpec(tuple(mesh.devices.shape), tuple(mesh.axis_names))


def degraded_mesh(lost_pods: int = 1) -> Mesh:
    """Elastic restart target after losing ``lost_pods`` pods: the same code
    compiles for the smaller mesh and checkpoints reshard on restore."""
    assert lost_pods < 2
    return make_production_mesh(multi_pod=False)
