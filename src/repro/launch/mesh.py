"""Production meshes.

Single pod: (data=16, model=16) — 256 chips (v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; the "pod" axis composes
with "data" into the DP/FSDP dimension (PartitionSpecs use ("pod","data")
tuples), so the same sharding rules scale to N pods: cross-pod traffic is
only the DP gradient all-reduce (DCN), ICI stays intra-pod.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state.
"""

from __future__ import annotations

import math

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)} — "
            "run under launch/dryrun.py which forces host platform devices")
    # Auto axes: the partitioner places what the logical-axis constraints
    # leave open (make_mesh defaults to Explicit axes)
    return jax.make_mesh(shape, axes, devices=devices[:need],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def data_axes_of(mesh) -> tuple:
    """The DP/FSDP axis group for a mesh (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")
