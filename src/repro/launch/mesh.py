"""Production meshes.

The mesh mirrors the paper's §5 deployment: each axis is a radix-16 XOR
CIN (16 = 2^4, so the XOR LACIN instance applies), giving a 16x16 HyperX
single pod (256 chips) and a 2x16x16 multi-pod system (512 chips) whose
"pod" axis is the Dragonfly-style global CIN.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state.
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    from jax.sharding import AxisType
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 2):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    model = min(model, n)
    data = n // model
    devs = np.array(jax.devices()[: data * model]).reshape(data, model)
    from jax.sharding import Mesh
    return Mesh(devs, ("data", "model"))


def describe_mesh(mesh) -> dict:
    """Report the mesh as the paper's fabric: per-axis CIN instances."""
    from repro.core.port_matrix import is_power_of_two
    out = {"axes": dict(mesh.shape), "devices": int(np.prod(list(mesh.shape.values())))}
    out["cin_instances"] = {
        name: ("xor" if is_power_of_two(size) else "circle")
        for name, size in mesh.shape.items()}
    out["schedule_steps"] = {
        name: (size - 1 if size % 2 == 0 or is_power_of_two(size) else size)
        for name, size in mesh.shape.items()}
    return out
