"""Production mesh construction (DESIGN.md §6, §Machine-models).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state. The dry-run sets XLA_FLAGS for 512 host devices
BEFORE importing jax; everything else sees the real device count.

``make_mapped_mesh`` is the partitioner's hook into mesh construction:
``device_order`` is a ``core.mapping.MeshMapping.device_to_bin`` array
(logical device i -> physical leaf/device index), so the makespan search
over the machine tree decides which physical chip backs each logical mesh
coordinate instead of a fixed axis table. ``device_order=None`` is the
identity mapping the fixed tables used to hardcode.

The machine model itself lives in ``core/machine.py`` — mesh shapes, axis
names and roofline capacities all come from a ``MachineSpec`` preset
(``--machine`` in the launchers). ``production_mesh_spec`` /
``make_production_mesh`` survive as deprecation shims over the
``tpu_v5e-256`` / ``tpu_v5e-512`` presets; the historical hardware
constants below are re-derived from the preset so old imports keep
reading today's numbers.
"""
from __future__ import annotations

import warnings
from typing import Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.machine import MachineSpec, machine_for_devices


def make_mapped_mesh(mesh_shape: Sequence[int], axes: Sequence[str],
                     device_order: Optional[np.ndarray] = None,
                     devices: Optional[Sequence] = None):
    """Mesh over ``devices`` (default: all) with an explicit logical ->
    physical assignment: logical device ``i`` (row-major index into
    ``mesh_shape``) is backed by physical device ``device_order[i]``.
    """
    devs = np.asarray(devices if devices is not None else jax.devices(),
                      dtype=object)
    shape = tuple(mesh_shape)
    n = int(np.prod(shape))
    if devs.size < n:
        raise ValueError(f"mesh shape {shape} needs {n} devices, "
                         f"got {devs.size}")
    devs = devs[:n]           # jax.make_mesh semantics: first n devices
    if device_order is not None:
        order = np.asarray(device_order)
        if sorted(order.tolist()) != list(range(n)):
            raise ValueError("device_order must be a permutation of "
                             f"range({n})")
        devs = devs[order]
    return jax.sharding.Mesh(devs.reshape(shape), tuple(axes))


def make_machine_mesh(machine: MachineSpec,
                      device_order: Optional[np.ndarray] = None,
                      devices: Optional[Sequence] = None):
    """Mesh of a declarative machine model: shape + axis names from the
    spec, leaves backed in (optionally searched) ``device_order``."""
    shape, axes = machine.mesh_spec()
    return make_mapped_mesh(shape, axes, device_order, devices)


def device_order_of(mesh) -> np.ndarray:
    """Inverse of ``make_mapped_mesh``: the physical index (position in
    ``jax.devices()``) backing each logical device, row-major."""
    ids = {d: i for i, d in enumerate(jax.devices())}
    return np.asarray([ids[d] for d in mesh.devices.ravel()])


def production_mesh_spec(multi_pod: bool = False
                         ) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    """Deprecated shim: (shape, axis names) of the historical production
    machine — now ``MachineSpec.preset('tpu_v5e-512'/'tpu_v5e-256')``."""
    warnings.warn(
        "production_mesh_spec is deprecated; use core.machine."
        "MachineSpec.preset('tpu_v5e-512' if multi_pod else "
        "'tpu_v5e-256').mesh_spec()", DeprecationWarning, stacklevel=2)
    return production_machine(multi_pod).mesh_spec()


def production_machine(multi_pod: bool = False) -> MachineSpec:
    """The machine the historical ``multi_pod`` flag selected."""
    return MachineSpec.preset("tpu_v5e-512" if multi_pod else "tpu_v5e-256")


def serving_mesh_spec(n_devices: Optional[int] = None
                      ) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    """(shape, axis names) for a serving process: the registered production
    machine whose device count matches (256/512 chips), otherwise a 1-D
    'data' mesh over the local devices (smoke / CPU). The serving driver
    routes through this + ``PlacementSession`` instead of hardcoding its
    own mesh."""
    n = len(jax.devices()) if n_devices is None else int(n_devices)
    spec = machine_for_devices(n)
    if spec is not None:
        return spec.mesh_spec()
    return (max(n, 1),), ("data",)


def make_production_mesh(*, multi_pod: bool = False,
                         device_order: Optional[np.ndarray] = None):
    """Deprecated shim: build the historical production mesh — now
    ``make_machine_mesh(MachineSpec.preset(...))``."""
    warnings.warn(
        "make_production_mesh is deprecated; use make_machine_mesh("
        "core.machine.MachineSpec.preset('tpu_v5e-512' if multi_pod else "
        "'tpu_v5e-256'))", DeprecationWarning, stacklevel=2)
    return make_machine_mesh(production_machine(multi_pod), device_order)


# Historical hardware constants (TPU v5e-class machine, DESIGN.md
# §Machine-models) — re-derived from the preset so legacy imports keep
# working; new code reads per-leaf capacities off a MachineSpec instead.
_V5E = MachineSpec.preset("tpu_v5e-512")
PEAK_FLOPS = float(_V5E.peak_flops.max())   # bf16 per chip
HBM_BW = float(_V5E.hbm_bw.max())           # bytes/s per chip
ICI_BW = float(_V5E.link_bw)                # bytes/s per link
CHIPS_SINGLE_POD = MachineSpec.preset("tpu_v5e-256").n_devices
CHIPS_MULTI_POD = _V5E.n_devices
