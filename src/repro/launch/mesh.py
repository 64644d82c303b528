"""Production mesh construction.

Single-pod: (data=16, model=16) — 256 chips (one v5e pod slice).
Multi-pod : (pod=2, data=16, model=16) — 512 chips; 'pod' is an outer
data-parallel axis (the only cross-pod collective is the once-per-step
gradient all-reduce, optionally compressed — see train.compression).

Defined as functions so importing this module never touches jax device
state (the dry-run pins the device count before first jax init)."""
from __future__ import annotations

import jax

from repro.core.distmat.types import make_mesh



def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """A (data, model) mesh over the first data·model devices; raises when
    fewer exist, so a run sized for a mesh never silently runs on less."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"mesh ({data}, {model}) needs {data * model} "
                         f"devices, {n} available")
    return make_mesh((data, model), ("data", "model"))


def axis_sizes(mesh, axes=None) -> tuple[int, ...]:
    """Per-axis device counts of `mesh` (all axes, or the named subset, a
    single name included) — the topology key the planner's collective
    model prices reductions against (`MachineModel.collective`)."""
    if axes is None:
        names = tuple(mesh.axis_names)
    elif isinstance(axes, str):
        names = (axes,)
    else:
        names = tuple(axes)
    return tuple(int(mesh.shape[a]) for a in names)
