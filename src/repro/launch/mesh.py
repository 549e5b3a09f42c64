"""Production meshes.

Single pod: v5e-256 as (data=16, model=16).
Multi-pod:  2 pods = 512 chips as (pod=2, data=16, model=16); the ``pod``
axis carries pure data parallelism across the DCN/ICI boundary.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module does not touch jax device state — only
launch/dryrun.py sets the 512-device XLA flag, before any jax import.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    import os
    override = os.environ.get("REPRO_MESH_OVERRIDE")  # e.g. "2,4" / "2,2,2"
    if override:
        shape = tuple(int(x) for x in override.split(","))
        axes = ("pod", "data", "model")[-len(shape):]
        return jax.make_mesh(shape, axes)
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def batch_axes(mesh) -> tuple:
    """Mesh axes a global-batch dimension shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def axis_size(mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


# TPU v5e hardware constants for the roofline model (per chip).
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link
