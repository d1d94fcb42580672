"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state.  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import so 256/512 placeholder host devices exist; smoke tests and benchmarks
see the real single CPU device.

Axis semantics (DESIGN.md §2):
  * ``data``  — the agent axis of decentralized training (K=16 agents), or
    the batch axis when serving.
  * ``model`` — within-agent tensor/expert parallelism.
  * ``pod``   — multi-pod only: intra-agent data parallelism (per-agent batch
    split across pods, gradients psum'd over ``pod``).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def data_axis_size(mesh) -> int:
    return mesh_axis_sizes(mesh)["data"]


def batch_axes(mesh) -> tuple[str, ...]:
    """Axes a global (non-agent) batch dimension shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


# TPU v5e hardware constants for the roofline analysis (per chip)
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link
