"""Production mesh definitions (TPU v5e target).

Single pod : (16, 16)    -> ("data", "model"), 256 chips
Multi-pod  : (2, 16, 16) -> ("pod", "data", "model"), 512 chips

Functions (not module constants) so importing never touches jax device
state — the dry-run must set XLA_FLAGS before the first jax call.

Every mesh is built by :func:`make_mesh` with ``Auto`` axes: the model
code places activations with ``with_sharding_constraint`` and lets GSPMD
propagate the rest, which ``Explicit`` axes (``jax.make_mesh``'s default
since JAX 0.7) reject.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12        # per chip, FLOP/s
HBM_BW = 819e9                  # per chip, bytes/s
ICI_BW = 50e9                   # per link, bytes/s


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (see module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False, shape=None, axes=None):
    """Production mesh, guarded against the runtime's device count.

    ``jax.make_mesh`` consumes ALL visible devices, so a mismatched
    device count surfaces as an opaque reshape error deep in jax; fail
    early instead, naming the device count, so a CPU box asking for the
    256-chip pod gets a clear message (use :func:`make_smoke_mesh`
    there).  ``shape``/``axes`` override the default single/multi-pod
    topologies together."""
    if (shape is None) != (axes is None):
        raise ValueError("pass shape and axes together (or neither)")
    if shape is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    have = jax.device_count()
    if have != need:
        raise ValueError(
            f"mesh shape {tuple(shape)} ({' x '.join(map(str, shape))} = "
            f"{need} chips) does not factor into this runtime's "
            f"{have} device(s); run on a {need}-chip slice or use "
            f"make_smoke_mesh() for single-device smoke tests")
    return make_mesh(shape, axes)


def make_smoke_mesh():
    """1-device mesh with the production axis names (CPU tests)."""
    return make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])


def mesh_chips(mesh) -> int:
    return mesh.devices.size
