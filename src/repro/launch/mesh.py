"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). The dry-run sets ``XLA_FLAGS=--xla_force_host_platform_
device_count=512`` before any jax import; everything else sees the real
single CPU device.

Mesh axes:
  * ``pod``   — across pods (multi-pod only; data-parallel across pods)
  * ``data``  — batch / FSDP axis within a pod
  * ``model`` — tensor/expert/sequence axis (the PIM "bank" axis, DESIGN §2.2)
"""

from __future__ import annotations

import jax
import numpy as np


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``.  The model places
    activations with ``with_sharding_constraint`` anchors and leaves the
    rest to GSPMD propagation (``distributed/axes.py``); JAX's default of
    ``Explicit`` axes would instead demand an output sharding on every
    ambiguous op (the embedding gather first) and reject those anchors."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Generic mesh for tests/small runs, e.g. ((2, 4), ('data', 'model'))."""
    n = int(np.prod(shape))
    if n > len(jax.devices()):
        raise ValueError(
            f"mesh {shape} needs {n} devices, have {len(jax.devices())}"
        )
    return _auto_mesh(shape, axes)


def single_device_mesh():
    return _auto_mesh((1, 1), ("data", "model"))


def parse_mesh_arg(arg: str) -> tuple[int, int]:
    """Parse a ``DxM`` CLI mesh spec (data x model), e.g. ``"1x4"``.

    Shared by ``serve_bench --mesh`` and ``dryrun --serve-mesh`` so both
    fail with the same usage message instead of a raw unpack traceback.
    """
    try:
        d, m = arg.lower().split("x")
        return int(d), int(m)
    except ValueError:
        raise SystemExit(
            f"mesh spec wants DxM (e.g. 1x4, data x model), got {arg!r}"
        ) from None


def data_axes(mesh) -> tuple[str, ...]:
    """Axes that carry the global batch ('pod' folds into data-parallel)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis_size(mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1
