"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state. The dry-run entry point is the ONLY place
that forces 512 host platform devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """A mesh whose axes are all ``Auto``: shardings are propagated by the
    compiler from the jit in/out shardings (``jax.make_mesh`` defaults to
    ``Explicit`` axes, under which every gather and contraction over a
    sharded dim needs an explicit output sharding)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return _auto_mesh((n // model_parallel, model_parallel), ("data", "model"))


def data_axis_names(mesh) -> tuple[str, ...]:
    """Axes carrying the batch/FSDP dimension ('pod' folds into data)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis_size(mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1
