"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (module import never touches jax
device state).  Single-pod: (16, 16) ("data", "model") = 256 chips.
Multi-pod: (2, 16, 16) ("pod", "data", "model") = 512 chips — the "pod"
axis carries only gradient all-reduce / batch split (slowest links, least
traffic; DESIGN §3).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with every axis Auto: the step builders annotate
    shardings and leave the rest to GSPMD propagation."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape: Tuple[int, ...] = None,
                   axes: Tuple[str, ...] = None) -> Mesh:
    """Small mesh over whatever devices exist (tests / examples).

    Defaults to putting all local devices on "model" (1×N)."""
    n = len(jax.devices())
    if shape is None:
        shape = (1, n)
        axes = ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def dp_degree(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
