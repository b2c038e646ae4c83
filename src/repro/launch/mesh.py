"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
XLA_FLAGS before first jax use, and smoke tests/benches must keep seeing
one device.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np

from repro.core.config import MeshLayout
from repro.core.meshes import layout_device_order
from repro.core.topology import TorusTopology


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_layout_mesh(*, multi_pod: bool = False,
                     layout: MeshLayout = MeshLayout.SPARSE):
    """Same production shape, devices permuted per the thread-placement
    analogue (core.meshes). NONE reproduces the topology-oblivious OS
    baseline; SPARSE/DENSE are the affinitized layouts."""
    from jax.sharding import Mesh

    topo = TorusTopology(n_pods=2 if multi_pod else 1)
    order = layout_device_order(layout, topo)   # (pods, x, y) of device ids
    devices = np.asarray(jax.devices())
    if devices.size < topo.n_chips:
        raise ValueError(f"need {topo.n_chips} devices, have {devices.size}")
    grid = devices[order.reshape(-1)].reshape(order.shape)
    if multi_pod:
        return Mesh(grid, ("pod", "data", "model"))
    return Mesh(grid[0], ("data", "model"))


def make_host_mesh(n_data: Optional[int] = None, n_model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    n_data = n_data or (n // n_model)
    return _auto_mesh((n_data, n_model), ("data", "model"))


def _auto_mesh(shape, axes):
    """Mesh with Auto axes: the LM steps place state through jit shardings
    (GSPMD), while jax.make_mesh defaults to Explicit axes."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
