"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before any jax
initialization.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axes: ("data", "model") single-pod, ("pod", "data", "model") multi-pod.
    TP ("model") stays inside a pod; DP spans ("pod", "data").
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small host-device mesh for CI tests.

    Checks the device count eagerly: ``jax.make_mesh`` raises a generic
    shape error, but the fix on a CPU host is a specific incantation that
    must be set BEFORE jax initializes — name it.
    """
    need = n_data * n_model
    have = len(jax.devices())
    if have < need:
        raise RuntimeError(
            f"make_debug_mesh({n_data}, {n_model}) needs {need} devices "
            f"but jax sees {have}.  On a CPU host, set "
            f'XLA_FLAGS="--xla_force_host_platform_device_count={need}" '
            "in the environment (or via os.environ) BEFORE importing/"
            "initializing jax — it has no effect once jax has picked its "
            "backend.  Tests should use the run_sharded fixture from "
            "tests/conftest.py, which spawns a fresh subprocess with the "
            "flag set.")
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def make_lane_mesh(shape, axes=None):
    """Data-axes-only mesh for ``solve(mesh=...)`` lane sharding.

    Axis names default to ``("data",)`` for 1-d shapes and
    ``("pod", "data")`` for 2-d — the axes ``repro.parallel`` shards lanes
    over.  Same eager device-count check as ``make_debug_mesh``.
    """
    shape = tuple(shape)
    if axes is None:
        axes = {1: ("data",), 2: ("pod", "data")}.get(len(shape))
        if axes is None:
            raise ValueError(
                f"make_lane_mesh: pass axes= for a {len(shape)}-d shape "
                "(defaults exist for 1-d and 2-d only)")
    need = math.prod(shape)
    have = len(jax.devices())
    if have < need:
        raise RuntimeError(
            f"make_lane_mesh({shape}) needs {need} devices but jax sees "
            f'{have}.  On a CPU host, set XLA_FLAGS='
            f'"--xla_force_host_platform_device_count={need}" BEFORE jax '
            "initializes (tests: use the run_sharded fixture in "
            "tests/conftest.py).")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
