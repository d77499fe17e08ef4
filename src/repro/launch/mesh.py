"""Production mesh construction.

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is pure data parallelism across pods (DCN-connected), "data"
is FSDP/DP within a pod, "model" is tensor/expert parallel on ICI.

Defined as functions (never module-level constants) so importing this
module does not touch jax device state.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes, **kw):
    # Auto axes: shardings stay annotations that GSPMD propagates (the
    # default Explicit axes would put them into every array's type)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """A (data, model) mesh over this host's first data*model devices.

    Raises when the host has fewer: a mesh silently shrunk to the
    devices present would run, and report, a different layout."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"a {data}x{model} mesh needs {data * model} "
                         f"devices; this host has {n}")
    return _auto_mesh((data, model), ("data", "model"))


def parse_mesh_arg(spec: str):
    """CLI mesh spec "DxM" (e.g. "2x4") -> (data, model).

    Raises with an actionable message when the host exposes fewer
    devices than requested (on CPU, export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before*
    starting python to split the host into N virtual devices).
    """
    try:
        data, model = (int(t) for t in spec.lower().split("x"))
    except ValueError as e:
        raise ValueError(f"--mesh wants DxM (e.g. 2x4), got {spec!r}") from e
    if data < 1 or model < 1:
        raise ValueError(f"--mesh sizes must be >= 1, got {spec!r}")
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(
            f"--mesh {spec} needs {data * model} devices but only {n} are "
            f"visible; on CPU set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={data * model} before launching")
    return data, model
