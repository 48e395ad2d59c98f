"""Mesh factories for the sharded training engine.

Nothing at module scope touches jax device state, so a caller can force
host devices (``XLA_FLAGS=--xla_force_host_platform_device_count=N``)
before jax starts.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(model_axis: int = 1):
    """Small mesh over whatever devices exist (CPU tests/examples)."""
    n = len(jax.devices())
    model_axis = min(model_axis, n)
    data = n // model_axis
    return _make_mesh((data, model_axis), ("data", "model"))


def parse_mesh_spec(spec: str):
    """``"DxM"`` (also ``"D×M"``) → (data, model) axis sizes; ``"auto"`` →
    all visible devices on the data axis. Raises on malformed specs."""
    if spec == "auto":
        return (len(jax.devices()), 1)
    parts = spec.replace("×", "x").lower().split("x")
    if len(parts) != 2 or not all(p.isdigit() for p in parts):
        raise ValueError(
            f"mesh spec {spec!r}: expected 'DATAxMODEL' (e.g. '4x1') or "
            "'auto'")
    return (int(parts[0]), int(parts[1]))


def make_training_mesh(spec: str = "auto"):
    """(data × model) mesh for the sharded PSL training engine.

    The product must not exceed the visible device count; on the CPU
    container, force N host devices *before importing jax* with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the canonical
    host-mesh recipe — see docs/training.md).
    """
    data, model = parse_mesh_spec(spec)
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(
            f"mesh {data}x{model} needs {data * model} devices but only "
            f"{n} are visible; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={data * model} before "
            "importing jax")
    return _make_mesh((data, model), ("data", "model"))
