"""Mesh factories for the production TPU v5e topology.

Nothing at module scope touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before importing
jax so ``make_production_mesh`` can build the full pod meshes on the CPU
container.
"""
from __future__ import annotations

import jax

# TPU v5e hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # per chip, FLOP/s
HBM_BW = 819e9                    # per chip, B/s
ICI_BW = 50e9                     # per link, B/s


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


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
