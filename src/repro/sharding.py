"""Logical-axis sharding rules (MaxText-style) for the PSL pod mesh.

Mesh axes: ``("data", "model")`` single-pod, ``("pod", "data", "model")``
multi-pod. PSL semantics drive two rule sets:

  * SERVER rules — the server segment is fully sharded: FSDP over the
    data axes (``embed`` dim) + tensor/expert parallel over ``model``.
  * CLIENT rules — client-segment params are *replicated* across the data
    axes (the paper keeps every client's copy identical at all times), and
    only tensor-sharded over ``model``.

Every ``ParamSpec`` dimension carries a logical axis name; ``spec_for``
resolves it to mesh axes with a divisibility check — a dimension that does
not divide the assigned mesh axes falls back to replication and the fallback
is recorded (surfaced in the dry-run report instead of failing the lowering).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.models import layers as L

Rules = Dict[str, Tuple[str, ...]]


def _data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def server_rules(mesh: Mesh, profile: str = "tp") -> Rules:
    """Sharding profiles (the §Perf hillclimb knob):

    * "tp"   — baseline: Megatron-style tensor parallel over `model` +
               FSDP over the data axes on the embed dim.
    * "fsdp" — no tensor parallelism: every weight fully sharded over ALL
               axes on its embed dim; batch over all axes (pure DP). Removes
               per-layer activation all-reduces at the cost of whole-weight
               all-gathers.
    """
    fsdp = _data_axes(mesh)
    if profile == "fsdp":
        allax = fsdp + ("model",)
        return {"embed": allax, "vocab": (), "heads": (), "kv_heads": (),
                "kv_heads_cache": ("model",), "ff": (), "expert_ff": (),
                "experts": (), "inner": (), "layers": (), "batch": allax}
    if profile == "ddp":
        # no tensor parallelism on layer weights: batch over ALL axes,
        # layer weights FSDP over the data axes only, vocab/embedding TP
        # over `model` (the one matmul big enough to want it).
        allax = fsdp + ("model",)
        return {"embed": fsdp, "vocab": ("model",), "heads": (),
                "kv_heads": (), "kv_heads_cache": ("model",),
                "cache_seq": ("model",), "ff": (), "expert_ff": (),
                "experts": ("model",), "inner": (), "layers": (),
                "batch": allax}
    return {
        "embed": fsdp,
        "vocab": ("model",),
        "heads": ("model",),
        "kv_heads": ("model",),
        "kv_heads_cache": ("model",),
        "cache_seq": ("model",),
        "ff": ("model",),
        "expert_ff": (),
        "experts": ("model",),
        "inner": ("model",),
        "layers": (),
        "batch": fsdp if profile == "tp" else fsdp + ("model",),
    }


def client_rules(mesh: Mesh, profile: str = "tp") -> Rules:
    r = dict(server_rules(mesh, profile))
    r["embed"] = ()          # replicated across data: identical client copies
    if profile == "fsdp":
        # client stays replicated on data axes but may use model axis
        r["embed"] = ("model",)
    return r


@dataclasses.dataclass
class ShardingReport:
    fallbacks: List[str] = dataclasses.field(default_factory=list)

    def note(self, msg: str):
        if msg not in self.fallbacks:
            self.fallbacks.append(msg)


def spec_for(shape: Sequence[int], axes: Sequence[Optional[str]],
             rules: Rules, mesh: Mesh,
             report: Optional[ShardingReport] = None) -> PartitionSpec:
    entries = []
    used: set = set()
    for dim, name in zip(shape, axes):
        if name is None:
            entries.append(None)
            continue
        mesh_axes = tuple(a for a in rules.get(name, ()) if a not in used)
        if not mesh_axes:
            entries.append(None)
            continue
        total = int(np.prod([mesh.shape[a] for a in mesh_axes]))
        if dim % total:
            # try a prefix of the axes before replicating entirely
            ok: Tuple[str, ...] = ()
            prod = 1
            for a in mesh_axes:
                if dim % (prod * mesh.shape[a]) == 0:
                    prod *= mesh.shape[a]
                    ok = ok + (a,)
                else:
                    break
            if not ok:
                if report:
                    report.note(f"axis {name!r} size {dim} !% {total} -> "
                                "replicated")
                entries.append(None)
                continue
            if report:
                report.note(f"axis {name!r} size {dim}: partial shard {ok}")
            mesh_axes = ok
        used.update(mesh_axes)
        entries.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
    return PartitionSpec(*entries)


def shardings_for_specs(spec_tree, mesh: Mesh, rules: Rules,
                        report: Optional[ShardingReport] = None):
    """ParamSpec tree → NamedSharding tree."""
    return L.tree_map_specs(
        lambda s: NamedSharding(mesh, spec_for(s.shape, s.axes, rules, mesh,
                                               report)),
        spec_tree)


def model_param_shardings(model, mesh: Mesh,
                          report: Optional[ShardingReport] = None,
                          profile: str = "tp"):
    """Client subtree replicated over data, server subtree per profile."""
    specs = model.param_specs()
    out = {}
    for part, rules in (("client", client_rules(mesh, profile)),
                        ("server", server_rules(mesh, profile))):
        out[part] = shardings_for_specs(specs[part], mesh, rules, report)
    return out


def replicated(mesh: Mesh):
    return NamedSharding(mesh, PartitionSpec())


def opt_state_shardings(opt_state_abs, params_sh, mesh: Mesh):
    """Optimizer-slot shardings: moment slots mirror the param shardings
    (they are param-shaped), scalar bookkeeping (count) is replicated."""
    rep = replicated(mesh)
    return {k: (params_sh if k in ("mu", "m", "v") else rep)
            for k in opt_state_abs}


def train_state_shardings(model, optimizer, mesh: Mesh,
                          report: Optional[ShardingReport] = None,
                          profile: str = "tp"):
    """TrainState-shaped sharding tree for the sharded PSL step: client
    subtree replicated over the data axes, server per profile, optimizer
    slots mirroring the params, step counter replicated."""
    from repro.optim import TrainState
    params_sh = model_param_shardings(model, mesh, report, profile=profile)
    opt_abs = jax.eval_shape(optimizer.init, model.abstract_params()
                             if hasattr(model, "abstract_params")
                             else jax.eval_shape(
                                 model.init, jax.random.PRNGKey(0)))
    return TrainState(params=params_sh,
                      opt_state=opt_state_shardings(opt_abs, params_sh, mesh),
                      step=replicated(mesh))


# --------------------------------------------------------------------------
# Activation sharding constraints (§Perf: GSPMD needs explicit hints to keep
# residual-stream activations sharded under ddp / sequence-parallel layouts;
# without them it replicates over idle axes — measured in EXPERIMENTS.md).
# Set by the launcher before tracing; consulted by the transformer blocks.
# --------------------------------------------------------------------------

_ACTIVATION_SHARDING: Optional[NamedSharding] = None


def set_activation_sharding(ns: Optional[NamedSharding]) -> None:
    global _ACTIVATION_SHARDING
    _ACTIVATION_SHARDING = ns


@contextlib.contextmanager
def activation_sharding(ns: Optional[NamedSharding]):
    """Set the activation constraint for one block (e.g. one trace) and
    restore the previous one after it."""
    prev = _ACTIVATION_SHARDING
    set_activation_sharding(ns)
    try:
        yield
    finally:
        set_activation_sharding(prev)


def constrain_activation(x):
    """Apply the configured (batch, seq, embed) sharding constraint."""
    if _ACTIVATION_SHARDING is None:
        return x
    return jax.lax.with_sharding_constraint(x, _ACTIVATION_SHARDING)


def activation_sharding_for(mesh: Mesh, layout: str) -> NamedSharding:
    """layout: 'batch' → (B over all axes, S, d) [ddp]; 'seq' → (B over data,
    S over model, d) [Megatron-style sequence parallelism]."""
    data = _data_axes(mesh)
    if layout == "batch":
        axes = data + ("model",)
        return NamedSharding(mesh, PartitionSpec(
            axes if len(axes) > 1 else axes[0], None, None))
    if layout == "seq":
        return NamedSharding(mesh, PartitionSpec(
            data if len(data) > 1 else data[0], "model", None))
    raise ValueError(layout)


def batch_axes(mesh: Mesh, profile: str = "tp") -> Tuple[str, ...]:
    axes = _data_axes(mesh)
    if profile == "fsdp":
        axes = axes + ("model",)
    return axes


def batch_spec(mesh: Mesh, profile: str = "tp") -> PartitionSpec:
    axes = batch_axes(mesh, profile)
    return PartitionSpec(axes if len(axes) > 1 else (axes[0] if axes
                                                     else None))


def batch_shardings(batch_tree, mesh: Mesh, global_batch: int,
                    report: Optional[ShardingReport] = None,
                    profile: str = "tp"):
    """Shard dim 0 (batch) of every batch leaf over the data axes, falling
    back to replication when the batch does not divide (long_500k, B=1)."""
    axes = batch_axes(mesh, profile)
    total = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1

    def one(x):
        if hasattr(x, "shape") and x.shape and x.shape[0] % total == 0 \
                and total > 1:
            return NamedSharding(mesh, batch_spec(mesh, profile))
        if report and total > 1:
            report.note(f"batch dim {getattr(x, 'shape', ())} !% {total} -> "
                        "replicated")
        return NamedSharding(mesh, PartitionSpec())

    return jax.tree_util.tree_map(one, batch_tree)


def cache_shardings(model, mesh: Mesh, batch: int, cache_len: int,
                    window=None,
                    report: Optional[ShardingReport] = None,
                    profile: str = "tp"):
    """KV/SSM decode-cache shardings from the cache ParamSpec tree: batch dim
    over the data axes, cache head / inner dims over `model`."""
    specs = model.cache_specs(batch, cache_len, window)
    return shardings_for_specs(specs, mesh, server_rules(mesh, profile),
                               report)
