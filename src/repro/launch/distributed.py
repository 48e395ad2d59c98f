"""Mesh-parallel GPSL training engine: the fused PSL step on a device mesh.

The paper's protocol fixes the effective global batch regardless of the
client population; this module fixes the *device program* regardless of the
client population too, by lowering the fused step of ``repro.core.psl`` onto
a (data × model) mesh. Two lowerings of the same optimization step:

  * ``lowering="gspmd"`` — the production path: ``jax.jit`` with explicit
    in/out shardings. Client-segment params are replicated across the data
    axes (every data shard holds the identical client copy, the paper's
    invariant), server-segment params follow the ``server_rules`` profiles
    of ``repro.sharding`` (tp / fsdp / ddp), the global batch is sharded on
    its leading axis (``batch_shardings``), and the TrainState is donated.
  * ``lowering="shard_map"`` — the *explicit* data-parallel program: the
    per-shard weighted-SUM gradients of ``accumulate_sum_grads`` are
    ``psum``-ed over the ``data`` axis and normalized once by the global
    weight mass. Because every slot carries its aggregation weight (padding
    slots carry 0), the psum-of-sums ÷ total-weight recombination computes
    exactly the fused step's gradient no matter how slots landed on shards.
    Used by the equivalence tests to pin down the collective structure that
    GSPMD must reproduce; params stay replicated (pure DP — run it on a
    D×1 mesh).

Both compose with microbatch gradient accumulation (``microbatches > 1``
scans slices of the per-shard batch) for global batches larger than
per-device activation memory.

Straggler model: ``shard_arrivals`` maps the plan row + per-client delays
(``repro.core.straggler.assign_delays``) to per-data-shard arrival times —
a shard can start its forward pass once *its* clients' cut activations have
arrived, so the step completes at ``base + max_shard(arrival)`` and the
max−min arrival spread measures how much straggler skew the shard layout
leaves on the table.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro import sharding as shard_lib
from repro.core.psl import (accumulate_sum_grads, make_train_step,
                            normalize_sum_grads)
from repro.launch.mesh import make_training_mesh
from repro.optim import Optimizer, TrainState, apply_updates


def data_shard_count(mesh, profile: str = "tp") -> int:
    """Number of batch shards the mesh/profile splits the global batch into."""
    axes = shard_lib.batch_axes(mesh, profile)
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def assign_clients_to_shards(num_clients: int, num_shards: int) -> np.ndarray:
    """Static client → data-shard map (round-robin). The serving analogue of
    slot assignment: client k's cut activations always land on shard
    k mod S, so per-shard arrival depends only on that shard's clients."""
    return np.arange(num_clients, dtype=np.int64) % max(num_shards, 1)


def shard_arrivals(sizes_row: np.ndarray, delays: np.ndarray,
                   shard_of_client: np.ndarray,
                   num_shards: int) -> np.ndarray:
    """(S,) per-shard arrival times for one global batch.

    Shard s is ready when the slowest of *its* contributing clients
    (B_k^t > 0, shard_of_client[k] == s) has sent; shards with no
    contributing client are ready at 0.
    """
    sizes_row = np.asarray(sizes_row)
    contributing = sizes_row > 0
    eff = np.where(contributing, np.asarray(delays, np.float64), -np.inf)
    arrivals = np.full(num_shards, -np.inf)
    np.maximum.at(arrivals, shard_of_client, eff)
    return np.where(np.isfinite(arrivals), arrivals, 0.0)


@dataclasses.dataclass(frozen=True)
class StepTiming:
    """Simulated distributed step timing (straggler accounting)."""
    step_ms: float          # base + slowest shard's arrival
    shard_skew_ms: float    # max − min arrival over contributing shards


def step_timing(sizes_row: np.ndarray, delays: np.ndarray,
                shard_of_client: np.ndarray, num_shards: int,
                base_step_ms: float = 60.0) -> StepTiming:
    arr = shard_arrivals(sizes_row, delays, shard_of_client, num_shards)
    return StepTiming(step_ms=float(base_step_ms + arr.max()),
                      shard_skew_ms=float(arr.max() - arr.min()))


_METRIC_KEYS = ("loss", "accuracy", "aux_loss", "tokens", "grad_norm")


class ShardedPSLEngine:
    """The fused PSL step lowered onto a (data × model) mesh.

    Usage::

        engine = ShardedPSLEngine(model, optimizer, mesh=mesh)
        state = engine.init_state(seed)
        state, metrics = engine.step(state, engine.put_batch(host_batch))

    ``put_batch`` transfers a host batch with its leading axis sharded over
    the data axes (one gather per shard); ``step`` donates the TrainState.
    """

    def __init__(self, model, optimizer: Optimizer, mesh=None,
                 profile: str = "tp", lowering: str = "gspmd",
                 microbatches: int = 1, donate: bool = True):
        if lowering not in ("gspmd", "shard_map"):
            raise ValueError(f"unknown lowering {lowering!r}")
        self.model = model
        self.optimizer = optimizer
        self.mesh = mesh if mesh is not None else make_training_mesh()
        self.profile = profile
        self.lowering = lowering
        self.microbatches = microbatches
        self.donate = donate
        self.report = shard_lib.ShardingReport()
        self._state_sh = shard_lib.train_state_shardings(
            model, optimizer, self.mesh,
            self.report if lowering == "gspmd" else None, profile=profile)
        if lowering == "shard_map":
            # explicit DP: params live replicated on every shard (the
            # profile layout — and its fallback notes — do not apply)
            rep = shard_lib.replicated(self.mesh)
            self._state_sh = jax.tree_util.tree_map(lambda _: rep,
                                                    self._state_sh)
        self.params_sh = self._state_sh.params
        self.num_shards = data_shard_count(self.mesh, profile)
        self._step: Optional[Callable] = None
        self._batch_sh = None

    # ------------------------------------------------------------- state
    def init_state(self, seed: int = 0) -> TrainState:
        with self.mesh:
            params = jax.jit(self.model.init,
                             out_shardings=self.params_sh)(
                jax.random.PRNGKey(seed))
            opt_state = jax.jit(self.optimizer.init,
                                out_shardings=self._state_sh.opt_state)(
                params)
        return TrainState(params=params, opt_state=opt_state,
                          step=jnp.zeros((), jnp.int32))

    # ------------------------------------------------------------- batch
    def batch_shardings(self, batch: Dict[str, Any]):
        if self._batch_sh is None:
            b = jax.tree_util.tree_leaves(batch)[0].shape[0]
            self._batch_sh = shard_lib.batch_shardings(
                batch, self.mesh, b, self.report, profile=self.profile)
        return self._batch_sh

    def put_batch(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        """Host batch → device batch, leading axis sharded over the data
        axes, in one transfer: each data shard receives only its B/S slice
        of the global batch (the sharded gather driven by the planner's
        schedule)."""
        with self.mesh:
            return jax.device_put(batch, self.batch_shardings(batch))

    # -------------------------------------------------------------- step
    def _constrained(self, fn: Callable) -> Callable:
        """``fn`` traced with the residual stream's batch axis pinned to
        the profile's batch axes.

        Under fsdp/ddp the weights are sharded over the same mesh axes as
        the batch; left alone, GSPMD may resolve that conflict by
        gathering the batch onto every device, which replicates every
        activation (and, at full width, overflows HBM). The constraint
        keeps activations batch-sharded and gathers weights instead. The
        tp profile keeps its own propagation."""
        if self.profile not in ("fsdp", "ddp"):
            return fn
        ns = shard_lib.activation_sharding_for(self.mesh, "batch")

        def traced(*args):
            with shard_lib.activation_sharding(ns):
                return fn(*args)
        return traced

    def _build_gspmd(self, batch) -> Callable:
        step = self._constrained(make_train_step(
            self.model, self.optimizer, microbatches=self.microbatches))
        rep = shard_lib.replicated(self.mesh)
        metrics_sh = {k: rep for k in _METRIC_KEYS}
        return jax.jit(step,
                       in_shardings=(self._state_sh,
                                     self.batch_shardings(batch)),
                       out_shardings=(self._state_sh, metrics_sh),
                       donate_argnums=(0,) if self.donate else ())

    def _build_shard_map(self, batch) -> Callable:
        mesh, model, optimizer = self.mesh, self.model, self.optimizer
        m = self.microbatches

        def per_shard(state: TrainState, local_batch):
            # global weight mass first (padding slots weigh 0, so shard
            # placement of padding is irrelevant), then psum of the local
            # weighted-sum grads and one normalization — exactly the fused
            # step's gradient, reassociated.
            w_local = local_batch["weights"].astype(jnp.float32).sum()
            w_total = jax.lax.psum(w_local, "data")
            g_sum, m_sum = accumulate_sum_grads(model, state.params,
                                                local_batch, m, w_total)
            g_sum = jax.lax.psum(g_sum, "data")
            m_sum = jax.lax.psum(m_sum, "data")
            # aux_sum was psum'd over shards too: normalize by shards·M
            grads, metrics = normalize_sum_grads(
                g_sum, m_sum, mesh.shape["data"] * m)
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
            params = apply_updates(state.params, updates)
            metrics["grad_norm"] = jnp.sqrt(sum(
                jnp.sum(g.astype(jnp.float32) ** 2)
                for g in jax.tree_util.tree_leaves(grads)))
            return TrainState(params=params, opt_state=opt_state,
                              step=state.step + 1), metrics

        rep = PartitionSpec()
        state_specs = jax.tree_util.tree_map(lambda _: rep, self._state_sh)
        batch_specs = jax.tree_util.tree_map(
            lambda _: PartitionSpec("data"), batch)
        metrics_specs = {k: rep for k in _METRIC_KEYS}
        mapped = jax.shard_map(per_shard, mesh=mesh,
                               in_specs=(state_specs, batch_specs),
                               out_specs=(state_specs, metrics_specs),
                               check_vma=False)
        return jax.jit(mapped,
                       donate_argnums=(0,) if self.donate else ())

    def step_fn(self, batch) -> Callable:
        if self._step is None:
            build = (self._build_shard_map if self.lowering == "shard_map"
                     else self._build_gspmd)
            self._step = build(batch)
        return self._step

    def step(self, state: TrainState, batch: Dict[str, Any]
             ) -> Tuple[TrainState, Dict[str, Any]]:
        with self.mesh:
            return self.step_fn(batch)(state, batch)

    # -------------------------------------------------------- diagnostics
    def grads(self, state: TrainState, batch: Dict[str, Any]):
        """Normalized full-batch gradient under this engine's lowering —
        the quantity the equivalence tests compare against the single-device
        fused backward and against ``decomposed_grads``."""
        from repro.core.psl import fused_grads

        def g(params, b):
            return fused_grads(self.model, params, b, self.microbatches)[0]

        with self.mesh:
            if self.lowering == "gspmd":
                fn = jax.jit(self._constrained(g),
                             in_shardings=(self.params_sh,
                                           self.batch_shardings(batch)))
                return fn(state.params, batch)

            def per_shard(params, local_batch):
                w_total = jax.lax.psum(
                    local_batch["weights"].astype(jnp.float32).sum(), "data")
                g_sum, m_sum = accumulate_sum_grads(
                    self.model, params, local_batch, self.microbatches,
                    w_total)
                g_sum = jax.lax.psum(g_sum, "data")
                denom = jnp.maximum(jax.lax.psum(m_sum["tokens"], "data"),
                                    1e-6)
                return jax.tree_util.tree_map(lambda x: x / denom, g_sum)

            rep = jax.tree_util.tree_map(lambda _: PartitionSpec(),
                                         self.params_sh)
            batch_specs = jax.tree_util.tree_map(
                lambda _: PartitionSpec("data"), batch)
            fn = jax.jit(jax.shard_map(per_shard, mesh=self.mesh,
                                       in_specs=(rep, batch_specs),
                                       out_specs=rep, check_vma=False))
            return fn(state.params, batch)
