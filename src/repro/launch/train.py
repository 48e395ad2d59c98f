"""End-to-end PSL training CLI — a thin shell over ``repro.api.run``.

The experiment is one :class:`repro.api.ExperimentSpec`; the CLI loads it
from ``--config spec.json``, applies dotted ``--set key=value`` overrides,
and hands it to the runner (spec → model/data/engine → shared loop). A few
legacy convenience flags (``--arch``, ``--steps``, ``--mesh``, …) map onto
spec overrides so existing invocations keep working.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b --reduced \
      --steps 100 --global-batch 16 --seq-len 128 --method ugs
  PYTHONPATH=src python -m repro.launch.train --config spec.json \
      --set sampler.method=lds --set sampler.kwargs.delta=1.5
"""
from __future__ import annotations

import argparse
import time
from typing import List

import jax
import numpy as np

from repro import api
from repro.data.federated import build_lm_client_store as _build_lm_store
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import TrainState


def build_lm_client_store(cfg, num_clients: int, sequences: int,
                          seq_len: int, seed: int = 0):
    """Deprecated: use repro.data.federated.build_lm_client_store."""
    return _build_lm_store(cfg.vocab_size, num_clients, sequences, seq_len,
                           seed=seed)


class PSLTrainer:
    """Sharded PSL trainer over an arbitrary (data × model) mesh.

    Deprecated epoch-level driver kept for existing callers: the engine
    lowering lives in ``repro.launch.distributed.ShardedPSLEngine`` and
    the plan-driven LM batch assembly in
    ``repro.api.protocols.lm_plan_batches`` — the same pieces the "psl"
    strategy composes when ``repro.api.run`` executes an LM spec.
    """

    def __init__(self, cfg, optimizer=None, mesh=None,
                 aggregation: str = "global_mean", profile: str = "tp",
                 lowering: str = "gspmd", microbatches: int = 1):
        from repro import optim as optim_lib
        from repro.launch.distributed import (ShardedPSLEngine,
                                              assign_clients_to_shards)
        from repro.launch.mesh import make_host_mesh
        from repro.models import build_model
        self.cfg = cfg
        self.model = build_model(cfg)
        self.optimizer = optimizer or optim_lib.adamw(1e-3)
        self.mesh = mesh or make_host_mesh()
        self.aggregation = aggregation
        self.engine = ShardedPSLEngine(self.model, self.optimizer,
                                       mesh=self.mesh, profile=profile,
                                       lowering=lowering,
                                       microbatches=microbatches)
        self._assign = assign_clients_to_shards
        self.report = self.engine.report

    def init_state(self, seed: int = 0) -> TrainState:
        return self.engine.init_state(seed)

    def train_epoch(self, state: TrainState, data, pop, plan,
                    seq_len: int, seed: int = 0, max_steps=None):
        """One PSL epoch from an EpochPlan over per-client token arrays."""
        from repro.api.protocols import lm_plan_batches
        shard_of_client = self._assign(len(data), self.engine.num_shards)
        metrics_hist = []
        for t, host in enumerate(lm_plan_batches(
                data, pop, plan, seq_len, self.aggregation,
                shard_of_client, seed=seed)):
            if max_steps is not None and t >= max_steps:
                break
            state, metrics = self.engine.step(state,
                                              self.engine.put_batch(host))
            metrics_hist.append(
                {k: float(v) for k, v in metrics.items()})
        return state, metrics_hist


def default_lm_spec() -> api.ExperimentSpec:
    """The CLI's baseline spec: reduced-friendly LM PSL on the host mesh."""
    return api.ExperimentSpec(
        model=api.ModelSpec(arch="granite-3-2b", reduced=False),
        optimizer=api.OptimizerSpec(name="adamw", lr=1e-3,
                                    weight_decay=0.1),
        data=api.DataSpec(kind="synthetic_lm", num_clients=8,
                          sequences=2048, seq_len=128),
        sampler=api.SamplerSpec(method="ugs"),
        protocol=api.ProtocolSpec(name="psl", epochs=1,
                                  global_batch_size=16),
        execution=api.ExecutionSpec(engine="sharded", max_steps=50),
        eval=api.EvalSpec(enabled=False))


def _legacy_overrides(args) -> List[str]:
    """Map the convenience flags onto dotted spec overrides."""
    sets: List[str] = []

    def add(key, value):
        # bare strings hit parse_set's plain-string fallback; numbers and
        # booleans round-trip through its JSON parse
        if value is not None:
            sets.append(f"{key}={value}")

    add("model.arch", args.arch)
    if args.reduced is not None:        # tri-state: --reduced/--no-reduced
        add("model.reduced", "true" if args.reduced else "false")
    add("execution.max_steps", args.steps)
    add("protocol.epochs", args.epochs)
    add("protocol.global_batch_size", args.global_batch)
    add("data.seq_len", args.seq_len)
    add("data.num_clients", args.clients)
    add("data.sequences", args.sequences)
    add("sampler.method", args.method)
    add("sampler.backend", args.planner_backend)
    add("sampler.plan_format", args.plan_format)
    add("protocol.aggregation", args.aggregation)
    add("execution.mesh", args.mesh)
    add("execution.sharding", args.sharding)
    add("execution.lowering", args.lowering)
    add("execution.microbatches", args.microbatches)
    add("optimizer.lr", args.lr)
    add("execution.checkpoint", args.checkpoint)
    add("seed", args.seed)
    add("data.seed", args.seed)
    if args.d_model:
        add("model.overrides.d_model", args.d_model)
        add("model.overrides.num_heads", max(4, args.d_model // 64))
        add("model.overrides.num_kv_heads", max(2, args.d_model // 128))
        add("model.overrides.d_ff", args.d_model * 4)
    if args.layers:
        add("model.overrides.num_layers", args.layers)
    return sets


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default=None, metavar="SPEC_JSON",
                    help="ExperimentSpec JSON file (see docs/api.md)")
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    dest="sets",
                    help="dotted spec override, e.g. protocol.epochs=2 or "
                         "sampler.kwargs.delta=1.5 (repeatable)")
    ap.add_argument("--print-spec", action="store_true",
                    help="print the resolved spec JSON and exit")
    # legacy convenience flags (all map onto --set overrides)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=None)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--global-batch", type=int, default=None)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--sequences", type=int, default=None)
    ap.add_argument("--method", default=None,
                    choices=["ugs", "lds", "fpls", "fls"])
    ap.add_argument("--planner-backend", default=None,
                    choices=["numpy", "jax", "auto"],
                    help="epoch-plan engine: numpy reference (default; "
                         "seed-for-seed reproducible), vectorized jax, or "
                         "auto (jax for large client counts)")
    ap.add_argument("--plan-format", default=None, dest="plan_format",
                    choices=["dense", "sparse", "auto"],
                    help="epoch-plan storage: dense (T, K) matrix, sparse "
                         "per-step segments (million-client path), or auto")
    ap.add_argument("--aggregation", default=None)
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="(data × model) mesh for the sharded engine, e.g. "
                         "'4x1' or '2x2'; default: one data axis over all "
                         "visible devices. On CPU, force host devices with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count"
                         "=N before launch (docs/training.md)")
    ap.add_argument("--sharding", default=None,
                    choices=["tp", "fsdp", "ddp"],
                    help="server-segment sharding profile")
    ap.add_argument("--lowering", default=None,
                    choices=["gspmd", "shard_map"],
                    help="gspmd: jit with profile shardings (production); "
                         "shard_map: explicit data-parallel program "
                         "(equivalence/diagnostics; use a Dx1 mesh)")
    ap.add_argument("--microbatches", type=int, default=None,
                    help="gradient-accumulation slices of the global batch")
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--d-model", type=int, default=None,
                    help="override d_model (e.g. ~100M-param presets)")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--seed", type=int, default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.config:
        spec = api.load_any_spec(args.config)
        if not isinstance(spec, api.ExperimentSpec):
            raise SystemExit(f"{args.config} is a {spec.kind!r} spec; "
                             f"the train CLI needs kind 'experiment' "
                             f"(use repro.launch.serve for serving)")
    else:
        spec = default_lm_spec()
    spec = api.apply_overrides(spec, _legacy_overrides(args) + args.sets)
    if args.print_spec:
        print(spec.to_json())
        return

    ctx = api.build_context(spec)
    shapes = jax.eval_shape(ctx.model.init, jax.random.PRNGKey(spec.seed))
    n_params = sum(int(np.prod(x.shape)) for x in
                   jax.tree_util.tree_leaves(shapes))
    print(f"arch={ctx.model.cfg.name} params={n_params/1e6:.1f}M "
          f"clients={ctx.data.pop.num_clients} "
          f"D0={ctx.data.pop.total_size} method={spec.sampler.method}")
    t0 = time.time()
    result = api.run(spec, callbacks=[api.ConsoleLogger(every=10)],
                     ctx=ctx)
    fallbacks = result.history.extras.get("sharding_fallbacks")
    if fallbacks:
        print("sharding fallbacks:", "; ".join(fallbacks))
    steps = len(result.step_metrics)
    if steps:
        print(f"{steps} steps in {time.time() - t0:.1f}s "
              f"(final loss {result.step_metrics[-1]['loss']:.4f})")
    if spec.execution.checkpoint:
        print("checkpoint saved to", spec.execution.checkpoint)


if __name__ == "__main__":
    main()
