"""The one training loop behind every entry point.

``fit(ctx, strategy, callbacks)`` drives any registered protocol strategy:
per epoch it asks the strategy for a plan, iterates the strategy's batch
stream, applies the strategy's step, runs the end-of-epoch aggregation
hook, and emits events (run_begin / epoch_begin / plan / step_end /
epoch_end / run_end) that callbacks turn into evaluation, timing, straggler
accounting, and checkpoints. ``repro.api.run`` builds the context from an
ExperimentSpec; the legacy ``repro.frameworks`` trainers build it from
already-constructed objects — both end here.

Telemetry (``ctx.spec.obs``, repro.obs): when enabled, the loop wraps each
phase in tracer spans under per-epoch ``epoch`` spans: ``plan`` (epoch
planning), then per step ``batch`` (host batch assembly; the strategy may
open children inside it through ``ctx.tracer``), ``step`` (the strategy's
step call, from entry until it returns) and ``callbacks`` (the monitor and
the ``step_end`` callbacks), and ``eval`` (end-of-epoch callbacks). It
feeds each step's plan segment to a live GPSL invariant monitor
(repro.obs.monitor), whose per-epoch summaries land in
``record.extras["gpsl_monitor"]``. Instrumentation touches no RNG and no
batch content: an instrumented run is bitwise-identical to a disabled one.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.api.events import EventBus
from repro.api.registry import ProtocolStrategy
from repro.obs import (maybe_jax_profiler, monitor_from_spec, null_tracer,
                       tracer_from_spec, write_outputs)


@dataclasses.dataclass
class History:
    """Per-epoch test accuracy + protocol extras (the stable result API)."""
    test_acc: List[float]
    extras: Dict[str, Any]

    @property
    def best(self) -> float:
        return max(self.test_acc) if self.test_acc else 0.0


@dataclasses.dataclass
class DataBundle:
    """The materialized data a run consumes.

    ``train`` is the pooled (features, labels) (CL); ``store`` the federated
    ClientStore (SL/FL/SFL/PSL); ``lm_data`` per-client token arrays
    (synthetic_lm); ``test`` the held-out (features, labels) or None.
    """
    kind: str = "synthetic_classification"
    train: Optional[Tuple] = None
    test: Optional[Tuple] = None
    store: Any = None
    lm_data: Optional[List] = None
    pop: Any = None
    seq_len: Optional[int] = None       # synthetic_lm: training seq length

    @classmethod
    def from_store(cls, store, test=None, train=None):
        return cls(store=store, test=test, train=train,
                   pop=store.population if store is not None else None)


@dataclasses.dataclass
class RunContext:
    """Everything a strategy may consult: built objects + the spec axes."""
    model: Any
    optimizer: Any
    data: DataBundle
    spec: Any                       # ExperimentSpec (or a spec-like shim)
    seed: int = 0
    mesh: Any = None                # prebuilt device mesh (sharded engine)
    tracer: Any = dataclasses.field(default_factory=null_tracer)  # fit() sets

    @property
    def protocol(self):
        return self.spec.protocol

    @property
    def sampler(self):
        return self.spec.sampler

    @property
    def execution(self):
        return self.spec.execution


@dataclasses.dataclass
class RunRecord:
    """Mutable sink the loop and callbacks write into."""
    test_acc: List[float] = dataclasses.field(default_factory=list)
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)
    step_metrics: List[Dict[str, Any]] = dataclasses.field(
        default_factory=list)
    steps: int = 0


@dataclasses.dataclass
class RunResult:
    """What a run returns: the History plus final params and step metrics."""
    history: History
    params: Any
    step_metrics: List[Dict[str, float]]
    state: Any = None               # final protocol state (engine access)

    @property
    def test_acc(self) -> List[float]:
        return self.history.test_acc

    @property
    def best(self) -> float:
        return self.history.best


_END = object()                       # batch-stream exhaustion sentinel


def fit(ctx: RunContext, strategy: ProtocolStrategy,
        callbacks=(), tracer=None) -> RunResult:
    """Run ``strategy`` under ``ctx`` for ``ctx.protocol.epochs`` epochs.

    ``tracer`` defaults to one built from ``ctx.spec.obs`` (the shared
    no-op NullTracer when absent or disabled); pass an explicit
    ``repro.obs.Tracer`` to collect spans programmatically. The strategy
    sees it as ``ctx.tracer``.
    """
    obs = getattr(ctx.spec, "obs", None)
    if tracer is None:
        tracer = tracer_from_spec(
            obs, meta={"kind": "train",
                       "protocol": getattr(ctx.protocol, "name", "?")})
    ctx = dataclasses.replace(ctx, tracer=tracer)
    record = RunRecord()
    bus = EventBus(callbacks, ctx, record)
    pstate = strategy.setup(ctx)
    max_steps = ctx.execution.max_steps
    bus.emit("run_begin")
    stop = False
    pop = getattr(ctx.data, "pop", None)
    with maybe_jax_profiler(obs), tracer.span("run", cat="train"):
        for epoch in range(ctx.protocol.epochs):
            with tracer.span("epoch", cat="train", epoch=epoch):
                bus.emit("epoch_begin", epoch=epoch)
                with tracer.span("plan", cat="plan", epoch=epoch):
                    plan = strategy.plan_epoch(ctx, epoch)
                if plan is not None:
                    bus.emit("plan", epoch=epoch, plan=plan)
                monitor = None
                if plan is not None and pop is not None:
                    monitor = monitor_from_spec(
                        obs, pop, plan.global_batch_size, epoch=epoch,
                        num_steps=plan.num_steps, tracer=tracer)
                epoch_step = 0
                batches = iter(strategy.epoch_batches(ctx, pstate, plan,
                                                      epoch))
                while True:
                    with tracer.span("batch", cat="data", epoch=epoch):
                        item = next(batches, _END)
                    if item is _END:
                        break
                    with tracer.span("step", cat="step", epoch=epoch,
                                     step=record.steps):
                        pstate, metrics = strategy.step(ctx, pstate, item)
                    record.step_metrics.append(metrics)
                    record.steps += 1
                    with tracer.span("callbacks", cat="step"):
                        if monitor is not None \
                                and epoch_step < plan.num_steps:
                            monitor.observe_plan_step(plan, epoch_step)
                        bus.emit("step_end", epoch=epoch, step=record.steps,
                                 metrics=metrics, info=item.info)
                    epoch_step += 1
                    if max_steps is not None and record.steps >= max_steps:
                        stop = True
                        break
                if monitor is not None:
                    summary = monitor.finish()
                    record.extras.setdefault("gpsl_monitor", []).append(
                        summary.to_dict())
                pstate = strategy.end_epoch(ctx, pstate, epoch)
                with tracer.span("eval", cat="eval", epoch=epoch):
                    bus.emit("epoch_end", epoch=epoch,
                             params=strategy.eval_params(ctx, pstate))
            if stop:
                break
        strategy.finalize(ctx, pstate, record)
        params = strategy.eval_params(ctx, pstate)
        bus.emit("run_end", params=params)
    write_outputs(tracer, obs)
    # one host sync at the end instead of one per step
    step_metrics = [{k: float(v) for k, v in m.items()}
                    for m in record.step_metrics]
    return RunResult(history=History(record.test_acc, record.extras),
                     params=params, step_metrics=step_metrics,
                     state=pstate)
