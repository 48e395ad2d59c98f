"""Built-in protocol strategies: CL, SL, FL, SFL, and PSL.

Each protocol from the paper's comparison (Sec. V) is a small strategy
object — plan, batch assembly, step, aggregation hook — registered under
its name and driven by the shared loop in :mod:`repro.api.loop`. The
implementations are transcriptions of the original reference trainers and
reproduce their trajectories seed-for-seed (tests/test_api.py proves the
PSL path bitwise against a frozen copy of the pre-refactor loop).

PSL consults the ExecutionSpec: engine "fused" jits the fused step on the
default device, with the state donated; engine "sharded" (and every LM
workload) lowers it through repro.launch.distributed.ShardedPSLEngine with
per-shard batch placement and straggler arrival accounting.
"""
from __future__ import annotations

from typing import Any, Dict, Iterator, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.evaluation import batch_from
from repro.api.registry import ProtocolStrategy, StepItem, register_protocol
from repro.core import sampling as sampling_lib
from repro.core.psl import make_train_step, slot_weights_segments
from repro.data.federated import GlobalBatchIterator
from repro.optim import TrainState


_END = object()


def _drawn(tracer, draws) -> Iterator[Any]:
    """``draws``, each ``next`` under a ``batch.draw`` span (the span
    closes before the item is handed on, so none stays open across a
    ``yield``)."""
    it = iter(draws)
    while True:
        with tracer.span("batch.draw", cat="data"):
            item = next(it, _END)
        if item is _END:
            return
        yield item


def _fresh_state(model, optimizer, seed: int) -> TrainState:
    params = model.init(jax.random.PRNGKey(seed))
    return TrainState(params, optimizer.init(params),
                      jnp.zeros((), jnp.int32))


def _donating_step(ctx):
    """The jitted fused step for a strategy that owns every buffer of the
    state it passes in: the state is donated, so the new one is written
    into its buffers instead of into fresh allocations, and the old
    state's arrays are deleted by the call."""
    return jax.jit(make_train_step(ctx.model, ctx.optimizer),
                   donate_argnums=(0,))


class _SingleStateStrategy(ProtocolStrategy):
    """Shared skeleton for protocols training one TrainState end to end."""

    def setup(self, ctx) -> Dict[str, Any]:
        return {"state": _fresh_state(ctx.model, ctx.optimizer, ctx.seed),
                "step": _donating_step(ctx),
                "rng": np.random.default_rng(ctx.seed)}

    def step(self, ctx, pstate, item: StepItem):
        pstate["state"], metrics = pstate["step"](pstate["state"],
                                                  item.batch)
        return pstate, metrics

    def eval_params(self, ctx, pstate):
        return pstate["state"].params


@register_protocol("cl")
class CLStrategy(_SingleStateStrategy):
    """Central learning on the pooled dataset (upper baseline)."""

    def epoch_batches(self, ctx, pstate, plan, epoch) -> Iterator[StepItem]:
        features, labels = ctx.data.train
        bs = ctx.protocol.batch_size
        n = len(features)
        order = pstate["rng"].permutation(n)
        for i in range(0, n - bs + 1, bs):
            idx = order[i:i + bs]
            yield StepItem(batch_from(features[idx], labels[idx]))


@register_protocol("sl")
class SLStrategy(_SingleStateStrategy):
    """Sequential split learning: clients take turns; weights hop along."""

    def epoch_batches(self, ctx, pstate, plan, epoch) -> Iterator[StepItem]:
        store = ctx.data.store
        rng = pstate["rng"]
        batch_size = ctx.protocol.batch_size
        for k in rng.permutation(store.num_clients):
            feats, labs = store.features[k], store.labels[k]
            order = rng.permutation(len(feats))
            bs = min(batch_size, len(feats))
            for i in range(0, len(feats) - bs + 1, bs):
                idx = order[i:i + bs]
                yield StepItem(batch_from(feats[idx], labs[idx]), scope=k)


def _tree_weighted_sum(trees, weights):
    return jax.tree_util.tree_map(
        lambda *xs: sum(w * x.astype(jnp.float32) for w, x in
                        zip(weights, xs)).astype(xs[0].dtype), *trees)


@register_protocol("fl")
class FLStrategy(ProtocolStrategy):
    """FedAvg: local epochs on full model copies; size-weighted average."""

    def setup(self, ctx) -> Dict[str, Any]:
        k = ctx.data.store.num_clients
        local_epochs = ctx.protocol.local_epochs
        if local_epochs is None:
            local_epochs = max(1, int(np.log2(k)) - 1)   # paper App. A
        params = ctx.model.init(jax.random.PRNGKey(ctx.seed))
        sizes = ctx.data.pop.dataset_sizes.astype(np.float64)
        # not donated: each client's state starts from global_params
        return {"global_params": params,
                "step": jax.jit(make_train_step(ctx.model, ctx.optimizer)),
                "rng": np.random.default_rng(ctx.seed),
                "local_epochs": local_epochs,
                "weights": sizes / sizes.sum(),
                "locals": [], "st": None, "client": None}

    def _push_local(self, pstate):
        if pstate["st"] is not None:
            pstate["locals"].append(pstate["st"].params)

    def epoch_batches(self, ctx, pstate, plan, epoch) -> Iterator[StepItem]:
        store = ctx.data.store
        rng = pstate["rng"]
        batch_size = ctx.protocol.batch_size
        for ki in range(store.num_clients):
            feats, labs = store.features[ki], store.labels[ki]
            bs = min(batch_size, len(feats))
            for _le in range(pstate["local_epochs"]):
                order = rng.permutation(len(feats))
                for i in range(0, len(feats) - bs + 1, bs):
                    idx = order[i:i + bs]
                    yield StepItem(batch_from(feats[idx], labs[idx]),
                                   scope=ki)

    def step(self, ctx, pstate, item: StepItem):
        if item.scope != pstate["client"]:
            self._push_local(pstate)
            gp = pstate["global_params"]
            pstate["st"] = TrainState(gp, ctx.optimizer.init(gp),
                                      jnp.zeros((), jnp.int32))
            pstate["client"] = item.scope
        pstate["st"], metrics = pstate["step"](pstate["st"], item.batch)
        return pstate, metrics

    def end_epoch(self, ctx, pstate, epoch):
        self._push_local(pstate)
        pstate["global_params"] = _tree_weighted_sum(pstate["locals"],
                                                     pstate["weights"])
        pstate.update(locals=[], st=None, client=None)
        return pstate

    def eval_params(self, ctx, pstate):
        return pstate["global_params"]


@register_protocol("sfl")
class SFLStrategy(ProtocolStrategy):
    """SplitFed-V1: shared server segment updated every batch; client
    segments FedAvg'd at the end of each round."""

    def setup(self, ctx) -> Dict[str, Any]:
        sizes = ctx.data.pop.dataset_sizes.astype(np.float64)
        # not donated: each client's state starts from params["client"]
        # and the shared server segment
        return {"params": ctx.model.init(jax.random.PRNGKey(ctx.seed)),
                "step": jax.jit(make_train_step(ctx.model, ctx.optimizer)),
                "rng": np.random.default_rng(ctx.seed),
                "weights": sizes / sizes.sum(),
                "client_params": [], "server_side": None,
                "st": None, "client": None}

    def epoch_batches(self, ctx, pstate, plan, epoch) -> Iterator[StepItem]:
        store = ctx.data.store
        rng = pstate["rng"]
        batch_size = ctx.protocol.batch_size
        for ki in range(store.num_clients):
            feats, labs = store.features[ki], store.labels[ki]
            bs = min(batch_size, len(feats))
            order = rng.permutation(len(feats))
            for i in range(0, len(feats) - bs + 1, bs):
                idx = order[i:i + bs]
                yield StepItem(batch_from(feats[idx], labs[idx]), scope=ki)

    def _push_local(self, pstate):
        if pstate["st"] is not None:
            pstate["client_params"].append(pstate["st"].params["client"])
            pstate["server_side"] = pstate["st"].params["server"]

    def step(self, ctx, pstate, item: StepItem):
        if item.scope != pstate["client"]:
            self._push_local(pstate)
            server = pstate["server_side"]
            if server is None:
                server = pstate["params"]["server"]
            seg = {"client": pstate["params"]["client"], "server": server}
            pstate["st"] = TrainState(seg, ctx.optimizer.init(seg),
                                      jnp.zeros((), jnp.int32))
            pstate["client"] = item.scope
        pstate["st"], metrics = pstate["step"](pstate["st"], item.batch)
        return pstate, metrics

    def end_epoch(self, ctx, pstate, epoch):
        self._push_local(pstate)
        pstate["params"] = {
            "client": _tree_weighted_sum(pstate["client_params"],
                                         pstate["weights"]),
            "server": pstate["server_side"]}
        pstate.update(client_params=[], server_side=None, st=None,
                      client=None)
        return pstate

    def eval_params(self, ctx, pstate):
        return pstate["params"]


# ---------------------------------------------------------------------------
# PSL — the paper's protocol, fused or sharded execution
# ---------------------------------------------------------------------------

def lm_plan_batches(data: List[np.ndarray], pop, plan, seq_len: int,
                    aggregation: str, shard_of_client: np.ndarray,
                    seed: int = 0) -> Iterator[Dict[str, np.ndarray]]:
    """Host LM batches for one epoch plan (the plan-driven token pipeline).

    One epoch of PSL-LM batch assembly: per step, each client contributes
    its next B_k^t locally-shuffled sequences, slots are grouped by the
    contributing client's home data shard, the final ragged step is padded
    with weight-0 slots, and per-slot aggregation weights are broadcast
    over the sequence axis. Shared by the PSL strategy's LM path and the
    legacy ``launch.train.PSLTrainer``.
    """
    rng = np.random.default_rng(seed)
    orders = [rng.permutation(len(d)) for d in data]
    cursors = np.zeros(len(data), np.int64)
    b = plan.global_batch_size
    for t in range(plan.num_steps):
        # stream the step's active-client segment; only active clients are
        # visited (same visit order as the old dense scan: segment ids are
        # ascending, and the stable argsort groups them by home shard)
        seg_ids, seg_cnts = plan.step_segments(t)
        seg_ids = np.asarray(seg_ids, np.int64)
        rows, ids, cnt_runs = [], [], []
        for j in np.argsort(shard_of_client[seg_ids], kind="stable"):
            k = int(seg_ids[j])
            n = int(seg_cnts[j])
            idx = orders[k][cursors[k]:cursors[k] + n]
            cursors[k] += n
            rows.append(data[k][idx])
            ids.append(np.full(n, k))
            cnt_runs.append(np.full(n, n))
        toks = np.concatenate(rows)
        cids = np.concatenate(ids)
        slot_cnts = np.concatenate(cnt_runs)
        if toks.shape[0] < b:
            pad = b - toks.shape[0]
            toks = np.concatenate(
                [toks, np.zeros((pad, toks.shape[1]), toks.dtype)])
            cids = np.concatenate([cids, np.full(pad, -1)])
            slot_cnts = np.concatenate([slot_cnts, np.ones(pad, np.int64)])
        w = slot_weights_segments(cids, slot_cnts, pop.dataset_sizes,
                                  aggregation)
        yield {"tokens": toks[:, :seq_len].astype(np.int32),
               "labels": toks[:, 1:seq_len + 1].astype(np.int32),
               "weights": np.repeat(w[:, None], seq_len, 1)}


@register_protocol("psl")
class PSLStrategy(ProtocolStrategy):
    """Parallel split learning with global batch composition from an
    EpochPlan (UGS / LDS / FPLS / FLS via repro.core.sampling)."""

    def _sharded(self, ctx) -> bool:
        return (ctx.execution.engine == "sharded"
                or ctx.data.kind == "synthetic_lm")

    def setup(self, ctx) -> Dict[str, Any]:
        if not self._sharded(ctx):
            return {"state": _fresh_state(ctx.model, ctx.optimizer,
                                          ctx.seed),
                    "step": _donating_step(ctx), "engine": None,
                    "donated": None, "client_pack": None}
        from repro.launch.distributed import (ShardedPSLEngine,
                                              assign_clients_to_shards)
        engine = ShardedPSLEngine(
            ctx.model, ctx.optimizer, mesh=self._mesh(ctx),
            profile=ctx.execution.sharding,
            lowering=ctx.execution.lowering,
            microbatches=ctx.execution.microbatches)
        num_clients = (len(ctx.data.lm_data)
                       if ctx.data.kind == "synthetic_lm"
                       else ctx.data.store.num_clients)
        return {"state": engine.init_state(ctx.seed), "engine": engine,
                "shard_of_client": assign_clients_to_shards(
                    num_clients, engine.num_shards)}

    def _mesh(self, ctx):
        if ctx.mesh is not None:
            return ctx.mesh
        from repro.launch.mesh import make_host_mesh, make_training_mesh
        if ctx.execution.mesh:
            return make_training_mesh(ctx.execution.mesh)
        return make_host_mesh()

    def plan_epoch(self, ctx, epoch: int):
        return sampling_lib.make_plan(
            ctx.sampler.method, ctx.data.pop,
            ctx.protocol.global_batch_size, seed=ctx.seed + epoch,
            backend=ctx.sampler.backend,
            plan_format=ctx.sampler.plan_format, **ctx.sampler.kwargs)

    def epoch_batches(self, ctx, pstate, plan, epoch) -> Iterator[StepItem]:
        """One item per step; each host draw runs under a ``batch.draw``
        span and each put to the device under ``batch.put``."""
        engine = pstate["engine"]
        tracer = ctx.tracer
        if engine is None:
            for gb in _drawn(tracer, GlobalBatchIterator(
                    ctx.data.store, plan, ctx.protocol.aggregation,
                    seed=ctx.seed * 1000 + epoch)):
                with tracer.span("batch.put", cat="data"):
                    batch = batch_from(gb["features"], gb["labels"],
                                       gb["weights"])
                yield StepItem(batch)
        elif ctx.data.kind == "synthetic_lm":
            for host in _drawn(tracer, lm_plan_batches(
                    ctx.data.lm_data, ctx.data.pop, plan, ctx.data.seq_len,
                    ctx.protocol.aggregation, pstate["shard_of_client"],
                    seed=ctx.seed + epoch)):
                with tracer.span("batch.put", cat="data"):
                    batch = engine.put_batch(host)
                yield StepItem(batch)
        else:
            for gb in _drawn(tracer, GlobalBatchIterator(
                    ctx.data.store, plan, ctx.protocol.aggregation,
                    seed=ctx.seed * 1000 + epoch,
                    num_shards=engine.num_shards)):
                info = None
                if ctx.protocol.track_tpe:
                    from repro.launch.distributed import step_timing
                    tm = step_timing(plan.step_sizes(gb["step"]),
                                     ctx.data.pop.delays,
                                     pstate["shard_of_client"],
                                     engine.num_shards,
                                     base_step_ms=ctx.protocol.base_step_ms)
                    info = {"step_ms": tm.step_ms,
                            "shard_skew_ms": tm.shard_skew_ms}
                with tracer.span("batch.put", cat="data"):
                    batch = engine.put_batch({  # host numpy → one sharded put
                        "images": np.asarray(gb["features"], np.float32),
                        "labels": np.asarray(gb["labels"], np.int32),
                        "weights": np.asarray(gb["weights"], np.float32)})
                yield StepItem(batch, info=info)

    def step(self, ctx, pstate, item: StepItem):
        if pstate["engine"] is None:
            old = pstate["state"]
            pstate["state"], metrics = pstate["step"](old, item.batch)
            if pstate["donated"] is None:       # once, at the first step
                leaves = jax.tree_util.tree_leaves(old)
                n = sum(x.is_deleted() for x in leaves)
                pstate["donated"] = {"donated": n, "leaves": len(leaves)}
                ctx.tracer.counter("psl.donated_state_leaves", n)
                pack = getattr(ctx.model, "client_pack", None)
                if pack is not None:
                    pstate["client_pack"] = pack(
                        item.batch["images"].shape[0])
                    ctx.tracer.counter("psl.client_pack",
                                       pstate["client_pack"])
        else:
            pstate["state"], metrics = pstate["engine"].step(
                pstate["state"], item.batch)
        return pstate, metrics

    def eval_params(self, ctx, pstate):
        return pstate["state"].params

    def finalize(self, ctx, pstate, record):
        engine = pstate.get("engine")
        if engine is not None:
            record.extras["sharding_fallbacks"] = engine.report.fallbacks
        elif pstate["donated"] is not None:
            record.extras["donated_state_leaves"] = pstate["donated"]
            if pstate["client_pack"] is not None:
                record.extras["client_pack"] = pstate["client_pack"]
