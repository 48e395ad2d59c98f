"""Training cells: GPSL split training through the program's own loop.

Set-up builds the one object the window drives: the program's PSL
strategy with the compiled fused step and its state, whose parameters
the benchmark makes from the seed. ``repro.api.loop.fit`` then plans,
assembles batches and steps as it does for any user. Its first three
steps run through that same call and feed on rows that all differ; they
are what the reference checks. The window opens a few steps later, once
everything has compiled, and closes on a ``block_until_ready``.
"""
from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from chipbench import gen, harness


class _WindowClosed(Exception):
    """Raised from the loop's step callback when the window is over."""


def _cnn_config(config: Dict[str, Any]):
    from repro.models.cnn import CNNConfig
    return CNNConfig(name=config["name"], num_classes=config["num_classes"],
                     image_size=config["image_size"],
                     channels=tuple(config["stage_widths"]),
                     blocks_per_stage=config["blocks_per_stage"],
                     group_size=config["groups"],
                     cut_stage=config["cut_stage"], dtype=config["dtype"])


def init_params_fn(shapes):
    """A jittable ``key -> params`` in the shapes and dtypes the program
    declares: GroupNorm scales 1, biases 0, every kernel a fan-in scaled
    normal (fan-in is every axis but the output one)."""
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    treedef = jax.tree_util.tree_structure(shapes)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (path, leaf) in zip(keys, leaves):
            name = jax.tree_util.keystr(path)
            if leaf.ndim == 1:
                val = 1.0 if name.endswith("['scale']") else 0.0
                out.append(jnp.full(leaf.shape, val, leaf.dtype))
            else:
                fan_in = int(np.prod(leaf.shape[:-1]))
                out.append((jax.random.normal(k, leaf.shape, jnp.float32)
                            / np.sqrt(fan_in)).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make


def make_data(traffic: Dict[str, Any], config: Dict[str, Any], seed: int):
    """The federation: images made on the device, split over clients by
    the extended-Dirichlet partition, stored client-major on the host."""
    import jax
    import jax.numpy as jnp
    from repro.core.types import ClientPopulation
    from repro.data.federated import ClientStore
    d = traffic["data"]
    make = jax.jit(gen.images_fn(d["num_samples"], config["num_classes"],
                                 config["image_size"]))
    images, labels = make(harness.seed_key(seed))
    labels_np = np.asarray(labels).astype(np.int64)
    parts = gen.dirichlet_partition(labels_np, d["num_clients"],
                                    config["num_classes"],
                                    d["classes_per_client"],
                                    d["concentration"], seed)
    order = np.concatenate(parts)
    size = config["image_size"]
    flat_images = np.asarray(jnp.take(images, jnp.asarray(order), axis=0)
                             ).reshape(-1, size, size, 3)
    del images
    sizes = np.array([len(p) for p in parts], np.int64)
    pop = ClientPopulation(
        dataset_sizes=sizes,
        class_counts=gen.class_counts(labels_np, parts,
                                      config["num_classes"]),
        delays=np.zeros(len(parts)))
    store = ClientStore.from_flat(flat_images, labels_np[order],
                                  np.cumsum(sizes) - sizes, pop)
    return store, pop


def check_plan(plan, sizes: np.ndarray, global_batch: int) -> int:
    """Violations of the planner's guarantees in one epoch plan: a step
    that is not a full global batch (the last may be short), a negative
    draw, a client drawn beyond its samples (a sample twice in the
    epoch), or an epoch that leaves samples undrawn."""
    bad = 0
    drawn = np.zeros_like(sizes)
    total = int(sizes.sum())
    steps = plan.num_steps
    for t in range(steps):
        ids, cnts = plan.step_segments(t)
        ids = np.asarray(ids, np.int64)
        cnts = np.asarray(cnts, np.int64)
        bad += int((cnts < 0).sum())
        n = int(cnts.sum())
        want = global_batch if t < steps - 1 else total - global_batch * t
        bad += int(n != want)
        np.add.at(drawn, ids, cnts)
    bad += int((drawn != sizes).sum())
    return bad


def run(cell, config, traffic, seed: int, seconds: float, trace: bool,
        options: Dict[str, Any]) -> Dict[str, Any]:
    import jax
    from repro import optim
    from repro.api import specs as S
    from repro.api.loop import DataBundle, RunContext, fit
    from repro.api.protocols import PSLStrategy
    from repro.api.runner import default_callbacks
    from repro.models.cnn import CNNModel
    from repro.optim import TrainState
    from chipbench.counts import gn_resnet as counts
    from chipbench.reference import gn_resnet as ref

    fault = options.get("fault")
    dev = harness.device_info()
    spans = harness.Spans(annotate=trace)
    tracer = harness.Tracer(trace, cell["name"])
    meter = options["meter"]

    model = CNNModel(_cnn_config(config))
    o = traffic["optimizer"]
    optimizer = optim.sgd(o["lr"], momentum=o["momentum"],
                          weight_decay=o["weight_decay"])
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params0 = jax.jit(init_params_fn(shapes))(harness.seed_key(seed ^ 0x5EED))
    p0_host = jax.device_get(params0)
    store, pop = make_data(traffic, config, seed)
    gb = traffic["protocol"]["global_batch"]
    prog_seed = harness.small_seed(seed)
    spec = S.ExperimentSpec(
        seed=prog_seed, model=S.ModelSpec(arch="paper-cnn"),
        optimizer=S.OptimizerSpec(name="sgd", lr=o["lr"],
                                  momentum=o["momentum"],
                                  weight_decay=o["weight_decay"]),
        data=S.DataSpec(kind="synthetic_classification",
                        num_clients=traffic["data"]["num_clients"]),
        sampler=S.SamplerSpec(method=traffic["sampler"]["method"],
                              backend=traffic["sampler"]["backend"]),
        protocol=S.ProtocolSpec(name="psl", epochs=1_000_000,
                                global_batch_size=gb,
                                aggregation=traffic["protocol"]
                                ["aggregation"]),
        execution=S.ExecutionSpec(engine="fused"),
        eval=S.EvalSpec(enabled=False))
    ctx = RunContext(model=model, optimizer=optimizer,
                     data=DataBundle(store=store, pop=pop), spec=spec,
                     seed=prog_seed)

    warm = int(traffic["warm_steps"])
    rec: Dict[str, Any] = {"plans": [], "losses": [], "batches": [],
                           "window_samples": 0, "window_steps": 0}

    class Strategy(PSLStrategy):
        """The program's PSL strategy: its own set-up builds the compiled
        step and the state, whose parameters are then replaced by the
        benchmark's; host spans go around the planner, the batch iterator
        and the step, and the states the reference compares are taken on
        the way."""

        n = 0

        def setup(self, ctx):
            out = super().setup(ctx)
            state = out["state"]
            out["state"] = TrainState(params0, ctx.optimizer.init(params0),
                                      state.step)
            return out

        def plan_epoch(self, ctx, epoch):
            with spans.span("plan"):
                plan = super().plan_epoch(ctx, epoch)
            rec["plans"].append(plan)
            return plan

        def epoch_batches(self, ctx, pstate, plan, epoch):
            it = iter(super().epoch_batches(ctx, pstate, plan, epoch))
            t = 0
            while True:
                with spans.span("batch"):
                    item = next(it, None)
                if item is None:
                    return
                item.info = {"samples": min(gb, int(pop.dataset_sizes.sum())
                                            - gb * t)}
                t += 1
                yield item

        def step(self, ctx, pstate, item):
            n = self.n
            if n < 3:
                rec["batches"].append(jax.device_get(item.batch))
            if n == 1:
                rec["mu1"] = jax.device_get(pstate["state"].opt_state["mu"])
            if n == 3:
                rec["p3"] = jax.device_get(pstate["state"].params)
            if fault == "half_batch":
                w = item.batch["weights"]
                item.batch = dict(item.batch,
                                  weights=w.at[w.shape[0] // 2:].set(0.0))
            old = pstate["state"]
            with spans.span("step"):
                pstate, metrics = super().step(ctx, pstate, item)
            if fault == "unchanged":
                pstate["state"] = old
            self.n += 1
            return pstate, metrics

    class Window:
        """Opens the window after the warm-up steps and closes it, on a
        ``block_until_ready``, once ``seconds`` have passed. A traced run
        then profiles ``trace_steps`` more steps of the same loop, so
        that the profiler's start and stop fall outside the window."""

        t0 = t1 = trace_stop = None

        def on_event(self, event, ctx, record):
            if event.name != "step_end":
                return
            if event.step <= 3:
                rec["losses"].append(float(event.metrics["loss"]))
            if event.step == warm:
                jax.block_until_ready(event.metrics)
                rec["compile_before"] = meter.snapshot()
                spans.reset()
                self.t0 = time.perf_counter()
            elif self.t0 is not None and self.t1 is None:
                rec["window_steps"] += 1
                rec["window_samples"] += event.info["samples"]
                if time.perf_counter() - self.t0 >= seconds:
                    jax.block_until_ready(event.metrics)
                    self.t1 = time.perf_counter()
                    rec["compile_after"] = meter.snapshot()
                    rec["window_spans"] = (dict(spans.total),
                                           dict(spans.count))
                    if not trace:
                        raise _WindowClosed
                    tracer.start()
                    self.trace_stop = event.step + traffic["trace_steps"]
            elif self.trace_stop is not None \
                    and event.step >= self.trace_stop:
                jax.block_until_ready(event.metrics)
                tracer.stop()
                raise _WindowClosed

    window = Window()
    try:
        fit(ctx, Strategy(), default_callbacks(spec, ctx.data) + [window])
    except _WindowClosed:
        pass
    if window.t1 is None or (trace and tracer.window_s is None):
        raise harness.Fault("the training loop ended before the window")
    setup_s = window.t0 - harness.PROCESS_START
    window_s = window.t1 - window.t0
    mem_peak = harness.memory_peak_bytes(jax.devices()[:cell["chips"]])
    reduced = tracer.reduce(cell["chips"]) if trace else None

    # ---------------- correctness: planner, then the first three steps
    sizes = np.asarray(pop.dataset_sizes, np.int64)
    plan_bad = sum(check_plan(p, sizes, gb) for p in rec["plans"])
    del ctx, store
    got = (rec["losses"], ref.first_gradient(rec["mu1"], p0_host,
                                             o["weight_decay"]), rec["p3"])
    readings = ref.compare(p0_host, rec["batches"], got, config, o)
    extra = {}
    if options.get("control"):
        # the control in the program's place: the reference's own three
        # steps in bfloat16, the precision below the configuration's
        extra = {"program_" + k: v for k, v in readings.items()}
        readings = ref.compare(p0_host, rec["batches"],
                               ref.control_steps(p0_host, rec["batches"],
                                                 config, o), config, o)
    limits = traffic["limits"]
    checks = [harness.check("plan_violations", plan_bad, 0)]
    for name in ("loss_gap", "grad_gap", "delta_gap"):
        checks.append(harness.check(name, readings[name], limits[name]))

    flops = counts.train_flops_per_sample(config)
    pk = options.get("peaks") or harness.peaks(dev["kind"])
    samples_per_s = rec["window_samples"] / window_s
    record = {
        "kind": "train", "window_s": window_s, "samples_per_s":
        samples_per_s, "steps": rec["window_steps"],
        "spans": rec["window_spans"][0], "span_counts": rec["window_spans"][1],
        "trace": reduced, "flops_per_sample": flops, "chips": cell["chips"], "peaks": pk,
        "step_program": "jit_step"}
    e2e = {"train_samples_per_s": harness.metric(samples_per_s,
                                                 "samples/s"),
           "setup_s": harness.metric(setup_s, "s")}
    device = dict(dev, memory_peak_bytes=mem_peak)
    out = {"correct": all(c["ok"] for c in checks),
           "attempted": rec["window_steps"], "failed": 0,
           "e2e": e2e, "record": record, "device": device,
           "checks": checks, "extra": extra,
           "compiles_in_window": (rec["compile_after"]["compiles"]
                                  - rec["compile_before"]["compiles"])}
    return out
