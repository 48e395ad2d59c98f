#!/usr/bin/env python3
"""Drive both main paths once on a TPU and check what comes out.

    python chip_smoke.py               # one chip: GPSL training + serving
    python chip_smoke.py --four-chips  # four chips: sharded GPSL training

Training phase (the paper's system): the full-width GN-ResNet-18
(``paper-cnn``: stage widths 64/128/256/512, 32x32 inputs, 10 classes)
trains through ``repro.api.run`` with PSL on the fused engine: a
CIFAR-10-shaped synthetic federation of 50,000 images split over 100
clients by the extended Dirichlet partition, global batch 64, the epoch
plan drawn by UGS on the jax planner backend. It passes when the plan
validates against the population, every loss is finite and the last five
losses average below the first.

Serving phase: granite-3-2b at published width and depth (40 layers,
d_model 2048, bf16, random weights from a seed) serves 8 greedy requests
(prompts of 128 and 512 tokens, 32 new tokens each) on the paged engine
through ``repro.api.run``, twice on one engine: a cold pass that compiles
and a warm pass that is timed. Every output is checked against
single-request decoding (``report.verify=-1``) and the page pool must end
leak-free.

``--four-chips`` runs only the sharded GPSL training path and what it is
compared with: granite-3-2b at full width and depth through the sharded
engine on a 4x1 mesh (fsdp profile, AdamW, global batch 16, sequence
length 512), then granite's width cut to 4 layers on 4x1 and on 1x1 from
the same plan and batches, whose first-step losses and updated parameters
must agree within a bf16 tolerance.

Each phase prints one JSON line: device kind and count, XLA compile
seconds (or persistent-cache retrieval), wall times of work that ended on
the host, and peak device memory. The last
line of a passing run is ``{"ok": true, "device": {...}}``. A run that
finds no TPU, or fails any check, exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

# A request whose output first diverges from single-request decoding where
# the reference's top-2 logit margin is below this is a bf16 near-tie, not
# a wrong answer. The served logits are bf16 products cast to fp32; with
# random weights they are ~N(0, 1), so the top logit of 49,155 sits in
# [4, 8), where one bf16 step is 2**-5. The tolerance is two such steps.
BF16_MARGIN = 2.0 ** -4

# 4x1 vs 1x1 agreement after the first AdamW step. The loss may differ by
# one bf16 step relative (2**-7). A parameter element agrees when it is
# within two bf16 steps of its magnitude; the shard-wise reduction of bf16
# gradients may flip the sign of near-zero gradients, and so the one-step
# AdamW update (about +-lr) of a few elements, but not of more than 2%.
LOSS_RTOL = 2.0 ** -7
PARAM_ULPS = 2
PARAM_MISMATCH_FRAC = 0.02


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileMeter:
    """Seconds XLA spent compiling device programs.

    Sums JAX's backend-compile durations, which on a persistent-cache hit
    are the retrieval instead, and counts the hits, while the meter is
    open (``with``). Tracing and lowering are left out: they nest (a jit
    traced inside another reports its own duration) and no cache skips
    them. JAX's monitoring listeners are process-wide, so open one meter
    at a time.
    """

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0

    def __enter__(self) -> "CompileMeter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == self._EVENT:
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> dict:
        """Totals since the last take, then reset."""
        out = {"compile_s": self.seconds,
               "compile_cache_hits": self.cache_hits}
        self.seconds, self.cache_hits = 0.0, 0
        return out


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(devices=None) -> list:
    """``peak_bytes_in_use`` per device since the process started (None
    where the backend keeps no such count)."""
    import jax
    out = []
    for d in (jax.devices()[:1] if devices is None else devices):
        stats = d.memory_stats()
        out.append(stats.get("peak_bytes_in_use") if stats else None)
    return out


# ---------------------------------------------------------------------------
# training phase: the paper's GPSL system
# ---------------------------------------------------------------------------

def train_phase(meter: CompileMeter, *, reduced: bool = False,
                image_size: int = 32, num_train: int = 50_000,
                num_clients: int = 100, global_batch: int = 64,
                steps: int = 20) -> dict:
    import jax
    from repro import api

    spec = api.ExperimentSpec(
        seed=0,
        model=api.ModelSpec(arch="paper-cnn", reduced=reduced),
        optimizer=api.OptimizerSpec(name="sgd", lr=2e-3, momentum=0.9,
                                    weight_decay=5e-4),
        data=api.DataSpec(kind="synthetic_classification",
                          num_train=num_train, image_size=image_size,
                          num_classes=10, partition="dirichlet",
                          num_clients=num_clients),
        sampler=api.SamplerSpec(method="ugs", backend="jax"),
        protocol=api.ProtocolSpec(name="psl", epochs=1,
                                  global_batch_size=global_batch),
        execution=api.ExecutionSpec(engine="fused", max_steps=steps),
        eval=api.EvalSpec(enabled=False))

    t0 = time.perf_counter()
    ctx = api.build_context(spec)
    data_s = time.perf_counter() - t0
    setup = meter.take()

    class Probe(api.Callback):
        """Keeps the epoch plan and a host timestamp after each step's
        metrics are ready on the device."""

        def __init__(self):
            self.plan = None
            self.t_begin = self.t_plan = None
            self.t_steps = []

        def on_event(self, event, ctx, record):
            if event.name == "run_begin":
                self.t_begin = time.perf_counter()
            elif event.name == "plan":
                self.plan = event.plan
                self.t_plan = time.perf_counter()
            elif event.name == "step_end":
                jax.block_until_ready(event.metrics)
                self.t_steps.append(time.perf_counter())

    probe = Probe()
    result = api.run(spec, callbacks=[probe], ctx=ctx)
    compiled = meter.take()

    _check(probe.plan is not None, "the PSL run emitted no epoch plan")
    probe.plan.validate_against(ctx.data.pop)     # raises on a bad plan
    losses = [m["loss"] for m in result.step_metrics]
    _check(len(losses) == steps, f"ran {len(losses)} steps, not {steps}")
    _check(all(math.isfinite(x) for x in losses),
           f"non-finite training loss: {losses}")
    last5 = sum(losses[-5:]) / len(losses[-5:])
    _check(last5 < losses[0],
           f"loss did not fall: first {losses[0]}, last-5 mean {last5}")
    gaps = [b - a for a, b in zip(probe.t_steps, probe.t_steps[1:])]
    return {
        "phase": "train", "device": device_info(),
        "model": ctx.model.cfg.name, "images": num_train,
        "image_size": image_size, "clients": num_clients,
        "global_batch": global_batch,
        "plan_steps": probe.plan.num_steps, "steps": steps,
        "data_s": data_s, "setup_compile_s": setup["compile_s"],
        "plan_s": probe.t_plan - probe.t_begin,
        "first_step_s": probe.t_steps[0] - probe.t_plan,
        "step_s_median": sorted(gaps)[len(gaps) // 2] if gaps else None,
        **compiled,
        "loss_first": losses[0], "loss_last5_mean": last5,
        "losses": losses, "peak_bytes_in_use": peak_bytes()[0]}


# ---------------------------------------------------------------------------
# serving phase: granite-3-2b on the paged engine
# ---------------------------------------------------------------------------

def serve_phase(meter: CompileMeter, *, reduced: bool = False,
                prompt_lens=(128, 512), max_new: int = 32,
                num_requests: int = 8,
                margin: float = BF16_MARGIN) -> dict:
    from repro import api
    from repro.api.serving import build_serve_context

    spec = api.ServeSpec(
        model=api.ModelSpec(arch="granite-3-2b", reduced=reduced),
        engine=api.EngineSpec(name="paged", seed=0),
        workload=api.WorkloadSpec(num_requests=num_requests,
                                  prompt_lens=list(prompt_lens),
                                  max_new_tokens=[max_new], seed=0),
        report=api.ReportSpec(verify=-1, verify_margin=margin))

    t0 = time.perf_counter()
    ctx = build_serve_context(spec)
    setup_s = time.perf_counter() - t0
    setup = meter.take()
    cold = api.run(spec, ctx=ctx)
    cold_compile = meter.take()
    warm = api.run(spec, ctx=ctx)
    warm_compile = meter.take()
    ctx.engine.pool.check_no_leaks()              # raises on a leak

    for report in (cold, warm):
        _check(report.num_requests == num_requests,
               f"served {report.num_requests} of {num_requests} requests")
        _check(all(len(r["tokens"]) == max_new for r in report.per_request),
               "a request ended short of its max_new_tokens")
        _check(report.verified is not None
               and report.verified["checked"] == num_requests,
               f"verification did not cover every request: "
               f"{report.verified}")
    _check([r["tokens"] for r in cold.per_request]
           == [r["tokens"] for r in warm.per_request],
           "the warm pass served different tokens than the cold pass")
    lat = sorted(r["latency_ms"] for r in warm.per_request)
    return {
        "phase": "serve", "device": device_info(),
        "model": ctx.engine.cfg.name, "engine": warm.engine,
        "requests": num_requests, "prompt_lens": list(prompt_lens),
        "max_new_tokens": max_new,
        "setup_s": setup_s, "setup_compile_s": setup["compile_s"],
        "cold_wall_s": cold.wall_s, **cold_compile,
        "warm_wall_s": warm.wall_s,
        "warm_compile_s": warm_compile["compile_s"],
        "warm_latency_ms_p50": lat[len(lat) // 2],
        "warm_latency_ms_max": lat[-1],
        "warm_decode_tok_per_s": warm.decode_tok_per_s,
        "verified": cold.verified["checked"],
        "excused_count": len(cold.verified.get("excused", [])),
        "excused": cold.verified.get("excused", []),
        "verify_margin": margin,
        "kv_peak_bytes": warm.cache_utilization["peak_in_use_bytes"],
        "peak_bytes_in_use": peak_bytes()[0]}


# ---------------------------------------------------------------------------
# four chips: the sharded GPSL training path
# ---------------------------------------------------------------------------

def _lm_spec(reduced: bool, seq_len: int, global_batch: int, steps: int,
             mesh: str, overrides=None):
    from repro import api
    return api.ExperimentSpec(
        seed=0,
        model=api.ModelSpec(arch="granite-3-2b", reduced=reduced,
                            overrides=dict(overrides or {})),
        optimizer=api.OptimizerSpec(name="adamw", lr=1e-3,
                                    weight_decay=0.1),
        data=api.DataSpec(kind="synthetic_lm", num_clients=8,
                          sequences=max(256, 4 * global_batch * steps),
                          seq_len=seq_len),
        sampler=api.SamplerSpec(method="ugs", backend="jax"),
        protocol=api.ProtocolSpec(name="psl", epochs=1,
                                  global_batch_size=global_batch),
        execution=api.ExecutionSpec(engine="sharded", mesh=mesh,
                                    sharding="fsdp", max_steps=steps),
        eval=api.EvalSpec(enabled=False))


def _whole_on_one_device(model, params, mesh, profile: str) -> list:
    """Parameter paths the profile's rules shard over more than one device
    but that some device holds whole."""
    import jax
    import numpy as np
    from repro import sharding as shard_lib
    from repro.models.layers import tree_map_specs
    specs = model.param_specs()
    bad = []
    for part, rules in (("client", shard_lib.client_rules(mesh, profile)),
                        ("server", shard_lib.server_rules(mesh, profile))):
        wanted = tree_map_specs(
            lambda s: max((int(np.prod([mesh.shape[a]
                                        for a in rules.get(ax, ())]))
                           for ax in s.axes if ax is not None), default=1),
            specs[part])
        flat = jax.tree_util.tree_leaves_with_path(params[part])
        for (path, leaf), ways in zip(flat, jax.tree_util.tree_leaves(wanted)):
            if ways > 1 and leaf.addressable_shards[0].data.shape \
                    == leaf.shape:
                bad.append(part + jax.tree_util.keystr(path))
    return bad


def four_chip_full_depth(meter: CompileMeter, *, reduced: bool = False,
                         seq_len: int = 512, global_batch: int = 16,
                         steps: int = 3) -> dict:
    import jax
    from repro import api
    spec = _lm_spec(reduced, seq_len, global_batch, steps, "4x1")
    t0 = time.perf_counter()
    ctx = api.build_context(spec)
    setup_s = time.perf_counter() - t0
    meter.take()
    t0 = time.perf_counter()
    result = api.run(spec, ctx=ctx)
    jax.block_until_ready(result.params)
    wall_s = time.perf_counter() - t0
    compiled = meter.take()
    losses = [m["loss"] for m in result.step_metrics]
    _check(len(losses) == steps, f"ran {len(losses)} steps, not {steps}")
    _check(all(math.isfinite(x) for x in losses),
           f"non-finite loss on the 4x1 mesh: {losses}")
    engine = result.state["engine"]
    whole = _whole_on_one_device(ctx.model, result.params, engine.mesh,
                                 engine.profile)
    _check(not whole, f"parameters the fsdp profile shards sit whole on "
                      f"one device: {whole[:8]}")
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(result.params))
    return {
        "phase": "four_chip_full_depth", "device": device_info(),
        "model": ctx.model.cfg.name, "layers": ctx.model.cfg.num_layers,
        "params": n_params, "mesh": "4x1", "profile": "fsdp",
        "global_batch": global_batch, "seq_len": seq_len,
        "setup_s": setup_s, "run_wall_s": wall_s, **compiled,
        "losses": losses,
        "sharding_fallbacks": result.history.extras["sharding_fallbacks"],
        "peak_bytes_in_use": peak_bytes(list(engine.mesh.devices.flat))}


def four_chip_agreement(meter: CompileMeter, *, reduced: bool = False,
                        layers: int = 4, seq_len: int = 512,
                        global_batch: int = 16) -> dict:
    """One AdamW step of the cut-depth model on 4x1 and on 1x1, fed the
    same plan and the same host batch."""
    import gc
    import jax
    # the full-depth phase's 8 GB of state per chip must be gone before
    # the 1x1 run puts the whole cut-depth state on one of the chips
    gc.collect()
    import numpy as np
    from repro.api.protocols import lm_plan_batches
    from repro.api.runner import build_context
    from repro.core.sampling import make_plan
    from repro.launch.distributed import (ShardedPSLEngine,
                                          assign_clients_to_shards)
    from repro.launch.mesh import make_training_mesh

    spec = _lm_spec(reduced, seq_len, global_batch, 1, "4x1",
                    overrides={"num_layers": layers})
    ctx = build_context(spec)
    pop = ctx.data.pop
    plan = make_plan("ugs", pop, global_batch, seed=spec.seed,
                     backend="jax")
    plan.validate_against(pop)
    host = next(lm_plan_batches(ctx.data.lm_data, pop, plan, seq_len,
                                "global_mean",
                                assign_clients_to_shards(pop.num_clients, 4),
                                seed=spec.seed))
    meter.take()
    runs = {}
    for mesh_spec in ("4x1", "1x1"):
        engine = ShardedPSLEngine(ctx.model, ctx.optimizer,
                                  mesh=make_training_mesh(mesh_spec),
                                  profile="fsdp")
        state = engine.init_state(spec.seed)
        state, metrics = engine.step(state, engine.put_batch(host))
        runs[mesh_spec] = (float(metrics["loss"]),
                           [np.asarray(x, np.float32) for x in
                            jax.tree_util.tree_leaves(state.params)])
        del state
    compiled = meter.take()
    (l4, p4), (l1, p1) = runs["4x1"], runs["1x1"]
    _check(abs(l4 - l1) <= LOSS_RTOL * abs(l1),
           f"first-step loss 4x1 {l4} vs 1x1 {l1} beyond rtol {LOSS_RTOL}")
    total = off = 0
    max_abs = 0.0
    for a, b in zip(p4, p1):
        mag = np.maximum(np.abs(a), np.abs(b))
        # one bf16 step at this magnitude: 2**(exponent - 7)
        ulp = np.exp2(np.floor(np.log2(np.maximum(mag, 1e-30))) - 7)
        diff = np.abs(a - b)
        off += int((diff > PARAM_ULPS * ulp).sum())
        total += diff.size
        max_abs = max(max_abs, float(diff.max()))
    frac = off / total
    _check(frac <= PARAM_MISMATCH_FRAC,
           f"{frac:.4%} of updated parameter elements differ between 4x1 "
           f"and 1x1 by more than {PARAM_ULPS} bf16 steps "
           f"(limit {PARAM_MISMATCH_FRAC:.0%})")
    return {
        "phase": "four_chip_agreement", "device": device_info(),
        "layers": layers, "seq_len": seq_len, "global_batch": global_batch,
        "loss_4x1": l4, "loss_1x1": l1, "params_compared": total,
        "params_beyond_tol": off, "params_beyond_tol_frac": frac,
        "param_max_abs_diff": max_abs, **compiled}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded GPSL training path on a "
                         "4x1 mesh and its 1x1 comparison")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    import jax

    cache_dir = enable_compile_cache()
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev['platform']!r}); "
              f"this script runs only on the chip", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if dev["count"] < need:
        print(f"chip_smoke: needs {need} chips, JAX sees {dev['count']}",
              file=sys.stderr)
        return 2
    print(json.dumps({"phase": "start", "device": dev,
                      "jax": jax.__version__, "compile_cache": cache_dir}),
          flush=True)
    if args.four_chips:
        phases = (four_chip_full_depth, four_chip_agreement)
    else:
        phases = (train_phase, serve_phase)
    with CompileMeter() as meter:
        for phase in phases:
            print(json.dumps(phase(meter)), flush=True)
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
