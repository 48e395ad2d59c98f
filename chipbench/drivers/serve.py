"""Serving cells: open-loop requests through the program's scheduler and
paged engine.

Set-up makes the weights on the device from the seed, builds the
program's ``PagedEngine`` with them, and warms every prefill shape the
traffic can ask for (each prompt length of the menu, in the group sizes
the scheduler's admission cap allows) and the decode step. The window
then hands ``Scheduler.run`` the requests due in it, on their due
times, and lets it drain them. Time to first token counts from the due
time; tokens per second counts the tokens emitted inside the window.
Once the drain is over and the engine is freed, a sample of the served
requests drawn from the seed is checked against the float32 reference.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from chipbench import gen, harness


def model_config(config: Dict[str, Any], slot_len: int):
    """The program's ``ModelConfig`` for a decoder configuration file."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=config["name"], family="dense",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        rope_theta=config["rope_theta"], norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        cut_layer=config["psl_cut_layer"], dtype=config["torch_dtype"],
        max_seq_len=slot_len)


def init_params_fn(shapes):
    """A jittable ``key -> params`` in the served dtype: norm weights 1,
    the embedding N(0, 0.02), every matrix a fan-in scaled normal (fan-in
    is the input axis; a leading axis stacks layers)."""
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    treedef = jax.tree_util.tree_structure(shapes)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (path, leaf) in zip(keys, leaves):
            name = jax.tree_util.keystr(path)
            if "norm" in name:
                out.append(jnp.ones(leaf.shape, leaf.dtype))
                continue
            std = 0.02 if name.endswith("['embed']") else \
                1.0 / np.sqrt(leaf.shape[-2])
            out.append((jax.random.normal(k, leaf.shape, jnp.float32)
                        * std).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make


def run(cell, config, traffic, seed: int, seconds: float, trace: bool,
        options: Dict[str, Any]) -> Dict[str, Any]:
    import jax
    from repro.models import build_model
    from repro.runtime.paging import PagedEngine
    from repro.runtime.queue import ServeRequest
    from repro.runtime.scheduler import Scheduler, WallClock
    from chipbench.counts import decoder as counts
    from chipbench.reference import decoder as ref

    fault = options.get("fault")
    dev = harness.device_info()
    meter = options["meter"]
    spans = harness.Spans(annotate=trace)
    tracer = harness.Tracer(trace, cell["name"])
    rate = options.get("rate") or traffic["rate_per_s"]
    traffic = dict(traffic, rate_per_s=rate)

    eng_cfg = traffic["engine"]
    slot_len = max(traffic["prompt"]["menu"]) + traffic["output"]["max"]
    mcfg = model_config(config, slot_len)
    model = build_model(mcfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.jit(init_params_fn(shapes))(harness.seed_key(seed ^ 0x5EED))
    page = eng_cfg["page_size"]
    slots = eng_cfg["num_slots"]
    engine = PagedEngine(mcfg, params=params, num_slots=slots,
                         slot_len=slot_len, model=model, page_size=page,
                         num_pages=slots * -(-slot_len // page))
    cap = eng_cfg["max_admits_per_step"]

    # warm every shape the traffic reaches: each menu length prefilled
    # in each group size the admission cap lets through, then decoded
    rng = np.random.default_rng([seed, 7])
    groups = sorted({g for g in engine._GROUP_SIZES if g <= cap})
    rid = 0
    for plen in traffic["prompt"]["menu"]:
        for g in groups:
            reqs = []
            for _ in range(g):
                reqs.append(ServeRequest(
                    rid=rid, prompt=rng.integers(
                        0, mcfg.vocab_size, plen).astype(np.int32),
                    max_new_tokens=2))
                rid += 1
            engine.admit_batch(reqs, time.perf_counter)
            while engine.num_active():
                engine.step(time.perf_counter)
            engine.reset()

    requests = gen.request_trace(traffic, seed, seconds, mcfg.vocab_size)
    prompts = {r["rid"]: r["prompt"] for r in requests}
    tokens: List = []                      # (rid, idx, tok, t_s)
    steps: List = []                       # (t0, t1, rows, context)
    prefills: List = []                    # (t0, t1, tokens, pairs)
    clock_box: Dict[str, Any] = {}

    def on_token(rid, idx, tok, t):
        if fault == "token" and idx == 1:
            tok = (tok + 1) % mcfg.vocab_size
            engine.records[rid]["tokens"][idx] = tok
        tokens.append((rid, idx, tok, t))

    base_step, base_admit = engine.step, engine.admit_batch

    def step(now):
        active = engine._rid >= 0
        rows = int(active.sum())
        ctx = int(engine.pool.pos[active].sum()) + rows
        t0 = clock_box["clock"].now()
        with spans.span("decode"):
            out = base_step(now)
        t1 = clock_box["clock"].now()
        steps.append((t0, t1, rows, ctx))
        return out

    def admit_batch(reqs, now):
        lens = [int(r.prompt.shape[0]) for r in reqs]
        t0 = clock_box["clock"].now()
        with spans.span("admit"):
            base_admit(reqs, now)
        t1 = clock_box["clock"].now()
        prefills.append((t0, t1, sum(lens),
                         sum(n * (n + 1) // 2 for n in lens)))

    engine.step, engine.admit_batch = step, admit_batch
    engine.on_token = on_token
    sched_reqs = [ServeRequest(rid=r["rid"], prompt=r["prompt"],
                               max_new_tokens=r["max_new_tokens"],
                               arrival_s=r["arrival_s"]) for r in requests]
    compile_before = meter.snapshot()
    tracer.start()
    sched = Scheduler(engine, token_budget=slots, clock=WallClock(),
                      max_admits_per_step=cap, policy="fifo")
    clock_box["clock"] = sched.clock
    setup_s = time.perf_counter() - harness.PROCESS_START
    t_run0 = time.perf_counter()
    sched.run(sched_reqs)
    drain_s = time.perf_counter() - t_run0
    tracer.stop()
    compile_end = meter.snapshot()
    mem_peak = harness.memory_peak_bytes(jax.devices()[:cell["chips"]])
    reduced = tracer.reduce(cell["chips"]) if trace else None

    recs = engine.records
    served = {rid: list(r["tokens"]) for rid, r in recs.items()}
    ttft, queue_wait, failed = [], [], 0
    for r in requests:
        rec = recs.get(r["rid"])
        if rec is None or not rec["tokens"]:
            failed += 1
            continue
        ttft.append((rec["first_token_s"] - rec["arrival_s"]) * 1e3)
        queue_wait.append((rec["admit_start_s"] - rec["arrival_s"]) * 1e3)
    by_rid: Dict[int, List[float]] = {}
    for rid, idx, tok, t in tokens:
        by_rid.setdefault(rid, []).append(t)
    gaps = [(b - a) * 1e3 for ts in by_rid.values()
            for a, b in zip(ts, ts[1:])]
    in_window = sum(1 for _, _, _, t in tokens if t <= seconds)
    e2e = {"serve_tokens_per_s": harness.metric(in_window / seconds,
                                                "tokens/s"),
           "ttft_p90_ms": harness.metric(float(np.percentile(ttft, 90)),
                                         "ms"),
           "itl_p95_ms": harness.metric(float(np.percentile(gaps, 95)),
                                        "ms"),
           "setup_s": harness.metric(setup_s, "s")}

    # ---------------- correctness: the reference over a sample, once
    # the engine's cache and programs are gone
    engine.pool.buffers = None
    del engine, sched, step, admit_batch, base_step, base_admit, on_token
    done = [r["rid"] for r in requests if served.get(r["rid"])]
    longest = max(done, key=lambda q: len(served[q]))
    pick = gen.sample_rows(len(done), traffic["check"]["requests"], seed,
                           must=(done.index(longest),))
    sample = [(prompts[done[i]], served[done[i]]) for i in pick]
    control = options.get("control", False)
    readings = ref.compare(params, sample, config, slot_len,
                           traffic["output"]["max"], control=control)
    # the control in the program's place: at each position of the same
    # prompts and served tokens, the token that int8 puts first
    gap = readings["control_gap"] if control else readings["gap"]
    checks = [harness.check("served_logit_gap", gap,
                            traffic["limits"]["served_logit_gap"]),
              harness.check("requests_failed", failed, 0)]

    pk = options.get("peaks") or harness.peaks(dev["kind"])
    record = {
        "kind": "serve", "window_s": seconds, "trace": reduced,
        "queue_wait_ms": queue_wait,
        "compiles": compile_end["compiles"] - compile_before["compiles"],
        "steps": steps, "prefills": prefills, "peaks": pk,
        "chips": cell["chips"], "counts": counts.summary(config),
        "window_end": seconds,
        "decode_program": "jit__step", "prefill_program": "jit_prefill"}
    device = dict(dev, memory_peak_bytes=mem_peak)
    extra = {k: v for k, v in readings.items()
             if k not in ("gap", "control_gap")}
    if control:
        extra["program_gap"] = readings["gap"]
    extra.update(requests=len(requests), tokens_in_window=in_window,
                 drain_s=drain_s, ttft_count=len(ttft), gaps=len(gaps),
                 attainment=_attainment(requests, recs, by_rid, traffic),
                 queue_wait_ms_p50_halves=_halves(queue_wait),
                 max_rows=max((s[2] for s in steps), default=0),
                 mean_rows=float(np.mean([s[2] for s in steps]))
                 if steps else 0.0)
    return {"correct": all(c["ok"] for c in checks),
            "attempted": len(requests), "failed": failed, "e2e": e2e,
            "record": record, "device": device, "checks": checks,
            "extra": extra, "compiles_in_window": record["compiles"]}


def _attainment(requests, recs, by_rid, traffic) -> float:
    """Share of requests that met both limits of the traffic file: time
    to first token, and the mean gap between their output tokens."""
    lim = traffic["slo"]
    ok = 0
    for r in requests:
        rec = recs.get(r["rid"])
        ts = by_rid.get(r["rid"], [])
        if rec is None or not ts:
            continue
        ttft = (rec["first_token_s"] - rec["arrival_s"]) * 1e3
        tpot = ((ts[-1] - ts[0]) / (len(ts) - 1) * 1e3) if len(ts) > 1 \
            else 0.0
        ok += ttft <= lim["ttft_ms"] and tpot <= lim["tpot_ms"]
    return ok / len(requests)


def _halves(values) -> List[float]:
    """Medians of the first and the second half of the window's
    requests, in due order: a queue that grows reads higher in the
    second."""
    h = len(values) // 2
    return [float(np.median(values[:h])) if h else 0.0,
            float(np.median(values[h:])) if values else 0.0]
