"""Materialize a ServeSpec and run it: the serving side of ``api.run``.

Mirrors :mod:`repro.api.runner` for inference: build the model the spec
describes (optionally restoring a trained params artifact from
``spec.checkpoint``), construct the registered engine sized by the spec,
synthesize the seeded request workload, and serve it — returning the
engine's :class:`repro.runtime.ServeReport`. Everything is pinned by the
spec, so::

    run_serve(ServeSpec.from_json(text))

replays a serving workload from one JSON document, and an
ExperimentSpec+ServeSpec JSON pair reproduces train-then-serve end to end.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Any, List, Optional

import numpy as np

from repro.api.registry import get_engine
from repro.api.runner import build_model
from repro.api.specs import ServeSpec, SpecError
from repro.obs import maybe_jax_profiler, tracer_from_spec, write_outputs


@dataclasses.dataclass
class ServeContext:
    """Built serving objects; pass back to ``run_serve`` to reuse the
    engine (and its compiled functions) across runs of related specs.
    The engine geometry is fixed at build time — a rebound spec may vary
    the workload and scheduling axes, not the pool size."""
    model: Any
    params: Any
    engine: Any
    spec: ServeSpec


def build_workload(spec: ServeSpec, vocab_size: int):
    """The seeded request trace a WorkloadSpec describes.

    Per request: a prompt length and output length drawn from the spec's
    menus, then uniform random token ids — one rng stream, so the trace is
    a pure function of the spec. Straggler arrivals (when configured) reuse
    the training-side delay model; ``workload.arrival`` instead draws
    absolute arrival times from a named process
    (repro.runtime.workload — poisson/bursty/diurnal/heavy_tail).
    ``workload.tenant_mix`` assigns each request a tenant by weight. Both
    extensions use their own seeded rng streams, so traces built without
    them are byte-identical to what this function always produced.
    """
    from repro.runtime.queue import ServeRequest
    w = spec.workload
    rng = np.random.default_rng(w.seed)
    reqs: List = []
    for i in range(w.num_requests):
        plen = int(rng.choice(w.prompt_lens))
        reqs.append(ServeRequest(
            rid=i, prompt=rng.integers(0, vocab_size, plen).astype(np.int32),
            max_new_tokens=int(rng.choice(w.max_new_tokens))))
    if w.arrivals is not None:
        from repro.core.straggler import straggler_arrivals
        a = w.arrivals
        delays = straggler_arrivals(w.num_requests, a.p_straggler, a.w_min,
                                    a.w_max, seed=a.seed,
                                    time_scale=w.time_scale)
        for r, t in zip(reqs, delays):
            r.arrival_s = float(t)
    elif w.arrival is not None:
        from repro.runtime.workload import generate_arrivals
        times = generate_arrivals(w.arrival, w.num_requests)
        for r, t in zip(reqs, times):
            r.arrival_s = float(t)
    if w.tenant_mix is not None:
        names = sorted(w.tenant_mix)
        weights = np.asarray([w.tenant_mix[t] for t in names], np.float64)
        trng = np.random.default_rng([int(w.seed), 0x7e7a])
        picks = trng.choice(len(names), size=w.num_requests,
                            p=weights / weights.sum())
        for r, k in zip(reqs, picks):
            r.tenant = names[int(k)]
    return reqs


def restore_params(model, path: str):
    """Load a checkpoint artifact and check it fits ``model``.

    The artifact comes from ``repro.checkpoint.save`` (a training run with
    ``execution.checkpoint`` set). Structure and leaf shapes are checked
    against the model's init — a mismatched arch fails here with the spec
    fields to fix, not deep inside a jit trace.
    """
    import jax
    from repro.checkpoint import restore
    params = restore(path)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got_leaves, got_tree = jax.tree_util.tree_flatten(params)
    want_leaves, want_tree = jax.tree_util.tree_flatten(want)
    if got_tree != want_tree:
        raise SpecError(
            f"checkpoint {path!r} does not match the spec's model tree "
            f"(arch/reduced/overrides must equal the training spec's)")
    for g, w in zip(got_leaves, want_leaves):
        if tuple(np.shape(g)) != tuple(w.shape):
            raise SpecError(
                f"checkpoint {path!r} leaf shape {tuple(np.shape(g))} != "
                f"model shape {tuple(w.shape)}; arch/reduced/overrides "
                f"must equal the training spec's")
    return params


def build_serve_context(spec: ServeSpec, params=None) -> ServeContext:
    """Spec → built engine, without serving anything."""
    spec.validate()
    # the slot length doubles as the model's working sequence length, the
    # same max_seq_len floor the training-side builder applies — so a
    # checkpointed LM trained at seq_len <= 256 restores shape-exact
    model = build_model(spec.model, seq_len=spec.resolved_slot_len())
    if params is None and spec.checkpoint:
        params = restore_params(model, spec.checkpoint)
    engine = get_engine(spec.engine.name).from_spec(model.cfg, spec,
                                                    params=params,
                                                    model=model)
    return ServeContext(model=engine.model, params=engine.params,
                        engine=engine, spec=spec)


def verify_report(report, ctx: ServeContext, requests=None,
                  n: int = -1, stream_events=None,
                  margin_tol: float = 0.0) -> dict:
    """Check served outputs token-identical to single-request decoding.

    ``n`` limits how many requests are replayed through
    ``reference_generate`` (-1 = all). A request whose output first
    diverges where the reference's top-2 logit margin is below
    ``margin_tol`` is excused instead of failing (``ReportSpec.
    verify_margin``; 0 keeps the check exact). When the run streamed
    (``stream_events`` from the engine's ``on_token`` hook), the stream
    order is additionally audited against the final token order. Raises
    RuntimeError listing each diverging rid with its first diverging
    token index and the reference's margin there; returns the audit dict
    recorded on the report.
    """
    from repro.runtime.engine import reference_generate
    if requests is None:
        requests = build_workload(ctx.spec, ctx.engine.cfg.vocab_size)
    k = len(requests) if n < 0 else min(n, len(requests))
    slot_len = ctx.engine.pool.slot_len
    by_rid = {r["rid"]: r["tokens"] for r in report.per_request}
    mismatches, excused = [], []
    for req in requests[:k]:
        want = reference_generate(ctx.model, ctx.params, req.prompt,
                                  req.max_new_tokens, slot_len)
        got = by_rid[req.rid]
        if got == want:
            continue
        first = next((i for i, (a, b) in enumerate(zip(got, want))
                      if a != b), min(len(got), len(want)))
        _, margins = reference_generate(ctx.model, ctx.params, req.prompt,
                                        req.max_new_tokens, slot_len,
                                        with_margins=True)
        row = {"rid": req.rid, "first_diverging_token": first,
               "top2_margin": (margins[first] if first < len(margins)
                               else None)}
        if row["top2_margin"] is not None \
                and row["top2_margin"] < margin_tol:
            excused.append(row)
        else:
            mismatches.append(row)
    if mismatches:
        where = [(m["first_diverging_token"], m["top2_margin"])
                 for m in mismatches]
        raise RuntimeError(
            f"{report.engine} outputs diverge from single-request "
            f"decoding: rids {[m['rid'] for m in mismatches]} "
            f"(first diverging token, reference top-2 margin: {where})")
    out = {"checked": k, "mismatches": []}
    if margin_tol:
        out["excused"] = excused
    if stream_events is not None:
        out["stream"] = audit_stream(report, stream_events)
    return out


def audit_stream(report, events) -> dict:
    """Stream order == final token order, per request.

    ``events`` are ``on_token`` emissions ``{"rid", "idx", "tok",
    "t_s"}`` in emission order. Every request's streamed token sequence
    must equal its report ``tokens`` list exactly (same tokens, same
    order, contiguous indices) — speculative bursts and plain decode
    emit through the same path, so this pins that path. Raises
    RuntimeError on divergence; returns the audit dict.
    """
    streamed: dict = {}
    for ev in events:
        seq = streamed.setdefault(ev["rid"], [])
        if ev["idx"] != len(seq):
            raise RuntimeError(
                f"stream emitted rid {ev['rid']} token index "
                f"{ev['idx']} out of order (expected {len(seq)})")
        seq.append(ev["tok"])
    bad = [r["rid"] for r in report.per_request
           if streamed.get(r["rid"], []) != r["tokens"]]
    if bad:
        raise RuntimeError(
            f"streamed token order diverges from the report for rids "
            f"{bad}")
    return {"events": len(events), "requests": len(streamed),
            "mismatches": []}


def run_serve(spec: ServeSpec, ctx: Optional[ServeContext] = None):
    """Run one serving workload: build from the spec, serve, report.

    Pass a prebuilt ``ctx`` to reuse an engine across runs (warmup + timed
    benchmark passes); the spec argument then rebinds the workload and
    scheduling axes while the engine keeps its compiled functions.

    Telemetry (``spec.obs``, repro.obs): when enabled, a tracer is built
    on the spec's scheduler clock — so a VirtualClock run yields a
    deterministic trace — and handed down through ``engine.serve``, which
    emits scheduler-phase spans (admit/decode_step/wait) and per-request
    enqueue→admit→prefill→decode→complete lifecycle spans. Artifacts go
    to ``spec.obs.trace_path`` / ``events_path``; instrumentation changes
    no served token.

    Streaming (``spec.stream``): the engine's ``on_token`` hook collects
    every emission in order; ``stream.path`` gets them as JSONL
    (``{"rid", "idx", "tok", "t_s"}`` per line) and ``audit_stream``
    checks stream order equals the final per-request token order.
    """
    if ctx is None:
        ctx = build_serve_context(spec)
    else:
        spec.validate()
        ctx = dataclasses.replace(ctx, spec=spec)
    obs = getattr(spec, "obs", None)
    clock = tracer = None
    if obs is not None and obs.enabled:
        from repro.runtime.scheduler import make_clock
        clock = make_clock(spec.clock.kind, spec.clock.tick_s)
        tracer = tracer_from_spec(
            obs, clock=clock.now,
            meta={"kind": "serve", "engine": spec.engine.name,
                  "clock": spec.clock.kind},
            wall_clock=spec.clock.kind == "wall")
    requests = build_workload(spec, ctx.engine.cfg.vocab_size)
    stream = getattr(spec, "stream", None)
    events: Optional[List[dict]] = None
    if stream is not None and stream.enabled:
        events = []
        ctx.engine.on_token = lambda rid, idx, tok, t_s: events.append(
            {"rid": rid, "idx": idx, "tok": tok, "t_s": round(t_s, 6)})
    try:
        with maybe_jax_profiler(obs):
            report = ctx.engine.serve(requests, spec, clock=clock,
                                      tracer=tracer)
    finally:
        ctx.engine.on_token = None
    if events is not None:
        if stream.path:
            pathlib.Path(stream.path).write_text(
                "".join(json.dumps(ev) + "\n" for ev in events))
        report.stream = audit_stream(report, events)
    if spec.report.verify:
        report.verified = verify_report(
            report, ctx, requests=requests, n=spec.report.verify,
            stream_events=events, margin_tol=spec.report.verify_margin)
    if tracer is not None:
        tracer.record("serve_report", **{
            k: v for k, v in report.to_json().items()
            if k != "per_request"})
        write_outputs(tracer, obs)
    if spec.report.out:
        j = report.to_json()
        if not spec.report.per_request:
            j.pop("per_request", None)
        pathlib.Path(spec.report.out).write_text(
            json.dumps(j, indent=2) + "\n")
    return report
