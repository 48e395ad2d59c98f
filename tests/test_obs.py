"""The unified telemetry layer (repro.obs, docs/observability.md).

Four guarantees, mirroring the layer's contract:

* **non-interference** — an instrumented training run is bitwise-identical
  (losses) to a disabled one, and an instrumented serving run is
  token-identical; the GPSL monitor watches *expected* compositions only,
  so it can never perturb RNG;
* **determinism** — a traced VirtualClock serving run is a pure function
  of the spec: byte-identical trace artifacts across runs;
* **soundness** — the live GPSL monitor stays silent on honest planner
  output and fires on a deliberately skewed plan;
* **plumbing** — ObsSpec round-trips through JSON on both spec kinds,
  the streamed TPE twin matches the dense simulator, the metrics
  primitives (P², percentiles with p99) agree with NumPy, and
  tools/trace_report.py renders both export formats.
"""
import dataclasses
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

pytest.importorskip("jax")

from repro import api
from repro.api.registry import get_protocol
from repro.core import ClientPopulation, make_plan
from repro.core.straggler import simulate_tpe, simulate_tpe_segments
from repro.obs import (GPSLMonitor, Histogram, NullTracer, P2Quantile,
                       Tracer, null_tracer, percentiles, tracer_from_spec)

REPO = pathlib.Path(__file__).resolve().parent.parent


def _pop(k=8, per=64, m=5, seed=0):
    return ClientPopulation.homogeneous(k, per, m, seed=seed)


def _skew_pop(k=4, per=40, m=4):
    """Class-pure clients: client i holds only class i % m."""
    sizes = np.full(k, per, np.int64)
    counts = np.zeros((k, m), np.int64)
    for i in range(k):
        counts[i, i % m] = per
    return ClientPopulation(sizes, counts, np.zeros(k))


def train_spec(**obs) -> api.ExperimentSpec:
    return api.ExperimentSpec(
        seed=0,
        model=api.ModelSpec(arch="paper-cnn", reduced=True),
        data=api.DataSpec(num_train=600, num_test=200, image_size=16,
                          num_clients=4, partition="dirichlet",
                          partition_seed=1),
        protocol=api.ProtocolSpec(name="psl", epochs=1,
                                  global_batch_size=32, batch_size=16),
        obs=api.ObsSpec(**obs))


def serve_spec(**obs) -> api.ServeSpec:
    return api.ServeSpec(
        model=api.ModelSpec(arch="granite-3-2b", reduced=True),
        engine=api.EngineSpec(num_slots=4, slot_len=64),
        workload=api.WorkloadSpec(num_requests=6, prompt_lens=[4, 8],
                                  max_new_tokens=[3, 5], seed=0),
        clock=api.ClockSpec(kind="virtual"),
        obs=api.ObsSpec(**obs))


# ---------------------------------------------------------------------------
# ObsSpec plumbing
# ---------------------------------------------------------------------------

def test_obs_spec_round_trips_on_both_spec_kinds():
    t = train_spec(enabled=True, trace_path="runs/t.json",
                   monitor_delta=0.01)
    assert api.ExperimentSpec.from_json(t.to_json()) == t
    s = serve_spec(enabled=True, events_path="runs/e.jsonl", monitor=False)
    assert api.ServeSpec.from_json(s.to_json()) == s
    d = json.loads(s.to_json())
    assert d["obs"] == {"enabled": True, "trace_path": None,
                        "events_path": "runs/e.jsonl", "monitor": False,
                        "monitor_delta": 0.05, "jax_profiler_dir": None}
    # off by default, and validation guards the delta
    assert api.ExperimentSpec().obs.enabled is False
    with pytest.raises(api.SpecError, match="monitor_delta"):
        train_spec(monitor_delta=1.5).validate()


def test_disabled_spec_yields_the_shared_null_tracer():
    assert tracer_from_spec(None) is tracer_from_spec(api.ObsSpec())
    assert isinstance(tracer_from_spec(api.ObsSpec()), NullTracer)
    nt = null_tracer()
    assert not nt.enabled
    # the no-op span is one shared reusable context manager
    assert nt.span("a") is nt.span("b")
    with nt.span("a"):
        pass


# ---------------------------------------------------------------------------
# Tracer mechanics
# ---------------------------------------------------------------------------

def test_tracer_spans_and_exports(tmp_path):
    ticks = iter(range(100))
    tr = Tracer(clock=lambda: float(next(ticks)), meta={"kind": "test"})
    with tr.span("outer", cat="phase", epoch=0):
        with tr.span("inner"):
            pass
    tr.counter("depth", 3)
    tr.record("monitor", step=0, deviation_ok=True)
    tr.request_lifecycle(7, 0.0, 1.0, 2.0, 5.0, prompt_len=4)
    doc = tr.chrome_trace()
    assert doc["otherData"] == {"kind": "test"}
    names = [e["name"] for e in doc["traceEvents"]]
    assert {"outer", "inner", "depth", "request", "enqueue", "prefill",
            "decode", "complete"} <= set(names)
    outer = next(e for e in doc["traceEvents"] if e["name"] == "outer")
    # clock reads: outer t0=0, inner 1,2, outer t1=3 → 3s in microseconds
    assert outer["ph"] == "X" and outer["dur"] == pytest.approx(3e6)
    assert outer["args"] == {"epoch": 0}
    rows = tr.jsonl_records()
    assert rows[0] == {"kind": "meta", "meta": {"kind": "test"}}
    kinds = {r["kind"] for r in rows}
    assert {"meta", "span", "counter", "monitor", "async_begin",
            "async_end", "instant"} <= kinds
    p = tmp_path / "trace.json"
    tr.write_chrome(p)
    assert json.loads(p.read_text())["traceEvents"] == doc["traceEvents"]
    q = tmp_path / "events.jsonl"
    tr.write_jsonl(q)
    lines = [json.loads(x) for x in q.read_text().splitlines()]
    assert lines == rows


# ---------------------------------------------------------------------------
# The training step's span tree, and spans on the profiler's clock
# ---------------------------------------------------------------------------

def _fit(tracer=None, max_steps=3, **obs):
    """A few PSL steps of the small CNN through ``fit()``."""
    spec = dataclasses.replace(
        train_spec(**obs), execution=api.ExecutionSpec(max_steps=max_steps))
    ctx = api.build_context(spec)
    return api.fit(ctx, get_protocol("psl")(),
                   api.default_callbacks(spec, ctx.data), tracer=tracer)


def _inside(child, parent) -> bool:
    return parent["ts"] <= child["ts"] \
        and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]


def _host_annotations(profile_dir):
    """``(name, stats)`` of every ``repro.*`` event the profiler wrote."""
    from jax.profiler import ProfileData
    files = list(pathlib.Path(profile_dir).rglob("*.xplane.pb"))
    assert len(files) == 1
    out = []
    for plane in ProfileData.from_file(str(files[0])).planes:
        for line in plane.lines:
            out.extend((e.name, dict(e.stats)) for e in line.events
                       if e.name.startswith("repro."))
    return out


def test_fit_span_tree_covers_each_step():
    tr = Tracer()
    res = _fit(tr, max_steps=3)
    assert len(res.step_metrics) == 3
    spans = {}
    for e in tr.chrome_trace()["traceEvents"]:
        spans.setdefault(e["name"], []).append(e)
    for name in ("batch", "batch.draw", "batch.put", "step", "callbacks"):
        assert len(spans[name]) == 3, name
    assert [e["args"]["step"] for e in spans["step"]] == [0, 1, 2]
    epoch, = spans["epoch"]
    for t in range(3):
        batch = spans["batch"][t]
        assert _inside(spans["batch.draw"][t], batch)
        assert _inside(spans["batch.put"][t], batch)
        assert spans["batch.draw"][t]["ts"] <= spans["batch.put"][t]["ts"]
        # batch, then step, then callbacks, in order and all in the epoch
        order = [batch, spans["step"][t], spans["callbacks"][t]]
        for a, b in zip(order, order[1:]):
            assert a["ts"] + a["dur"] <= b["ts"]
        assert all(_inside(e, epoch) for e in order)


def test_annotating_tracer_writes_spans_to_the_profiler(tmp_path):
    prof = tmp_path / "prof"
    _fit(max_steps=2, enabled=True, jax_profiler_dir=str(prof))
    events = _host_annotations(prof)
    names = {name for name, _ in events}
    assert {"repro.run", "repro.epoch", "repro.plan", "repro.batch",
            "repro.batch.draw", "repro.batch.put", "repro.step",
            "repro.callbacks", "repro.eval"} <= names
    steps = [stats for name, stats in events if name == "repro.step"]
    assert sorted(s["step"] for s in steps) == [0, 1]
    assert all(s["epoch"] == 0 for s in steps)


def test_null_and_virtual_clock_tracers_write_no_annotations(tmp_path):
    import jax
    from repro.runtime.scheduler import VirtualClock, WallClock
    prof = str(tmp_path / "prof")
    obs = api.ObsSpec(enabled=True, jax_profiler_dir=prof)
    virtual = tracer_from_spec(obs, clock=VirtualClock().now,
                               wall_clock=False)
    assert not virtual.annotate
    assert tracer_from_spec(obs, clock=WallClock().now).annotate
    assert not tracer_from_spec(api.ObsSpec(enabled=True)).annotate
    with jax.profiler.trace(prof):
        with null_tracer().span("null", step=0), virtual.span("virtual"):
            pass
        with tracer_from_spec(obs).span("wall", step=1):
            pass
    assert _host_annotations(prof) == [("repro.wall", {"step": 1})]
    assert [e["name"] for e in virtual.chrome_trace()["traceEvents"]] \
        == ["virtual"]


def test_fused_step_hlo_names_the_psl_halves():
    import jax
    from repro.api.protocols import _fresh_state
    from repro.core.psl import make_train_step
    ctx = api.build_context(train_spec())
    state = _fresh_state(ctx.model, ctx.optimizer, 0)
    batch = api.batch_from(np.zeros((8, 16, 16, 3), np.float32),
                           np.zeros(8, np.int64))
    text = jax.jit(make_train_step(ctx.model, ctx.optimizer)).lower(
        state, batch).as_text(debug_info=True)
    for scope in ("jvp(psl.client)", "transpose(jvp(psl.client))",
                  "jvp(psl.server)", "transpose(jvp(psl.server))",
                  "psl.update"):
        assert scope in text, scope


# ---------------------------------------------------------------------------
# Metrics primitives
# ---------------------------------------------------------------------------

def test_percentiles_match_numpy_and_include_p99():
    xs = list(np.random.default_rng(0).uniform(0, 100, 500))
    p = percentiles(xs)
    assert p["p50"] == pytest.approx(np.percentile(xs, 50))
    assert p["p95"] == pytest.approx(np.percentile(xs, 95))
    assert p["p99"] == pytest.approx(np.percentile(xs, 99))
    assert p["max"] == max(xs)
    assert percentiles([]) == {"mean": 0.0, "p50": 0.0, "p95": 0.0,
                               "p99": 0.0, "max": 0.0}


def test_p2_quantile_tracks_true_quantile():
    rng = np.random.default_rng(1)
    xs = rng.normal(50, 10, 5000)
    q = P2Quantile(0.95)
    for x in xs:
        q.update(float(x))
    assert q.value() == pytest.approx(np.percentile(xs, 95), rel=0.05)


def test_histogram_exact_below_cutoff_then_streams():
    h = Histogram()
    for x in range(100):
        h.observe(float(x))
    snap = h.snapshot()                 # exact regime
    assert snap["count"] == 100
    assert snap["p50"] == pytest.approx(np.percentile(range(100), 50))
    rng = np.random.default_rng(2)
    for x in rng.uniform(0, 100, 5000):
        h.observe(float(x))
    snap = h.snapshot()                 # P² regime
    assert snap["count"] == 5100
    assert snap["p95"] == pytest.approx(95.0, abs=5.0)


# ---------------------------------------------------------------------------
# GPSL monitor: silent on honest plans, fires on skew
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["ugs", "lds"])
def test_monitor_silent_on_planner_output(method):
    pop = _pop(k=10, per=50, m=5, seed=3)
    plan = make_plan(method, pop, 64, seed=1)
    mon = GPSLMonitor(pop, 64, epoch=0, num_steps=plan.num_steps)
    for t in range(plan.num_steps):
        mon.observe_plan_step(plan, t)
    s = mon.finish()
    assert s.ok, s.to_dict()
    assert s.steps == plan.num_steps
    assert s.residual_mass == 0
    assert s.max_class_deviation <= s.epsilon


def test_monitor_fires_on_skewed_plan():
    pop = _skew_pop(k=4, per=40, m=4)

    class SkewPlan:
        """Each step drains one class-pure client whole: max class
        proportion deviation is 1 - 1/4, far past any Serfling radius."""
        num_steps = 4
        global_batch_size = 40

        def step_segments(self, t):
            return np.array([t]), np.array([40])

    plan = SkewPlan()
    mon = GPSLMonitor(pop, 40, num_steps=4)
    for t in range(4):
        mon.observe_plan_step(plan, t)
    s = mon.finish()
    assert not s.ok
    assert s.deviation_violations == 4
    assert s.max_class_deviation == pytest.approx(0.75)
    assert s.residual_mass == 0


def test_monitor_flags_batch_size_and_overdraw():
    pop = _pop(k=4, per=16, m=4, seed=0)
    mon = GPSLMonitor(pop, 32, num_steps=2)
    r = mon.observe_step(0, [0, 1], [16, 8])      # 24 != 32 mid-epoch
    assert not r["batch_fixed"]
    r = mon.observe_step(1, [0], [10], final=True)  # client 0 is empty
    assert r["overdraw"] == 1
    s = mon.finish()
    assert s.batch_size_violations == 1
    assert s.overdraw_violations == 1
    assert s.residual_mass > 0


def test_monitor_truncated_epoch_residual_not_flagged():
    """max_steps-style truncation legitimately leaves data undrawn: the
    summary reports the residual but stays ok (complete=False)."""
    pop = _pop(k=10, per=50, m=5, seed=3)
    plan = make_plan("ugs", pop, 64, seed=1)
    mon = GPSLMonitor(pop, 64, num_steps=plan.num_steps)
    for t in range(2):
        mon.observe_plan_step(plan, t)
    s = mon.finish()
    assert not s.complete
    assert s.residual_mass > 0
    assert s.ok, s.to_dict()


def test_monitor_records_flow_into_tracer():
    pop = _pop(k=6, per=30, m=3, seed=5)
    plan = make_plan("ugs", pop, 36, seed=2)
    tr = Tracer(clock=lambda: 0.0)
    mon = GPSLMonitor(pop, 36, num_steps=plan.num_steps, tracer=tr)
    for t in range(plan.num_steps):
        mon.observe_plan_step(plan, t)
    mon.finish()
    kinds = [r["kind"] for r in tr.jsonl_records()]
    assert kinds.count("monitor") == plan.num_steps
    assert kinds.count("monitor_summary") == 1


# ---------------------------------------------------------------------------
# Streamed TPE twin (the plan_format="auto" enabler)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["ugs", "lds"])
def test_simulate_tpe_segments_matches_dense(method):
    pop = ClientPopulation.homogeneous(12, 40, 6, seed=7)
    pop = ClientPopulation(pop.dataset_sizes, pop.class_counts,
                           np.random.default_rng(7).uniform(0, 300, 12))
    plan = make_plan(method, pop, 48, seed=4)
    dense = simulate_tpe(plan.local_batch_sizes, pop.delays,
                         base_step_ms=60.0, per_sample_ms=0.5)
    seg = simulate_tpe_segments(plan, pop.delays,
                                base_step_ms=60.0, per_sample_ms=0.5)
    np.testing.assert_allclose(seg.per_step_ms, dense.per_step_ms)
    assert seg.total_ms == pytest.approx(dense.total_ms)
    np.testing.assert_array_equal(seg.contributing, dense.contributing)


# ---------------------------------------------------------------------------
# Non-interference + artifacts: training
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_traced_training_bitwise_identical_and_artifacts(tmp_path):
    trace = tmp_path / "trace.json"
    events = tmp_path / "events.jsonl"
    on = api.run(train_spec(enabled=True, trace_path=str(trace),
                            events_path=str(events)))
    off = api.run(train_spec())
    assert [m["loss"] for m in on.step_metrics] \
        == [m["loss"] for m in off.step_metrics]
    # the monitor's verdict lands in the run record (and is clean)
    mons = on.history.extras["gpsl_monitor"]
    assert len(mons) == 1 and mons[0]["ok"]
    assert "gpsl_monitor" not in off.history.extras
    doc = json.loads(trace.read_text())
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"run", "epoch", "plan", "batch", "step", "eval"} <= names
    steps = [e for e in doc["traceEvents"] if e["name"] == "step"]
    assert len(steps) == len(on.step_metrics)
    rows = [json.loads(x) for x in events.read_text().splitlines()]
    assert rows[0]["kind"] == "meta" and rows[0]["meta"]["kind"] == "train"
    assert sum(r["kind"] == "monitor" for r in rows) == mons[0]["steps"]


# ---------------------------------------------------------------------------
# Non-interference + determinism: serving
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_ctx():
    return api.build_serve_context(serve_spec())


def test_virtual_clock_serving_writes_no_annotations(tmp_path, serve_ctx):
    prof = tmp_path / "prof"
    api.run_serve(serve_spec(enabled=True, jax_profiler_dir=str(prof)),
                  ctx=serve_ctx)
    assert not [name for name, _ in _host_annotations(prof)
                if name.startswith("repro.")]


@pytest.mark.slow
def test_traced_serving_token_identical_and_deterministic(tmp_path,
                                                          serve_ctx):
    p1, p2 = tmp_path / "t1.json", tmp_path / "t2.json"
    on = api.run_serve(serve_spec(enabled=True, trace_path=str(p1),
                                  events_path=str(tmp_path / "e1.jsonl")),
                       ctx=serve_ctx)
    off = api.run_serve(serve_spec(), ctx=serve_ctx)
    assert [r["tokens"] for r in on.per_request] \
        == [r["tokens"] for r in off.per_request]
    doc = json.loads(p1.read_text())
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"admit", "decode_step", "request", "enqueue", "prefill",
            "decode", "complete", "active_slots", "queued"} <= names
    # one lifecycle track per request
    assert sum(e["name"] == "request" and e["ph"] == "b"
               for e in doc["traceEvents"]) == on.num_requests
    # VirtualClock trace is a pure function of the spec: byte-identical
    api.run_serve(serve_spec(enabled=True, trace_path=str(p2)),
                  ctx=serve_ctx)
    assert p1.read_text() == p2.read_text()


@pytest.mark.slow
def test_traced_static_serving_shared_ttft(tmp_path):
    trace = tmp_path / "static.json"
    spec = serve_spec(enabled=True, trace_path=str(trace)).replace(
        engine=api.EngineSpec(name="static"), clock=api.ClockSpec())
    rep = api.run_serve(spec)
    assert rep.ttft_shared
    assert rep.to_json()["ttft_shared"] is True
    ttfts = {r["ttft_ms"] for r in rep.per_request}
    assert len(ttfts) == 1               # one shared post-prefill stamp
    names = {e["name"] for e in
             json.loads(trace.read_text())["traceEvents"]}
    assert {"admit", "decode", "request", "prefill", "complete"} <= names


# ---------------------------------------------------------------------------
# trace_report CLI
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_trace_report_renders_both_formats(tmp_path, serve_ctx):
    trace = tmp_path / "trace.json"
    events = tmp_path / "events.jsonl"
    api.run_serve(serve_spec(enabled=True, trace_path=str(trace),
                             events_path=str(events)), ctx=serve_ctx)
    for artifact in (trace, events):
        out = subprocess.run(
            [sys.executable, str(REPO / "tools" / "trace_report.py"),
             str(artifact)], capture_output=True, text=True, check=True)
        assert "decode_step" in out.stdout
        assert "lifecycle" in out.stdout
    doc = json.loads(subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace_report.py"),
         str(trace), "--json"], capture_output=True, text=True,
        check=True).stdout)
    assert doc["meta"]["kind"] == "serve"
    assert doc["phases"]["decode_step"]["count"] >= 1
    assert doc["requests"]["request"]["count"] == 6


def test_trace_report_flags_monitor_violations(tmp_path):
    pop = _skew_pop()
    tr = Tracer(clock=lambda: 0.0, meta={"kind": "train"})
    mon = GPSLMonitor(pop, 40, num_steps=4, tracer=tr)

    class SkewPlan:
        num_steps = 4
        global_batch_size = 40

        def step_segments(self, t):
            return np.array([t]), np.array([40])

    for t in range(4):
        mon.observe_plan_step(SkewPlan(), t)
    mon.finish()
    events = tmp_path / "events.jsonl"
    tr.write_jsonl(events)
    out = subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace_report.py"),
         str(events)], capture_output=True, text=True)
    assert out.returncode == 1           # violations → non-zero exit
    assert "VIOLATION" in out.stdout
