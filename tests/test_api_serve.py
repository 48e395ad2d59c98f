"""The declarative serving API: ServeSpec round trips, policy registries,
spec-driven serving token-identity (fifo + ljf), engine A/B through the
registry, and the train→checkpoint→serve artifact loop (docs/api.md)."""
import json

import numpy as np
import pytest

from repro import api

ARCH = "granite-3-2b"


def tiny_serve_spec() -> api.ServeSpec:
    """Small mixed-length workload on a 2-slot continuous pool."""
    return api.ServeSpec(
        model=api.ModelSpec(arch=ARCH, reduced=True),
        admission=api.AdmissionSpec(token_budget=2),
        workload=api.WorkloadSpec(num_requests=5, prompt_lens=[4, 7, 12],
                                  max_new_tokens=[2, 5], seed=3),
        clock=api.ClockSpec(kind="virtual"))


@pytest.fixture(scope="module")
def served_ctx():
    """One engine (compiled once) reused across the spec-variant tests;
    variants may change scheduling/workload axes, not the pool geometry."""
    return api.build_serve_context(tiny_serve_spec())


# ---------------------------------------------------------------------------
# Spec serialization + validation
# ---------------------------------------------------------------------------

def test_serve_spec_json_round_trip_is_deterministic():
    spec = tiny_serve_spec().replace(
        scheduler=api.SchedulerSpec(policy="ljf"),
        workload=api.WorkloadSpec(
            num_requests=9, prompt_lens=[8, 16], max_new_tokens=[4],
            arrivals=api.StragglerSpec(p_straggler=0.5, seed=11)),
        checkpoint="runs/params.npz")
    text = spec.to_json()
    again = api.ServeSpec.from_json(text)
    assert again == spec
    assert again.to_json() == text                 # fixed point
    d = json.loads(text)
    assert d["kind"] == "serve"
    assert d["workload"]["prompt_lens"] == [8, 16]
    assert d["workload"]["arrivals"]["p_straggler"] == 0.5
    assert d["checkpoint"] == "runs/params.npz"


def test_serve_spec_rejects_unknown_fields_and_bad_values():
    with pytest.raises(api.SpecError, match="unknown field"):
        api.ServeSpec.from_dict({"engine": {"nome": "continuous"}})
    with pytest.raises(api.SpecError, match="unknown engine"):
        tiny_serve_spec().replace(
            engine=api.EngineSpec(name="warp")).validate()
    with pytest.raises(api.SpecError, match="unknown scheduler policy"):
        tiny_serve_spec().replace(
            scheduler=api.SchedulerSpec(policy="psjf")).validate()
    with pytest.raises(api.SpecError, match="unknown admission policy"):
        tiny_serve_spec().replace(
            admission=api.AdmissionSpec(policy="oracle")).validate()
    with pytest.raises(api.SpecError, match="budgeted slots"):
        tiny_serve_spec().replace(
            engine=api.EngineSpec(num_slots=2),
            admission=api.AdmissionSpec(token_budget=5)).validate()
    with pytest.raises(api.SpecError, match="decoder LM"):
        tiny_serve_spec().replace(
            model=api.ModelSpec(arch="paper-cnn")).validate()
    with pytest.raises(api.SpecError, match="kind"):
        tiny_serve_spec().replace(kind="experiment").validate()
    # static engine: no token-identity verify, no staggered arrivals
    static = tiny_serve_spec().replace(engine=api.EngineSpec(name="static"))
    with pytest.raises(api.SpecError, match="continuous engine"):
        static.replace(report=api.ReportSpec(verify=-1)).validate()
    with pytest.raises(api.SpecError, match="up front"):
        static.replace(workload=static.workload.replace(
            arrivals=api.StragglerSpec())).validate()


def test_serve_spec_geometry_resolution():
    spec = tiny_serve_spec()
    assert spec.resolved_num_slots() == 2          # ← token budget
    assert spec.resolved_slot_len() == 12 + 5      # max prompt + max new
    assert spec.replace(
        engine=api.EngineSpec(num_slots=4, slot_len=64)
    ).resolved_num_slots() == 4
    bare = spec.replace(admission=api.AdmissionSpec())
    assert bare.resolved_num_slots() == 5          # ← workload size


def test_load_any_spec_dispatches_on_kind(tmp_path):
    train = tmp_path / "train.json"
    serve = tmp_path / "serve.json"
    train.write_text(api.ExperimentSpec().to_json())
    serve.write_text(tiny_serve_spec().to_json())
    assert isinstance(api.load_any_spec(str(train)), api.ExperimentSpec)
    assert isinstance(api.load_any_spec(str(serve)), api.ServeSpec)
    bad = tmp_path / "bad.json"
    bad.write_text('{"kind": "deploy"}')
    with pytest.raises(api.SpecError, match="unknown spec kind"):
        api.load_any_spec(str(bad))


# ---------------------------------------------------------------------------
# Policy registries
# ---------------------------------------------------------------------------

def test_registries_list_builtins_and_reject_unknown():
    assert {"fifo", "ljf"} <= set(api.available_scheduler_policies())
    assert "budget" in api.available_admission_policies()
    assert {"continuous", "static"} <= set(api.available_engines())
    with pytest.raises(api.UnknownPolicyError, match="sjf"):
        api.get_scheduler_policy("sjf")
    with pytest.raises(api.UnknownPolicyError, match="warp"):
        api.get_engine("warp")
    with pytest.raises(ValueError, match="already registered"):
        api.register_scheduler_policy("fifo")(type("X", (), {}))


def test_builtins_survive_early_custom_registration():
    """A custom policy registered before the first lookup must not shadow
    the built-ins (regression: lazy loading keyed on table emptiness)."""
    import os
    import pathlib
    import subprocess
    import sys
    code = (
        "from repro.api import register_scheduler_policy, "
        "available_scheduler_policies\n"
        "@register_scheduler_policy('early')\n"
        "class Early:\n"
        "    def order(self, ready):\n"
        "        pass\n"
        "names = set(available_scheduler_policies())\n"
        "assert {'early', 'fifo', 'ljf'} <= names, names\n")
    env = dict(os.environ,
               PYTHONPATH=str(pathlib.Path(__file__).parent.parent / "src"))
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stderr


def test_custom_scheduler_policy_is_one_registry_entry(served_ctx):
    """A new admission order = one decorator; reachable from the spec."""
    @api.register_scheduler_policy("_test_sjf")
    class ShortestJobFirst:
        def order(self, ready):
            ready.sort(key=lambda r: r.max_new_tokens)

    try:
        spec = tiny_serve_spec().replace(
            scheduler=api.SchedulerSpec(policy="_test_sjf"),
            report=api.ReportSpec(verify=-1))
        report = api.run_serve(spec, ctx=served_ctx)
        assert report.verified == {"checked": 5, "mismatches": []}
    finally:
        from repro.api import registry
        registry._SCHEDULER_POLICIES.pop("_test_sjf")


# ---------------------------------------------------------------------------
# api.run on a ServeSpec: token-identity + invariants
# ---------------------------------------------------------------------------

def test_api_run_serve_token_identical_fifo_and_ljf(served_ctx):
    """The acceptance bar: spec-driven serving reproduces single-request
    greedy decoding token for token, under both admission orders."""
    from repro.runtime import reference_generate
    for policy in ("fifo", "ljf"):
        spec = tiny_serve_spec().replace(
            scheduler=api.SchedulerSpec(policy=policy),
            report=api.ReportSpec(verify=-1))
        report = api.run_serve(spec, ctx=served_ctx)
        assert report.engine == "continuous"
        assert report.num_requests == 5
        assert report.verified == {"checked": 5, "mismatches": []}
        # belt and braces: re-derive the reference outside verify_report
        reqs = api.build_workload(spec, served_ctx.engine.cfg.vocab_size)
        got = {r["rid"]: r["tokens"] for r in report.per_request}
        for req in reqs[:2]:
            assert got[req.rid] == reference_generate(
                served_ctx.model, served_ctx.params, req.prompt,
                req.max_new_tokens, served_ctx.engine.pool.slot_len)


def test_verify_report_locates_divergence_and_excuses_near_ties(
        served_ctx):
    """A divergence is reported with its first diverging token and the
    reference's top-2 logit margin there. A margin tolerance above that
    margin excuses it; the default tolerance of 0 keeps the check exact."""
    from repro.runtime import reference_generate
    spec = tiny_serve_spec()
    report = api.run_serve(spec, ctx=served_ctx)
    row = next(r for r in report.per_request if len(r["tokens"]) >= 2)
    row["tokens"][1] = (row["tokens"][1] + 1) \
        % served_ctx.engine.cfg.vocab_size
    with pytest.raises(RuntimeError, match=rf"rids \[{row['rid']}\]"):
        api.verify_report(report, served_ctx)
    audit = api.verify_report(report, served_ctx, margin_tol=float("inf"))
    assert audit["mismatches"] == []
    [excused] = audit["excused"]
    assert excused["rid"] == row["rid"]
    assert excused["first_diverging_token"] == 1
    req = next(r for r in api.build_workload(
        spec, served_ctx.engine.cfg.vocab_size) if r.rid == row["rid"])
    toks, margins = reference_generate(
        served_ctx.model, served_ctx.params, req.prompt,
        req.max_new_tokens, served_ctx.engine.pool.slot_len,
        with_margins=True)
    assert toks == reference_generate(
        served_ctx.model, served_ctx.params, req.prompt,
        req.max_new_tokens, served_ctx.engine.pool.slot_len)
    assert excused["top2_margin"] == margins[1] >= 0.0


def test_run_serve_with_arrivals_keeps_admission_invariant(served_ctx):
    spec = tiny_serve_spec().replace(
        workload=tiny_serve_spec().workload.replace(
            arrivals=api.StragglerSpec(p_straggler=0.6, w_min=1.0,
                                       w_max=30.0, seed=5)))
    report = api.run_serve(spec, ctx=served_ctx)
    assert report.num_requests == 5
    assert report.step_active and max(report.step_active) <= 2
    served_ctx.engine.pool.check_no_leaks()
    arrivals = sorted(r["arrival_s"] for r in report.per_request)
    assert arrivals[-1] > 0.0                      # someone straggled
    assert all(r["ttft_ms"] >= 0.0 for r in report.per_request)


def test_run_serve_report_out_respects_per_request(tmp_path, served_ctx):
    out = tmp_path / "report.json"
    spec = tiny_serve_spec().replace(
        report=api.ReportSpec(per_request=False, out=str(out)))
    api.run_serve(spec, ctx=served_ctx)
    j = json.loads(out.read_text())
    assert j["engine"] == "continuous"
    assert j["num_requests"] == 5
    assert "per_request" not in j


def test_api_run_dispatches_on_spec_kind(served_ctx):
    report = api.run(tiny_serve_spec().replace(
        report=api.ReportSpec(verify=2)), ctx=served_ctx)
    assert report.engine == "continuous"
    assert report.verified == {"checked": 2, "mismatches": []}
    with pytest.raises(ValueError, match="training-loop feature"):
        api.run(tiny_serve_spec(), callbacks=[api.ConsoleLogger()])


# ---------------------------------------------------------------------------
# Engine A/B through the registry
# ---------------------------------------------------------------------------

def test_static_engine_matches_continuous_on_equal_lengths(served_ctx):
    """Same-length prompts involve no static padding, so the two registered
    engines must emit identical tokens for the same seeded workload."""
    wl = api.WorkloadSpec(num_requests=3, prompt_lens=[7],
                          max_new_tokens=[4], seed=9)
    cont = api.run_serve(tiny_serve_spec().replace(workload=wl),
                         ctx=served_ctx)
    static_spec = tiny_serve_spec().replace(
        engine=api.EngineSpec(name="static"), workload=wl)
    static = api.run(static_spec)
    assert static.engine == "static"
    assert static.steps == 3                       # max_new - 1
    assert static.decode_tokens == 3 * 3           # every row rides along
    got_c = {r["rid"]: r["tokens"] for r in cont.per_request}
    got_s = {r["rid"]: r["tokens"] for r in static.per_request}
    assert got_c == got_s


# ---------------------------------------------------------------------------
# The train→checkpoint→serve artifact loop
# ---------------------------------------------------------------------------

def test_train_checkpoint_then_serve_pipeline(tmp_path):
    """Two JSON files reproduce train-then-serve end to end: the training
    spec emits a params artifact; the serve spec references it by path and
    serves the *trained* model, token-identical to reference decoding."""
    from repro.checkpoint import restore, tree_equal
    ckpt = tmp_path / "params.npz"
    train_spec = api.ExperimentSpec(
        seed=0,
        model=api.ModelSpec(arch=ARCH, reduced=True),
        optimizer=api.OptimizerSpec(name="adamw", lr=1e-3),
        data=api.DataSpec(kind="synthetic_lm", num_clients=2,
                          sequences=24, seq_len=16),
        protocol=api.ProtocolSpec(name="psl", epochs=1,
                                  global_batch_size=8),
        execution=api.ExecutionSpec(max_steps=2, checkpoint=str(ckpt)),
        eval=api.EvalSpec(enabled=False))
    serve_spec = api.ServeSpec(
        model=api.ModelSpec(arch=ARCH, reduced=True),
        checkpoint=str(ckpt),
        admission=api.AdmissionSpec(token_budget=2),
        workload=api.WorkloadSpec(num_requests=3, prompt_lens=[5, 9],
                                  max_new_tokens=[3, 4], seed=7),
        clock=api.ClockSpec(kind="virtual"),
        report=api.ReportSpec(verify=-1))
    (tmp_path / "train.json").write_text(train_spec.to_json())
    (tmp_path / "serve.json").write_text(serve_spec.to_json())

    # from here on, the two JSON files are the only inputs
    result = api.run(api.load_any_spec(str(tmp_path / "train.json")))
    assert len(result.step_metrics) == 2
    assert result.history.extras["checkpoint"] == str(ckpt)
    assert ckpt.exists()
    assert tree_equal(restore(str(ckpt)), result.params)

    report = api.run(api.load_any_spec(str(tmp_path / "serve.json")))
    assert report.num_requests == 3
    # verify=-1 ran inside run_serve against the *restored* params — and
    # the artifact equals the trained params, so the served model is the
    # trained one, not a fresh init
    assert report.verified == {"checked": 3, "mismatches": []}


def test_restore_params_rejects_mismatched_artifact(tmp_path):
    from repro.checkpoint import save
    bad = tmp_path / "bad.npz"
    save(str(bad), {"not": {"the": np.zeros(3, np.float32)}})
    spec = tiny_serve_spec().replace(checkpoint=str(bad))
    with pytest.raises(api.SpecError, match="does not match"):
        api.build_serve_context(spec)
