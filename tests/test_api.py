"""The declarative experiment API: spec round trips, the protocol registry,
dotted overrides, and the seed-for-seed equivalence of ``api.run(spec)``
against a frozen transcription of the pre-refactor ``train_psl`` loop."""
import itertools
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, optim
from repro.configs import get_config
from repro.core import sampling as sampling_lib
from repro.core.partition import partition_dirichlet
from repro.core.psl import make_train_step
from repro.data.federated import ClientStore, GlobalBatchIterator
from repro.data.synthetic import make_classification_dataset
from repro.models.cnn import CNNModel
from repro.optim import TrainState


def small_spec(**protocol_over) -> api.ExperimentSpec:
    proto = dict(name="psl", epochs=2, global_batch_size=32, batch_size=16)
    proto.update(protocol_over)
    return api.ExperimentSpec(
        seed=0,
        model=api.ModelSpec(arch="paper-cnn", reduced=True),
        optimizer=api.OptimizerSpec(name="sgd", lr=5e-2, momentum=0.9,
                                    weight_decay=5e-4),
        data=api.DataSpec(num_train=600, num_test=200, image_size=16,
                          num_clients=4, partition="dirichlet",
                          partition_seed=1),
        protocol=api.ProtocolSpec(**proto))


# ---------------------------------------------------------------------------
# Spec serialization
# ---------------------------------------------------------------------------

def test_spec_json_round_trip_is_deterministic():
    spec = small_spec()
    spec = spec.replace(
        sampler=api.SamplerSpec(method="lds", kwargs={"delta": 1.5}),
        data=spec.data.replace(straggler=api.StragglerSpec(
            p_straggler=0.2, seed=20)))
    text = spec.to_json()
    again = api.ExperimentSpec.from_json(text)
    assert again == spec
    assert again.to_json() == text                 # fixed point
    assert json.loads(text)["sampler"]["kwargs"] == {"delta": 1.5}
    assert json.loads(text)["data"]["straggler"]["p_straggler"] == 0.2


def test_spec_rejects_unknown_fields_and_bad_values():
    with pytest.raises(api.SpecError, match="unknown field"):
        api.ExperimentSpec.from_dict({"protocol": {"nome": "psl"}})
    with pytest.raises(api.SpecError, match="unknown protocol"):
        small_spec(name="gossip").validate()
    with pytest.raises(api.SpecError, match="unknown sampling method"):
        small_spec().replace(
            sampler=api.SamplerSpec(method="antigravity")).validate()
    with pytest.raises(api.SpecError, match="sharded engine"):
        small_spec(name="fl").replace(
            execution=api.ExecutionSpec(engine="sharded")).validate()


def test_spec_defaults_validate():
    assert api.ExperimentSpec().validate() is not None


# ---------------------------------------------------------------------------
# Protocol registry
# ---------------------------------------------------------------------------

def test_registry_lists_builtins_and_rejects_unknown():
    names = api.available_protocols()
    assert {"cl", "sl", "fl", "sfl", "psl"} <= set(names)
    with pytest.raises(api.UnknownProtocolError, match="cyclesl"):
        api.get_protocol("cyclesl")


def test_registry_registration_and_duplicate_guard():
    @api.register_protocol("_test_proto")
    class TestStrategy(api.ProtocolStrategy):
        def setup(self, ctx):
            return {"steps": 0}

        def epoch_batches(self, ctx, pstate, plan, epoch):
            for i in range(3):
                yield api.StepItem(i)

        def step(self, ctx, pstate, item):
            pstate["steps"] += 1
            return pstate, {"loss": float(item.batch)}

        def eval_params(self, ctx, pstate):
            return None

    try:
        assert api.get_protocol("_test_proto") is TestStrategy
        with pytest.raises(ValueError, match="already registered"):
            api.register_protocol("_test_proto")(TestStrategy)
        # a registered strategy is drivable by the shared loop as-is
        # (fit never consults protocol.name — the strategy is explicit)
        spec = small_spec()
        ctx = api.RunContext(model=None, optimizer=None,
                             data=api.DataBundle(), spec=spec)
        result = api.fit(ctx, TestStrategy())
        assert len(result.step_metrics) == 6      # 2 epochs x 3 items
        assert result.step_metrics[0]["loss"] == 0.0
    finally:
        from repro.api import registry
        registry._PROTOCOLS.pop("_test_proto", None)


# ---------------------------------------------------------------------------
# Dotted overrides
# ---------------------------------------------------------------------------

def test_parse_set_value_forms():
    assert api.parse_set("protocol.epochs=3") == ("protocol.epochs", 3)
    assert api.parse_set("sampler.kwargs.delta=1.5") == \
        ("sampler.kwargs.delta", 1.5)
    assert api.parse_set("model.reduced=true") == ("model.reduced", True)
    assert api.parse_set("sampler.method=lds") == ("sampler.method", "lds")
    assert api.parse_set('model.arch="paper-cnn"') == \
        ("model.arch", "paper-cnn")
    with pytest.raises(api.SpecError, match="key=value"):
        api.parse_set("no-equals-sign")


def test_apply_overrides_walks_and_validates_paths():
    spec = small_spec()
    out = api.apply_overrides(spec, [
        "protocol.epochs=9", "sampler.method=lds",
        "sampler.kwargs.delta=1.5", "data.num_clients=16",
        "execution.mesh=2x2"])
    assert out.protocol.epochs == 9
    assert out.sampler.method == "lds"
    assert out.sampler.kwargs == {"delta": 1.5}
    assert out.data.num_clients == 16
    assert out.execution.mesh == "2x2"
    assert spec.protocol.epochs == 2               # original untouched
    with pytest.raises(api.SpecError, match="unknown field"):
        api.apply_overrides(spec, ["protocol.epochz=9"])
    with pytest.raises(api.SpecError, match="unknown field"):
        api.apply_overrides(spec, ["protocl.epochs=9"])
    with pytest.raises(api.SpecError, match="leaf"):
        api.apply_overrides(spec, ["protocol.epochs.deep=9"])


# ---------------------------------------------------------------------------
# Shared helpers
# ---------------------------------------------------------------------------

def test_jitted_predict_is_cached_per_model():
    model = CNNModel(get_config("paper-cnn", reduced=True))
    assert api.jitted_predict(model) is api.jitted_predict(model)
    other = CNNModel(get_config("paper-cnn", reduced=True))
    assert api.jitted_predict(other) is not api.jitted_predict(model)


def test_lm_plan_batches_shapes_and_padding():
    from repro.api.protocols import lm_plan_batches
    from repro.core.types import ClientPopulation
    pop = ClientPopulation.homogeneous(3, 10, 4, seed=0)
    rng = np.random.default_rng(0)
    seq = 8
    data = [rng.integers(0, 50, (n, seq + 1)).astype(np.int64)
            for n in pop.dataset_sizes]
    plan = sampling_lib.make_plan("ugs", pop, 8, seed=0)
    shard_of_client = np.arange(3) % 2
    batches = list(lm_plan_batches(data, pop, plan, seq, "global_mean",
                                   shard_of_client, seed=0))
    assert len(batches) == plan.num_steps
    for b in batches:
        assert b["tokens"].shape == (8, seq)
        assert b["labels"].shape == (8, seq)
        assert b["weights"].shape == (8, seq)
    # final ragged step is padded with weight-0 slots
    total = int(pop.total_size)
    used = sum(int((b["weights"][:, 0] > 0).sum()) for b in batches)
    assert used == total


# ---------------------------------------------------------------------------
# Equivalence: api.run(spec) == the pre-refactor train_psl loop
# ---------------------------------------------------------------------------

def _legacy_train_psl(model, optimizer, store, test, *, epochs,
                      global_batch_size, method="ugs",
                      aggregation="global_mean", seed=0):
    """Frozen transcription of the pre-refactor ``train_psl`` (PR 3 state),
    recording per-step losses alongside the per-epoch accuracies."""
    def _batch_from(features, labels, weights=None):
        b = {"labels": jnp.asarray(labels, jnp.int32),
             "weights": jnp.asarray(
                 np.ones(len(labels), np.float32) if weights is None
                 else weights)}
        b["images"] = jnp.asarray(features)
        return b

    def _evaluate(params, features, labels, batch_size=512):
        correct = 0
        predict = jax.jit(model.predict)
        for i in range(0, len(features), batch_size):
            logits = predict(params, jnp.asarray(features[i:i + batch_size]))
            correct += int((np.asarray(logits).argmax(-1)
                            == labels[i:i + batch_size]).sum())
        return correct / len(features)

    step = jax.jit(make_train_step(model, optimizer))
    params = model.init(jax.random.PRNGKey(seed))
    state = TrainState(params, optimizer.init(params),
                       jnp.zeros((), jnp.int32))
    hist, losses = [], []
    for e in range(epochs):
        plan = sampling_lib.make_plan(method, store.population,
                                      global_batch_size, seed=seed + e,
                                      backend="numpy")
        for gb in GlobalBatchIterator(store, plan, aggregation,
                                      seed=seed * 1000 + e):
            state, m = step(state, _batch_from(gb["features"], gb["labels"],
                                               gb["weights"]))
            losses.append(m["loss"])
        hist.append(_evaluate(state.params, *test))
    return hist, [float(x) for x in losses]


def test_api_run_matches_legacy_train_psl_bitwise():
    spec = api.ExperimentSpec.from_json(small_spec().to_json())
    result = api.run(spec)

    X, y = make_classification_dataset(600, image_size=16, seed=0)
    Xt, yt = make_classification_dataset(200, image_size=16, seed=99)
    parts, pop = partition_dirichlet(y, 4, 10, seed=1)
    store = ClientStore.from_partition(X, y, parts, pop)
    model = CNNModel(get_config("paper-cnn", reduced=True))
    hist, losses = _legacy_train_psl(
        model, optim.sgd(5e-2, momentum=0.9, weight_decay=5e-4), store,
        (Xt, yt), epochs=2, global_batch_size=32, seed=0)

    assert result.test_acc == hist                          # bitwise
    assert [m["loss"] for m in result.step_metrics] == losses
    assert result.history.extras["em_iterations"] == 0
    assert result.history.extras["tpe_ms"] == []


def test_all_legacy_entry_points_run_via_shims():
    from repro.frameworks import (train_cl, train_fl, train_psl,
                                  train_psl_sharded, train_sfl, train_sl)
    X, y = make_classification_dataset(300, image_size=16, seed=0)
    Xt, yt = make_classification_dataset(80, image_size=16, seed=99)
    parts, pop = partition_dirichlet(y, 4, 10, seed=1)
    store = ClientStore.from_partition(X, y, parts, pop)
    model = CNNModel(get_config("paper-cnn", reduced=True))
    mk = lambda: optim.sgd(5e-2, momentum=0.9)
    hists = {
        "cl": train_cl(model, mk(), X, y, (Xt, yt), epochs=1,
                       batch_size=32, seed=0),
        "psl": train_psl(model, mk(), store, (Xt, yt), epochs=1,
                         global_batch_size=32, seed=0),
        "psl_sharded": train_psl_sharded(model, mk(), store, (Xt, yt),
                                         epochs=1, global_batch_size=32,
                                         seed=0),
        "sl": train_sl(model, mk(), store, (Xt, yt), epochs=1,
                       batch_size=16, seed=0),
        "fl": train_fl(model, mk(), store, (Xt, yt), epochs=1,
                       batch_size=16, seed=0),
        "sfl": train_sfl(model, mk(), store, (Xt, yt), epochs=1,
                         batch_size=16, seed=0),
    }
    for name, h in hists.items():
        assert len(h.test_acc) == 1, name
        assert np.isfinite(h.test_acc[0]), name
    # the single-device sharded engine runs the same protocol (identical
    # plans/batches; grads differ only by sum-then-normalize reassociation)
    np.testing.assert_allclose(hists["psl_sharded"].test_acc,
                               hists["psl"].test_acc, atol=0.05)
    assert hists["psl_sharded"].extras["sharding_fallbacks"] is not None


def test_every_shim_warns_deprecation_and_matches_api_run():
    """Each of the six legacy ``train_*`` entry points emits a
    DeprecationWarning and returns the exact trajectory ``api.run(spec)``
    produces for the equivalent spec (same seeds, same callbacks)."""
    from repro.frameworks import (train_cl, train_fl, train_psl,
                                  train_psl_sharded, train_sfl, train_sl)
    X, y = make_classification_dataset(300, image_size=16, seed=0)
    Xt, yt = make_classification_dataset(80, image_size=16, seed=99)
    parts, pop = partition_dirichlet(y, 4, 10, seed=1)
    store = ClientStore.from_partition(X, y, parts, pop)
    model = CNNModel(get_config("paper-cnn", reduced=True))
    mk = lambda: optim.sgd(5e-2, momentum=0.9)

    def spec_for(protocol, engine="fused"):
        return api.ExperimentSpec(
            seed=0,
            model=api.ModelSpec(arch="paper-cnn", reduced=True),
            optimizer=api.OptimizerSpec(name="sgd", lr=5e-2, momentum=0.9,
                                        weight_decay=0.0),
            data=api.DataSpec(num_train=300, num_test=80, image_size=16,
                              num_clients=4),
            protocol=api.ProtocolSpec(name=protocol, epochs=1,
                                      batch_size=16,
                                      global_batch_size=32),
            execution=api.ExecutionSpec(engine=engine))

    shim_calls = {
        "cl": lambda: train_cl(model, mk(), X, y, (Xt, yt), epochs=1,
                               batch_size=16, seed=0),
        "sl": lambda: train_sl(model, mk(), store, (Xt, yt), epochs=1,
                               batch_size=16, seed=0),
        "fl": lambda: train_fl(model, mk(), store, (Xt, yt), epochs=1,
                               batch_size=16, seed=0),
        "sfl": lambda: train_sfl(model, mk(), store, (Xt, yt), epochs=1,
                                 batch_size=16, seed=0),
        "psl": lambda: train_psl(model, mk(), store, (Xt, yt), epochs=1,
                                 global_batch_size=32, seed=0),
        "psl_sharded": lambda: train_psl_sharded(
            model, mk(), store, (Xt, yt), epochs=1, global_batch_size=32,
            seed=0),
    }
    for name, call in shim_calls.items():
        with pytest.warns(DeprecationWarning, match="deprecated"):
            hist = call()
        protocol = "psl" if name.startswith("psl") else name
        engine = "sharded" if name == "psl_sharded" else "fused"
        got = api.run(spec_for(protocol, engine))
        assert hist.test_acc == got.test_acc, name        # bitwise
        assert set(hist.extras) == set(got.history.extras), name


def test_run_with_prebuilt_ctx_honors_the_passed_spec():
    base = small_spec(epochs=1)
    ctx = api.build_context(base)
    psl = api.run(base, ctx=ctx)
    cl = api.run(api.apply_overrides(base, ["protocol.name=cl"]), ctx=ctx)
    # the override spec must win over the (stale) spec inside ctx: the CL
    # run has no plan-driven extras, and trains per-epoch CL step counts
    assert "em_iterations" in psl.history.extras
    assert cl.history.extras == {}
    n = base.data.num_train
    assert len(cl.step_metrics) == n // base.protocol.batch_size
    assert len(psl.step_metrics) == -(-n // base.protocol.global_batch_size)


def test_run_with_straggler_spec_tracks_tpe():
    spec = small_spec(track_tpe=True, epochs=1)
    spec = spec.replace(
        sampler=api.SamplerSpec(method="lds", kwargs={"delta": 0.0}),
        data=spec.data.replace(straggler=api.StragglerSpec(
            p_straggler=0.5, w_min=100, w_max=500, seed=2)))
    h = api.run(spec).history
    assert len(h.extras["tpe_ms"]) == 1
    assert h.extras["tpe_ms"][0] > 0
    assert h.extras["em_iterations"] > 0


# ---------------------------------------------------------------------------
# State ownership: who donates its TrainState to the step
# ---------------------------------------------------------------------------

def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


@pytest.mark.parametrize("name", ["psl", "cl", "sl"])
def test_owned_state_is_donated_and_steps_stay_bitwise(name):
    ctx = api.build_context(small_spec(name=name, epochs=1))
    strategy = api.get_protocol(name)()
    pstate = strategy.setup(ctx)
    ref_state = jax.tree_util.tree_map(jnp.copy, pstate["state"])
    ref_step = jax.jit(make_train_step(ctx.model, ctx.optimizer))
    items = strategy.epoch_batches(ctx, pstate, strategy.plan_epoch(ctx, 0),
                                   0)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for item in itertools.islice(items, 3):
            old = pstate["state"]
            pstate, metrics = strategy.step(ctx, pstate, item)
            ref_state, ref_metrics = ref_step(ref_state, item.batch)
            assert all(x.is_deleted() for x in _leaves(old))
            assert {k: float(v) for k, v in metrics.items()} \
                == {k: float(v) for k, v in ref_metrics.items()}
            for got, want in zip(_leaves(pstate["state"]),
                                 _leaves(ref_state)):
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(want))
    assert not [w for w in caught if "donated buffers" in str(w.message)]


def test_psl_counts_its_donated_state_leaves_once():
    from repro.obs import Tracer
    spec = small_spec(epochs=1).replace(
        execution=api.ExecutionSpec(max_steps=3))
    ctx = api.build_context(spec)
    tracer = Tracer()
    result = api.fit(ctx, api.get_protocol("psl")(),
                     api.default_callbacks(spec, ctx.data), tracer=tracer)
    n = len(_leaves(result.state["state"]))
    counts = [e["args"]["value"] for e in tracer.events
              if e["ph"] == "C" and e["name"] == "psl.donated_state_leaves"]
    assert counts == [n]
    assert result.history.extras["donated_state_leaves"] \
        == {"donated": n, "leaves": n}


@pytest.mark.parametrize("name", ["fl", "sfl"])
def test_per_client_strategies_keep_their_global_params(name):
    ctx = api.build_context(small_spec(name=name, epochs=1))
    strategy = api.get_protocol(name)()
    pstate = strategy.setup(ctx)
    key = "global_params" if name == "fl" else "params"
    kept = pstate[key]
    # a copy on the device: a host view of a CPU buffer would itself
    # keep the buffer from being donated
    kept_copy = jax.tree_util.tree_map(jnp.copy, kept)
    ref_step = jax.jit(make_train_step(ctx.model, ctx.optimizer))
    ref, client, switches = None, None, 0
    for item in strategy.epoch_batches(ctx, pstate, None, 0):
        if item.scope != client:
            # FL starts each client from the global params; SFL from the
            # global client segment and the server as the last client
            # left it
            start = kept
            if name == "sfl" and ref is not None:
                start = {"client": kept["client"],
                         "server": ref.params["server"]}
            ref = TrainState(start, ctx.optimizer.init(start),
                             jnp.zeros((), jnp.int32))
            client, switches = item.scope, switches + 1
        pstate, metrics = strategy.step(ctx, pstate, item)
        ref, ref_metrics = ref_step(ref, item.batch)
        assert float(metrics["loss"]) == float(ref_metrics["loss"])
        assert not any(x.is_deleted() for x in _leaves(kept))
    assert switches == ctx.data.store.num_clients
    pstate = strategy.end_epoch(ctx, pstate, 0)
    for got, want in zip(_leaves(kept), _leaves(kept_copy)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert all(np.isfinite(np.asarray(x)).all()
               for x in _leaves(strategy.eval_params(ctx, pstate)))
