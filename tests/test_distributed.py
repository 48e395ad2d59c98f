"""Mesh-parallel training engine correctness.

The sharded fused step must compute *the same update* as the single-device
step — the paper's protocol does not change when the server becomes a mesh.
Three layers of evidence:

  1. in-process (single real CPU device): microbatch gradient accumulation
     reproduces the single-pass backward and the decomposed six-substep
     protocol; slot-weight invariants hold for any batch composition.
  2. subprocess (4 forced host devices — XLA locks the device count at
     first jax init, the test_dryrun.py pattern): both lowerings (gspmd
     profile shardings and explicit shard_map data parallelism) produce
     gradients equal to the single-device fused step and to
     ``decomposed_grads``, and multi-step training trajectories stay
     identical within fp tolerance. Microbatching composes with the mesh.
  3. the distributed straggler accounting (per-shard arrivals) is
     consistent with the single-server TPE model.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core.psl import (decomposed_grads, fused_grads, make_train_step,
                            slot_weights)
from repro.models.cnn import CNNConfig, CNNModel
from repro.optim import TrainState


def _cnn_batch(n=16, seed=0, ragged=False):
    rng = np.random.default_rng(seed)
    cids = rng.integers(0, 4, n)
    if ragged:
        cids[-3:] = -1          # padding slots
    sizes = np.bincount(cids[cids >= 0], minlength=4)
    w = slot_weights(cids, sizes, np.full(4, 100), "global_mean")
    return {"images": jnp.asarray(rng.normal(size=(n, 16, 16, 3)),
                                  jnp.float32),
            "labels": jnp.asarray(rng.integers(0, 10, n), jnp.int32),
            "weights": jnp.asarray(w)}


def _model():
    return CNNModel(CNNConfig(channels=(8, 16), image_size=16))


def _maxdiff(a, b):
    return max(float(np.abs(np.asarray(x, np.float32)
                            - np.asarray(y, np.float32)).max())
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# ------------------------------------------------------ microbatching


@pytest.mark.parametrize("ragged", [False, True])
def test_microbatch_accumulation_equals_single_pass(ragged):
    """M-slice accumulation == one backward == the decomposed protocol,
    including when the batch carries zero-weight padding slots."""
    model = _model()
    params = model.init(jax.random.PRNGKey(0))
    batch = _cnn_batch(16, seed=1, ragged=ragged)
    g_ref = jax.grad(lambda p: model.loss_fn(p, batch)[0])(params)
    _, g_dec, _ = decomposed_grads(model, params, batch)
    for m in (1, 4):
        g_m, metrics = fused_grads(model, params, batch, m)
        assert _maxdiff(g_m, g_ref) < 1e-5
        assert _maxdiff(g_m, g_dec) < 1e-5
        # recombined metrics match the single-pass ones
        _, ref_metrics = model.loss_fn(params, batch)
        assert abs(float(metrics["loss"]) - float(ref_metrics["loss"])) < 1e-5
        assert abs(float(metrics["tokens"])
                   - float(ref_metrics["tokens"])) < 1e-5


def test_microbatched_train_step_matches_plain_step():
    model = _model()
    opt = optim.sgd(0.05, momentum=0.9)
    step1 = jax.jit(make_train_step(model, opt))
    step4 = jax.jit(make_train_step(model, opt, microbatches=4))
    params = model.init(jax.random.PRNGKey(0))
    s1 = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
    s4 = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
    for t in range(3):
        batch = _cnn_batch(16, seed=t)
        s1, m1 = step1(s1, batch)
        s4, m4 = step4(s4, batch)
        assert abs(float(m1["loss"]) - float(m4["loss"])) < 1e-5
    assert _maxdiff(s1.params, s4.params) < 1e-5


def test_microbatch_requires_divisible_batch():
    model = _model()
    with pytest.raises(ValueError, match="not divisible"):
        fused_grads(model, model.init(jax.random.PRNGKey(0)),
                    _cnn_batch(16), 3)


# ------------------------------------------------- slot-weight invariants


def test_slot_weights_global_mean_mass_invariant():
    """Under global_mean the total weight mass equals the valid-slot count,
    for any batch composition — the quantity the sharded engine psums and
    normalizes by, so shard/microbatch splits cannot change the update."""
    rng = np.random.default_rng(0)
    for trial in range(50):
        k = int(rng.integers(1, 12))
        b = int(rng.integers(1, 64))
        cids = rng.integers(-1, k, b)
        sizes = np.bincount(cids[cids >= 0], minlength=k)
        d = rng.integers(10, 1000, k)
        w = slot_weights(cids, sizes, d, "global_mean")
        assert w.sum() == (cids >= 0).sum()
        assert (w[cids < 0] == 0).all()
        # weight mass is additive over any partition into microbatches
        cut = b // 2
        assert abs(w[:cut].sum() + w[cut:].sum() - w.sum()) < 1e-6


def test_slot_weights_client_weighted_padding_carries_no_mass():
    rng = np.random.default_rng(1)
    k, b = 5, 32
    cids = rng.integers(-1, k, b)
    sizes = np.bincount(cids[cids >= 0], minlength=k)
    d = rng.integers(50, 500, k)
    w = slot_weights(cids, sizes, d, "client_weighted")
    assert (w[cids < 0] == 0).all()
    assert (w[cids >= 0] > 0).all()


# ------------------------------------------------- straggler shard model


def test_shard_arrivals_match_global_straggler_model():
    from repro.core.straggler import assign_delays, simulate_tpe
    from repro.launch.distributed import (assign_clients_to_shards,
                                          shard_arrivals, step_timing)
    rng = np.random.default_rng(2)
    k, s = 16, 4
    delays = assign_delays(k, 0.3, 100, 500, seed=3)
    shard_of = assign_clients_to_shards(k, s)
    sizes = rng.integers(0, 5, k)
    arr = shard_arrivals(sizes, delays, shard_of, s)
    assert arr.shape == (s,)
    # slowest shard == slowest contributing client (the global TPE model)
    contributing = sizes > 0
    want = delays[contributing].max() if contributing.any() else 0.0
    assert arr.max() == want
    tm = step_timing(sizes, delays, shard_of, s, base_step_ms=60.0)
    ref = simulate_tpe(sizes[None, :], delays, base_step_ms=60.0)
    assert abs(tm.step_ms - ref.total_ms) < 1e-9
    assert tm.shard_skew_ms >= 0.0


def test_empty_shard_arrives_immediately():
    from repro.launch.distributed import shard_arrivals
    sizes = np.array([2, 0, 0, 0])        # only client 0 contributes
    delays = np.array([250.0, 400.0, 10.0, 0.0])
    arr = shard_arrivals(sizes, delays, np.array([0, 1, 2, 3]), 4)
    np.testing.assert_array_equal(arr, [250.0, 0.0, 0.0, 0.0])


# ------------------------------------------------ sharded batch layout


def test_iterator_shard_layout_groups_slots_and_preserves_weights():
    from repro.core import ClientPopulation, make_plan
    from repro.data.federated import ClientStore, GlobalBatchIterator
    rng = np.random.default_rng(0)
    k, per = 6, 40
    X = rng.normal(size=(k * per, 4)).astype(np.float32)
    y = rng.integers(0, 10, k * per)
    pop = ClientPopulation.homogeneous(k, per, 10, seed=0)
    parts = [np.arange(i * per, (i + 1) * per) for i in range(k)]
    store = ClientStore.from_partition(X, y, parts, pop)
    plan = make_plan("ugs", pop, 32, seed=0)
    plain = list(GlobalBatchIterator(store, plan, seed=7))
    sharded = list(GlobalBatchIterator(store, plan, seed=7, num_shards=2))
    for gb_p, gb_s in zip(plain, sharded):
        # same multiset of samples and total weight mass, per step
        assert sorted(gb_p["labels"].tolist()) == \
            sorted(gb_s["labels"].tolist())
        assert abs(gb_p["weights"].sum() - gb_s["weights"].sum()) < 1e-6
        # shard tags: valid slots tagged k mod S, in nondecreasing order
        tags = gb_s["shard"]
        valid = gb_s["client_ids"] >= 0
        np.testing.assert_array_equal(tags[valid],
                                      gb_s["client_ids"][valid] % 2)
        assert (np.diff(tags[valid]) >= 0).all()
        assert (tags[~valid] == -1).all()


# -------------------------------------------- 4-way host-mesh equivalence

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np, jax, jax.numpy as jnp
from repro import optim
from repro.models.cnn import CNNModel, CNNConfig
from repro.core.psl import make_train_step, decomposed_grads
from repro.optim import TrainState
from repro.launch.mesh import make_training_mesh
from repro.launch.distributed import ShardedPSLEngine

model = CNNModel(CNNConfig(channels=(8, 16), image_size=16))
opt = optim.sgd(0.05, momentum=0.9)
N, STEPS = 16, 3

def mkbatch(s):
    r = np.random.default_rng(s)
    return {"images": r.normal(size=(N, 16, 16, 3)).astype(np.float32),
            "labels": r.integers(0, 10, N).astype(np.int32),
            "weights": np.ones(N, np.float32)}

def leaves(t):
    return jax.tree_util.tree_leaves(t)

def maxdiff(a, b):
    return max(float(np.abs(np.asarray(x, np.float32)
                            - np.asarray(y, np.float32)).max())
               for x, y in zip(leaves(a), leaves(b)))

# single-device baseline (default device; mesh untouched)
params = model.init(jax.random.PRNGKey(0))
step = jax.jit(make_train_step(model, opt))
st0 = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
for t in range(STEPS):
    st0, _ = step(st0, {k: jnp.asarray(v) for k, v in mkbatch(t).items()})
_, g_dec, _ = decomposed_grads(model, params,
                               {k: jnp.asarray(v)
                                for k, v in mkbatch(0).items()})

out = {"devices": len(jax.devices())}
mesh = make_training_mesh("4x1")
for lowering in ("gspmd", "shard_map"):
    for mb in (1, 2):
        eng = ShardedPSLEngine(model, opt, mesh=mesh, lowering=lowering,
                               microbatches=mb)
        st = eng.init_state(0)
        key = f"{lowering}_mb{mb}"
        out[key + "_grads_vs_decomposed"] = maxdiff(
            eng.grads(st, eng.put_batch(mkbatch(0))), g_dec)
        for t in range(STEPS):
            st, met = eng.step(st, eng.put_batch(mkbatch(t)))
        out[key + "_params_vs_single"] = maxdiff(st.params, st0.params)
        out[key + "_fallbacks"] = eng.report.fallbacks
print("RESULTS_JSON:" + json.dumps(out))
"""


def test_sharded_step_equivalence_4way_host_mesh():
    """gspmd and shard_map lowerings × microbatch counts all reproduce the
    single-device fused step (same trajectory) and the decomposed protocol
    (same gradient) on a 4-way CPU host mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULTS_JSON:")][0]
    results = json.loads(line[len("RESULTS_JSON:"):])
    assert results.pop("devices") == 4
    for key, val in results.items():
        if key.endswith("_fallbacks"):
            assert val == [], (key, val)
        else:
            assert val < 1e-4, (key, val)
