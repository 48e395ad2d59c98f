"""PSL protocol correctness: fused step ≡ the paper's six-substep protocol,
slot-weight aggregation semantics, straggler TPE model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import ClientPopulation, assign_delays, lds_plan, simulate_tpe, ugs_plan
from repro.core.psl import (cut_transfer_bytes, decomposed_grads,
                            make_train_step, slot_weights)
from repro.models import build_model
from repro.models.cnn import CNNConfig, CNNModel
from repro.configs import get_config
from repro.optim import TrainState


def _cnn_batch(n=16, seed=0):
    rng = np.random.default_rng(seed)
    return {"images": jnp.asarray(rng.normal(size=(n, 16, 16, 3)),
                                  jnp.float32),
            "labels": jnp.asarray(rng.integers(0, 10, n), jnp.int32),
            "weights": jnp.ones(n, jnp.float32)}


def test_decomposed_equals_fused_cnn():
    """Client FP → server BP → cut grad → client BP == one fused backward."""
    model = CNNModel(CNNConfig(channels=(8, 16), image_size=16))
    params = model.init(jax.random.PRNGKey(0))
    batch = _cnn_batch()
    loss_d, g_d, cut = decomposed_grads(model, params, batch)
    loss_f, metrics = model.loss_fn(params, batch)
    g_f = jax.grad(lambda p: model.loss_fn(p, batch)[0])(params)
    assert abs(float(loss_d) - float(loss_f)) < 1e-6
    for a, b in zip(jax.tree_util.tree_leaves(g_d),
                    jax.tree_util.tree_leaves(g_f)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    assert cut.ndim == 4   # (B, H, W, C) activations at the cut


def test_decomposed_equals_fused_lm():
    cfg = get_config("granite-3-2b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(2)
    b, s = 2, 32
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)),
                                   jnp.int32),
             "weights": jnp.ones((b, s), jnp.float32)}
    loss_d, g_d, _ = decomposed_grads(model, params, batch)
    g_f = jax.grad(lambda p: model.loss_fn(p, batch)[0])(params)
    for a, bb in zip(jax.tree_util.tree_leaves(g_d),
                     jax.tree_util.tree_leaves(g_f)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(bb, np.float32),
                                   atol=2e-4, rtol=2e-3)


def test_slot_weights_global_mean():
    cids = np.array([0, 0, 1, 2, -1])
    sizes = np.array([2, 1, 1])
    d = np.array([100, 200, 300])
    w = slot_weights(cids, sizes, d, "global_mean")
    np.testing.assert_array_equal(w, [1, 1, 1, 1, 0])


def test_slot_weights_client_weighted_matches_paper_average():
    """Σ_k (D_k/D0)·mean_k ≡ weighted slot sum (paper step 5)."""
    rng = np.random.default_rng(0)
    k, b = 3, 12
    d = np.array([100., 300., 600.])
    cids = rng.integers(0, k, b)
    sizes = np.bincount(cids, minlength=k)
    losses = rng.normal(size=b)
    w = slot_weights(cids, sizes, d, "client_weighted")
    got = (w * losses).sum() / w.sum()
    want = sum((d[j] / d.sum()) * losses[cids == j].mean()
               for j in range(k) if sizes[j] > 0)
    want /= sum(d[j] / d.sum() for j in range(k) if sizes[j] > 0)
    assert abs(got - want) < 1e-9


def test_train_step_reduces_loss():
    model = CNNModel(CNNConfig(channels=(8, 16), image_size=16))
    opt = optim.sgd(0.05, momentum=0.9)
    step = jax.jit(make_train_step(model, opt))
    params = model.init(jax.random.PRNGKey(0))
    state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
    batch = _cnn_batch(32)
    losses = []
    # fan-in-scaled conv init starts at ~ln(10); 30 steps reach the 20% cut
    for _ in range(30):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.8
    assert int(state.step) == 30


def test_cut_transfer_bytes():
    cfg = get_config("granite-3-2b", reduced=True)
    model = build_model(cfg)
    b, s = 4, 32
    batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    tb = cut_transfer_bytes(model, batch)
    assert tb["activations"] == b * s * cfg.d_model * 4  # f32 reduced cfg
    assert tb["total"] == 2 * tb["activations"]


def test_tpe_straggler_model():
    """LDS with higher Δ cuts simulated TPE (Table IV direction)."""
    pop = ClientPopulation.homogeneous(16, 200, 10, seed=0)
    pop.delays[:] = assign_delays(16, 0.2, 100, 500, seed=1)
    t0 = simulate_tpe(lds_plan(pop, 128, delta=0.0, seed=0)
                      .local_batch_sizes, pop.delays)
    t15 = simulate_tpe(lds_plan(pop, 128, delta=1.5, seed=0)
                       .local_batch_sizes, pop.delays)
    assert t15.total_ms < t0.total_ms * 0.75
    assert len(t0.per_step_ms) == t0.contributing.shape[0]
