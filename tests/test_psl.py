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
from repro.models.cnn import CNNConfig, CNNModel, conv, group_norm
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


def _group_norm_reshape_form(x, p, groups, eps=1e-5):
    """GroupNorm over a (b, h, w, g, c/g) reshape: the formula group_norm
    replaced, kept as its reference."""
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xf = x.astype(jnp.float32).reshape(b, h, w, g, c // g)
    mean = xf.mean(axis=(1, 2, 4), keepdims=True)
    var = xf.var(axis=(1, 2, 4), keepdims=True)
    xf = ((xf - mean) * jax.lax.rsqrt(var + eps)).reshape(b, h, w, c)
    return (xf * p["scale"] + p["bias"]).astype(x.dtype)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("c,groups", [(64, 32), (16, 8), (48, 32)])
def test_group_norm_lane_dense_matches_reshape_form(c, groups):
    """Per-channel statistics give the reshape form's forward and VJP to
    float32 rounding; (48, 32) falls back to 24 groups. No rank-5
    reshape of the activation appears, forward or backward."""
    rng = np.random.default_rng(c + groups)
    x = jnp.asarray(rng.normal(1.5, 2.0, size=(4, 8, 8, c)), jnp.float32)
    p = {"scale": jnp.asarray(rng.normal(1.0, 0.5, size=c), jnp.float32),
         "bias": jnp.asarray(rng.normal(0.0, 0.5, size=c), jnp.float32)}
    ct = jnp.asarray(rng.normal(size=x.shape), jnp.float32)

    def close(a, ref):
        a, ref = np.asarray(a), np.asarray(ref)
        np.testing.assert_allclose(a, ref, rtol=1e-5,
                                   atol=1e-5 * np.abs(ref).max())

    def gn(x, p):
        return group_norm(x, p, groups)

    y, vjp = jax.vjp(gn, x, p)
    y_ref, vjp_ref = jax.vjp(
        lambda x, p: _group_norm_reshape_form(x, p, groups), x, p)
    close(y, y_ref)
    (dx, dp), (dx_ref, dp_ref) = vjp(ct), vjp_ref(ct)
    close(dx, dx_ref)
    close(dp["scale"], dp_ref["scale"])
    close(dp["bias"], dp_ref["bias"])

    for fn in (gn, lambda x, p: jax.vjp(gn, x, p)[1](ct)):
        ranks = [e.outvars[0].aval.ndim
                 for e in _eqns(jax.make_jaxpr(fn)(x, p).jaxpr)
                 if e.primitive.name == "reshape"]
        assert 5 not in ranks, ranks


def _unpacked_client_forward(cfg, cp, x):
    """The client segment with one image per channel group of the batch
    axis: stem, then stride-1 residual blocks, GroupNorm after each
    convolution. The formula the packed segment must reproduce."""
    def norm(x, p):
        return group_norm(x, p, cfg.group_size)

    x = jax.nn.relu(norm(conv(x, cp["stem"]), cp["stem_gn"]))
    for blocks in cp["stages"]:
        for p in blocks:
            y = jax.nn.relu(norm(conv(x, p["conv1"]), p["gn1"]))
            y = norm(conv(y, p["conv2"]), p["gn2"])
            sc = conv(x, p["proj"]) if "proj" in p else x
            x = jax.nn.relu(y + sc)
    return x


@pytest.mark.parametrize("channels,cut_stage,groups,b,pack", [
    ((64, 128), 1, 32, 4, 2),       # the paper's stage 0: 2 x 64 lanes
    ((32, 64), 1, 8, 8, 4),
    ((16, 32, 64), 2, 8, 4, 4),     # a projection inside the client
    ((64, 128), 1, 32, 3, 1),       # an odd batch is not packed
])
def test_packed_client_segment_matches_unpacked(channels, cut_stage, groups,
                                                b, pack):
    """`client_forward` packs `pack` images into the channel axis; its
    cut and its VJP (client params, images) match the unpacked formula to
    float32 rounding, and every convolution runs at `pack` times the
    published channels."""
    cfg = CNNConfig(channels=channels, cut_stage=cut_stage, image_size=8,
                    group_size=groups)
    model = CNNModel(cfg)
    assert model.client_pack(b) == pack
    params = model.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(b + pack)
    x = jnp.asarray(rng.normal(size=(b, 8, 8, 3)), jnp.float32)

    def packed(cp, x):
        return model.client_forward({**params, "client": cp}, {"images": x})

    def unpacked(cp, x):
        return _unpacked_client_forward(cfg, cp, x)

    cut, vjp = jax.vjp(packed, params["client"], x)
    cut_ref, vjp_ref = jax.vjp(unpacked, params["client"], x)
    ct = jnp.asarray(rng.normal(size=cut.shape), jnp.float32)
    got = jax.tree_util.tree_leaves((cut, vjp(ct)))
    want = jax.tree_util.tree_leaves((cut_ref, vjp_ref(ct)))
    assert cut.shape == (b, 8, 8, channels[cut_stage - 1])
    for a, ref in zip(got, want):
        a, ref = np.asarray(a), np.asarray(ref)
        np.testing.assert_allclose(a, ref, rtol=1e-5,
                                   atol=1e-5 * np.abs(ref).max())

    convs = [e for e in _eqns(jax.make_jaxpr(packed)(params["client"],
                                                     x).jaxpr)
             if e.primitive.name == "conv_general_dilated"]
    assert convs
    for e in convs:
        assert e.outvars[0].aval.shape[0] == b // pack
        assert e.outvars[0].aval.shape[-1] in \
            {pack * c for c in channels[:cut_stage]}


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
