"""Plain float32 reference of the GroupNorm ResNet and its SGD steps.

Written from the published description (ResNet-18 of He et al. 2016 in
its CIFAR form: a 3x3 stem, four stages of two basic blocks, the first
block of each later stage strided by 2 with a 1x1 projection; BatchNorm
replaced by GroupNorm as in the GPSL paper's App. A), in straightforward
``jax.numpy`` at ``highest`` precision. It reads the parameters in the
layout the benchmark hands the program (``client``: stem and the stages
before the cut; ``server``: the rest and the head), and imports nothing
of the program.

``compare`` follows a run's first three steps from the same weights on
the same batches and returns the gaps that decide ``correct``. The run
is the program's, or, for the control, ``control_steps``: the same
reference computed in bfloat16, the precision below the
configuration's, put in the program's place.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np


def _conv(x, w, stride, precision):
    import jax
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _group_norm(x, p, groups, eps=1e-5):
    import jax
    import jax.numpy as jnp
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.reshape(b, h, w, g, c // g)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype))
    return xg.reshape(b, h, w, c) * p["scale"] + p["bias"]


def _block(p, x, stride, groups, precision):
    import jax
    y = _conv(x, p["conv1"], stride, precision)
    y = jax.nn.relu(_group_norm(y, p["gn1"], groups))
    y = _conv(y, p["conv2"], 1, precision)
    y = _group_norm(y, p["gn2"], groups)
    if "proj" in p:
        sc = _conv(x, p["proj"], stride, precision)
    elif stride != 1:
        sc = x[:, ::stride, ::stride]
    else:
        sc = x
    return jax.nn.relu(y + sc)


def logits(params, images, groups: int, precision):
    import jax
    c = params["client"]
    x = _conv(images, c["stem"], 1, precision)
    x = jax.nn.relu(_group_norm(x, c["stem_gn"], groups))
    for stage in c["stages"]:                       # before the cut
        for bp in stage:
            x = _block(bp, x, 1, groups, precision)
    s = params["server"]
    for stage in s["stages"]:
        for i, bp in enumerate(stage):
            x = _block(bp, x, 2 if i == 0 else 1, groups, precision)
    x = x.mean(axis=(1, 2))
    return x @ s["head"] + s["head_b"]


def loss(params, batch, groups: int, precision):
    """Mean cross-entropy over the slots, weighted by the slot weights
    (padding slots weigh 0)."""
    import jax
    import jax.numpy as jnp
    dt = params["server"]["head"].dtype
    out = logits(params, batch["images"].astype(dt), groups, precision)
    logp = jax.nn.log_softmax(out.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, batch["labels"][:, None], axis=-1)[:, 0]
    w = batch["weights"].astype(jnp.float32)
    return (nll * w).sum() / jnp.maximum(w.sum(), 1e-6)


def trajectory(p0, batches: List[Dict], groups: int, opt: Dict, dtype):
    """Three SGD (momentum, weight decay) steps in ``dtype``: the losses,
    the first gradient and the parameters after the third step."""
    import jax
    import jax.numpy as jnp
    precision = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    lr, mom, wd = opt["lr"], opt["momentum"], opt["weight_decay"]

    @jax.jit
    def run(p0, batches):
        params = jax.tree_util.tree_map(lambda a: a.astype(dtype), p0)
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, g1 = [], None
        for batch in batches:
            lval, g = jax.value_and_grad(loss)(params, batch, groups,
                                               precision)
            if g1 is None:
                g1 = g
            g = jax.tree_util.tree_map(lambda gi, p: gi + wd * p, g, params)
            mu = jax.tree_util.tree_map(lambda m, gi: mom * m + gi, mu, g)
            params = jax.tree_util.tree_map(lambda p, m: p - lr * m,
                                            params, mu)
            losses.append(lval)
        return jnp.stack(losses), g1, params

    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        losses, g1, p3 = run(p0, batches)
    return (np.asarray(losses, np.float64),
            jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), g1),
            jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p3))


def leaf_gap(got, want, grad_ref, exclude_below: float = 1e-3) -> float:
    """The worst leaf's gap between two norms, against the larger of the
    reference leaf's norm and the median leaf's. Leaves whose reference
    gradient is under ``exclude_below`` of the median leaf's are left
    out: they move by round-off alone."""
    import jax
    g = [float(np.linalg.norm(x)) for x in jax.tree_util.tree_leaves(got)]
    w = [float(np.linalg.norm(x)) for x in jax.tree_util.tree_leaves(want)]
    r = [float(np.linalg.norm(x)) for x in jax.tree_util.tree_leaves(grad_ref)]
    med_w, med_r = float(np.median(w)), float(np.median(r))
    gaps = [abs(a - b) / max(b, med_w, 1e-30)
            for a, b, c in zip(g, w, r) if c >= exclude_below * med_r]
    return max(gaps)


def first_gradient(mu1, p0, weight_decay: float):
    """The first gradient as the optimizer got it, from its momentum
    buffer after one step: that buffer holds the gradient plus weight
    decay times the initial weights, which is taken off again here."""
    import jax
    return jax.tree_util.tree_map(
        lambda m, p: np.asarray(m, np.float64)
        - weight_decay * np.asarray(p, np.float64), mu1, p0)


def _batches(batches):
    import jax.numpy as jnp
    return [{k: jnp.asarray(v) for k, v in b.items()} for b in batches]


def control_steps(p0, batches, config: Dict[str, Any], opt: Dict[str, Any]):
    """The control: the reference's three steps in bfloat16, the
    precision below the configuration's float32; the same (losses, first
    gradient, parameters after three steps) the program gives."""
    import jax.numpy as jnp
    return trajectory(p0, _batches(batches), config["groups"], opt,
                      jnp.bfloat16)


def compare(p0, batches, got, config: Dict[str, Any],
            opt: Dict[str, Any]) -> Dict[str, float]:
    """Gaps between three steps of a run (``got``: its losses, its first
    gradient and its parameters after the third step) and the float32
    reference's from the same weights on the same batches: the worst
    relative loss gap, and per leaf the gap between norms of the first
    gradient and of the parameters' change (``leaf_gap``)."""
    import jax
    import jax.numpy as jnp
    losses, g1, p3 = got
    ref_losses, ref_g1, ref_p3 = trajectory(p0, _batches(batches),
                                            config["groups"], opt,
                                            jnp.float32)
    sub = lambda a, b: jax.tree_util.tree_map(  # noqa: E731
        lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64),
        a, b)
    return {
        "loss_gap": float(np.max(np.abs(np.asarray(losses) - ref_losses)
                                 / np.abs(ref_losses))),
        "grad_gap": leaf_gap(g1, ref_g1, ref_g1),
        "delta_gap": leaf_gap(sub(p3, p0), sub(ref_p3, p0), ref_g1)}
