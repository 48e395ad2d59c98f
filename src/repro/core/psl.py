"""The PSL training protocol as JAX step functions.

Two equivalent realizations of one optimization step (Sec. III, steps 1–6):

  * ``make_train_step``  — the *fused* step: one backward through the whole
    split model with per-slot weights encoding the server-side gradient
    aggregation. This is the production path (pjit/shard_map lowers it to
    the pod mesh; the client/server param split drives the sharding rules).
  * ``decomposed_grads`` — the *literal* protocol: client FP → cut-activation
    transfer → server FP/BP → cut-gradient broadcast → client BP → weighted
    client-gradient averaging. Used by tests to prove the fused step computes
    exactly the paper's update, and by the latency model to count transfer
    bytes at the cut.

Slot-weight semantics (how the global batch encodes the paper's step 5):
  aggregation="global_mean"     w_i = 1                (mean over the B slots)
  aggregation="client_weighted" w_i = (D_k/D_0)·B/B_k^t  for slot i of client
    k — reproducing  ḡ = Σ_k (D_k/D_0) ḡ_k  (per-client means weighted by
    dataset size, the scheme of Jeon & Kim [19]). The two coincide exactly
    when B_k^t = B·D_k/D_0 (Theorem 1's premise) and differ by O(1/B) noise
    under UGS.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim import Optimizer, TrainState, apply_updates


def slot_weights(client_ids: np.ndarray, local_batch_sizes: np.ndarray,
                 dataset_sizes: np.ndarray,
                 aggregation: str = "global_mean") -> np.ndarray:
    """Per-slot loss weights for one global batch.

    client_ids: (B,) source client of each slot (-1 = padding).
    local_batch_sizes: (K,) this step's B_k^t.
    """
    valid = client_ids >= 0
    if aggregation == "global_mean":
        return valid.astype(np.float32)
    if aggregation != "client_weighted":
        raise ValueError(aggregation)
    d = dataset_sizes.astype(np.float64)
    pi = d / d.sum()
    bk = np.maximum(local_batch_sizes, 1)
    b = max(int(valid.sum()), 1)
    w = np.where(valid, pi[np.maximum(client_ids, 0)]
                 / bk[np.maximum(client_ids, 0)] * b, 0.0)
    return w.astype(np.float32)


def slot_weights_segments(client_ids: np.ndarray, slot_counts: np.ndarray,
                          dataset_sizes: np.ndarray,
                          aggregation: str = "global_mean") -> np.ndarray:
    """Segment-streamed twin of :func:`slot_weights`.

    Takes the owning client's B_k^t *per slot* (``slot_counts``, e.g.
    ``np.repeat(counts, counts)`` from a sparse plan segment) instead of the
    dense (K,) row, so computing weights never materializes O(K) per-step
    state. Arithmetic is slot-for-slot identical to the dense form —
    d[k]/D / B_k^t · B in the same operation order — hence bit-identical
    weights.

    client_ids: (B,) source client of each slot (-1 = padding).
    slot_counts: (B,) B_k^t of each slot's owner (any value ≥ 1 on padding).
    """
    valid = client_ids >= 0
    if aggregation == "global_mean":
        return valid.astype(np.float32)
    if aggregation != "client_weighted":
        raise ValueError(aggregation)
    d = dataset_sizes.astype(np.float64)
    total = d.sum()
    bk = np.maximum(slot_counts, 1)
    b = max(int(valid.sum()), 1)
    w = np.where(valid, d[np.maximum(client_ids, 0)] / total / bk * b, 0.0)
    return w.astype(np.float32)


def _grad_norm(grads):
    return jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                        for g in jax.tree_util.tree_leaves(grads)))


def accumulate_sum_grads(model, params, batch, num_microbatches: int,
                         w_total):
    """fp32 gradient of the *weighted-sum* objective, microbatch by microbatch.

    Splits every batch leaf into ``num_microbatches`` leading-axis slices and
    scans over them, accumulating

        Σ_m ∇ [ loss_m · w_m  +  aux_m · w_total / M ]

    where w_m is microbatch m's weight mass (``metrics["tokens"]``) and
    ``w_total`` the full batch's. Both loss_fn implementations normalize by
    their own weight mass, so loss_m · w_m recovers the un-normalized
    weighted nll sum and the accumulated gradient equals w_total · ∇(full
    weighted-mean loss) exactly; dividing by w_total afterwards reproduces
    the fused single-pass gradient up to fp reassociation. The aux term
    (MoE load balancing; zero for the CNN and dense LMs) enters as the mean
    over microbatches — the standard accumulation approximation, exact
    whenever aux_loss ≡ 0.

    Returns ``(grad_sums, metric_sums)`` where ``metric_sums`` holds
    {loss_sum (Σ loss_m·w_m), acc_sum (Σ acc_m·w_m), aux_sum, tokens}.
    This sum form composes across data shards: psum it over the mesh's data
    axis and normalize once (see repro.launch.distributed).
    """
    m = num_microbatches

    def split(x):
        if x.shape[0] % m:
            raise ValueError(
                f"global batch axis {x.shape[0]} not divisible into "
                f"{m} microbatches")
        return x.reshape((m, x.shape[0] // m) + x.shape[1:])

    micro = jax.tree_util.tree_map(split, batch)

    def scaled_loss(p, mb):
        total, metrics = model.loss_fn(p, mb)
        w_m = metrics["tokens"]
        return metrics["loss"] * w_m + metrics["aux_loss"] * (w_total / m), \
            metrics

    def body(carry, mb):
        g_acc, s = carry
        (_, metrics), g = jax.value_and_grad(scaled_loss, has_aux=True)(
            params, mb)
        g_acc = jax.tree_util.tree_map(
            lambda a, b: a + b.astype(jnp.float32), g_acc, g)
        w_m = metrics["tokens"]
        s = {"loss_sum": s["loss_sum"] + metrics["loss"] * w_m,
             "acc_sum": s["acc_sum"] + metrics["accuracy"] * w_m,
             "aux_sum": s["aux_sum"] + metrics["aux_loss"],
             "tokens": s["tokens"] + w_m}
        return (g_acc, s), None

    g0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    s0 = {k: jnp.float32(0) for k in ("loss_sum", "acc_sum", "aux_sum",
                                      "tokens")}
    (grad_sums, metric_sums), _ = jax.lax.scan(body, (g0, s0), micro)
    return grad_sums, metric_sums


def normalize_sum_grads(grad_sums, metric_sums, num_microbatches: int):
    """Sum-form grads/metrics → the fused step's (grads, metrics)."""
    denom = jnp.maximum(metric_sums["tokens"], 1e-6)
    grads = jax.tree_util.tree_map(lambda g: g / denom, grad_sums)
    metrics = {"loss": metric_sums["loss_sum"] / denom,
               "accuracy": metric_sums["acc_sum"] / denom,
               "aux_loss": metric_sums["aux_sum"] / num_microbatches,
               "tokens": metric_sums["tokens"]}
    return grads, metrics


def fused_grads(model, params, batch, microbatches: int = 1):
    """Normalized full-batch gradient via microbatch accumulation.

    The reference for equivalence tests and the grads entry point of the
    distributed engine; with ``microbatches=1`` it is the fused backward in
    sum-then-normalize form.
    """
    w_total = batch["weights"].astype(jnp.float32).sum()
    g_sum, m_sum = accumulate_sum_grads(model, params, batch, microbatches,
                                        w_total)
    return normalize_sum_grads(g_sum, m_sum, microbatches)


def make_train_step(model, optimizer: Optimizer,
                    microbatches: int = 1) -> Callable:
    """Fused PSL optimization step: (state, batch) -> (state, metrics).

    ``microbatches > 1`` accumulates gradients over that many slices of the
    global batch (for global batches larger than per-device activation
    memory); the resulting update equals the single-pass step within fp
    tolerance whenever aux_loss is zero (see accumulate_sum_grads).
    """

    def step(state: TrainState, batch: Dict[str, Any]):
        if microbatches > 1:
            grads, metrics = fused_grads(model, state.params, batch,
                                         microbatches)
        else:
            def loss(params):
                return model.loss_fn(params, batch)
            (total, metrics), grads = jax.value_and_grad(
                loss, has_aux=True)(state.params)
        with jax.named_scope("psl.update"):
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
            params = apply_updates(state.params, updates)
        metrics = dict(metrics)
        metrics["grad_norm"] = _grad_norm(grads)
        return TrainState(params=params, opt_state=opt_state,
                          step=state.step + 1), metrics

    return step


def decomposed_grads(model, params, batch):
    """The six-substep PSL protocol, made explicit (Sec. III).

    Returns (loss, grads, cut_activations) with grads structured like params.
    Substeps:
      1/2. client FP → cut activations (the client→server transfer);
      3.   server FP + BP — grads w.r.t. server params AND the cut;
      4.   cut gradient broadcast → client BP (vjp through client segment);
      5/6. the weighted averaging over clients is encoded in the slot
           weights already present in `batch` (see slot_weights).
    """
    cut, client_vjp = jax.vjp(
        lambda cp: model.client_forward({**params, "client": cp}, batch),
        params["client"])
    loss, server_vjp = jax.vjp(
        lambda sp, c: model.server_loss(sp, c, batch),
        params["server"], cut)
    g_server, g_cut = server_vjp(jnp.ones_like(loss))
    (g_client,) = client_vjp(g_cut)
    return loss, {"client": g_client, "server": g_server}, cut


def cut_transfer_bytes(model, batch: Dict[str, Any]) -> Dict[str, int]:
    """Bytes crossing the client↔server boundary per step (both directions:
    activations up, cut gradients down). Used by the latency model."""
    shapes = jax.eval_shape(
        lambda p, b: model.client_forward(p, b),
        model.abstract_params() if hasattr(model, "abstract_params")
        else model.param_specs(), batch)
    n = int(np.prod(shapes.shape)) * shapes.dtype.itemsize
    return {"activations": n, "gradients": n, "total": 2 * n}


@dataclasses.dataclass
class PSLSimulator:
    """Host-side epoch driver: plan → global batches → fused device steps.

    This is the single-host simulation of the full protocol used by the
    paper-repro experiments: the sampler produces the epoch plan, clients
    contribute their slices, and the device executes the fused step. Delay
    accounting (straggler TPE) is tracked analytically alongside.
    """
    model: Any
    optimizer: Optimizer
    aggregation: str = "global_mean"

    def init_state(self, key) -> TrainState:
        params = self.model.init(key)
        return TrainState(params=params,
                          opt_state=self.optimizer.init(params),
                          step=jnp.zeros((), jnp.int32))
