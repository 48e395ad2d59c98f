"""GroupNorm ResNet for the paper-faithful PSL experiments.

The paper trains ResNet18 (BatchNorm → GroupNorm with 32 groups, cut after
the third layer) on CIFAR10. We reproduce that setup on synthetic
CIFAR-like data: a GN ResNet with the PSL cut after the stem+first stage,
exposing the same client/server param split as the LMs. The benchmark runs
it at ResNet-18's full widths (64/128/256/512, `configs/paper_cnn.py`
CONFIG); tests and the quickstart run the reduced widths (REDUCED).

The client segment packs `p` adjacent images side by side in the channel
axis, with `p` the largest power of two that divides the batch and keeps
`p` times the segment's widest channel count within the TPU's 128 lanes:
block-diagonal convolution weights and `p`-fold GroupNorm groups keep
the images apart, and the cut unpacks to the same (b, h, w, c) tensor.

BatchNorm is deliberately NOT used: the paper replaces it because PSL's
variable local batch sizes break batch statistics (App. A); GroupNorm is
batch-size independent.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ParamSpec
from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    name: str = "gn-resnet"
    num_classes: int = 10
    image_size: int = 32
    channels: Tuple[int, ...] = (32, 64, 128)
    blocks_per_stage: int = 1
    # the number of GroupNorm groups (`group_norm`'s `groups`), not the
    # channels per group; the paper's "group size 32" means 32 groups
    group_size: int = 8
    cut_stage: int = 1          # client: stem + first `cut_stage` stages
    dtype: str = "float32"

    @property
    def jnp_dtype(self):
        return jnp.float32 if self.dtype == "float32" else jnp.bfloat16


def _conv_spec(cin, cout, k=3):
    return ParamSpec((k, k, cin, cout), (None, None, None, None))


def _gn_specs(c):
    return {"scale": ParamSpec((c,), (None,), init="ones"),
            "bias": ParamSpec((c,), (None,), init="zeros")}


LANES = 128     # the minor (lane) width of a TPU vreg and HBM tile


def group_count(c: int, groups: int) -> int:
    """The GroupNorm group count `group_norm` takes for `c` channels: at
    most `groups`, lowered until it divides `c`."""
    g = min(groups, c)
    while c % g:
        g -= 1
    return g


def group_norm(x, p, groups: int, eps: float = 1e-5):
    """GroupNorm over `groups` groups of an NHWC activation, in float32.

    The activation keeps its (b, h, w, c) layout, channels on the minor
    (lane) axis: statistics are per-channel sums over (h, w), combined
    into groups on the small (b, c) tensor and broadcast back per
    channel. The variance is two-pass (mean first), as `jnp.var` takes it.
    """
    b, h, w, c = x.shape
    g = group_count(c, groups)
    n = h * w * (c // g)

    def per_channel_group_mean(s):          # (b, c) sums -> (b, c) means
        s = s.reshape(b, g, c // g).sum(-1, keepdims=True) / n
        return jnp.broadcast_to(s, (b, g, c // g)).reshape(b, c)

    xf = x.astype(jnp.float32)
    mean = per_channel_group_mean(xf.sum((1, 2)))[:, None, None, :]
    d = xf - mean
    var = per_channel_group_mean((d * d).sum((1, 2)))
    coef = (jax.lax.rsqrt(var + eps) * p["scale"])[:, None, None, :]
    return (d * coef + p["bias"]).astype(x.dtype)


def conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def block_diagonal(w, pack: int):
    """(k, k, cin, cout) HWIO -> (k, k, pack*cin, pack*cout) with `pack`
    copies of `w` on the diagonal and exact zeros elsewhere. A dense
    kernel, not `feature_group_count`: XLA lays the grouped form out
    with half-empty lanes."""
    if pack == 1:
        return w
    k1, k2, cin, cout = w.shape
    eye = jnp.eye(pack, dtype=w.dtype)
    return jnp.einsum("ij,hwab->hwiajb", eye, w).reshape(
        k1, k2, pack * cin, pack * cout)


def pack_images(x, pack: int):
    """(b, h, w, c) -> (b/pack, h, w, pack*c): adjacent images side by side
    in the channel axis."""
    if pack == 1:
        return x
    b, h, w, c = x.shape
    return x.reshape(b // pack, pack, h, w, c).transpose(
        0, 2, 3, 1, 4).reshape(b // pack, h, w, pack * c)


def unpack_images(x, pack: int):
    """The inverse of `pack_images`."""
    if pack == 1:
        return x
    bp, h, w, pc = x.shape
    return x.reshape(bp, h, w, pack, pc // pack).transpose(
        0, 3, 1, 2, 4).reshape(bp * pack, h, w, pc // pack)


class CNNModel:
    """GroupNorm ResNet with a PSL client/server split."""

    def __init__(self, cfg: CNNConfig):
        self.cfg = cfg

    def _block_specs(self, cin, cout) -> Dict[str, Any]:
        specs = {"conv1": _conv_spec(cin, cout), "gn1": _gn_specs(cout),
                 "conv2": _conv_spec(cout, cout), "gn2": _gn_specs(cout)}
        if cin != cout:
            specs["proj"] = _conv_spec(cin, cout, k=1)
        return specs

    def param_specs(self):
        cfg = self.cfg
        stages = []
        cin = cfg.channels[0]
        for ci, cout in enumerate(cfg.channels):
            blocks = []
            for bi in range(cfg.blocks_per_stage):
                blocks.append(self._block_specs(cin if bi == 0 else cout,
                                                cout))
                cin = cout
            stages.append(blocks)
        client = {"stem": _conv_spec(3, cfg.channels[0]),
                  "stem_gn": _gn_specs(cfg.channels[0]),
                  "stages": stages[:cfg.cut_stage]}
        server = {"stages": stages[cfg.cut_stage:],
                  "head": ParamSpec((cfg.channels[-1], cfg.num_classes),
                                    (None, None)),
                  "head_b": ParamSpec((cfg.num_classes,), (None,),
                                      init="zeros")}
        return {"client": client, "server": server}

    def init(self, key):
        return L.materialize(self.param_specs(), key, self.cfg.jnp_dtype)

    def client_pack(self, batch_size: int) -> int:
        """Images the client segment packs into the channel axis: the
        largest power of two `p` that divides `batch_size` with `p` times
        the segment's widest channel count within `LANES`."""
        cfg = self.cfg
        c = max(cfg.channels[:max(cfg.cut_stage, 1)])
        p = 1
        while 2 * p * c <= LANES and batch_size % (2 * p) == 0:
            p *= 2
        return p

    def _norm(self, x, p, pack: int = 1):
        """GroupNorm of each of the `pack` images packed in `x`: `pack`
        times the groups, scale and bias tiled, so that no group
        straddles two images."""
        groups = group_count(x.shape[-1] // pack, self.cfg.group_size)
        if pack > 1:
            p = {k: jnp.tile(v, pack) for k, v in p.items()}
        return group_norm(x, p, pack * groups)

    def _block(self, p, x, stride, pack: int = 1):
        y = conv(x, block_diagonal(p["conv1"], pack), stride)
        y = jax.nn.relu(self._norm(y, p["gn1"], pack))
        y = conv(y, block_diagonal(p["conv2"], pack))
        y = self._norm(y, p["gn2"], pack)
        sc = x
        if "proj" in p:
            sc = conv(x, block_diagonal(p["proj"], pack), stride)
        elif stride != 1:
            sc = x[:, ::stride, ::stride]
        return jax.nn.relu(y + sc)

    def _run_stages(self, stages, x, first_stride):
        for si, blocks in enumerate(stages):
            for bi, bp in enumerate(blocks):
                stride = first_stride if bi == 0 and si > 0 else 1
                x = self._block(bp, x, stride)
        return x

    def client_forward(self, params, batch):
        x = batch["images"].astype(self.cfg.jnp_dtype)
        pack = self.client_pack(x.shape[0])
        cp = params["client"]
        x = pack_images(x, pack)
        x = conv(x, block_diagonal(cp["stem"], pack))
        x = jax.nn.relu(self._norm(x, cp["stem_gn"], pack))
        for blocks in cp["stages"]:
            for bp in blocks:
                x = self._block(bp, x, 1, pack)
        return unpack_images(x, pack)

    def server_forward(self, server_params, cut_acts):
        x = cut_acts
        for si, blocks in enumerate(server_params["stages"]):
            for bi, bp in enumerate(blocks):
                x = self._block(bp, x, 2 if bi == 0 else 1)
        x = x.mean(axis=(1, 2))
        return x @ server_params["head"] + server_params["head_b"]

    def server_loss(self, server_params, cut_acts, batch):
        logits = self.server_forward(server_params, cut_acts)
        return self._xent(logits, batch)

    @staticmethod
    def _xent(logits, batch):
        labels, weights = batch["labels"], batch["weights"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        return (nll * weights).sum() / jnp.maximum(weights.sum(), 1e-6)

    def loss_fn(self, params, batch):
        # the scopes name the split's halves in the HLO metadata; the
        # backward carries them as transpose(jvp(...))
        with jax.named_scope("psl.client"):
            cut = self.client_forward(params, batch)
        with jax.named_scope("psl.server"):
            logits = self.server_forward(params["server"], cut)
            loss = self._xent(logits, batch)
        acc = ((logits.argmax(-1) == batch["labels"]) * batch["weights"]
               ).sum() / jnp.maximum(batch["weights"].sum(), 1e-6)
        return loss, {"loss": loss, "accuracy": acc,
                      "aux_loss": jnp.float32(0),
                      "tokens": batch["weights"].sum()}

    def predict(self, params, images):
        cut = self.client_forward(params, {"images": images})
        return self.server_forward(params["server"], cut)
