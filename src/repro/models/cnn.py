"""Small GroupNorm ResNet for the paper-faithful PSL experiments.

The paper trains ResNet18 (BatchNorm → GroupNorm, group size 32, cut after
the third layer) on CIFAR10. We reproduce that setup at reduced scale on
synthetic CIFAR-like data: a GN ResNet with the PSL cut after the stem+first
stage, exposing the same client/server param split as the LMs.

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


def group_norm(x, p, groups: int, eps: float = 1e-5):
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xf = x.astype(jnp.float32).reshape(b, h, w, g, c // g)
    mean = xf.mean(axis=(1, 2, 4), keepdims=True)
    var = xf.var(axis=(1, 2, 4), keepdims=True)
    xf = (xf - mean) * jax.lax.rsqrt(var + eps)
    xf = xf.reshape(b, h, w, c)
    return (xf * p["scale"] + p["bias"]).astype(x.dtype)


def conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


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

    def _block(self, p, x, stride):
        cfg = self.cfg
        y = conv(x, p["conv1"], stride)
        y = jax.nn.relu(group_norm(y, p["gn1"], cfg.group_size))
        y = conv(y, p["conv2"])
        y = group_norm(y, p["gn2"], cfg.group_size)
        sc = x
        if "proj" in p:
            sc = conv(x, p["proj"], stride)
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
        cfg = self.cfg
        x = batch["images"].astype(cfg.jnp_dtype)
        x = conv(x, params["client"]["stem"])
        x = jax.nn.relu(group_norm(x, params["client"]["stem_gn"],
                                   cfg.group_size))
        for blocks in params["client"]["stages"]:
            for bp in blocks:
                x = self._block(bp, x, 1)
        return x

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
