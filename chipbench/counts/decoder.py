"""Operations and bytes of a dense decoder, counted from its shapes.

Linear layers count 2 operations per multiply-add. Attention counts the
scores and the weighted sum of the values: ``4 * heads * head_dim``
operations per layer for each (query, visible key) pair. The embedding
lookup is a gather and counts none.

The least bytes a decode step must move are the weights it reads once
(every layer, the final norm and the head; of the embedding only the
rows looked up, which are left out) and the logical KV cache of the live
rows, read once and written once for the new token: ``2 * layers *
kv_heads * head_dim`` elements a token, in the served dtype. These are
the bytes of the algorithm, not of any implementation: a dense gather
of pages, a replicated KV head or tile padding is not counted.
"""
from __future__ import annotations

from typing import Dict

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def summary(config: Dict) -> Dict[str, float]:
    d = config["hidden_size"]
    layers = config["num_hidden_layers"]
    hq = config["num_attention_heads"]
    hkv = config["num_key_value_heads"]
    hd = d // hq
    ff = config["intermediate_size"]
    vocab = config["vocab_size"]
    elt = DTYPE_BYTES[config["torch_dtype"]]
    per_layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * ff
    linear = layers * per_layer + d * vocab          # + the LM head
    weights_read = (layers * (per_layer + 2 * d) + d + d * vocab) * elt
    return {
        "linear_flops_per_token": 2 * linear,
        "attn_flops_per_pair": 4 * layers * hq * hd,
        "weight_bytes_per_step": weights_read,
        "kv_bytes_per_token": 2 * layers * hkv * hd * elt,
    }


def decode_step_bytes(s: Dict[str, float], rows: int,
                      context_tokens: int) -> float:
    """Least bytes of one decode step whose ``rows`` live rows attend to
    ``context_tokens`` positions in all (their new tokens included),
    whose KV is read, and write one new token's KV each."""
    return s["weight_bytes_per_step"] + s["kv_bytes_per_token"] \
        * (context_tokens + rows)


def decode_step_flops(s: Dict[str, float], rows: int,
                      context_tokens: int) -> float:
    return s["linear_flops_per_token"] * rows \
        + s["attn_flops_per_pair"] * context_tokens


def prefill_flops(s: Dict[str, float], tokens: int, pairs: int) -> float:
    """``pairs``: visible (query, key) pairs, n(n+1)/2 for a causal
    prompt of n tokens."""
    return s["linear_flops_per_token"] * tokens \
        + s["attn_flops_per_pair"] * pairs
