"""Plain float32 reference of a dense decoder (Granite 3.0 as the
configuration file states it) and the comparison of served tokens.

Written from the published architecture: token embedding; per layer
RMSNorm, grouped-query attention with rotary positions (rotate-half,
``rope_theta``) and a causal softmax at ``1/sqrt(head_dim)``, the output
projection and the residual, RMSNorm, a SwiGLU MLP and the residual; a
final RMSNorm and the LM head. Departures of the configuration as run
(no muP multipliers, an untied head) are listed in its file and followed
here. The weights are read in the layout the benchmark hands the program
(``client``: embedding and the layers before the cut; ``server``: the
other layers, the final norm and the head), layer by layer and upcast to
float32 inside the scan, so the float32 weights are never whole in
memory. Nothing of the program is imported.

``compare`` runs the reference once over each sampled prompt with its
served tokens (teacher forcing, one forward at a fixed padded length) and
returns the widest gap by which a served token's reference logit lies
below the reference's best at that position. With ``control`` it also
returns that gap for the token that an int8 computation (weights per
output channel, activations per token, products in int32) puts first at
each of those positions: the control, which a ``--control`` run then
judges in the program's place.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

ROWS_PER_CALL = 2


def _mm_f32(x, w):
    import jax
    import jax.numpy as jnp
    return jnp.dot(x, w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def _mm_int8(x, w):
    """x (..., k) f32 @ w (k, n): both quantized to int8, symmetric,
    per token for x and per output channel for w; int32 products."""
    import jax.numpy as jnp
    w = w.astype(jnp.float32)
    sw = jnp.maximum(jnp.abs(w).max(axis=0), 1e-12) / 127.0
    sx = jnp.maximum(jnp.abs(x).max(axis=-1, keepdims=True), 1e-12) / 127.0
    wq = jnp.round(w / sw).astype(jnp.int8)
    xq = jnp.round(x / sx).astype(jnp.int8)
    acc = jnp.dot(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, positions, theta):
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs   # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, cfg, mm):
    import jax
    import jax.numpy as jnp
    b, t, d = x.shape
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // hq
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(t)
    h = _rms(x, lp["norm1"], eps)
    a = lp["attn"]
    q = _rope(mm(h, a["wq"]).reshape(b, t, hq, hd), pos, theta)
    k = _rope(mm(h, a["wk"]).reshape(b, t, hkv, hd), pos, theta)
    v = mm(h, a["wv"]).reshape(b, t, hkv, hd)
    group = hq // hkv
    k = jnp.repeat(k, group, axis=2)          # q head i reads kv head i//g
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(b, t, hq * hd)
    x = x + mm(o, a["wo"])
    h = _rms(x, lp["norm2"], eps)
    m = lp["mlp"]
    x = x + mm(jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_up"]),
               m["w_down"])
    return x


def _forward(params, seqs, rows, cfg, mm):
    """Logits (B, R, V) at positions ``rows`` (B, R) of ``seqs`` (B, T)."""
    import jax
    import jax.numpy as jnp
    x = params["client"]["embed"][seqs].astype(jnp.float32)

    def body(xx, lp):
        return _layer(xx, lp, cfg, mm), None

    x, _ = jax.lax.scan(body, x, params["client"]["blocks"])
    x, _ = jax.lax.scan(body, x, params["server"]["blocks"])
    h = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    h = _rms(h, params["server"]["final_norm"], cfg["rms_norm_eps"])
    return mm(h, params["server"]["lm_head"])


def _pack(sample: List[Tuple[np.ndarray, List[int]]], seq_len: int,
          max_rows: int):
    """Each (prompt, served) as one padded row: the prompt and every
    served token but the last; the positions that predict each served
    token; and the served tokens (-1 where padded)."""
    seqs = np.zeros((len(sample), seq_len), np.int32)
    rows = np.zeros((len(sample), max_rows), np.int32)
    want = np.full((len(sample), max_rows), -1, np.int64)
    for i, (prompt, served) in enumerate(sample):
        ctx = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        seqs[i, :len(ctx)] = ctx
        n = len(served)
        rows[i, :n] = len(prompt) - 1 + np.arange(n)
        want[i, :n] = served
    return seqs, rows, want


def compare(params, sample, config: Dict[str, Any], seq_len: int,
            max_rows: int, control: bool = False) -> Dict[str, float]:
    """``sample``: (prompt, served tokens) pairs. Every call has the same
    shapes (``ROWS_PER_CALL`` rows of ``seq_len`` tokens, ``max_rows``
    served tokens each), so the reference compiles once."""
    import jax
    import jax.numpy as jnp
    seqs, rows, want = _pack(sample, seq_len, max_rows)
    pad = -len(sample) % ROWS_PER_CALL
    if pad:
        seqs = np.concatenate([seqs, np.zeros((pad, seq_len), np.int32)])
        rows = np.concatenate([rows, np.zeros((pad, max_rows), np.int32)])
        want = np.concatenate([want, np.full((pad, max_rows), -1)])
    ref_fn = jax.jit(lambda p, s, r: _forward(p, s, r, config, _mm_f32))
    ctl_fn = jax.jit(lambda p, s, r: _forward(p, s, r, config, _mm_int8))
    gap, ctl_gap, count = 0.0, 0.0, 0
    for i in range(0, len(seqs), ROWS_PER_CALL):
        sl = slice(i, i + ROWS_PER_CALL)
        logits = np.asarray(ref_fn(params, jnp.asarray(seqs[sl]),
                                   jnp.asarray(rows[sl])), np.float64)
        w = want[sl]
        mask = w >= 0
        best = logits.max(axis=-1)
        got = np.take_along_axis(logits, np.maximum(w, 0)[..., None],
                                 axis=-1)[..., 0]
        if mask.any():
            gap = max(gap, float((best - got)[mask].max()))
            count += int(mask.sum())
        if control:
            c = np.asarray(ctl_fn(params, jnp.asarray(seqs[sl]),
                                  jnp.asarray(rows[sl])))
            pick = c.argmax(axis=-1)
            cgot = np.take_along_axis(logits, pick[..., None], axis=-1)[..., 0]
            if mask.any():
                ctl_gap = max(ctl_gap, float((best - cgot)[mask].max()))
    out = {"gap": gap, "tokens_compared": count,
           "requests_compared": len(sample)}
    if control:
        out["control_gap"] = ctl_gap
    return out
