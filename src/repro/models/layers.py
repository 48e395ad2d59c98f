"""Neural-net building blocks: spec machinery, norms, rotary, attention
(blockwise/flash-style in pure JAX), SwiGLU MLP, MoE dispatch, SSM scans.

Everything is a pure function over explicit parameter pytrees; parameters are
declared via :class:`ParamSpec` trees so the same definitions drive random
init, abstract (shape-only) init, and sharding-spec derivation.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig, ParamSpec

# ---------------------------------------------------------------------------
# ParamSpec tree utilities
# ---------------------------------------------------------------------------

def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def tree_map_specs(fn, spec_tree):
    return jax.tree_util.tree_map(fn, spec_tree,
                                  is_leaf=lambda x: is_spec(x))


# Leading axes that stack independent weights (scanned layers, expert banks)
# rather than feed one output unit.
_STACK_AXES = ("layers", "experts")


def _fan_in(spec) -> int:
    """Inputs feeding one output unit of a weight whose last axis is the
    output: every other axis but the stacking ones — ``in`` for an
    (in, out) matrix, ``kh*kw*cin`` for an HWIO conv kernel, ``in`` for a
    (layers, in, out) stack."""
    if len(spec.shape) == 1:
        return spec.shape[0]
    return math.prod(n for n, ax in zip(spec.shape[:-1], spec.axes[:-1])
                     if ax not in _STACK_AXES)


def materialize(spec_tree, key, dtype) -> Any:
    """Randomly initialize parameters from a spec tree."""
    leaves, treedef = jax.tree_util.tree_flatten(spec_tree, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, spec in zip(keys, leaves):
        dt = spec.dtype or dtype
        if spec.init == "zeros":
            arr = jnp.zeros(spec.shape, dt)
        elif spec.init == "ones":
            arr = jnp.ones(spec.shape, dt)
        elif spec.init == "embed":
            arr = (jax.random.normal(k, spec.shape, jnp.float32)
                   * 0.02 * spec.scale).astype(dt)
        elif spec.init == "ssm_a":
            # mamba: A = -exp(A_log); init A_log = log(1..N) broadcast
            n = spec.shape[-1]
            base = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
            arr = jnp.broadcast_to(base, spec.shape).astype(jnp.float32)
        else:  # fan-in scaled normal
            std = spec.scale / math.sqrt(max(_fan_in(spec), 1))
            arr = (jax.random.normal(k, spec.shape, jnp.float32)
                   * std).astype(dt)
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out)


def abstractify(spec_tree, dtype) -> Any:
    """ShapeDtypeStruct stand-ins — no allocation."""
    def one(spec: ParamSpec):
        return jax.ShapeDtypeStruct(spec.shape, spec.dtype or dtype)
    return tree_map_specs(one, spec_tree)


def logical_axes(spec_tree) -> Any:
    return tree_map_specs(lambda s: s.axes, spec_tree)


# ---------------------------------------------------------------------------
# Norms & positional encodings
# ---------------------------------------------------------------------------

def rms_norm(x, weight, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def rotary_angles(positions, head_dim: int, theta: float):
    """positions (...,) -> (cos, sin) of shape (..., head_dim//2), fp32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x, cos, sin):
    """x (..., S, H, hd); cos/sin broadcastable to (..., S, 1, hd//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out1 = xf1 * cos - xf2 * sin
    out2 = xf2 * cos + xf1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention — pure JAX, TPU-lowerable, O(chunk) memory
# ---------------------------------------------------------------------------

_NEG_INF = -1e30


def _attn_block(qr, kb, vb, q_pos, k_pos, carry, causal, window, scale):
    """One (q-chunk × kv-chunk) online-softmax update.

    qr: (B, qc, Hk, rep, hd); kb/vb: (B, kc, Hk, hd);
    carry = (acc (B,qc,Hk,rep,hd) f32, m, l (B,qc,Hk,rep) f32).
    """
    acc, m, l = carry
    s = jnp.einsum("bqhrd,bkhd->bqhrk", qr, kb,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= k_pos[None, :] > (q_pos[:, None] - window)
    s = jnp.where(mask[None, :, None, None, :], s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum("bqhrk,bkhd->bqhrd", p.astype(vb.dtype), vb,
                    preferred_element_type=jnp.float32)
    acc_new = acc * alpha[..., None] + pv
    return acc_new, m_new, l_new


def _largest_divisor_leq(n: int, bound: int) -> int:
    for d in range(min(bound, n), 0, -1):
        if n % d == 0:
            return d
    return 1


def _chunk_sizes(s: int, t: int, q_chunk: int, kv_chunk: int):
    """Largest divisors ≤ the preferred chunk sizes (handles non-power-of-two
    sequence lengths like whisper's 1500 encoder frames without degenerating
    to tiny chunks)."""
    return (_largest_divisor_leq(s, min(q_chunk, s)),
            _largest_divisor_leq(t, min(kv_chunk, t)))


def _kv_range(q_start, q_chunk, kv_chunk, nk, causal, window, block_skip):
    lo, hi = 0, nk
    if block_skip:
        if causal:
            hi = min(nk, (q_start + q_chunk + kv_chunk - 1) // kv_chunk)
        if window is not None:
            lo = max(0, (q_start - window) // kv_chunk)
    return lo, hi


def _q_range(k_start, kv_chunk, q_chunk, nq, causal, window, block_skip):
    """q chunks that can see kv chunk starting at k_start."""
    lo, hi = 0, nq
    if block_skip:
        if causal:
            lo = max(0, k_start // q_chunk)
        if window is not None:
            hi = min(nq, (k_start + kv_chunk + window + q_chunk - 1)
                     // q_chunk)
    return lo, hi


def _blockwise_attention_fwd_impl(q, k, v, causal, window, q_chunk,
                                  kv_chunk, block_skip):
    """Online-softmax forward. Returns (out, lse) with lse (B, S, Hq) f32."""
    b, s, hq, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    rep = hq // hkv
    q_chunk, kv_chunk = _chunk_sizes(s, t, q_chunk, kv_chunk)
    nq, nk = s // q_chunk, t // kv_chunk
    scale = 1.0 / math.sqrt(hd)

    qr = q.reshape(b, nq, q_chunk, hkv, rep, hd)
    kr = k.reshape(b, nk, kv_chunk, hkv, hd)
    vr = v.reshape(b, nk, kv_chunk, hkv, hd)

    outs, lses = [], []
    for qi in range(nq):
        q_start = qi * q_chunk
        q_pos = q_start + jnp.arange(q_chunk)
        lo, hi = _kv_range(q_start, q_chunk, kv_chunk, nk, causal, window,
                           block_skip)
        acc = jnp.zeros((b, q_chunk, hkv, rep, hd), jnp.float32)
        m = jnp.full((b, q_chunk, hkv, rep), _NEG_INF, jnp.float32)
        l = jnp.zeros((b, q_chunk, hkv, rep), jnp.float32)

        def body(carry, inputs):
            kb, vb, ki = inputs
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            carry = _attn_block(qr[:, qi], kb, vb, q_pos, k_pos, carry,
                                causal, window, scale)
            return carry, None

        ks = jnp.moveaxis(kr[:, lo:hi], 1, 0)       # (nchunks, B, kc, Hkv, hd)
        vs = jnp.moveaxis(vr[:, lo:hi], 1, 0)
        idxs = jnp.arange(lo, hi)
        (acc, m, l), _ = jax.lax.scan(body, (acc, m, l), (ks, vs, idxs))
        l = jnp.maximum(l, 1e-20)
        out = (acc / l[..., None]).astype(q.dtype)
        outs.append(out.reshape(b, q_chunk, hq, hd))
        lses.append((m + jnp.log(l)).reshape(b, q_chunk, hq))
    return jnp.concatenate(outs, axis=1), jnp.concatenate(lses, axis=1)


def blockwise_attention(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        q_chunk: int = 512, kv_chunk: int = 512,
                        block_skip: bool = True) -> jnp.ndarray:
    """Keyword-friendly wrapper over the custom-vjp implementation."""
    return _blockwise_attention_vjp(q, k, v, causal, window, q_chunk,
                                    kv_chunk, block_skip)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _blockwise_attention_vjp(q, k, v, causal: bool = True,
                             window: Optional[int] = None,
                             q_chunk: int = 512, kv_chunk: int = 512,
                             block_skip: bool = True) -> jnp.ndarray:
    """Memory-O(chunk²) flash-semantics attention (GQA-aware), pure JAX.

    q: (B, S, Hq, hd); k, v: (B, T, Hkv, hd) with Hq % Hkv == 0. Self- or
    cross-attention (causality assumes aligned ends). ``block_skip``
    statically skips fully-masked kv blocks — halving causal attention
    FLOPs, the lowered-HLO analogue of flash attention's block skipping.

    custom_vjp: only (q, k, v, out, lse) are saved; the backward pass
    recomputes probabilities blockwise (the flash-attention-2 recipe), so
    the online-softmax scan carries never become per-step residuals.
    """
    out, _ = _blockwise_attention_fwd_impl(q, k, v, causal, window, q_chunk,
                                           kv_chunk, block_skip)
    return out


def _bw_attn_fwd(q, k, v, causal, window, q_chunk, kv_chunk, block_skip):
    out, lse = _blockwise_attention_fwd_impl(q, k, v, causal, window,
                                             q_chunk, kv_chunk, block_skip)
    return out, (q, k, v, out, lse)


def _bw_attn_bwd(causal, window, q_chunk, kv_chunk, block_skip, res, do):
    q, k, v, out, lse = res
    b, s, hq, hd = q.shape
    t, hkv = k.shape[1], k.shape[2]
    rep = hq // hkv
    q_chunk, kv_chunk = _chunk_sizes(s, t, q_chunk, kv_chunk)
    nq, nk = s // q_chunk, t // kv_chunk
    scale = 1.0 / math.sqrt(hd)

    qr = q.reshape(b, nq, q_chunk, hkv, rep, hd)
    kr = k.reshape(b, nk, kv_chunk, hkv, hd)
    vr = v.reshape(b, nk, kv_chunk, hkv, hd)
    do_r = do.reshape(b, nq, q_chunk, hkv, rep, hd)
    lse_r = lse.reshape(b, nq, q_chunk, hkv, rep)
    delta = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    delta_r = delta.reshape(b, nq, q_chunk, hkv, rep)

    def probs(qi_block, k_pos, q_pos, lse_block, kb):
        sblk = jnp.einsum("bqhrd,bkhd->bqhrk", qi_block, kb,
                          preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= k_pos[None, :] > (q_pos[:, None] - window)
        p = jnp.exp(sblk - lse_block[..., None])
        return jnp.where(mask[None, :, None, None, :], p, 0.0)

    # pass 1: dq, one q chunk at a time
    dqs = []
    for qi in range(nq):
        q_start = qi * q_chunk
        q_pos = q_start + jnp.arange(q_chunk)
        lo, hi = _kv_range(q_start, q_chunk, kv_chunk, nk, causal, window,
                           block_skip)

        def body(dq_acc, inputs):
            kb, vb, ki = inputs
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            p = probs(qr[:, qi], k_pos, q_pos, lse_r[:, qi], kb)
            dp = jnp.einsum("bqhrd,bkhd->bqhrk", do_r[:, qi], vb,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - delta_r[:, qi][..., None])
            dq_acc = dq_acc + jnp.einsum(
                "bqhrk,bkhd->bqhrd", ds, kb,
                preferred_element_type=jnp.float32) * scale
            return dq_acc, None

        ks = jnp.moveaxis(kr[:, lo:hi], 1, 0)
        vs = jnp.moveaxis(vr[:, lo:hi], 1, 0)
        idxs = jnp.arange(lo, hi)
        dq0 = jnp.zeros((b, q_chunk, hkv, rep, hd), jnp.float32)
        dq_acc, _ = jax.lax.scan(body, dq0, (ks, vs, idxs))
        dqs.append(dq_acc.reshape(b, q_chunk, hq, hd))
    dq = jnp.concatenate(dqs, axis=1).astype(q.dtype)

    # pass 2: dk, dv, one kv chunk at a time
    dks, dvs = [], []
    for ki in range(nk):
        k_start = ki * kv_chunk
        k_pos = k_start + jnp.arange(kv_chunk)
        lo, hi = _q_range(k_start, kv_chunk, q_chunk, nq, causal, window,
                          block_skip)

        def body2(carry, inputs):
            dk_acc, dv_acc = carry
            qb, dob, lseb, deltab, qi = inputs
            q_pos = qi * q_chunk + jnp.arange(q_chunk)
            p = probs(qb, k_pos, q_pos, lseb, kr[:, ki])
            dv_acc = dv_acc + jnp.einsum(
                "bqhrk,bqhrd->bkhd", p, dob.astype(jnp.float32),
                preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqhrd,bkhd->bqhrk", dob, vr[:, ki],
                            preferred_element_type=jnp.float32)
            ds = p * (dp - deltab[..., None])
            dk_acc = dk_acc + jnp.einsum(
                "bqhrk,bqhrd->bkhd", ds, qb.astype(jnp.float32),
                preferred_element_type=jnp.float32) * scale
            return (dk_acc, dv_acc), None

        qs = jnp.moveaxis(qr[:, lo:hi], 1, 0)
        dos = jnp.moveaxis(do_r[:, lo:hi], 1, 0)
        lses = jnp.moveaxis(lse_r[:, lo:hi], 1, 0)
        deltas = jnp.moveaxis(delta_r[:, lo:hi], 1, 0)
        idxs = jnp.arange(lo, hi)
        z = jnp.zeros((b, kv_chunk, hkv, hd), jnp.float32)
        (dk_acc, dv_acc), _ = jax.lax.scan(body2, (z, z),
                                           (qs, dos, lses, deltas, idxs))
        dks.append(dk_acc)
        dvs.append(dv_acc)
    dk = jnp.concatenate(dks, axis=1).astype(k.dtype)
    dv = jnp.concatenate(dvs, axis=1).astype(v.dtype)
    return dq, dk, dv


_blockwise_attention_vjp.defvjp(_bw_attn_fwd, _bw_attn_bwd)


def decode_attention(q, k_cache, v_cache, pos, *,
                     window: Optional[int] = None) -> jnp.ndarray:
    """Single-token attention over a (possibly ring-buffered) KV cache.

    q: (B, 1, Hq, hd); caches: (B, C, Hc, hd) where Hc divides Hq (cache may
    hold sharding-replicated kv heads). ``pos`` is the absolute position of
    the new token — a scalar (whole batch at one position) or a (B,) vector
    (continuous batching: every slot decodes at its own position). For ring
    caches (C == window) slot validity is min(pos+1, C); ordering inside the
    ring is irrelevant because keys carry their rotary phase.
    """
    b, _, hq, hd = q.shape
    c, hc = k_cache.shape[1], k_cache.shape[2]
    rep = hq // hc
    scale = 1.0 / math.sqrt(hd)
    qr = q.reshape(b, 1, hc, rep, hd)
    s = jnp.einsum("bqhrd,bkhd->bqhrk", qr, k_cache,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.broadcast_to(jnp.asarray(pos), (b,))
    n_valid = jnp.minimum(pos + 1, c)                       # (B,)
    idx = jnp.arange(c)
    valid = idx[None, :] < n_valid[:, None]                 # (B, C)
    if window is not None and c > window:
        # non-ring cache with a window: mask positions outside it
        valid &= idx[None, :] > pos[:, None] - window
    s = jnp.where(valid[:, None, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bqhrk,bkhd->bqhrd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype).reshape(b, 1, hq, hd)


def paged_decode_attention(q, k_pages, v_pages, page_table,
                           pos) -> jnp.ndarray:
    """Single-token attention over a paged KV cache (repro.runtime.paging).

    q: (B, 1, Hq, hd); pages: (NP, P, Hc, hd); page_table: (B, M) int32 —
    logical page j of row b lives at physical page ``page_table[b, j]``;
    pos: (B,) absolute decode positions. Gathers the rows' pages into
    position order and reuses :func:`decode_attention`'s masked-softmax
    math, so a paged cache is token-identical to a contiguous slot under
    greedy decoding (garbage past ``pos`` — padded table entries included
    — is masked exactly as a slot's unwritten tail is). Fully-masked
    softmax columns contribute exp(-1e30)≡0, so the result does not
    depend on M*P vs the slot length. The Pallas gather kernel
    (repro.kernels.paged_attention) computes the same quantity blockwise
    for the accelerator path.
    """
    b, _, hq, hd = q.shape
    psize, hc = k_pages.shape[1], k_pages.shape[2]
    m = page_table.shape[1]
    kc = k_pages[page_table].reshape(b, m * psize, hc, hd)
    vc = v_pages[page_table].reshape(b, m * psize, hc, hd)
    return decode_attention(q, kc, vc, pos, window=None)


def paged_window_attention(q, k_pages, v_pages, page_table,
                           q_pos) -> jnp.ndarray:
    """W-query speculative-window attention over a paged KV cache.

    q: (B, W, Hq, hd); pages: (NP, P, Hc, hd); page_table: (B, M) int32;
    q_pos: (B, W) int32 — the absolute position of each of the row's W
    window tokens (the speculative engine passes pos, pos+1, …, pos+γ;
    lanes past a row's window length point at a scratch position whose
    output is discarded). Key position k is visible to query i iff
    ``k <= q_pos[b, i]`` — for W == 1 this is exactly
    :func:`decode_attention`'s ``idx < pos + 1`` mask, so a one-token
    window reproduces plain paged decode bit-for-bit. The Pallas window
    kernel (repro.kernels.spec_verify) computes the same quantity
    blockwise for the accelerator path.
    """
    b, w, hq, hd = q.shape
    psize, hc = k_pages.shape[1], k_pages.shape[2]
    m = page_table.shape[1]
    rep = hq // hc
    scale = 1.0 / math.sqrt(hd)
    kc = k_pages[page_table].reshape(b, m * psize, hc, hd)
    vc = v_pages[page_table].reshape(b, m * psize, hc, hd)
    qr = q.reshape(b, w, hc, rep, hd)
    s = jnp.einsum("bqhrd,bkhd->bqhrk", qr, kc,
                   preferred_element_type=jnp.float32) * scale
    idx = jnp.arange(m * psize)
    valid = idx[None, None, :] <= q_pos[:, :, None]          # (B, W, K)
    s = jnp.where(valid[:, :, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bqhrk,bkhd->bqhrd", p.astype(vc.dtype), vc,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype).reshape(b, w, hq, hd)


# ---------------------------------------------------------------------------
# Attention block (params + apply)
# ---------------------------------------------------------------------------

def attention_specs(cfg: ModelConfig, kv_heads: Optional[int] = None
                    ) -> Dict[str, ParamSpec]:
    d, hd = cfg.d_model, cfg.head_dim
    hq = cfg.num_heads
    hkv = kv_heads if kv_heads is not None else cfg.num_kv_heads
    specs = {
        "wq": ParamSpec((d, hq * hd), ("embed", "heads")),
        "wk": ParamSpec((d, hkv * hd), ("embed", "kv_heads")),
        "wv": ParamSpec((d, hkv * hd), ("embed", "kv_heads")),
        "wo": ParamSpec((hq * hd, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((hq * hd,), ("heads",), init="zeros")
        specs["bk"] = ParamSpec((hkv * hd,), ("kv_heads",), init="zeros")
        specs["bv"] = ParamSpec((hkv * hd,), ("kv_heads",), init="zeros")
    return specs


def attention_qkv(p, x, cfg: ModelConfig, positions, *, rope: bool = True):
    """Project to q, k, v (+bias, +rotary). x: (B, S, d)."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, -1, hd)
    k = k.reshape(b, s, -1, hd)
    v = v.reshape(b, s, -1, hd)
    if rope and not cfg.learned_pos_embed:
        cos, sin = rotary_angles(positions, hd, cfg.rope_theta)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    return q, k, v


def self_attention(p, x, cfg: ModelConfig, *, causal: bool = True,
                   window: Optional[int] = None, positions=None):
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    q, k, v = attention_qkv(p, x, cfg, positions)
    out = blockwise_attention(q, k, v, causal=causal, window=window,
                              q_chunk=cfg.attn_q_chunk,
                              kv_chunk=cfg.attn_kv_chunk,
                              block_skip=cfg.causal_block_skip)
    return out.reshape(b, s, -1) @ p["wo"]


def cross_attention_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    return attention_specs(cfg, kv_heads=cfg.num_kv_heads)


def cross_attention(p, x, enc, cfg: ModelConfig):
    """x: (B, S, d) queries; enc: (B, T, d) encoder states (no rotary)."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = (x @ p["wq"]).reshape(b, s, -1, hd)
    k = (enc @ p["wk"]).reshape(b, enc.shape[1], -1, hd)
    v = (enc @ p["wv"]).reshape(b, enc.shape[1], -1, hd)
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(1, 1, -1, hd)
        k = k + p["bk"].reshape(1, 1, -1, hd)
        v = v + p["bv"].reshape(1, 1, -1, hd)
    out = blockwise_attention(q, k, v, causal=False,
                              q_chunk=cfg.attn_q_chunk,
                              kv_chunk=cfg.attn_kv_chunk)
    return out.reshape(b, s, -1) @ p["wo"]


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_specs(cfg: ModelConfig, d_ff: Optional[int] = None, gelu: bool = False
              ) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    if gelu:  # whisper-style 2-matrix GELU MLP
        return {"w_in": ParamSpec((d, ff), ("embed", "ff")),
                "b_in": ParamSpec((ff,), ("ff",), init="zeros"),
                "w_out": ParamSpec((ff, d), ("ff", "embed")),
                "b_out": ParamSpec((d,), ("embed",), init="zeros")}
    return {"w_gate": ParamSpec((d, ff), ("embed", "ff")),
            "w_up": ParamSpec((d, ff), ("embed", "ff")),
            "w_down": ParamSpec((ff, d), ("ff", "embed"))}


def mlp_apply(p, x, gelu: bool = False):
    if gelu:
        h = jax.nn.gelu((x @ p["w_in"] + p["b_in"]).astype(jnp.float32))
        return h.astype(x.dtype) @ p["w_out"] + p["b_out"]
    g = jax.nn.silu((x @ p["w_gate"]).astype(jnp.float32)).astype(x.dtype)
    return (g * (x @ p["w_up"])) @ p["w_down"]


# ---------------------------------------------------------------------------
# Mixture of Experts (top-k, capacity-dropped, scatter-based dispatch)
# ---------------------------------------------------------------------------

def moe_specs(cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    ffe = cfg.d_ff_expert or cfg.d_ff
    e = cfg.num_experts
    specs: Dict[str, Any] = {
        "router": ParamSpec((d, e), ("embed", None)),
        "w_gate": ParamSpec((e, d, ffe), ("experts", "embed", "expert_ff")),
        "w_up": ParamSpec((e, d, ffe), ("experts", "embed", "expert_ff")),
        "w_down": ParamSpec((e, ffe, d), ("experts", "expert_ff", "embed")),
    }
    if cfg.moe_shared_expert:
        specs["shared"] = mlp_specs(cfg, d_ff=cfg.d_ff)
    return specs


def moe_apply(p, x, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routed experts with static capacity. x: (B, S, d).

    Returns (output, aux_loss). Dispatch is scatter-based: tokens are written
    into a static capacity buffer whose expert axis shards over the `model`
    mesh axis (the canonical all-to-all expert-parallel exchange).

    With ``cfg.moe_groups = G > 0`` the dispatch runs within G independent
    token groups (aligned to the data shards): the buffer gains a leading
    group axis that shards over the data axes, so expert compute scales with
    the whole mesh instead of only the expert axis. Semantics: capacity
    dropping becomes per-group (each group owns C/G slots per expert) — the
    standard deployment behaviour of MoE frameworks; G=0 reproduces single
    global dispatch.
    """
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.experts_per_token
    tokens = b * s
    grp = cfg.moe_groups if cfg.moe_groups and tokens % cfg.moe_groups == 0 \
        else 1
    tl = tokens * k // grp                                     # slots/group
    xt = x.reshape(tokens, d)

    logits = (xt @ p["router"]).astype(jnp.float32)           # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)           # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9)

    capacity = int(math.ceil(tokens * k / e / grp
                             * cfg.moe_capacity_factor))
    capacity = max(capacity, 1)

    flat_expert = expert_idx.reshape(grp, tl)                 # (G, T*k/G)
    onehot = jax.nn.one_hot(flat_expert, e, dtype=jnp.int32)  # (G, Tl, E)
    pos_in_expert = jnp.cumsum(onehot, axis=1) - 1            # per group
    pos = jnp.take_along_axis(pos_in_expert, flat_expert[..., None],
                              axis=2)[..., 0]                 # (G, Tl)
    keep = pos < capacity
    safe_pos = jnp.where(keep, pos, 0)

    xk = jnp.repeat(xt, k, axis=0).reshape(grp, tl, d)        # (G, Tl, d)
    # G is a vmapped batch dim (not a scatter-indexed dim) so GSPMD keeps
    # the per-group scatter local to its data shard — no cross-shard
    # all-reduce of the capacity buffer.
    buf = jax.vmap(
        lambda fe, sp, upd: jnp.zeros((e, capacity, d), x.dtype)
        .at[fe, sp].add(upd, mode="drop"))(
            flat_expert, safe_pos, jnp.where(keep[..., None], xk, 0))

    g = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["w_gate"],
                               preferred_element_type=jnp.float32))
    u = jnp.einsum("gecd,edf->gecf", buf, p["w_up"],
                   preferred_element_type=jnp.float32)
    h = (g * u).astype(x.dtype)
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["w_down"])    # (G, E, C, d)

    gathered = jax.vmap(lambda ob, fe, sp: ob[fe, sp])(
        out_buf, flat_expert, safe_pos)                       # (G, Tl, d)
    gathered = jnp.where(keep[..., None], gathered, 0)
    weighted = gathered * gate_vals.reshape(grp, tl, 1).astype(x.dtype)
    y = weighted.reshape(tokens, k, d).sum(axis=1)

    if cfg.moe_shared_expert:
        y = y + mlp_apply(p["shared"], xt)

    # Load-balance auxiliary loss (Switch-style): E * sum_e f_e * p_e.
    me = probs.mean(axis=0)                                   # (E,)
    ce = onehot.reshape(tokens, k, e).sum(axis=1).astype(jnp.float32)
    fe = ce.mean(axis=0) / k
    aux = e * jnp.sum(fe * me) * cfg.router_aux_loss
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# State-space blocks (Mamba1 / Mamba2), chunked scans
# ---------------------------------------------------------------------------

def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv. x: (B, S, C); w: (C, K); b: (C,).

    If `state` (B, K-1, C) is given, performs streaming conv (decode) and
    returns (y, new_state).
    """
    k = w.shape[1]
    if state is not None:
        xin = jnp.concatenate([state, x], axis=1)             # (B, K-1+S, C)
        new_state = xin[:, -(k - 1):, :]
    else:
        xin = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
        new_state = None
    y = sum(xin[:, i:i + x.shape[1], :] * w[:, i][None, None, :]
            for i in range(k))
    y = y + b[None, None, :]
    y = jax.nn.silu(y.astype(jnp.float32)).astype(x.dtype)
    return (y, new_state) if state is not None else y


def _chunked_ssm_scan(a, bx, chunk: int, h0=None):
    """Linear recurrence h_t = a_t * h_{t-1} + bx_t, chunked over time.

    a, bx: (B, L, ...) with elementwise state dims trailing. Returns
    (y (B, L, ...), h_last). Uses an associative scan inside each chunk and a
    sequential carry across chunks — the TPU-friendly schedule (VMEM-resident
    chunks, O(L/chunk) HBM round trips) mirrored by the Pallas kernel.
    """
    b, l = a.shape[0], a.shape[1]
    chunk = min(chunk, l)
    while l % chunk:
        chunk //= 2
    n = l // chunk
    state_shape = a.shape[2:]
    ar = a.reshape((b, n, chunk) + state_shape)
    br = bx.reshape((b, n, chunk) + state_shape)
    if h0 is None:
        h0 = jnp.zeros((b,) + state_shape, a.dtype)

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, b1 * a2 + b2

    @jax.checkpoint   # recompute each chunk in backward: residual = carry h
    def body(h, inputs):
        ac, bc = inputs                                       # (B, chunk, ...)
        a_cum, b_cum = jax.lax.associative_scan(combine, (ac, bc), axis=1)
        h_new = a_cum * h[:, None] + b_cum                    # (B, chunk, ...)
        return h_new[:, -1], h_new

    h_last, ys = jax.lax.scan(body, h0,
                              (jnp.moveaxis(ar, 1, 0), jnp.moveaxis(br, 1, 0)))
    ys = jnp.moveaxis(ys, 0, 1).reshape((b, l) + state_shape)
    return ys, h_last


def mamba1_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, di, n, r = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    return {
        "in_proj": ParamSpec((d, 2 * di), ("embed", "inner")),
        "conv_w": ParamSpec((di, cfg.ssm_conv), ("inner", None)),
        "conv_b": ParamSpec((di,), ("inner",), init="zeros"),
        "x_proj": ParamSpec((di, r + 2 * n), ("inner", None)),
        "dt_proj": ParamSpec((r, di), (None, "inner")),
        "dt_bias": ParamSpec((di,), ("inner",), init="zeros"),
        "a_log": ParamSpec((di, n), ("inner", None), init="ssm_a",
                           dtype=jnp.float32),
        "d_skip": ParamSpec((di,), ("inner",), init="ones",
                            dtype=jnp.float32),
        "out_proj": ParamSpec((di, d), ("inner", "embed")),
    }


def mamba1_apply(p, x, cfg: ModelConfig, state=None,
                 return_state: bool = False):
    """Mamba-1 selective SSM. x: (B, S, d).

    state: None (training/prefill from zero) or dict(conv (B,K-1,di),
    ssm (B,di,N)) for streaming decode. Returns y or (y, new_state);
    ``return_state=True`` makes the stateless (prefill) path also return the
    final streaming state.
    """
    b, s, _ = x.shape
    di, n, r = cfg.d_inner, cfg.ssm_state, cfg.dt_rank
    xz = x @ p["in_proj"]
    xs, z = jnp.split(xz, 2, axis=-1)                         # (B,S,di) each
    if state is not None:
        xs, conv_state = _causal_conv(xs, p["conv_w"], p["conv_b"],
                                      state["conv"])
    else:
        kq = cfg.ssm_conv - 1
        conv_in_tail = jnp.pad(xs, ((0, 0), (max(kq - s, 0), 0),
                                    (0, 0)))[:, -kq:, :]
        xs = _causal_conv(xs, p["conv_w"], p["conv_b"])
        conv_state = conv_in_tail if return_state else None

    proj = xs @ p["x_proj"]                                   # (B,S,r+2N)
    dt_in, bmat, cmat = jnp.split(proj, [r, r + n], axis=-1)
    dt = jax.nn.softplus((dt_in @ p["dt_proj"] + p["dt_bias"])
                         .astype(jnp.float32))                # (B,S,di)
    a = -jnp.exp(p["a_log"])                                  # (di,N) f32
    # discretize: a_bar = exp(dt*A); b_bar*x = dt * B * x
    dta = dt[..., None] * a[None, None]                       # (B,S,di,N)
    a_bar = jnp.exp(dta)
    bx = (dt * xs.astype(jnp.float32))[..., None] \
        * bmat.astype(jnp.float32)[:, :, None, :]             # (B,S,di,N)

    if state is not None:
        h = a_bar[:, 0] * state["ssm"] + bx[:, 0]             # (B,di,N)
        y = (h * cmat.astype(jnp.float32)[:, 0, None, :]).sum(-1)[:, None]
        new_ssm = h
    else:
        hs, new_ssm = _chunked_ssm_scan(a_bar, bx, cfg.ssm_chunk)
        y = (hs * cmat.astype(jnp.float32)[:, :, None, :]).sum(-1)
    y = y + p["d_skip"][None, None] * xs.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    out = y @ p["out_proj"]
    if state is not None or return_state:
        return out, {"conv": conv_state, "ssm": new_ssm}
    return out


def mamba2_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    d, di, n = cfg.d_model, cfg.d_inner, cfg.ssm_state
    nh = cfg.ssm_num_heads
    conv_dim = di + 2 * n
    return {
        "in_proj": ParamSpec((d, 2 * di + 2 * n + nh), ("embed", "inner")),
        "conv_w": ParamSpec((conv_dim, cfg.ssm_conv), ("inner", None)),
        "conv_b": ParamSpec((conv_dim,), ("inner",), init="zeros"),
        "a_log": ParamSpec((nh,), (None,), init="ssm_a", dtype=jnp.float32),
        "dt_bias": ParamSpec((nh,), (None,), init="zeros",
                             dtype=jnp.float32),
        "d_skip": ParamSpec((nh,), (None,), init="ones", dtype=jnp.float32),
        "norm_w": ParamSpec((di,), ("inner",), init="ones"),
        "out_proj": ParamSpec((di, d), ("inner", "embed")),
    }


def mamba2_apply(p, x, cfg: ModelConfig, state=None,
                 return_state: bool = False):
    """Mamba-2 (SSD, scalar decay per head, ngroups=1). x: (B, S, d)."""
    b, s, _ = x.shape
    di, n, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_num_heads
    hd = di // nh
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt_in = jnp.split(zxbcdt, [di, 2 * di + 2 * n], axis=-1)
    if state is not None:
        xbc, conv_state = _causal_conv(xbc, p["conv_w"], p["conv_b"],
                                       state["conv"])
    else:
        kq = cfg.ssm_conv - 1
        conv_in_tail = jnp.pad(xbc, ((0, 0), (max(kq - s, 0), 0),
                                     (0, 0)))[:, -kq:, :]
        xbc = _causal_conv(xbc, p["conv_w"], p["conv_b"])
        conv_state = conv_in_tail if return_state else None
    xs, bmat, cmat = jnp.split(xbc, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt_in.astype(jnp.float32)
                         + p["dt_bias"][None, None])          # (B,S,nh)
    a = -jnp.exp(p["a_log"])                                  # (nh,) f32
    a_bar = jnp.exp(dt * a[None, None])                       # (B,S,nh)
    xh = xs.reshape(b, s, nh, hd).astype(jnp.float32)
    # h update: h (B, nh, hd, N); bx_t = dt * x_t ⊗ B_t
    bx = (dt[..., None, None] * xh[..., None]
          * bmat.astype(jnp.float32)[:, :, None, None, :])    # (B,S,nh,hd,N)
    a_full = a_bar[..., None, None] * jnp.ones((1, 1, 1, hd, n), jnp.float32)
    if state is not None:
        h = a_full[:, 0] * state["ssm"] + bx[:, 0]            # (B,nh,hd,N)
        y = (h * cmat.astype(jnp.float32)[:, 0, None, None, :]).sum(-1)
        y = y[:, None]                                        # (B,1,nh,hd)
        new_ssm = h
    else:
        hs, new_ssm = _chunked_ssm_scan(a_full, bx, cfg.ssm_chunk)
        y = (hs * cmat.astype(jnp.float32)[:, :, None, None, :]).sum(-1)
    y = y + p["d_skip"][None, None, :, None] * xh[:, :y.shape[1]]
    y = y.reshape(b, -1, di)
    y = (y * jax.nn.silu(z[:, :y.shape[1]].astype(jnp.float32)))
    y = rms_norm(y.astype(x.dtype), p["norm_w"], cfg.norm_eps)
    out = y @ p["out_proj"]
    if state is not None or return_state:
        return out, {"conv": conv_state, "ssm": new_ssm}
    return out


def ssm_state_shapes(cfg: ModelConfig, batch: int) -> Dict[str, Tuple]:
    """Decode-state shapes for one SSM block."""
    k = cfg.ssm_conv - 1
    if cfg.ssm_variant == "mamba1":
        return {"conv": (batch, k, cfg.d_inner),
                "ssm": (batch, cfg.d_inner, cfg.ssm_state)}
    conv_dim = cfg.d_inner + 2 * cfg.ssm_state
    return {"conv": (batch, k, conv_dim),
            "ssm": (batch, cfg.ssm_num_heads,
                    cfg.d_inner // cfg.ssm_num_heads, cfg.ssm_state)}
