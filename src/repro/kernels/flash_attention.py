"""Pallas TPU flash attention (causal / sliding-window, GQA-aware).

Blockwise-softmax attention with explicit VMEM tiling:
  grid = (batch, q_heads, q_blocks, kv_blocks); the kv axis is the innermost,
  sequential ("arbitrary") dimension so the running max / denominator / output
  accumulator live in VMEM scratch across kv steps. Block shapes are MXU
  aligned (q/kv block sizes multiples of 128 in production; tests also sweep
  smaller tiles, which interpret mode accepts).

Layout: (B, H, S, D) — heads-major so each (head, q-block) owns contiguous
VMEM tiles. GQA maps q head h to kv head h // (Hq // Hkv) via the BlockSpec
index map, so kv tiles are fetched once per kv head group.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_kv: int, num_kv_blocks: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                   # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                   # (bkv, d)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_kv), 0)
    k_pos = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32,
                                                     (block_q, block_kv), 1)
    mask = jnp.ones((block_q, block_kv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_cur = s.max(axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * alpha + p.sum(axis=-1)
    acc = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-20)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, S, D); k, v: (B, Hkv, T, D); Hq % Hkv == 0."""
    b, hq, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    if hq % hkv:
        raise ValueError("Hq must be a multiple of Hkv")
    rep = hq // hkv
    block_q = min(block_q, s)
    block_kv = min(block_kv, t)
    if s % block_q or t % block_kv:
        raise ValueError("sequence lengths must divide block sizes")
    nq, nk = s // block_q, t // block_kv
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, num_kv_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bi, hi, qi, ki, rep=rep:
                         (bi, hi // rep, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda bi, hi, qi, ki, rep=rep:
                         (bi, hi // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
