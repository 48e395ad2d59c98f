"""Pallas TPU paged-attention decode kernel (gather over page tables).

Single-token decode attention where each request's KV history lives in
non-contiguous fixed-size pages (repro.runtime.paging). The page table is
a scalar-prefetch operand (``pltpu.PrefetchScalarGridSpec``), so the
BlockSpec index map can translate the logical page walk into physical
page DMAs before the kernel body runs — the gather costs index
arithmetic, not a materialized contiguous copy.

  grid = (batch, logical_pages); the page axis is innermost and
  sequential ("arbitrary"), so the online-softmax running max /
  denominator / accumulator live in VMEM scratch across the page walk.
  GQA folds q heads onto kv heads inside the block (q is reshaped to
  (Hkv, rep, D) and batched dot_generals contract per kv-head group).

Layout: q (B, Hq, D) — one query token per request; k/v pages
(NP, P, Hkv, D); page_table (B, M) int32; pos (B,) int32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _paged_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, page_size: int,
                  rep: int, num_logical: int):
    bi = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                   # (Hq, D)
    k = k_ref[0].astype(jnp.float32)                   # (P, Hkv, D)
    v = v_ref[0].astype(jnp.float32)
    hq, d = q.shape
    hkv = k.shape[1]

    qr = q.reshape(hkv, rep, d)
    kh = jnp.swapaxes(k, 0, 1)                         # (Hkv, P, D)
    vh = jnp.swapaxes(v, 0, 1)
    s = jax.lax.dot_general(qr, kh, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    s = s.reshape(hq, page_size)                       # (Hq, P)

    k_pos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    s = jnp.where(k_pos <= pos_ref[bi], s, _NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_cur = s.max(axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_prev * alpha + p.sum(axis=-1)
    pv = jax.lax.dot_general(p.reshape(hkv, rep, page_size), vh,
                             (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + pv.reshape(hq, d)
    m_scr[...] = m_new

    @pl.when(j == num_logical - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-20)[:, None]
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, page_table, pos, *,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, D); k_pages/v_pages: (NP, P, Hkv, D);
    page_table: (B, M) int32; pos: (B,) int32 → (B, Hq, D)."""
    b, hq, d = q.shape
    page_size, hkv = k_pages.shape[1], k_pages.shape[2]
    m = page_table.shape[1]
    if hq % hkv:
        raise ValueError("Hq must be a multiple of Hkv")
    rep = hq // hkv
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _paged_kernel, scale=scale, page_size=page_size, rep=rep,
        num_logical=m)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, m),
        in_specs=[
            pl.BlockSpec((1, hq, d),
                         lambda bi, j, table, pos: (bi, 0, 0)),
            pl.BlockSpec((1, page_size, hkv, d),
                         lambda bi, j, table, pos: (table[bi, j], 0, 0, 0)),
            pl.BlockSpec((1, page_size, hkv, d),
                         lambda bi, j, table, pos: (table[bi, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hq, d),
                               lambda bi, j, table, pos: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq,), jnp.float32),
            pltpu.VMEM((hq,), jnp.float32),
            pltpu.VMEM((hq, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32), q,
      k_pages, v_pages)
