"""Pallas TPU speculative-verify window kernel (paged attention, W queries).

Speculative decoding verifies a whole draft window — the last accepted
token plus γ draft proposals — in one batched target step. The attention
core of that step is this kernel: W = γ+1 query tokens per request score
against the request's paged KV history in a single pass, instead of W
separate single-token decode calls (repro.kernels.paged_attention).

Same structure as the decode kernel: the page table is a scalar-prefetch
operand (``pltpu.PrefetchScalarGridSpec``) so the BlockSpec index map
walks logical pages into physical-page DMAs, and so are the window
positions, which the kernel reads as scalars from SMEM; grid = (batch,
logical_pages) with the page axis innermost and sequential
("arbitrary"), so the online-softmax running max / denominator /
accumulator carry a leading window axis in VMEM scratch across the page
walk. In-window causality comes from the per-query position operand:
key position k is visible to query i iff ``k <= q_pos[b, i]``, so draft
token i sees the drafts before it but never the ones after.

Layout: q (B, W, Hq, D); q_pos (B, W) int32 (absolute position of every
window token; lanes past a row's window length point at a scratch
position); k/v pages (NP, P, Hkv, D); page_table (B, M) int32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _verify_kernel(table_ref, qp_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, page_size: int,
                   rep: int, num_logical: int):
    bi = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                   # (W, Hq, D)
    k = k_ref[0].astype(jnp.float32)                   # (P, Hkv, D)
    v = v_ref[0].astype(jnp.float32)
    w, hq, d = q.shape
    hkv = k.shape[1]

    # GQA: fold the window axis into the per-kv-head query group so one
    # batched dot_general scores all W queries against the page.
    qr = jnp.swapaxes(q.reshape(w, hkv, rep, d), 0, 1)
    qr = qr.reshape(hkv, w * rep, d)
    kh = jnp.swapaxes(k, 0, 1)                         # (Hkv, P, D)
    vh = jnp.swapaxes(v, 0, 1)
    s = jax.lax.dot_general(qr, kh, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.swapaxes(s.reshape(hkv, w, rep, page_size), 0, 1)
    s = s.reshape(w, hq, page_size)                    # (W, Hq, P)

    # (W, 1, 1) window positions assembled from the SMEM scalars
    lane = jax.lax.broadcasted_iota(jnp.int32, (w, 1, 1), 0)
    qp = jnp.zeros((w, 1, 1), jnp.int32)
    for i in range(w):
        qp = jnp.where(lane == i, qp_ref[bi, i], qp)
    k_pos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, page_size), 2)
    s = jnp.where(k_pos <= qp, s, _NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]            # (W, Hq)
    m_cur = s.max(axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_scr[...] = l_prev * alpha + p.sum(axis=-1)
    pr = jnp.swapaxes(p.reshape(w, hkv, rep, page_size), 0, 1)
    pv = jax.lax.dot_general(pr.reshape(hkv, w * rep, page_size), vh,
                             (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    pv = jnp.swapaxes(pv.reshape(hkv, w, rep, d), 0, 1).reshape(w, hq, d)
    acc_scr[...] = acc_scr[...] * alpha[..., None] + pv
    m_scr[...] = m_new

    @pl.when(j == num_logical - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-20)[..., None]
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def spec_verify(q, k_pages, v_pages, page_table, q_pos, *,
                interpret: bool = False) -> jnp.ndarray:
    """q: (B, W, Hq, D); k_pages/v_pages: (NP, P, Hkv, D);
    page_table: (B, M) int32; q_pos: (B, W) int32 → (B, W, Hq, D)."""
    b, w, hq, d = q.shape
    page_size, hkv = k_pages.shape[1], k_pages.shape[2]
    m = page_table.shape[1]
    if hq % hkv:
        raise ValueError("Hq must be a multiple of Hkv")
    rep = hq // hkv
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _verify_kernel, scale=scale, page_size=page_size, rep=rep,
        num_logical=m)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, m),
        in_specs=[
            pl.BlockSpec((1, w, hq, d),
                         lambda bi, j, table, qp: (bi, 0, 0, 0)),
            pl.BlockSpec((1, page_size, hkv, d),
                         lambda bi, j, table, qp: (table[bi, j], 0, 0, 0)),
            pl.BlockSpec((1, page_size, hkv, d),
                         lambda bi, j, table, qp: (table[bi, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, w, hq, d),
                               lambda bi, j, table, qp: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((w, hq), jnp.float32),
            pltpu.VMEM((w, hq), jnp.float32),
            pltpu.VMEM((w, hq, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, w, hq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), q_pos.astype(jnp.int32), q,
      k_pages, v_pages)
