"""Jit'd public wrappers around the Pallas kernels.

These adapt model-layout tensors to kernel layouts, choose hardware-aligned
block sizes, and expose an `interpret` switch. It defaults to False, which
lowers the kernel through Mosaic for the TPU; the CPU tests pass
``interpret=True`` so the kernel body executes in Python.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention as _paged_attention
from repro.kernels.spec_verify import spec_verify as _spec_verify
from repro.kernels.ssm_scan import ssm_scan
from repro.kernels.cross_entropy import fused_cross_entropy


def _pick_block(size: int, preferred: int) -> int:
    b = min(preferred, size)
    while size % b:
        b //= 2
    return max(b, 1)


@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret"))
def attention(q, k, v, *, causal: bool = True,
              window: Optional[int] = None, interpret: bool = False):
    """Model-layout attention. q: (B, S, Hq, D); k, v: (B, T, Hkv, D)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    bq = _pick_block(qt.shape[2], 128)
    bkv = _pick_block(kt.shape[2], 128)
    out = flash_attention(qt, kt, vt, causal=causal, window=window,
                          block_q=bq, block_kv=bkv, interpret=interpret)
    return jnp.swapaxes(out, 1, 2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, page_table, pos, *,
                    interpret: bool = False):
    """Paged decode attention; shapes as in
    repro.kernels.ref.paged_attention_ref. q: (B, Hq, D); k_pages/v_pages:
    (NP, P, Hkv, D); page_table: (B, M) int32; pos: (B,) int32."""
    return _paged_attention(q, k_pages, v_pages, page_table, pos,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spec_verify(q, k_pages, v_pages, page_table, q_pos, *,
                interpret: bool = False):
    """Speculative-verify window attention; shapes as in
    repro.kernels.ref.spec_verify_ref. q: (B, W, Hq, D); k_pages/v_pages:
    (NP, P, Hkv, D); page_table: (B, M) int32; q_pos: (B, W) int32."""
    return _spec_verify(q, k_pages, v_pages, page_table, q_pos,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan(x, dt, a, bmat, cmat, *, interpret: bool = False):
    """Mamba1 recurrence; shapes as in repro.kernels.ref.ssm_scan_ref."""
    bl = _pick_block(x.shape[1], 64)
    bd = _pick_block(x.shape[2], 128)
    return ssm_scan(x, dt, a, bmat, cmat, block_l=bl, block_d=bd,
                    interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cross_entropy(hidden, w_vocab, labels, *, interpret: bool = False):
    """Fused NLL; hidden (T, d), w_vocab (d, V), labels (T,) → (T,) fp32."""
    bt = _pick_block(hidden.shape[0], 256)
    bv = _pick_block(w_vocab.shape[1], 1024)
    return fused_cross_entropy(hidden, w_vocab, labels, block_t=bt,
                               block_v=bv, interpret=interpret)


# re-export oracles for convenience
attention_ref = ref.attention_ref
paged_attention_ref = ref.paged_attention_ref
spec_verify_ref = ref.spec_verify_ref
selective_scan_ref = ref.ssm_scan_ref
cross_entropy_ref = ref.cross_entropy_ref
