"""Pallas TPU fused cross-entropy over vocab blocks.

For the assigned archs the LM-head logits tensor is the single largest
activation (vocab up to 202k): (B·S, V) bf16 at train_4k would be ~400 GB.
This kernel streams the vocab axis through VMEM in `block_v` tiles with an
online logsumexp, so logits never exist in HBM:

  grid = (token_blocks, vocab_blocks); vocab is the sequential axis carrying
  (m, l, target-logit) scratch; each step computes the (block_t, block_v)
  logits tile with an MXU matmul against the (d, block_v) weight tile and
  folds it into the running reduction. The label's logit is extracted with a
  one-hot dot (TPU-friendly — no gather).

Output: per-token NLL (T,) fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _xent_kernel(h_ref, w_ref, lab_ref, out_ref, m_scr, l_scr, t_scr, *,
                 block_v: int, num_v_blocks: int):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        t_scr[...] = jnp.zeros_like(t_scr)

    h = h_ref[...].astype(jnp.float32)                     # (bt, d)
    w = w_ref[...].astype(jnp.float32)                     # (d, bv)
    labels = lab_ref[...]                                  # (bt, 1)

    s = jax.lax.dot_general(h, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bt, bv)

    # online logsumexp; per-token running values are (bt, 1) columns
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_cur = s.max(axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    l_new = l_prev * jnp.exp(m_prev - m_new) \
        + jnp.exp(s - m_new).sum(axis=-1, keepdims=True)
    m_scr[...] = m_new
    l_scr[...] = l_new

    # target logit via one-hot dot (labels local to this vocab block)
    local = labels - iv * block_v                          # (bt, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    onehot = (cols == local).astype(jnp.float32)
    t_scr[...] = t_scr[...] + (s * onehot).sum(axis=-1, keepdims=True)

    @pl.when(iv == num_v_blocks - 1)
    def _finish():
        lse = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))
        out_ref[...] = (lse - t_scr[...]).astype(out_ref.dtype)


def fused_cross_entropy(hidden, w_vocab, labels, *, block_t: int = 256,
                        block_v: int = 1024, interpret: bool = False):
    """hidden: (T, d); w_vocab: (d, V); labels: (T,) int32 → NLL (T,) fp32.

    Labels, output and scratch are (T, 1) columns: Mosaic tiles the last
    two dimensions of a block, and a 1-D block's layout disagrees with the
    one XLA gives the operand."""
    t, d = hidden.shape
    v = w_vocab.shape[1]
    block_t = min(block_t, t)
    block_v = min(block_v, v)
    if t % block_t or v % block_v:
        raise ValueError("T, V must divide block sizes")
    nt, nv = t // block_t, v // block_v

    kernel = functools.partial(_xent_kernel, block_v=block_v,
                               num_v_blocks=nv)
    out = pl.pallas_call(
        kernel,
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((d, block_v), lambda ti, vi: (0, vi)),
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((t, 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(hidden, w_vocab, labels.reshape(t, 1))
    return out[:, 0]
