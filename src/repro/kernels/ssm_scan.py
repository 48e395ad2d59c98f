"""Pallas TPU selective-scan kernel (Mamba-1 core recurrence).

TPU adaptation of the CUDA selective-scan: instead of a warp-level parallel
scan, the sequence axis becomes the innermost *sequential* grid dimension in
chunks of `block_l`; the (block_d, N) hidden state lives in VMEM scratch and
is carried across chunk steps, so HBM traffic is O(L) in inputs/outputs and
the state never round-trips. The channel axis is tiled over `block_d`
(lane-aligned multiples of 128 in production) and is embarrassingly parallel.

  h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) ⊗ B_t ;  y_t = h_t · C_t + D·x

(The D-skip and gating stay outside the kernel — they are cheap elementwise.)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_scr,
                *, block_l: int, num_l_blocks: int):
    il = pl.program_id(2)

    @pl.when(il == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[...].astype(jnp.float32)        # (bd, N)
    # Mosaic lowers no dynamic slice of a value, and a one-row dynamic ref
    # load must start on a sublane-tile boundary: the loop reads and writes
    # sublane-aligned chunks of rows through pl.ds and walks each chunk's
    # rows with static slices.
    chunk = math.gcd(block_l, 8)

    def step(c, h):
        s = pl.multiple_of(c * chunk, chunk)
        rows = pl.ds(s, chunk)
        x = x_ref[0, rows, :].astype(jnp.float32)         # (chunk, bd)
        dt = dt_ref[0, rows, :].astype(jnp.float32)
        bm = b_ref[0, rows, :].astype(jnp.float32)        # (chunk, N)
        cm = c_ref[0, rows, :].astype(jnp.float32)
        dt_cols, dx_cols = dt.T, (dt * x).T               # (bd, chunk)
        ys = []
        for t in range(chunk):
            a_bar = jnp.exp(dt_cols[:, t:t + 1] * a)      # (bd, N)
            h = a_bar * h + dx_cols[:, t:t + 1] * bm[t:t + 1]
            ys.append((h * cm[t:t + 1]).sum(axis=1, keepdims=True))
        y_ref[0, rows, :] = jnp.concatenate(ys, axis=1).T.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, block_l // chunk, step, h_scr[...])

    @pl.when(il == num_l_blocks - 1)
    def _finish():
        hout_ref[0] = h_scr[...].astype(hout_ref.dtype)


def ssm_scan(x, dt, a, bmat, cmat, *, block_l: int = 64,
             block_d: int = 128, interpret: bool = False):
    """x, dt: (B, L, D); a: (D, N); bmat, cmat: (B, L, N).

    Returns (y (B, L, D) fp32, h_last (B, D, N) fp32)."""
    bsz, l, d = x.shape
    n = a.shape[1]
    block_l = min(block_l, l)
    block_d = min(block_d, d)
    if l % block_l or d % block_d:
        raise ValueError("L, D must divide block sizes")
    nl, nd = l // block_l, d // block_d

    kernel = functools.partial(_ssm_kernel, block_l=block_l,
                               num_l_blocks=nl)
    return pl.pallas_call(
        kernel,
        grid=(bsz, nd, nl),
        in_specs=[
            pl.BlockSpec((1, block_l, block_d),
                         lambda bi, di, li: (bi, li, di)),      # x
            pl.BlockSpec((1, block_l, block_d),
                         lambda bi, di, li: (bi, li, di)),      # dt
            pl.BlockSpec((block_d, n),
                         lambda bi, di, li: (di, 0)),           # a
            pl.BlockSpec((1, block_l, n),
                         lambda bi, di, li: (bi, li, 0)),       # B
            pl.BlockSpec((1, block_l, n),
                         lambda bi, di, li: (bi, li, 0)),       # C
        ],
        out_specs=[
            pl.BlockSpec((1, block_l, block_d),
                         lambda bi, di, li: (bi, li, di)),      # y
            pl.BlockSpec((1, block_d, n),
                         lambda bi, di, li: (bi, di, 0)),       # h_last
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, l, d), jnp.float32),
            jax.ShapeDtypeStruct((bsz, d, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, a, bmat, cmat)
