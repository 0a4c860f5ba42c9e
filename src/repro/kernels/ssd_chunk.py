"""Pallas TPU kernel: chunk-parallel Mamba-2 SSD scan (zamba2 mixer).

Grid: (batch, ssm_heads, n_chunks), chunk innermost; the (P x N) state lives
in VMEM scratch across chunks. Scalar-per-head decay makes the within-chunk
form a masked (C x C) matmul (``scores = (C B^T) * decay``) plus two (C x P/N)
GEMMs — MXU-shaped when C, P, N are multiples of the native tile.
Semantics == ref.ssd_ref (kernel tests sweep shapes/dtypes in interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EXACT = jax.lax.Precision.HIGHEST


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref,
                y_ref, hout_ref, state_scr, *, chunk: int, n_chunks: int):
    h = pl.program_id(1)
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)          # (C, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (C, 1)
    A = a_ref[h]                                  # scalar decay coef (SMEM)
    Bm = b_ref[0, 0].astype(jnp.float32)         # (C, N)
    Cm = c_ref[0, 0].astype(jnp.float32)         # (C, N)
    D = d_ref[h]                                  # scalar (SMEM)

    la = dt * A                                  # (C, 1) log decay
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sums as triangular matmuls (Mosaic has no cumsum),
    # once as a column and once as a row for the pairwise decay
    tril = jnp.where(jj <= ii, 1.0, 0.0)
    cum = jax.lax.dot_general(tril, la, (((1,), (0,)), ((), ())),
                              precision=_EXACT)   # (C, 1)
    cum_row = jax.lax.dot_general(la, tril, (((0,), (1,)), ((), ())),
                                  precision=_EXACT)   # (1, C)
    # the chunk's total log decay as an (1, N) row: a (1, 1) value cannot
    # broadcast over both sublanes and lanes of the (P, N) state
    cum_last = jnp.sum(jnp.broadcast_to(la, Bm.shape), axis=0,
                       keepdims=True)

    xdt = x * dt                                  # (C, P)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # (C, C)
    decay = jnp.exp(jnp.clip(cum - cum_row, -60.0, 0.0))
    scores = jnp.where(jj <= ii, scores * decay, 0.0)

    h_prev = state_scr[...]                      # (P, N)
    y = jax.lax.dot_general(scores, xdt, (((1,), (0,)), ((), ())))   # (C, P)
    q_dec = Cm * jnp.exp(cum)
    y = y + jax.lax.dot_general(q_dec, h_prev, (((1,), (1,)), ((), ())))
    y = y + D * x
    y_ref[0, 0] = y.astype(y_ref.dtype)

    k_rem = Bm * jnp.exp(cum_last - cum)
    h_new = (jnp.exp(cum_last) * h_prev
             + jax.lax.dot_general(xdt, k_rem, (((0,), (0,)), ((), ()))))
    state_scr[...] = h_new

    @pl.when(ic == n_chunks - 1)
    def _final():
        hout_ref[0, 0] = h_new.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(x, dt, A, B, C, D, initial_state=None, chunk: int = 64,
               interpret: bool = False):
    """Same semantics as ref.ssd_ref. x: (b,S,H,P); dt: (b,S,H);
    A,D: (H,); B,C: (b,S,H,N)."""
    b, S, H, Pd = x.shape
    N = B.shape[-1]
    assert S % chunk == 0
    n_chunks = S // chunk
    if initial_state is None:
        initial_state = jnp.zeros((b, H, Pd, N), jnp.float32)

    xt = x.transpose(0, 2, 1, 3)                 # (b, H, S, P)
    dtt = dt.transpose(0, 2, 1)[..., None]       # (b, H, S, 1)
    Bt = B.transpose(0, 2, 1, 3)
    Ct = C.transpose(0, 2, 1, 3)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks)
    y, h_fin = pl.pallas_call(
        kernel,
        grid=(b, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, Pd), lambda b_, h, ic: (b_, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b_, h, ic: (b_, h, ic, 0)),
            # per-head scalars: the whole (H,) vector sits in SMEM and the
            # kernel indexes it by head (a rank-1 VMEM block cannot tile)
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, N), lambda b_, h, ic: (b_, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b_, h, ic: (b_, h, ic, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, Pd, N), lambda b_, h, ic: (b_, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, Pd), lambda b_, h, ic: (b_, h, ic, 0)),
            pl.BlockSpec((1, 1, Pd, N), lambda b_, h, ic: (b_, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, H, S, Pd), jnp.float32),
            jax.ShapeDtypeStruct((b, H, Pd, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Pd, N), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, A.astype(jnp.float32), Bt, Ct, D.astype(jnp.float32),
      initial_state)
    return y.transpose(0, 2, 1, 3), h_fin
