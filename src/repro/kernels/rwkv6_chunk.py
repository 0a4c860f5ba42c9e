"""Pallas TPU kernel: chunk-parallel RWKV6 (WKV) recurrence.

Grid: (batch, heads, n_chunks) with the chunk dimension innermost; the
(dk x dv) state matrix lives in VMEM scratch and carries across chunk
iterations — HBM traffic is one pass over r/k/v/w plus the y output, while
the within-chunk math is dense (C x C and C x d matmuls on the MXU), i.e.
the same matmul form as kernels/chunked.wkv6_chunked (the jnp oracle-adjacent
implementation); ref.wkv6_ref is the semantic ground truth.

Stability contract is shared with chunked.py: |log w| * C < ~80 (the models
clamp log-decay; default C=64..128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_EXACT = jax.lax.Precision.HIGHEST


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
                state_scr, *, chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)          # (C, dk)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)          # (C, dv)
    w = w_ref[0, 0].astype(jnp.float32)          # (C, dk) decay in (0,1)
    u = u_ref[0].astype(jnp.float32)             # (1, dk)

    logw = jnp.log(w)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # prefix sum as a lower-triangular matmul (Mosaic has no cumsum)
    tril = jnp.where(jj <= ii, 1.0, 0.0)
    cum = jax.lax.dot_general(tril, logw, (((1,), (0,)), ((), ())),
                              precision=_EXACT)   # cum_{i+1}
    cum_in = cum - logw                          # cum_i
    cum_last = cum[-1:, :]                       # (1, dk)

    q_dec = r * jnp.exp(cum_in)
    k_dec = k * jnp.exp(-cum)
    k_rem = k * jnp.exp(cum_last - cum)

    scores = jax.lax.dot_general(q_dec, k_dec, (((1,), (1,)), ((), ())))
    scores = jnp.where(jj < ii, scores, 0.0)     # strict lower triangle
    bonus = jnp.sum(r * (u * k), axis=-1)          # (C,)
    scores = scores + jnp.where(jj == ii, bonus[:, None], 0.0)

    S = state_scr[...]                           # (dk, dv)
    y = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())))
    y = y + jax.lax.dot_general(q_dec, S, (((1,), (0,)), ((), ())))
    y_ref[0, 0] = y.astype(y_ref.dtype)

    S_new = (jnp.exp(cum_last).T * S
             + jax.lax.dot_general(k_rem, v, (((0,), (0,)), ((), ()))))
    state_scr[...] = S_new

    @pl.when(ic == n_chunks - 1)
    def _final():
        sout_ref[0, 0] = S_new.astype(sout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_pallas(r, k, v, w, u, initial_state=None, chunk: int = 64,
                interpret: bool = False):
    """Same semantics as ref.wkv6_ref. r,k,w: (B,S,H,dk); v: (B,S,H,dv)."""
    B, S, H, dk = r.shape
    dv = v.shape[-1]
    assert S % chunk == 0
    n_chunks = S // chunk
    if initial_state is None:
        initial_state = jnp.zeros((B, H, dk, dv), jnp.float32)

    # (B, H, S, d) layout for chunk-blocked access
    rt, kt, wt = (x.transpose(0, 2, 1, 3) for x in (r, k, w))
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_wkv_kernel, chunk=chunk, n_chunks=n_chunks)
    y, s_fin = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dk), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, dk), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, dv), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, dk), lambda b, h, ic: (b, h, ic, 0)),
            # (1, 1, dk) of (H, 1, dk): the last two block dims equal the
            # array's, which the TPU tiling rule accepts for any dk
            pl.BlockSpec((1, 1, dk), lambda b, h, ic: (h, 0, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, dv), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, dv), jnp.float32),
            jax.ShapeDtypeStruct((B, H, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(rt, kt, vt, wt, u.reshape(H, 1, dk), initial_state)
    return y.transpose(0, 2, 1, 3), s_fin
