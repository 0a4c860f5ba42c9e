"""Pallas TPU kernel: write each slot's new KV rows into a stacked cache.

The decode cache is ``(L, B, Hkv, D, Smax)``, positions last. After the layer
scan each slot ``b`` has one new row per layer, ``(L, Hkv, D)``, to go in at
position ``pos[b]``. The output aliases the cache, so with the cache donated
the kernel touches only the blocks it visits: for each slot, the aligned
window of 128 positions (one lane tile) that holds ``pos[b]``, a few layers
at a time. Each block is read, the row is selected into its lane, and the
block is written back whole.

Both XLA forms are slower on a v5e at 16 slots x 2048: a one-position
``dynamic_update_slice`` a slot is a masked write into every tile it
touches, and a 128-position ``dynamic_slice``/select/``dynamic_update_slice``
a slot runs as 16 separate in-place fusions.

A position outside the cache selects nothing: its block (the first or the
last window) is written back as it was, as ``layers.with_row`` leaves it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WINDOW = 128          # positions a block: one lane tile
BLOCK_BYTES = 1 << 20  # at most this much cache a block


def _kernel(win_ref, pos_ref, row_ref, cache_ref, out_ref):
    b = pl.program_id(0)
    at = win_ref[b] * WINDOW + lax.broadcasted_iota(jnp.int32,
                                                    out_ref.shape, 4)
    # the row's D moves from lanes to sublanes, to broadcast along the window
    row = row_ref[...].astype(jnp.float32)[..., None].astype(out_ref.dtype)
    out_ref[...] = jnp.where(at == pos_ref[b], row, cache_ref[...])


def write_rows_pallas(cache, rows, pos, interpret: bool = False):
    """``cache`` (L, B, Hkv, D, Smax) with ``rows`` (L, B, Hkv, D) at
    position ``pos`` (scalar or (B,)); ``Smax`` a multiple of 128. Equal to
    ``layers.with_row`` bit for bit."""
    nl, b, h, d, smax = cache.shape
    assert smax % WINDOW == 0, smax
    item = cache.dtype.itemsize
    lb = next(k for k in (8, 4, 2, 1)
              if nl % k == 0 and k * h * d * WINDOW * item <= BLOCK_BYTES
              or k == 1)
    pos = jnp.broadcast_to(pos, (b,)).astype(jnp.int32)
    win = jnp.clip(pos // WINDOW, 0, smax // WINDOW - 1)
    block = pl.BlockSpec((lb, 1, h, d, WINDOW),
                         lambda i, l, win, pos: (l, i, 0, 0, win[i]))
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, nl // lb),
            in_specs=[pl.BlockSpec((lb, 1, h, d),
                                   lambda i, l, win, pos: (l, i, 0, 0)),
                      block],
            out_specs=block),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(win, pos, rows.astype(cache.dtype), cache)
