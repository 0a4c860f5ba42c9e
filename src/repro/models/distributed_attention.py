"""Distributed flash-decode over a sequence-sharded KV cache (shard_map).

When a model's KV heads are too few to shard (GQA kv < mesh model-axis), the
decode KV cache is sharded along the *sequence* dimension instead. Left to
GSPMD, the compiled HLO all-gathers the entire cache every step (hundreds of
GB on the wire per token — see EXPERIMENTS.md §Perf HC2 baseline). This
module replaces that with the explicit distributed flash-decode:

  * each model-axis shard holds a contiguous cache slice and the q heads it
    owns; it computes a *partial* softmax (m, l, acc) over its slice, with
    the new token's K/V read at position ``cache_len`` by the shard that
    owns it (a select, nothing written);
  * partials merge with a max/sum-exp reduction: two tiny psums of
    O(B x H_local x head_dim) — kilobytes instead of the cache.

Caches are (B, Hkv, D, Smax) a layer, positions last (see
DecoderLM.cache_specs), sharded on Smax. The new rows are written after the
layer stack (``DecoderLM._stack``), by a select each shard applies to its own
slice.
Requires n_q_heads % model_axis_size == 0.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.layers import with_row


NEG_INF = -1e30


def decode_attention_seq_sharded(q, k_cache, v_cache, k_new, v_new,
                                 cache_len, *, model_axis: str,
                                 data_axes: tuple):
    """q: (B,1,Hq,D); caches: (B,Hkv,D,Smax) seq-sharded over model_axis;
    k_new/v_new: (B,1,Hkv,D), attended to at position ``cache_len``.
    Returns o (B,1,Hq,D); the caches are not written."""
    mesh = jax.sharding.get_abstract_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    b, _, hq, d = q.shape
    hkv = k_cache.shape[1]
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    dax = tuple(a for a in data_axes if a in sizes)
    bspec = dax if (b > 1 and dax) else None

    def per_shard(q_l, ck_l, cv_l, kn_l, vn_l, clen):
        # q is REPLICATED across the model axis: every shard computes all
        # heads over ITS sequence slice; the psum merge below combines the
        # per-slice partial softmaxes (flash-decode split-KV semantics).
        s_loc = ck_l.shape[3]
        start = lax.axis_index(model_axis) * s_loc
        pos = start + jnp.arange(s_loc)
        # -- the new token read where it will be written (owner shard) -------
        ck_l = with_row(ck_l, kn_l[:, 0], clen - start)
        cv_l = with_row(cv_l, vn_l[:, 0], clen - start)
        # -- local partial flash-decode over my cache slice (GQA-native) -----
        qg = q_l.reshape(b_l := q_l.shape[0], 1, hkv, g, d)
        s = jnp.einsum("bqhgd,bhdk->bhgqk", qg.astype(jnp.float32),
                       ck_l.astype(jnp.float32)) * scale
        mask = pos[None, :] < jnp.reshape(clen + 1, (-1, 1))
        s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
        m_loc = s.max(axis=-1)                           # (B, hkv, g, 1)
        p = jnp.exp(s - m_loc[..., None])
        l_loc = p.sum(axis=-1)
        acc = jnp.einsum("bhgqk,bhdk->bhgqd", p, cv_l.astype(jnp.float32))
        # -- merge partials across the model axis (tiny collectives) ---------
        m_glob = lax.pmax(m_loc, model_axis)
        corr = jnp.exp(m_loc - m_glob)
        l_glob = lax.psum(l_loc * corr, model_axis)
        acc_glob = lax.psum(acc * corr[..., None], model_axis)
        out = acc_glob / jnp.maximum(l_glob, 1e-20)[..., None]
        # (B, hkv, g, 1, D) -> (B, 1, Hq, D)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b_l, 1, hq, d)
        return out.astype(q_l.dtype)

    # replication checking is off: the psum merge above is manual
    return jax.shard_map(
        per_shard, mesh=mesh, check_vma=False,
        in_specs=(P(bspec, None, None, None),            # q: replicated over m
                  P(bspec, None, None, model_axis),      # caches: seq sharded
                  P(bspec, None, None, model_axis),
                  P(bspec, None, None, None),            # new K/V replicated
                  P(bspec, None, None, None),
                  P()),
        out_specs=P(bspec, None, None, None),
    )(q, k_cache, v_cache, k_new, v_new, jnp.asarray(cache_len, jnp.int32))

