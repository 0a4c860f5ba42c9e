"""Distributed flash-decode over a sequence-sharded KV cache (shard_map).

When a model's KV heads are too few to shard (GQA kv < mesh model-axis), the
decode KV cache is sharded along the *sequence* dimension instead. Left to
GSPMD, the compiled HLO all-gathers the entire cache every step (hundreds of
GB on the wire per token — see EXPERIMENTS.md §Perf HC2 baseline). This
module replaces that with the explicit distributed flash-decode:

  * each model-axis shard holds a contiguous cache slice and the q heads it
    owns; it computes a *partial* softmax (m, l, acc) over its slice;
  * the new token's K/V is written by exactly the shard that owns position
    ``cache_len`` (predicated dynamic-update-slice);
  * partials merge with a max/sum-exp reduction: two tiny psums of
    O(B x H_local x head_dim) — kilobytes instead of the cache.

Requires n_q_heads % model_axis_size == 0.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


NEG_INF = -1e30


def decode_attention_seq_sharded(q, k_cache, v_cache, k_new, v_new,
                                 cache_len, *, model_axis: str,
                                 data_axes: tuple):
    """q: (B,1,Hq,D); caches: (B,Smax,Hkv,D) seq-sharded over model_axis;
    k_new/v_new: (B,1,Hkv,D). Returns (o, ck_updated, cv_updated)."""
    mesh = jax.sharding.get_abstract_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    b, _, hq, d = q.shape
    hkv = k_cache.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    dax = tuple(a for a in data_axes if a in sizes)
    bspec = dax if (b > 1 and dax) else None

    def per_shard(q_l, ck_l, cv_l, kn_l, vn_l, clen):
        # q is REPLICATED across the model axis: every shard computes all
        # heads over ITS sequence slice; the psum merge below combines the
        # per-slice partial softmaxes (flash-decode split-KV semantics).
        i = lax.axis_index(model_axis)
        s_loc = ck_l.shape[1]
        start = i * s_loc
        # -- predicated cache write (the owner shard writes the new token) --
        li = jnp.clip(clen - start, 0, s_loc - 1)
        own = jnp.logical_and(clen >= start, clen < start + s_loc)
        old_k = lax.dynamic_slice(ck_l, (0, li, 0, 0), kn_l.shape)
        old_v = lax.dynamic_slice(cv_l, (0, li, 0, 0), vn_l.shape)
        ck_l = lax.dynamic_update_slice(
            ck_l, jnp.where(own, kn_l, old_k), (0, li, 0, 0))
        cv_l = lax.dynamic_update_slice(
            cv_l, jnp.where(own, vn_l, old_v), (0, li, 0, 0))
        # -- local partial flash-decode over my cache slice (GQA-native) -----
        qg = q_l.reshape(b_l := q_l.shape[0], 1, hkv, g, d)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                       ck_l.astype(jnp.float32)) * scale
        pos = start + jnp.arange(s_loc)
        mask = pos[None, :] < jnp.reshape(clen + 1, (-1, 1))
        s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
        m_loc = s.max(axis=-1)                           # (B, hkv, g, 1)
        p = jnp.exp(s - m_loc[..., None])
        l_loc = p.sum(axis=-1)
        acc = jnp.einsum("bhgqk,bkhd->bhgqd", p, cv_l.astype(jnp.float32))
        # -- merge partials across the model axis (tiny collectives) ---------
        m_glob = lax.pmax(m_loc, model_axis)
        corr = jnp.exp(m_loc - m_glob)
        l_glob = lax.psum(l_loc * corr, model_axis)
        acc_glob = lax.psum(acc * corr[..., None], model_axis)
        out = acc_glob / jnp.maximum(l_glob, 1e-20)[..., None]
        # (B, hkv, g, 1, D) -> (B, 1, Hq, D)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b_l, 1, hq, d)
        return out.astype(q_l.dtype), ck_l, cv_l

    # replication checking is off: the psum merge above is manual
    o, ck, cv = jax.shard_map(
        per_shard, mesh=mesh, check_vma=False,
        in_specs=(P(bspec, None, None, None),            # q: replicated over m
                  P(bspec, model_axis, None, None),      # caches: seq sharded
                  P(bspec, model_axis, None, None),
                  P(bspec, None, None, None),            # new K/V replicated
                  P(bspec, None, None, None),
                  P()),
        out_specs=(P(bspec, None, None, None),
                   P(bspec, model_axis, None, None),
                   P(bspec, model_axis, None, None)),
    )(q, k_cache, v_cache, k_new, v_new,
      jnp.asarray(cache_len, jnp.int32))
    return o, ck, cv
