"""Shared model components: norms, rotary embeddings, attention, MLPs.

All functions are pure JAX (jnp + lax) so they lower for any backend. The
blocked online-softmax attention is written with ``lax.scan`` so the dry-run
HLO is portable and memory-bounded (no S×S score materialization at 32 K
context). The Pallas TPU kernels in ``repro.kernels`` are not wired in.

Sharding is expressed with ``jax.lax.with_sharding_constraint`` using
PartitionSpecs built from logical axis names; see models/api.py.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


# -- initializers -------------------------------------------------------------

def dense_init(key, shape, dtype=jnp.float32, scale: Optional[float] = None):
    """Normal with std 1/sqrt(fan_in) for a (..., d_in, d_out) weight; the
    leading axes are stacked layers or experts, not inputs."""
    fan_in = shape[-2] if len(shape) >= 2 else 1
    scale = scale if scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# -- norms ---------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * lax.rsqrt(var + eps)
    return (out * weight).astype(dt)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mu) * lax.rsqrt(var + eps)
    return (out * weight + bias).astype(dt)


# -- rotary embeddings -----------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10000.0) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                      # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, sections: Tuple[int, ...],
                theta: float = 10000.0) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL): head_dim/2 split into (t, h, w) sections,
    each rotated by its own position stream. positions: (B, S, 3)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                      # (D/2,)
    # build a per-frequency position by selecting the section's stream
    sec_id = []
    for i, s in enumerate(sections):
        sec_id += [i] * s
    sec_id = jnp.array(sec_id, dtype=jnp.int32)       # (D/2,)
    pos = positions.astype(jnp.float32)[..., sec_id]  # (B,S,D/2)
    angles = pos * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -- attention --------------------------------------------------------------------

NEG_INF = -1e30


def repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    """(B, S, Hkv, D) -> (B, S, Hkv*groups, D) for GQA."""
    if groups == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, groups, d)
                            ).reshape(b, s, h * groups, d)


def attention_reference(q, k, v, causal: bool = True,
                        q_offset: int = 0) -> jax.Array:
    """O(S^2)-memory oracle. q: (B,Sq,H,D), k/v: (B,Skv,H,D)."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        qi = jnp.arange(sq)[:, None] + q_offset
        ki = jnp.arange(skv)[None, :]
        scores = jnp.where((ki <= qi)[None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def flash_attention_jnp(q, k, v, causal: bool = True, q_chunk: int = 512,
                        kv_chunk: int = 1024, q_offset: int = 0,
                        unroll: bool = False) -> jax.Array:
    """Blocked online-softmax attention in pure jnp (FlashAttention algorithm).

    GQA-native: q has Hq heads, k/v have Hkv heads with Hq = G*Hkv; KV is
    never materialized at Hq width. Memory is O(Sq*D + q_chunk*kv_chunk)
    instead of O(Sq*Skv). ``unroll=True`` inlines the chunk loops so XLA
    ``cost_analysis`` counts every iteration (dry-run exactness; see
    EXPERIMENTS.md §Roofline).
    q: (B, Sq, Hq, D); k/v: (B, Skv, Hkv, D).
    """
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, k.shape[1])
    skv = k.shape[1]
    n_q = -(-sq // q_chunk)
    n_kv = -(-skv // kv_chunk)
    pq = n_q * q_chunk - sq
    pk = n_kv * kv_chunk - skv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))

    # (nq, B, Hkv, G, cq, D) / (nkv, B, Hkv, ck, D)
    qc = q.reshape(b, n_q, q_chunk, hkv, g, d).transpose(1, 0, 3, 4, 2, 5)
    kc = k.reshape(b, n_kv, kv_chunk, hkv, d).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, n_kv, kv_chunk, hkv, d).transpose(1, 0, 3, 2, 4)

    def q_step(_, qi_and_idx):
        qi, iq = qi_and_idx
        qi32 = qi.astype(jnp.float32)

        def kv_step(carry, kv_and_idx):
            m, l, acc = carry
            (ki, vi), ik = kv_and_idx
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qi32,
                           ki.astype(jnp.float32)) * scale
            if causal:
                qpos = iq * q_chunk + jnp.arange(q_chunk)[:, None] + q_offset
                kpos = ik * kv_chunk + jnp.arange(kv_chunk)[None, :]
                s = jnp.where((kpos <= qpos)[None, None, None], s, NEG_INF)
            if pk:
                kpos2 = ik * kv_chunk + jnp.arange(kv_chunk)
                s = jnp.where((kpos2 < skv)[None, None, None, None], s,
                              NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, vi.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, q_chunk, d), jnp.float32)
        idx = jnp.arange(n_kv)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), ((kc, vc), idx),
                                  unroll=unroll)
        out = acc / jnp.maximum(l, 1e-20)[..., None]
        return None, out

    iqs = jnp.arange(n_q)
    _, outs = lax.scan(q_step, None, (qc, iqs), unroll=unroll)
    # (nq, B, Hkv, G, cq, D) -> (B, S, Hq, D)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, n_q * q_chunk, hq, d)
    return out[:, :sq].astype(q.dtype)


def decode_attention_jnp(q, k_cache, v_cache, cache_len) -> jax.Array:
    """Single-token decode against a KV cache with a length mask. GQA-native.

    q: (B, 1, Hq, D); caches: (B, Smax, Hkv, D); cache_len: int32 scalar.
    """
    return decode_attention_positions_last(
        q, jnp.moveaxis(k_cache, 1, 3), jnp.moveaxis(v_cache, 1, 3), cache_len)


def decode_attention_positions_last(q, k_cache, v_cache,
                                    cache_len) -> jax.Array:
    """``decode_attention_jnp`` on caches (B, Hkv, D, Smax), positions last:
    the layout of DecoderLM's decode cache, which the TPU's dots read as it
    is stored. cache_len: int32 scalar or (B,)."""
    b, _, hq, d = q.shape
    hkv, smax = k_cache.shape[1], k_cache.shape[3]
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(b, 1, hkv, g, d)
    s = jnp.einsum("bqhgd,bhdk->bhgqk", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * scale
    pos = jnp.arange(smax)
    mask = pos[None, :] < jnp.reshape(cache_len, (-1, 1))      # (B, Smax)
    s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bhdk->bqhgd", w, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, hq, d).astype(q.dtype)


def with_row(cache, row, pos) -> jax.Array:
    """``cache`` (..., B, Hkv, D, Smax), positions last, with ``row``
    (..., B, Hkv, D) at position ``pos`` (scalar or (B,); a position past the
    end writes nothing). A select: inside the layer scan XLA fuses it into
    the attention's reads of the cache, so nothing is written there. Over
    a whole cache it reads and writes every element."""
    hot = jnp.arange(cache.shape[-1]) == jnp.reshape(pos, (-1, 1))
    return jnp.where(hot[:, None, None, :], row[..., None], cache)


# -- MLPs --------------------------------------------------------------------------

def swiglu(x, w_gate, w_up, w_down):
    g = jnp.einsum("bsd,df->bsf", x, w_gate)
    u = jnp.einsum("bsd,df->bsf", x, w_up)
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, w_down)


def gelu_mlp(x, w_up, b_up, w_down, b_down):
    h = jnp.einsum("bsd,df->bsf", x, w_up) + b_up
    out = jnp.einsum("bsf,fd->bsd", jax.nn.gelu(h).astype(x.dtype), w_down)
    return (out + b_down).astype(x.dtype)
