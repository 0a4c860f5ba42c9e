"""Decoder-only transformer LM covering the dense / moe / vlm families.

Design notes:
  * params for the repeated blocks are stacked on a leading L axis so the
    layer stack can run as ``lax.scan`` (fast compile) or unrolled (exact
    HLO cost analysis for the dry-run), selected by RunConfig.layer_mode;
  * sharding is expressed as PartitionSpec trees (param_pspecs/input_pspecs)
    consumed by pjit at the launcher level, plus with_sharding_constraint on
    activations;
  * attention: blocked flash (jnp) for train/prefill, masked dense for
    single-token decode; GQA via KV-head repetition *after* cache update so
    the KV cache stays at n_kv_heads;
  * the decode KV cache is ``[L, B, Hkv, D, Smax]``, positions last: the
    TPU's own layout for it, which the attention's dots read with no
    relayout. The layer scan reads each layer's cache in place, with the new
    row selected in, and returns only the rows; after the scan, on one
    TPU, a Pallas kernel writes each slot's rows in place into the cache,
    which the serving executable takes donated (serving/exec_cache.py).
    Undonated, XLA copies the cache before that write. On a mesh of more
    than one device, and off the TPU, one select over the whole cache
    writes them.
  * MoE: expert-parallel sorted dispatch under shard_map (see moe.py).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec
from repro.kernels.kv_write import WINDOW, write_rows_pallas
from repro.models import layers as L
from repro.models.api import RunConfig
from repro.models.sharding import constrain, mesh_axis_sizes
from repro.models.moe import moe_ffn, moe_param_specs, moe_param_pspecs, \
    init_moe_params


def _dt(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _write_rows(cache, rows, pos):
    """``cache`` (L, B, Hkv, D, Smax) with each slot's new rows (L, B, Hkv,
    D) at its position ``pos``. On one TPU a Pallas kernel writes them in
    place (kernels/kv_write.py). Off the TPU, on a mesh of more than one
    device (XLA cannot partition a Pallas call) and for a length that is
    no multiple of 128, a select over the whole cache writes them."""
    if cache.shape[-1] % WINDOW or any(
            n > 1 for n in mesh_axis_sizes().values()):
        return L.with_row(cache, rows, pos)
    return lax.platform_dependent(cache, rows, pos, tpu=write_rows_pallas,
                                  default=L.with_row)


class DecoderLM:
    # "reference" swaps the blocked prefill attention for the O(S^2) oracle
    ATTN_IMPLS = ("jnp", "reference")

    def __init__(self, cfg: ArchConfig, run_cfg: RunConfig):
        self.cfg = cfg
        self.run = run_cfg

    # ------------------------------------------------------------------ params
    def _layer_shapes(self) -> Dict[str, Tuple[tuple, Any]]:
        cfg = self.cfg
        d, hd = cfg.d_model, cfg.resolved_head_dim
        hq, hkv, f = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff
        dt = _dt(cfg)
        shapes = {
            "ln1": ((d,), jnp.float32),
            "ln2": ((d,), jnp.float32),
            "wq": ((d, hq * hd), dt),
            "wk": ((d, hkv * hd), dt),
            "wv": ((d, hkv * hd), dt),
            "wo": ((hq * hd, d), dt),
        }
        if cfg.qk_norm:
            shapes["q_norm"] = ((hd,), jnp.float32)
            shapes["k_norm"] = ((hd,), jnp.float32)
        if cfg.moe is None:
            if cfg.mlp == "swiglu":
                shapes.update({
                    "w_gate": ((d, f), dt),
                    "w_up": ((d, f), dt),
                    "w_down": ((f, d), dt),
                })
            else:
                shapes.update({
                    "w_up": ((d, f), dt),
                    "b_up": ((f,), jnp.float32),
                    "w_down": ((f, d), dt),
                    "b_down": ((d,), jnp.float32),
                })
        return shapes

    def param_specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        dt = _dt(cfg)
        Lx = cfg.n_layers
        layers = {k: jax.ShapeDtypeStruct((Lx,) + s, d)
                  for k, (s, d) in self._layer_shapes().items()}
        if cfg.moe is not None:
            layers.update(moe_param_specs(cfg, Lx))
        out = {
            "embed": jax.ShapeDtypeStruct((cfg.vocab, cfg.d_model), dt),
            "final_norm": jax.ShapeDtypeStruct((cfg.d_model,), jnp.float32),
            "layers": layers,
        }
        if not cfg.tie_embeddings:
            out["lm_head"] = jax.ShapeDtypeStruct((cfg.d_model, cfg.vocab), dt)
        return out

    def param_pspecs(self) -> Dict[str, Any]:
        cfg, m = self.cfg, self.run.model_axis
        layers = {
            "ln1": P(None, None), "ln2": P(None, None),
            "wq": P(None, None, m), "wo": P(None, m, None),
        }
        # KV projections: shard heads on the model axis only when there are
        # enough KV heads; MQA/GQA-with-few-heads replicates KV (cheap).
        kv_spec = P(None, None, m) if cfg.n_kv_heads >= 16 else P(None, None, None)
        layers["wk"] = kv_spec
        layers["wv"] = kv_spec
        if cfg.qk_norm:
            layers["q_norm"] = P(None, None)
            layers["k_norm"] = P(None, None)
        if cfg.moe is None:
            if cfg.mlp == "swiglu":
                layers.update({"w_gate": P(None, None, m),
                               "w_up": P(None, None, m),
                               "w_down": P(None, m, None)})
            else:
                layers.update({"w_up": P(None, None, m), "b_up": P(None, m),
                               "w_down": P(None, m, None),
                               "b_down": P(None, None)})
        else:
            layers.update(moe_param_pspecs(
                cfg, m,
                fsdp_axes=(self.run.data_axes if self.run.fsdp_experts
                           else None)))
        out = {
            "embed": P(m, None),
            "final_norm": P(None),
            "layers": layers,
        }
        if not cfg.tie_embeddings:
            out["lm_head"] = P(None, m)
        return out

    def init_params(self, rng) -> Dict[str, Any]:
        cfg = self.cfg
        dt = _dt(cfg)
        keys = jax.random.split(rng, 8)
        Lx = cfg.n_layers
        layers = {}
        for i, (k, (shape, d)) in enumerate(self._layer_shapes().items()):
            if k.startswith("ln") or k.endswith("norm"):
                layers[k] = jnp.ones((Lx,) + shape, d)
            elif k.startswith("b_"):
                layers[k] = jnp.zeros((Lx,) + shape, d)
            else:
                key = jax.random.fold_in(keys[0], i)
                layers[k] = L.dense_init(key, (Lx,) + shape, d)
        if cfg.moe is not None:
            layers.update(init_moe_params(cfg, keys[1], Lx))
        out = {
            "embed": L.dense_init(keys[2], (cfg.vocab, cfg.d_model), dt,
                                  scale=0.02),
            "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
            "layers": layers,
        }
        if not cfg.tie_embeddings:
            out["lm_head"] = L.dense_init(keys[3], (cfg.d_model, cfg.vocab),
                                          dt)
        return out

    # ------------------------------------------------------------------ inputs
    def input_specs(self, shape: ShapeSpec) -> Dict[str, Any]:
        b, s = shape.global_batch, shape.seq_len
        if shape.kind == "train":
            return {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
                    "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        if shape.kind == "prefill":
            return {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        # decode: one new token against a cache of length s
        return {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
                "cache_len": jax.ShapeDtypeStruct((), jnp.int32)}

    def input_pspecs(self, shape: ShapeSpec) -> Dict[str, Any]:
        dax = self.run.data_axes if shape.global_batch > 1 else None
        if shape.kind == "train":
            return {"tokens": P(dax, None), "labels": P(dax, None)}
        if shape.kind == "prefill":
            return {"tokens": P(dax, None)}
        return {"tokens": P(dax, None), "cache_len": P()}

    def cache_specs(self, shape: ShapeSpec) -> Dict[str, Any]:
        cfg = self.cfg
        b, smax = shape.global_batch, shape.seq_len
        hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        dt = _dt(cfg)
        return {
            "k": jax.ShapeDtypeStruct((cfg.n_layers, b, hkv, hd, smax), dt),
            "v": jax.ShapeDtypeStruct((cfg.n_layers, b, hkv, hd, smax), dt),
        }

    def cache_pspecs(self, shape: ShapeSpec) -> Dict[str, Any]:
        dax = self.run.data_axes if shape.global_batch > 1 else None
        cfg = self.cfg
        m = self.run.model_axis
        if cfg.n_kv_heads >= 16:
            kv = P(None, dax, m, None, None)     # shard KV heads
        else:
            kv = P(None, dax, None, None, m)     # shard cache sequence
        return {"k": kv, "v": kv}

    def init_cache(self, shape: ShapeSpec, batch: Optional[int] = None):
        cfg = self.cfg
        b = batch or shape.global_batch
        hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        dt = _dt(cfg)
        dims = (cfg.n_layers, b, hkv, hd, shape.seq_len)
        # two buffers: a call that donates the cache cannot take one twice
        return {"k": jnp.zeros(dims, dt), "v": jnp.zeros(dims, dt)}

    # ------------------------------------------------------------------ blocks
    def _positions(self, tokens, offset=0):
        b, s = tokens.shape
        if hasattr(offset, "ndim") and getattr(offset, "ndim", 0) == 1:
            offset = offset[:, None]               # per-slot offsets (B, 1)
        pos = jnp.arange(s, dtype=jnp.int32)[None, :] + offset
        pos = jnp.broadcast_to(pos, (b, s))
        if self.cfg.mrope_sections is not None:
            return jnp.stack([pos, pos, pos], axis=-1)   # text-only stream
        return pos

    def _rope(self, x, pos):
        cfg = self.cfg
        if cfg.mrope_sections is not None:
            return L.apply_mrope(x, pos, cfg.mrope_sections, cfg.rope_theta)
        return L.apply_rope(x, pos, cfg.rope_theta)

    def _attn(self, w, x, pos, cache_kv=None, cache_len=None):
        """Returns (attn_out, (k, v)): this layer's new keys and values,
        (B, S, Hkv, D). In decode ``cache_kv`` is this layer's (k, v) cache,
        (B, Hkv, D, Smax), read in place; the new row, (B, Hkv, D), is
        attended to where ``_stack`` writes it after the layer scan."""
        cfg, run = self.cfg, self.run
        b, s, d = x.shape
        hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
        h = L.rms_norm(x, w["ln1"]) if cfg.norm == "rmsnorm" else \
            L.layer_norm(x, w["ln1"], jnp.zeros_like(w["ln1"]))
        q = jnp.einsum("bsd,dh->bsh", h, w["wq"]).reshape(b, s, hq, hd)
        k = jnp.einsum("bsd,dh->bsh", h, w["wk"]).reshape(b, s, hkv, hd)
        v = jnp.einsum("bsd,dh->bsh", h, w["wv"]).reshape(b, s, hkv, hd)
        if cfg.qk_norm:
            q = L.rms_norm(q, w["q_norm"])
            k = L.rms_norm(k, w["k_norm"])
        q = self._rope(q, pos)
        k = self._rope(k, pos)
        if cache_kv is None:
            if run.attn_impl == "reference":
                o = L.attention_reference(q, L.repeat_kv(k, hq // hkv),
                                          L.repeat_kv(v, hq // hkv),
                                          causal=True)
            else:
                o = L.flash_attention_jnp(q, k, v, causal=True,
                                          q_chunk=run.q_chunk,
                                          kv_chunk=run.kv_chunk,
                                          unroll=run.attn_unroll)
        else:
            ck, cv = cache_kv
            if self._use_sharded_decode() and \
                    getattr(cache_len, "ndim", 0) == 0:
                from repro.models.distributed_attention import \
                    decode_attention_seq_sharded
                o = decode_attention_seq_sharded(
                    q, ck, cv, k, v, cache_len,
                    model_axis=self.run.model_axis,
                    data_axes=self.run.data_axes)
            else:
                with jax.named_scope("kv_write"):
                    ck = L.with_row(ck, k[:, 0], cache_len)
                    cv = L.with_row(cv, v[:, 0], cache_len)
                o = L.decode_attention_positions_last(q, ck, cv,
                                                     cache_len + 1)
            k, v = k[:, 0], v[:, 0]
        o = jnp.einsum("bsh,hd->bsd", o.reshape(b, s, hq * hd), w["wo"])
        return o, (k, v)

    def _use_sharded_decode(self) -> bool:
        """HC2: explicit distributed flash-decode when the cache is
        sequence-sharded (few KV heads) and q heads divide the model axis."""
        if not self.run.sharded_decode or self.cfg.n_kv_heads >= 16:
            return False
        from repro.models.sharding import mesh_axis_sizes
        return mesh_axis_sizes().get(self.run.model_axis, 1) > 1

    def _mlp(self, w, x):
        cfg = self.cfg
        h = L.rms_norm(x, w["ln2"]) if cfg.norm == "rmsnorm" else \
            L.layer_norm(x, w["ln2"], jnp.zeros_like(w["ln2"]))
        if cfg.moe is not None:
            return moe_ffn(cfg, self.run, w, h)
        if cfg.mlp == "swiglu":
            return L.swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
        return L.gelu_mlp(h, w["w_up"], w["b_up"], w["w_down"], w["b_down"])

    def _block(self, w, x, pos, cache_kv=None, cache_len=None):
        dax, m = self.run.data_axes, self.run.model_axis
        with jax.named_scope("attn"):
            o, new_kv = self._attn(w, x, pos, cache_kv, cache_len)
        x = x + o
        x = constrain(x, P(dax, None, None))
        with jax.named_scope("mlp"):
            x = x + self._mlp(w, x)
        x = constrain(x, P(dax, None, None))
        return x, new_kv

    def _stack(self, params, x, pos, cache=None, cache_len=None):
        """Run the layer stack; returns (x, new_cache or None).

        In decode each layer reads its cache in place and gives back only its
        new (k, v) rows; after the scan ``_write_rows`` writes each slot's
        rows at its length, in place on one TPU, where a select reads and
        writes the whole cache. Passed through the scan's ``xs`` and
        ``ys``, the cache would be copied out and back a layer at a time on
        every step; written into the scan's carry layer by layer, XLA relays
        the carried cache positions-major for the write, and each layer back
        for the dots."""
        layers = params["layers"]
        if cache is None:
            block = self._block
            if self.run.remat:
                if self.run.remat_policy == "dots":
                    block = jax.checkpoint(
                        block, policy=jax.checkpoint_policies
                        .dots_with_no_batch_dims_saveable)
                else:
                    block = jax.checkpoint(block)

            def body(x, w):
                return block(w, x, pos)[0], None
            carry = x
        else:
            def body(carry, w):
                i, x = carry
                kv = (cache["k"][i], cache["v"][i])
                x, rows = self._block(w, x, pos, kv, cache_len)
                return (i + 1, x), rows
            carry = (0, x)

        if self.run.layer_mode == "scan":
            carry, rows = lax.scan(body, carry, layers)
        else:
            rows = []
            for i in range(self.cfg.n_layers):
                carry, r = body(carry, jax.tree.map(lambda a: a[i], layers))
                rows.append(r)
            rows = jax.tree.map(lambda *r: jnp.stack(r), *rows)
        if cache is None:
            return carry, None
        with jax.named_scope("kv_write"):
            return carry[1], {n: _write_rows(cache[n], r, cache_len)
                              for n, r in zip(("k", "v"), rows)}

    # ------------------------------------------------------------------ steps
    def forward(self, params, batch) -> jax.Array:
        """Training/prefill forward -> logits (B, S, V)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = jnp.take(params["embed"], tokens, axis=0).astype(_dt(cfg))
        x = constrain(x, P(self.run.data_axes, None, None))
        pos = self._positions(tokens)
        x, _ = self._stack(params, x, pos)
        x = L.rms_norm(x, params["final_norm"]) if cfg.norm == "rmsnorm" else \
            L.layer_norm(x, params["final_norm"],
                         jnp.zeros_like(params["final_norm"]))
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T
        return jnp.einsum("bsd,dv->bsv", x, head)

    def loss_fn(self, params, batch) -> jax.Array:
        logits = self.forward(params, batch)
        labels = batch["labels"]
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None],
                                   axis=-1)[..., 0]
        return jnp.mean(lse - gold)

    def decode_step(self, params, cache, batch):
        """One decode step: batch = {tokens (B,1), cache_len ()} -> logits.

        Its parts carry ``jax.named_scope`` names (``embed``, ``attn`` with
        ``kv_write`` inside, ``mlp``, ``final_norm``, ``logits``, and the
        ``kv_write`` after the layer scan that writes the new rows), so that
        a device trace can place each operation of the step."""
        cfg = self.cfg
        tokens, cache_len = batch["tokens"], batch["cache_len"]
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(_dt(cfg))
        pos = self._positions(tokens, offset=cache_len)
        x, new_cache = self._stack(params, x, pos, cache=cache,
                                   cache_len=cache_len)
        with jax.named_scope("final_norm"):
            x = L.rms_norm(x, params["final_norm"]) \
                if cfg.norm == "rmsnorm" else \
                L.layer_norm(x, params["final_norm"],
                             jnp.zeros_like(params["final_norm"]))
        head = params.get("lm_head")
        if head is None:
            head = params["embed"].T
        with jax.named_scope("logits"):
            logits = jnp.einsum("bsd,dv->bsv", x, head)[:, -1]
        return logits, new_cache
