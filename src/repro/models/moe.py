"""Mixture-of-Experts FFN with expert parallelism (kimi-k2, arctic).

Strategy ("sorted EP"): experts are sharded over the ``model`` mesh axis;
token activations are sharded over the data axes and *replicated* across
``model`` (as in tensor-parallel FFN). Each model-shard:

  1. computes router top-k locally (router weights are replicated);
  2. selects the assignments that target ITS experts, packs them into a
     fixed-capacity buffer by sorting (capacity = local_tokens * top_k /
     n_shards * capacity_factor — overflow drops, standard for capacity-based
     MoE);
  3. runs the packed tokens through its local experts with
     ``jax.lax.ragged_dot`` (grouped GEMM, MXU-friendly);
  4. scatter-adds weighted outputs back to token positions;
  5. a ``psum`` over ``model`` combines expert outputs across shards (this
     doubles as the top-k combine) — the same all-reduce a TP FFN needs.

This avoids the O(tokens x experts x capacity) one-hot dispatch tensors that
make dense-dispatch MoE infeasible at 384 experts / 1 M tokens, and keeps
every shape static for the 512-device dry-run.

Implemented with ``shard_map`` over (data-axes x model); inside, plain jnp.
The dense residual branch (arctic) runs as ordinary tensor-parallel swiglu
*outside* the shard_map.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig


def _dt(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def moe_param_specs(cfg: ArchConfig, Lx: int) -> Dict[str, Any]:
    e = cfg.moe
    d, fe = cfg.d_model, e.d_ff_expert
    dt = _dt(cfg)
    out = {
        "router": jax.ShapeDtypeStruct((Lx, d, e.n_experts), jnp.float32),
        "e_gate": jax.ShapeDtypeStruct((Lx, e.n_experts, d, fe), dt),
        "e_up": jax.ShapeDtypeStruct((Lx, e.n_experts, d, fe), dt),
        "e_down": jax.ShapeDtypeStruct((Lx, e.n_experts, fe, d), dt),
    }
    if e.dense_residual_ff:
        fr = e.dense_residual_ff
        out.update({
            "r_gate": jax.ShapeDtypeStruct((Lx, d, fr), dt),
            "r_up": jax.ShapeDtypeStruct((Lx, d, fr), dt),
            "r_down": jax.ShapeDtypeStruct((Lx, fr, d), dt),
        })
    return out


def moe_param_pspecs(cfg: ArchConfig, m: str,
                     fsdp_axes=None) -> Dict[str, Any]:
    """Experts sharded over `m`; with fsdp_axes, the expert FF dim is
    additionally sharded over the data axes (ZeRO-3-style for the 97% of
    kimi-k2's parameters that are experts) and gathered per layer."""
    fa = fsdp_axes
    out = {
        "router": P(None, None, None),
        "e_gate": P(None, m, None, fa),
        "e_up": P(None, m, None, fa),
        "e_down": P(None, m, fa, None),
    }
    if cfg.moe.dense_residual_ff:
        out.update({"r_gate": P(None, None, m), "r_up": P(None, None, m),
                    "r_down": P(None, m, None)})
    return out


def init_moe_params(cfg: ArchConfig, rng, Lx: int) -> Dict[str, Any]:
    specs = moe_param_specs(cfg, Lx)
    out = {}
    for i, (k, s) in enumerate(specs.items()):
        key = jax.random.fold_in(rng, i)
        scale = 0.02 if k == "router" else 1.0 / (s.shape[-2] ** 0.5)
        out[k] = (jax.random.normal(key, s.shape) * scale).astype(s.dtype)
    return out


def _local_moe(cfg: ArchConfig, run_cfg, w, x, *, n_shards: int, shard_id):
    """Per-device MoE computation (runs inside shard_map).

    x: (T, D) local tokens (replicated across the model axis).
    w experts: (E_local, D, Fe). Returns the *partial* output (T, D) which the
    caller psums over the model axis.
    """
    e = cfg.moe
    T, D = x.shape
    E_local = w["e_gate"].shape[0]
    k = e.top_k

    # 1) routing (replicated math — identical on every model shard)
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), w["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs, k)                    # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # 2) my assignments: flatten (T*k,) and pack those targeting my experts
    flat_e = top_e.reshape(-1)                            # (T*k,)
    flat_p = top_p.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T), k)
    first = shard_id * E_local
    mine = (flat_e >= first) & (flat_e < first + E_local)
    local_e = jnp.where(mine, flat_e - first, E_local)    # E_local = overflow
    C = max(int(T * k / n_shards * run_cfg.moe_capacity_factor), k)
    C = min(C, T * k)
    # sort by (local_e) so my assignments come first, grouped by expert
    order = jnp.argsort(local_e)                          # stable
    sel = order[:C]                                       # (C,)
    sel_e = local_e[sel]                                  # (C,) in [0, E_local]
    sel_tok = flat_tok[sel]
    sel_p = jnp.where(sel_e < E_local, flat_p[sel], 0.0)
    group_sizes = jnp.bincount(sel_e, length=E_local + 1)[:E_local]

    xin = x[sel_tok].astype(_dt(cfg))                     # (C, D)
    g = lax.ragged_dot(xin, w["e_gate"], group_sizes)
    u = lax.ragged_dot(xin, w["e_up"], group_sizes)
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
         ).astype(_dt(cfg))
    out = lax.ragged_dot(h, w["e_down"], group_sizes)     # (C, D)
    out = out.astype(jnp.float32) * sel_p[:, None]

    # 4) scatter-add back to token positions
    y = jnp.zeros((T, D), jnp.float32).at[sel_tok].add(out)
    return y


def moe_ffn(cfg: ArchConfig, run_cfg, w, x) -> jax.Array:
    """x: (B, S, D) sharded (data, None, None). Returns same shape/sharding."""
    e = cfg.moe
    m = run_cfg.model_axis
    dax = run_cfg.data_axes
    mesh = jax.sharding.get_abstract_mesh()
    axis_sizes = dict(zip(mesh.axis_names, mesh.axis_sizes)) if mesh.axis_names else {}
    n_shards = axis_sizes.get(m, 1)
    B, S, D = x.shape

    moe_w = {k: w[k] for k in ("router", "e_gate", "e_up", "e_down")}

    if m not in axis_sizes:
        # no mesh context (single-device smoke tests): run the local path
        y = _local_moe(cfg, run_cfg, moe_w, x.reshape(B * S, D),
                       n_shards=1, shard_id=0)
        y = y.reshape(B, S, D).astype(x.dtype)
    else:
        def per_shard(xl, wl):
            # xl: (B_local, S, D); wl experts: (E_local, ...)
            shard_id = lax.axis_index(m) if n_shards > 1 else 0
            if run_cfg.fsdp_experts and dax_present:
                # FSDP: gather the FF-dim weight shards just-in-time
                wl = dict(wl)
                wl["e_gate"] = lax.all_gather(wl["e_gate"], dax_present,
                                              axis=2, tiled=True)
                wl["e_up"] = lax.all_gather(wl["e_up"], dax_present,
                                            axis=2, tiled=True)
                wl["e_down"] = lax.all_gather(wl["e_down"], dax_present,
                                              axis=1, tiled=True)
            T = xl.shape[0] * xl.shape[1]
            y = _local_moe(cfg, run_cfg, wl, xl.reshape(T, D),
                           n_shards=n_shards, shard_id=shard_id)
            y = lax.psum(y, m) if n_shards > 1 else y
            return y.reshape(xl.shape).astype(xl.dtype)

        dax_present = tuple(a for a in dax if a in axis_sizes)
        fsdp = run_cfg.fsdp_experts and dax_present
        fa = dax_present if fsdp else None
        in_specs = (P(dax_present, None, None),
                    {"router": P(None, None), "e_gate": P(m, None, fa),
                     "e_up": P(m, None, fa), "e_down": P(m, fa, None)})
        y = jax.shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                          out_specs=P(dax_present, None, None),
                          check_vma=False)(x, moe_w)

    if e.dense_residual_ff:
        from repro.models.layers import swiglu
        y = y + swiglu(x, w["r_gate"], w["r_up"], w["r_down"])
    return y
