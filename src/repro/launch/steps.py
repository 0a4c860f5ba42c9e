"""Builds jitted, sharded step functions per (arch x shape x mesh).

The three step kinds map to the assigned shape classes:
  * train  -> full train_step (fwd + bwd + AdamW update), params+opt donated;
  * prefill -> last-position logits from a full forward;
  * decode -> one-token serve_step against a donated KV/state cache.
"""
from __future__ import annotations

from dataclasses import replace
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, SHAPES
from repro.configs.base import ArchConfig, ShapeSpec
from repro.models.api import RunConfig, build_model
from repro.models.sharding import filter_spec
from repro.train.optimizer import (adamw_init_specs, adamw_pspecs,
                                   adamw_update)
from repro.train.train_step import make_train_step


def _shard(mesh, spec_tree):
    """PartitionSpec tree -> NamedSharding tree, filtered to mesh axes."""
    def conv(s):
        fs = filter_spec(s)
        return NamedSharding(mesh, fs if fs is not None else s)
    with jax.set_mesh(mesh):
        return jax.tree.map(conv, spec_tree,
                            is_leaf=lambda x: isinstance(x, P))


def default_run_config(mesh, shape: ShapeSpec, **overrides) -> RunConfig:
    dax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    kw = dict(data_axes=dax)
    kw.update(overrides)
    return RunConfig(**kw)


def build_step(arch: str, shape_name: str, mesh,
               run_cfg: Optional[RunConfig] = None, lr: float = 3e-4,
               cfg_override: Optional[ArchConfig] = None):
    """Returns (jitted_fn, example_args (ShapeDtypeStructs), meta)."""
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    shape = SHAPES[shape_name]
    run_cfg = run_cfg or default_run_config(mesh, shape)
    model = build_model(cfg, run_cfg)

    p_specs = model.param_specs()
    p_shard = _shard(mesh, model.param_pspecs())
    in_specs = model.input_specs(shape)
    in_shard = _shard(mesh, model.input_pspecs(shape))
    meta = {"arch": arch, "shape": shape_name, "kind": shape.kind,
            "run_cfg": run_cfg}

    if shape.kind == "train":
        opt_specs = adamw_init_specs(p_specs)
        opt_shard = _shard(mesh, adamw_pspecs(
            model.param_pspecs(), p_specs, use_zero1=run_cfg.use_zero1,
            dax=run_cfg.data_axes))
        step = make_train_step(model, lr=lr)
        rng_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
        fn = jax.jit(step,
                     in_shardings=(p_shard, opt_shard, in_shard, None),
                     out_shardings=(p_shard, opt_shard, None),
                     donate_argnums=(0, 1))
        args = (p_specs, opt_specs, in_specs, rng_spec)
        return fn, args, meta

    if shape.kind == "prefill":
        def prefill_step(params, batch):
            logits = model.forward(params, batch)
            return logits[:, -1, :]      # serving prefill returns next-token logits

        fn = jax.jit(prefill_step, in_shardings=(p_shard, in_shard))
        return fn, (p_specs, in_specs), meta

    # decode
    cache_specs = model.cache_specs(shape)
    cache_shard = _shard(mesh, model.cache_pspecs(shape))

    def decode(params, cache, batch):
        return model.decode_step(params, cache, batch)

    fn = jax.jit(decode,
                 in_shardings=(p_shard, cache_shard, in_shard),
                 out_shardings=(None, cache_shard),
                 donate_argnums=(1,))
    return fn, (p_specs, cache_specs, in_specs), meta
