"""Model factory + run configuration.

``build_model(cfg, run_cfg)`` returns a ``Model`` with a uniform surface:

    init_params(rng)        -> real param pytree (smoke tests / examples)
    param_specs()           -> ShapeDtypeStruct pytree (dry-run, no alloc)
    param_pspecs()          -> PartitionSpec pytree (logical sharding rules)
    input_specs(shape)      -> dict of ShapeDtypeStructs for the step fn
    input_pspecs(shape)     -> matching PartitionSpecs
    train_step              -> (params, opt_state, batch) -> (params, opt_state, metrics)
    forward                 -> (params, batch) -> logits (prefill/train fwd)
    decode_step             -> (params, cache, batch) -> (logits, cache)
    init_cache(shape)       -> cache specs / zeros for decode shapes
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from repro.configs.base import ArchConfig


@dataclass(frozen=True)
class RunConfig:
    """Execution knobs (orthogonal to the architecture)."""

    attn_impl: str = "jnp"        # jnp | reference (see build_model)
    q_chunk: int = 512
    kv_chunk: int = 1024
    layer_mode: str = "scan"      # scan | unroll (unroll => exact HLO costs)
    attn_unroll: bool = False     # inline attention chunk loops (exact costs)
    remat: bool = True            # activation checkpointing per layer
    remat_policy: str = "full"    # full | dots (save matmul outputs)
    sharded_decode: bool = False  # shard_map distributed flash-decode (HC2)
    fsdp_experts: bool = False    # shard expert FF dim over data axes (HC1)
    moe_capacity_factor: float = 1.25
    seq_chunk: int = 256          # rwkv/ssd chunk length
    data_axes: tuple = ("pod", "data")  # batch sharding axes
    model_axis: str = "model"
    use_zero1: bool = False       # shard optimizer state over data axes
    grad_compress: bool = False   # int8 gradient all-reduce (train/compress)
    grad_accum: int = 1


def build_model(cfg: ArchConfig, run_cfg: Optional[RunConfig] = None):
    run_cfg = run_cfg or RunConfig()
    if cfg.family in ("dense", "vlm", "moe"):
        from repro.models.transformer import DecoderLM as cls
    elif cfg.family == "ssm":
        from repro.models.rwkv import RWKV6Model as cls
    elif cfg.family == "hybrid":
        from repro.models.ssm import Zamba2Model as cls
    elif cfg.family == "audio":
        from repro.models.whisper import WhisperModel as cls
    else:
        raise ValueError(f"unknown family {cfg.family}")
    # a value the model does not implement must fail here, not run another
    # attention form under its name
    impls = getattr(cls, "ATTN_IMPLS", ("jnp",))
    if run_cfg.attn_impl not in impls:
        raise ValueError(f"{cfg.name} implements attn_impl {impls}, "
                         f"not {run_cfg.attn_impl!r}")
    return cls(cfg, run_cfg)
