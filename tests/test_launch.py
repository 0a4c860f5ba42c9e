"""Launch-layer tests: mesh construction, sharding specs, step building on a
single-device mesh with reduced configs (the 512-device matrix is exercised
by launch/dryrun.py; see results/dryrun)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import SHAPES, ShapeSpec, applicable_shapes
from repro.launch.hlo_parse import parse_collectives
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import build_step, default_run_config
from repro.models.api import RunConfig, build_model
from repro.models.sharding import filter_spec


def test_applicable_shapes_policy():
    # long_500k only for sub-quadratic archs
    assert "long_500k" in applicable_shapes(get_config("rwkv6-7b"))
    assert "long_500k" in applicable_shapes(get_config("zamba2-2.7b"))
    assert "long_500k" not in applicable_shapes(get_config("qwen3-32b"))
    for arch in ("qwen3-32b", "rwkv6-7b", "whisper-small"):
        shapes = applicable_shapes(get_config(arch))
        assert {"train_4k", "prefill_32k", "decode_32k"} <= set(shapes)


def test_filter_spec_no_mesh():
    assert filter_spec(P("model", None)) is None      # no mesh -> no-op


def test_build_step_reduced_on_local_mesh():
    mesh = make_local_mesh()
    cfg = get_config("qwen3-32b").reduced()
    shape = ShapeSpec("t", 64, 4, "train")
    with jax.set_mesh(mesh):
        run = default_run_config(mesh, shape, q_chunk=16, kv_chunk=16)
        model = build_model(cfg, run)
        # spec trees are structurally consistent
        specs = model.param_specs()
        pspecs = model.param_pspecs()
        assert jax.tree.structure(specs) == jax.tree.structure(
            pspecs, is_leaf=lambda x: isinstance(x, P))
        import repro.train.train_step as ts
        from repro.train.optimizer import adamw_init
        params = model.init_params(jax.random.PRNGKey(0))
        fn = jax.jit(ts.make_train_step(model))
        batch = {"tokens": jnp.ones((4, 64), jnp.int32),
                 "labels": jnp.ones((4, 64), jnp.int32)}
        p2, o2, m = fn(params, adamw_init(params), batch,
                       jax.random.PRNGKey(1))
        assert not bool(jnp.isnan(m["loss"]))


def test_parse_collectives():
    hlo = """
  %ar = bf16[16,1024]{1,0} all-reduce(bf16[16,1024]{1,0} %x), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag.1 = f32[4,256]{1,0} all-gather(f32[1,256]{1,0} %y), replica_groups=[8,4]<=[32], dimensions={0}
  %p = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) collective-permute(...)
  %notacoll = bf16[2,2]{1,0} add(bf16[2,2] %a, bf16[2,2] %b)
"""
    out = parse_collectives(hlo)
    kinds = sorted(c["kind"] for c in out)
    assert kinds == ["all-gather", "all-reduce", "collective-permute"]
    ar = next(c for c in out if c["kind"] == "all-reduce")
    assert ar["bytes"] == 16 * 1024 * 2
    assert ar["group"] == 4
    ag = next(c for c in out if c["kind"] == "all-gather")
    assert ag["bytes"] == 4 * 256 * 4
    assert ag["group"] == 4
    cp = next(c for c in out if c["kind"] == "collective-permute")
    assert cp["bytes"] == 2 * 8 * 8 * 2


def test_roofline_math():
    from repro.launch.roofline import collective_bytes_on_wire, \
        model_flops_per_device
    s = {"all-reduce": {"bytes": 1000, "group": 16},
         "all-gather": {"bytes": 1600, "group": 16}}
    wire = collective_bytes_on_wire(s)
    assert abs(wire - (2 * 1000 * 15 / 16 + 1600 * 15 / 16)) < 1e-6
    mf = model_flops_per_device("qwen3-32b", "train_4k", 256)
    cfg = get_config("qwen3-32b")
    expect = 6 * cfg.n_params * 4096 * 256 / 256
    assert abs(mf - expect) / expect < 1e-6
    # MoE uses active params
    mfk = model_flops_per_device("kimi-k2-1t-a32b", "train_4k", 256)
    k = get_config("kimi-k2-1t-a32b")
    assert abs(mfk - 6 * k.n_active_params * 4096 * 256 / 256) / mfk < 1e-6
