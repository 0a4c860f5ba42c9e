"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

The TPU compiler is installed wherever jaxlib's TPU support is, and it
compiles for a topology that is only described. These tests compile the
served decode step at the full smollm-360m width (with its Pallas write
of the new KV rows), the decode step sharded over the 2x2 mesh, and the
four other Pallas kernels at real head shapes, so a block shape or a
memory budget the chip's compiler refuses fails here instead of on the
chip. Interpret-mode tests
(test_kernels.py) cannot catch either.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file. Keep all such compiles in this one file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rwkv6_chunk import wkv6_pallas
from repro.kernels.ssd_chunk import ssd_pallas
from repro.models.api import RunConfig
from repro.serving.engine import SERVE_RUN_CFG
from repro.serving.exec_cache import ExecutableCache

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda s: _spec(s.shape, s.dtype, sharding), tree)


def test_served_decode_step_full_smollm_width(one_chip):
    """The batcher's decode signature at the published smollm-360m widths in
    bf16: 8 slots x 2048 cache positions, per-slot cache lengths."""
    cfg = get_config("smollm-360m")
    assert cfg.dtype == "bfloat16" and cfg.n_layers == 32
    slots, max_seq = 8, 2048
    entry = ExecutableCache().get(cfg, SERVE_RUN_CFG)
    model = entry.model
    params = _on(model.param_specs(), one_chip)
    cache = _on(model.cache_specs(ShapeSpec("serve", max_seq, slots,
                                            "decode")), one_chip)
    batch = {"tokens": _spec((slots, 1), jnp.int32, one_chip),
             "cache_len": _spec((slots,), jnp.int32, one_chip)}
    compiled = entry.decode.lower(params, cache, batch).compile()
    mem = compiled.memory_analysis()
    weights = sum(s.size * s.dtype.itemsize
                  for s in jax.tree.leaves(model.param_specs()))
    assert 0.7e9 < weights < 0.75e9            # ~362M params in bf16
    # params + cache in, logits + cache out (the cache donated to the output)
    assert mem.argument_size_in_bytes >= weights
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < V5E_HBM_BYTES


def test_served_decode_step_copies_no_layer_of_cache(one_chip):
    """At the benchmark's 16 slots x 2048 positions, the compiled decode step
    neither copies nor transposes an array the size of one layer's KV cache:
    the layer scan reads the cache as it is stored, and the new rows are
    written into it in place. (Passed through the scan's xs and ys, every
    layer's cache was copied out, relaid out for the dots and stacked back;
    written in place into a cache that is not donated, it is copied
    first.)"""
    import math
    import re

    cfg = get_config("smollm-360m")
    slots, max_seq = 16, 2048
    entry = ExecutableCache().get(cfg, SERVE_RUN_CFG)
    model = entry.model
    params = _on(model.param_specs(), one_chip)
    cache = _on(model.cache_specs(ShapeSpec("serve", max_seq, slots,
                                            "decode")), one_chip)
    batch = {"tokens": _spec((slots, 1), jnp.int32, one_chip),
             "cache_len": _spec((slots,), jnp.int32, one_chip)}
    text = entry.decode.lower(params, cache, batch).compile().as_text()
    layer = math.prod(cache["k"].shape[1:])
    moved = []
    for shape, op in re.findall(r"= \w+\[([\d,]+)\]\S* (copy|transpose)\(",
                                text):
        dims = [int(d) for d in shape.split(",")]
        if max_seq in dims and math.prod(dims) >= layer:
            moved.append((op, dims))
    assert not moved, moved


def test_served_decode_step_writes_rows_in_place(one_chip):
    """At 16 slots x 2048 positions the served decode step takes its cache
    donated, the output aliasing both caches, and writes only the new rows:
    a select over the whole cache reads and writes every element of it
    (3.44e9 bytes accessed a step), the kernel that writes the rows in
    place about 0.75e9."""
    cfg = get_config("smollm-360m")
    slots, max_seq = 16, 2048
    entry = ExecutableCache().get(cfg, SERVE_RUN_CFG)
    model = entry.model
    params = _on(model.param_specs(), one_chip)
    cache = _on(model.cache_specs(ShapeSpec("serve", max_seq, slots,
                                            "decode")), one_chip)
    batch = {"tokens": _spec((slots, 1), jnp.int32, one_chip),
             "cache_len": _spec((slots,), jnp.int32, one_chip)}
    compiled = entry.decode.lower(params, cache, batch).compile()
    cache_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(cache))
    assert cache_bytes == 1_342_177_280
    assert compiled.memory_analysis().alias_size_in_bytes == cache_bytes
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] < 1.5e9


@pytest.mark.parametrize("lens", [(4,), ()], ids=["per_slot", "scalar"])
def test_sharded_decode_step_selects_rows_in(topo, lens):
    """On a 2x2 mesh the decode cache is sharded (slots over ``data``,
    positions over ``model``, few KV heads), and the new rows go in by the
    select, which XLA partitions with the cache: the Pallas write would be
    refused there (Mosaic kernels are not partitioned), and a gathered
    cache would cost a whole cache's traffic. The donated step gathers
    nothing and calls no kernel."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    cfg = get_config("qwen3-32b").reduced(n_layers=2, d_model=64, n_heads=8,
                                          n_kv_heads=2, d_ff=128, vocab=256)
    shape = ShapeSpec("t", 1024, 4, "decode")

    def on(tree, pspecs=None):
        pspecs = pspecs or jax.tree.map(lambda _: P(), tree)
        return jax.tree.map(lambda s, p: _spec(s.shape, s.dtype,
                                               NamedSharding(mesh, p)),
                            tree, pspecs)

    with jax.set_mesh(mesh):
        entry = ExecutableCache().get(cfg, RunConfig(
            q_chunk=16, kv_chunk=16, data_axes=("data",)))
        model = entry.model
        batch = on({"tokens": jax.ShapeDtypeStruct((4, 1), jnp.int32),
                    "cache_len": jax.ShapeDtypeStruct(lens, jnp.int32)})
        compiled = entry.decode.lower(
            on(model.param_specs()),
            on(model.cache_specs(shape), model.cache_pspecs(shape)),
            batch).compile()
    text = compiled.as_text()
    assert "all-gather" not in text
    assert "tpu_custom_call" not in text
    assert compiled.memory_analysis().alias_size_in_bytes > 0


def test_decode_attention_pallas_smollm_heads(one_chip):
    q = _spec((8, 1, 15, 64), jnp.bfloat16, one_chip)
    kv = _spec((8, 2048, 5, 64), jnp.bfloat16, one_chip)
    n = _spec((), jnp.int32, one_chip)
    text = decode_attention_pallas.lower(q, kv, kv, n).compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_attention_pallas_smollm_heads(one_chip):
    q = _spec((1, 2048, 15, 64), jnp.bfloat16, one_chip)
    kv = _spec((1, 2048, 5, 64), jnp.bfloat16, one_chip)
    text = flash_attention_pallas.lower(q, kv, kv).compile().as_text()
    assert "tpu_custom_call" in text


def test_wkv6_pallas_rwkv6_heads(one_chip):
    B, S, H, d = 1, 256, 64, 64                 # rwkv6-7b: 64 heads of 64
    x = _spec((B, S, H, d), jnp.bfloat16, one_chip)
    w = _spec((B, S, H, d), jnp.float32, one_chip)
    u = _spec((H, d), jnp.float32, one_chip)
    s0 = _spec((B, H, d, d), jnp.float32, one_chip)
    text = wkv6_pallas.lower(x, x, x, w, u, s0).compile().as_text()
    assert "tpu_custom_call" in text


def test_ssd_pallas_zamba2_heads(one_chip):
    b, S, H, P, N = 1, 256, 80, 64, 64          # zamba2-2.7b mamba2 heads
    x = _spec((b, S, H, P), jnp.bfloat16, one_chip)
    dt = _spec((b, S, H), jnp.float32, one_chip)
    a = _spec((H,), jnp.float32, one_chip)
    bc = _spec((b, S, H, N), jnp.bfloat16, one_chip)
    h0 = _spec((b, H, P, N), jnp.float32, one_chip)
    text = ssd_pallas.lower(x, dt, a, bc, bc, a, h0).compile().as_text()
    assert "tpu_custom_call" in text
