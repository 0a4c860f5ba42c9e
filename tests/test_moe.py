"""MoE layer tests: EP shard-count invariance, capacity dropping, FSDP specs."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ArchConfig, MoEConfig
from repro.models.api import RunConfig, build_model
from repro.models.moe import _local_moe, moe_param_pspecs


def _mini_moe_cfg(n_experts=8, top_k=2, cf=8.0):
    base = get_config("kimi-k2-1t-a32b").reduced()
    import dataclasses
    return dataclasses.replace(
        base, moe=dataclasses.replace(base.moe, n_experts=n_experts,
                                      top_k=top_k, capacity_factor=cf))


def test_local_moe_matches_dense_reference():
    """With generous capacity, sorted-EP output == the dense per-expert sum."""
    cfg = _mini_moe_cfg()
    run = RunConfig(moe_capacity_factor=8.0)
    T, D = 16, cfg.d_model
    Fe = cfg.moe.d_ff_expert
    E = cfg.moe.n_experts
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    w = {
        "router": jax.random.normal(ks[0], (D, E)) * 0.5,
        "e_gate": jax.random.normal(ks[1], (E, D, Fe)) * 0.1,
        "e_up": jax.random.normal(ks[2], (E, D, Fe)) * 0.1,
        "e_down": jax.random.normal(ks[3], (E, Fe, D)) * 0.1,
    }
    x = jax.random.normal(ks[4], (T, D))
    y = _local_moe(cfg, run, w, x, n_shards=1, shard_id=0)

    # dense reference
    logits = x @ w["router"]
    probs = jax.nn.softmax(logits, -1)
    top_p, top_e = jax.lax.top_k(probs, cfg.moe.top_k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    y_ref = jnp.zeros((T, D))
    for t in range(T):
        for j in range(cfg.moe.top_k):
            e = int(top_e[t, j])
            h = jax.nn.silu(x[t] @ w["e_gate"][e]) * (x[t] @ w["e_up"][e])
            y_ref = y_ref.at[t].add(float(top_p[t, j]) * (h @ w["e_down"][e]))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-3,
                               rtol=1e-3)


def test_capacity_drops_overflow():
    """With capacity ~0, outputs go to ~zero (dropped tokens), no NaNs."""
    cfg = _mini_moe_cfg(cf=8.0)
    run_full = RunConfig(moe_capacity_factor=8.0)
    run_tight = RunConfig(moe_capacity_factor=0.01)
    T, D = 32, cfg.d_model
    E, Fe = cfg.moe.n_experts, cfg.moe.d_ff_expert
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    w = {
        "router": jax.random.normal(ks[0], (D, E)) * 0.5,
        "e_gate": jax.random.normal(ks[1], (E, D, Fe)) * 0.1,
        "e_up": jax.random.normal(ks[2], (E, D, Fe)) * 0.1,
        "e_down": jax.random.normal(ks[3], (E, Fe, D)) * 0.1,
    }
    x = jax.random.normal(ks[4], (T, D))
    y_full = _local_moe(cfg, run_full, w, x, n_shards=1, shard_id=0)
    y_tight = _local_moe(cfg, run_tight, w, x, n_shards=1, shard_id=0)
    assert not bool(jnp.isnan(y_tight).any())
    # tight capacity serves at most a couple of assignments
    served_tight = int(jnp.sum(jnp.any(jnp.abs(y_tight) > 0, axis=-1)))
    served_full = int(jnp.sum(jnp.any(jnp.abs(y_full) > 0, axis=-1)))
    assert served_tight < served_full


def test_fsdp_pspecs_no_duplicate_axes():
    from jax.sharding import PartitionSpec as P
    cfg = get_config("kimi-k2-1t-a32b")
    specs = moe_param_pspecs(cfg, "model", fsdp_axes=("pod", "data"))
    for name, sp in specs.items():
        used = []
        for entry in sp:
            if entry is None:
                continue
            used += list(entry) if isinstance(entry, tuple) else [entry]
        assert len(used) == len(set(used)), f"duplicate axes in {name}: {sp}"


def test_ep_shard_invariance_subprocess():
    """MoE output must be identical at 1 vs 4 EP shards (fake devices)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        import dataclasses
        from repro.configs import get_config
        from repro.models.api import build_model, RunConfig
        base = get_config("kimi-k2-1t-a32b").reduced()
        cfg = dataclasses.replace(
            base, moe=dataclasses.replace(base.moe, n_experts=8, top_k=2))
        run = RunConfig(q_chunk=16, kv_chunk=16, data_axes=("data",),
                        moe_capacity_factor=8.0)
        model = build_model(cfg, run)
        params = model.init_params(jax.random.PRNGKey(0))
        batch = {"tokens": jnp.arange(64, dtype=jnp.int32).reshape(2, 32) % 100}
        y1 = model.forward(params, batch)          # no mesh: local path
        from repro.models.sharding import auto_mesh
        mesh = auto_mesh((1, 4), ("data", "model"))
        with jax.set_mesh(mesh):
            y4 = jax.jit(model.forward)(params, batch)
        err = float(jnp.abs(y1 - y4).max())
        assert err < 2e-2, f"EP shard mismatch: {err}"
        print("EP-invariance OK", err)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                                        "HOME": "/root"}, cwd="/root/repo",
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "EP-invariance OK" in r.stdout


def test_sharded_decode_subprocess():
    """Distributed flash-decode == plain decode on an 8-device mesh."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.configs.base import ShapeSpec
        from repro.models.api import build_model, RunConfig
        cfg = get_config("qwen3-32b").reduced(n_layers=2, d_model=64,
                                              n_heads=8, n_kv_heads=2,
                                              d_ff=128, vocab=256)
        m0 = build_model(cfg, RunConfig(q_chunk=16, kv_chunk=16,
                                        data_axes=("data",)))
        params = m0.init_params(jax.random.PRNGKey(0))
        B = 4
        cache = m0.init_cache(ShapeSpec("t", 32, B, "decode"))
        batch = {"tokens": jnp.ones((B, 1), jnp.int32),
                 "cache_len": jnp.array(3, jnp.int32)}
        l_ref, _ = jax.jit(m0.decode_step)(params, cache, batch)
        from repro.models.sharding import auto_mesh
        mesh = auto_mesh((2, 4), ("data", "model"))
        with jax.set_mesh(mesh):
            m1 = build_model(cfg, RunConfig(q_chunk=16, kv_chunk=16,
                                            data_axes=("data",),
                                            sharded_decode=True))
            l1, _ = jax.jit(m1.decode_step)(params, cache, batch)
        err = float(jnp.abs(l1 - l_ref).max())
        assert err < 1e-4, err
        print("sharded-decode OK", err)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                                        "HOME": "/root"}, cwd="/root/repo",
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "sharded-decode OK" in r.stdout
