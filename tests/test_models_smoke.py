"""Per-architecture smoke tests (deliverable f).

Each assigned arch is instantiated at a REDUCED same-family config and runs
one forward + one train step + one decode step on CPU, asserting output
shapes and no NaNs. The FULL configs are exercised only via the dry-run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_arch_names, get_config
from repro.configs.base import ShapeSpec
from repro.models.api import RunConfig, build_model
from repro.train.optimizer import adamw_init
from repro.train.train_step import make_train_step

ARCHS = all_arch_names()
RUN = RunConfig(q_chunk=16, kv_chunk=16, seq_chunk=16, layer_mode="scan")


def _batch(cfg, B=2, S=32):
    batch = {"tokens": jnp.ones((B, S), jnp.int32),
             "labels": jnp.ones((B, S), jnp.int32)}
    if cfg.family == "audio":
        batch["frames"] = jnp.full((B, cfg.enc_dec.encoder_seq, cfg.d_model),
                                   0.01, jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_train_step(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg, RUN)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits = model.forward(params, batch)
    assert logits.shape == (2, 32, cfg.vocab)
    assert not bool(jnp.isnan(logits).any())

    step = jax.jit(make_train_step(model, lr=1e-3))
    opt = adamw_init(params)
    params2, opt2, m = step(params, opt, batch, jax.random.PRNGKey(1))
    assert not bool(jnp.isnan(m["loss"]))
    assert float(m["grad_norm"]) > 0
    # parameters actually moved
    moved = any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert moved


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg, RUN)
    params = model.init_params(jax.random.PRNGKey(0))
    B = 2
    shape = ShapeSpec("t", 64, B, "decode")
    cache = model.init_cache(shape)
    if cfg.family == "audio":
        cache = model.prefill_cross(
            params, _batch(cfg, B=B)["frames"], cache)
    batch = {"tokens": jnp.ones((B, 1), jnp.int32),
             "cache_len": jnp.array(3, jnp.int32)}
    logits, cache2 = jax.jit(model.decode_step)(params, cache, batch)
    assert logits.shape == (B, cfg.vocab)
    assert not bool(jnp.isnan(logits).any())


@pytest.mark.parametrize("arch", ["qwen3-32b", "rwkv6-7b", "zamba2-2.7b"])
def test_prefill_decode_consistency(arch):
    """Token-by-token decode must reproduce the parallel forward's last
    logits — validates KV caches / recurrent state carries exactly."""
    cfg = get_config(arch).reduced()
    run = RunConfig(q_chunk=8, kv_chunk=8, seq_chunk=8, layer_mode="scan")
    model = build_model(cfg, run)
    params = model.init_params(jax.random.PRNGKey(1))
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)
    ref_logits = model.forward(params, {"tokens": toks})[:, -1]
    cache = model.init_cache(ShapeSpec("t", 32, B, "decode"))
    lg = None
    for t in range(S):
        lg, cache = model.decode_step(
            params, cache, {"tokens": toks[:, t:t + 1],
                            "cache_len": jnp.array(t, jnp.int32)})
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref_logits),
                               atol=2e-4, rtol=2e-4)


def test_param_counts_match_assignment():
    """Config-table param counts are in-family (catches config typos)."""
    expect = {
        "qwen3-32b": (28, 38), "granite-34b": (30, 38),
        "smollm-360m": (0.3, 0.5), "glm4-9b": (8, 11),
        "kimi-k2-1t-a32b": (950, 1150), "arctic-480b": (430, 520),
        "rwkv6-7b": (5.5, 8), "zamba2-2.7b": (2.2, 3.5),
        "whisper-small": (0.1, 0.35), "qwen2-vl-72b": (65, 80),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).n_params / 1e9
        assert lo <= n <= hi, f"{arch}: {n:.1f}B outside [{lo}, {hi}]"


def test_moe_active_params():
    kimi = get_config("kimi-k2-1t-a32b")
    assert 25 <= kimi.n_active_params / 1e9 <= 40     # "a32b"


@pytest.mark.parametrize("arch", ARCHS)
def test_unimplemented_attn_impl_raises(arch):
    """No model runs another attention form under a name it does not
    implement; "pallas" was once accepted and silently ran the jnp form."""
    with pytest.raises(ValueError, match="attn_impl"):
        build_model(get_config(arch).reduced(),
                    RunConfig(attn_impl="pallas"))


def test_dense_init_fan_in_is_the_input_width():
    """Stacked (L, d_in, d_out) weights are scaled by d_in, not by L."""
    from repro.models.layers import dense_init

    w = dense_init(jax.random.PRNGKey(0), (2, 900, 64))
    assert abs(float(jnp.std(w)) - 1 / 30) < 1e-3
