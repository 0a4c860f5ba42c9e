"""Training substrate: loss descent, accumulation, compression, checkpoints."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.api import RunConfig, build_model
from repro.train.checkpoint import (latest_step, restore_checkpoint,
                                    save_checkpoint)
from repro.train.compress import quantize_int8, dequantize_int8, roundtrip_tree
from repro.train.data import ZipfLMStream, random_tokens
from repro.train.optimizer import adamw_init, adamw_pspecs
from repro.train.train_step import make_train_step

CFG = get_config("smollm-360m").reduced(n_layers=2, d_model=64, n_heads=4,
                                        d_ff=128, vocab=256)


def _setup(run_kw=None):
    run = RunConfig(q_chunk=16, kv_chunk=16, **(run_kw or {}))
    model = build_model(CFG, run)
    params = model.init_params(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    step = jax.jit(make_train_step(model, lr=1e-3))
    return model, params, opt, step


def test_loss_decreases():
    model, params, opt, step = _setup()
    stream = ZipfLMStream(vocab=256, seq=32, batch=8, seed=3)
    losses = []
    for i in range(15):
        params, opt, m = step(params, opt, stream.batch_at(i),
                              jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert not any(np.isnan(losses))


def test_grad_accum_equivalence():
    """accum=2 over a batch == single step over the same batch (same math
    modulo fp reordering)."""
    model1, params, opt, step1 = _setup()
    _, _, _, step2 = _setup({"grad_accum": 2})
    batch = random_tokens(0, 8, 32, 256)
    p1, _, m1 = step1(params, opt, batch, jax.random.PRNGKey(0))
    p2, _, m2 = step2(params, opt, batch, jax.random.PRNGKey(0))
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-4, rtol=1e-3)


def test_int8_quantizer_unbiased():
    x = jax.random.normal(jax.random.PRNGKey(0), (4096,)) * 3.0
    keys = jax.random.split(jax.random.PRNGKey(1), 64)
    acc = jnp.zeros_like(x)
    for k in keys:
        q, s = quantize_int8(x, k)
        acc = acc + dequantize_int8(q, s)
    mean = acc / len(keys)
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    # stochastic rounding: E[deq] == x (within sampling noise ~ scale/sqrt(n))
    assert float(jnp.abs(mean - x).max()) < scale * 1.2


def test_compressed_training_still_learns():
    model, params, opt, _ = _setup({"grad_compress": True})
    step = jax.jit(make_train_step(model, lr=1e-3))
    stream = ZipfLMStream(vocab=256, seq=32, batch=8, seed=5)
    losses = []
    for i in range(15):
        params, opt, m = step(params, opt, stream.batch_at(i),
                              jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_checkpoint_resume_bitexact(tmp_path):
    """save -> restore -> continue == continuous run (fault tolerance)."""
    model, params, opt, step = _setup()
    stream = ZipfLMStream(vocab=256, seq=32, batch=8, seed=7)
    for i in range(4):
        params, opt, m = step(params, opt, stream.batch_at(i),
                              jax.random.PRNGKey(i))
    save_checkpoint(str(tmp_path), 4, {"params": params, "opt": opt})
    # continue the original
    p_cont, o_cont = params, opt
    for i in range(4, 8):
        p_cont, o_cont, _ = step(p_cont, o_cont, stream.batch_at(i),
                                 jax.random.PRNGKey(i))
    # restart from the checkpoint
    (restored, step_n) = restore_checkpoint(str(tmp_path), None,
                                            {"params": params, "opt": opt})
    assert step_n == 4
    p_re, o_re = restored["params"], restored["opt"]
    for i in range(4, 8):
        p_re, o_re, _ = step(p_re, o_re, stream.batch_at(i),
                             jax.random.PRNGKey(i))
    for a, b in zip(jax.tree.leaves(p_cont), jax.tree.leaves(p_re)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_async_and_latest(tmp_path):
    model, params, opt, step = _setup()
    t = save_checkpoint(str(tmp_path), 1, {"p": params}, async_save=True)
    t.join()
    save_checkpoint(str(tmp_path), 5, {"p": params})
    assert latest_step(str(tmp_path)) == 5


def test_elastic_restore_resharding(tmp_path):
    """Restore a checkpoint onto a different mesh (shrunk data axis) — the
    elastic-rescale path after node loss."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    model, params, opt, step = _setup()
    save_checkpoint(str(tmp_path), 2, {"params": params})
    from repro.models.sharding import auto_mesh
    mesh = auto_mesh((1, 1), ("data", "model"))
    shardings = jax.tree.map(
        lambda sp: NamedSharding(mesh, sp),
        model.param_pspecs(),
        is_leaf=lambda x: isinstance(x, P))
    (restored, _) = restore_checkpoint(str(tmp_path), 2, {"params": params},
                                       shardings={"params": shardings})
    for a, b in zip(jax.tree.leaves(restored["params"]),
                    jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_zero1_pspecs_shard_moments():
    from jax.sharding import PartitionSpec as P
    model, params, _, _ = _setup({"use_zero1": True})
    specs = model.param_specs()
    ps = adamw_pspecs(model.param_pspecs(), specs, use_zero1=True,
                      dax=("data",))
    flat = jax.tree.leaves(ps.mu, is_leaf=lambda x: isinstance(x, P))
    # at least the large moment tensors picked up a data-axis shard
    assert any("data" in str(p) for p in flat)


def test_data_stream_determinism():
    s1 = ZipfLMStream(vocab=128, seq=16, batch=4, seed=9)
    s2 = ZipfLMStream(vocab=128, seq=16, batch=4, seed=9)
    b1, b2 = s1.batch_at(17), s2.batch_at(17)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
