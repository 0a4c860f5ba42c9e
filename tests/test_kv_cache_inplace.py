"""The decode step reads the KV cache in place and writes only its new rows.

``DecoderLM._stack`` once passed the cache through the layer scan's ``xs``
and ``ys``: every layer's cache was copied out, written, and stacked back on
every step. ``XsYsLM`` keeps that formulation as the reference. The current
step must give the same logits and the same cache, bit for bit, and its
layer scan must move no cache-sized array. The served executable donates
the cache, and on a TPU a Pallas kernel writes each slot's rows into it;
the step must agree with the undonated one, and the kernel with
``with_row``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.kernels.kv_write import write_rows_pallas
from repro.models import layers as L
from repro.models.api import RunConfig
from repro.models.transformer import DecoderLM
from repro.serving.exec_cache import ExecutableCache

B, SMAX, STEPS = 4, 32, 6


class XsYsLM(DecoderLM):
    """The reference: each layer's cache slice rides in the scan's ``xs``,
    gets its new row written, and is stacked back as the scan's ``ys``."""

    def _stack(self, params, x, pos, cache=None, cache_len=None):
        if cache is None:
            return super()._stack(params, x, pos)

        def body(x, wl):
            w, (ck, cv) = wl
            x, (kr, vr) = self._block(w, x, pos, (ck, cv), cache_len)
            return x, (_write(ck, kr, cache_len), _write(cv, vr, cache_len))

        if self.run.layer_mode == "scan":
            x, (nk, nv) = lax.scan(body, x, (params["layers"],
                                             (cache["k"], cache["v"])))
        else:
            outs = []
            for i in range(self.cfg.n_layers):
                wl = jax.tree.map(lambda a: a[i], params["layers"])
                x, kv = body(x, (wl, (cache["k"][i], cache["v"][i])))
                outs.append(kv)
            nk = jnp.stack([k for k, _ in outs])
            nv = jnp.stack([v for _, v in outs])
        return x, {"k": nk, "v": nv}


def _write(c, row, n):
    """Write ``row`` (B, Hkv, D) into one layer's cache (B, Hkv, D, S) at
    ``n``: a scatter for per-slot lengths, else a dynamic_update_slice."""
    if n.ndim == 1:
        return c.at[jnp.arange(c.shape[0]), :, :, n].set(row)
    return lax.dynamic_update_slice(c, row[..., None], (0, 0, 0, n))


def _steps(model, params, cache, lens, toks):
    out = []
    for t in range(STEPS):
        batch = {"tokens": toks[:, t:t + 1], "cache_len": lens + t}
        logits, cache = jax.jit(model.decode_step)(params, cache, batch)
        out.append(np.asarray(logits))
    return out, jax.tree.map(np.asarray, cache)


@pytest.mark.parametrize("layer_mode", ["scan", "unroll"])
@pytest.mark.parametrize("per_slot", [True, False], ids=["per_slot", "scalar"])
@pytest.mark.parametrize("arch", ["smollm-360m", "qwen3-32b",
                                  "kimi-k2-1t-a32b"])
def test_decode_matches_xs_ys_reference(arch, per_slot, layer_mode):
    """Six steps from mixed per-slot lengths (or one scalar length) over a
    cache of random stale values: logits and cache equal the reference's."""
    cfg = get_config(arch).reduced()
    run = RunConfig(q_chunk=8, kv_chunk=8, layer_mode=layer_mode)
    model, ref = DecoderLM(cfg, run), XsYsLM(cfg, run)
    params = model.init_params(jax.random.PRNGKey(0))
    spec = model.cache_specs(ShapeSpec("t", SMAX, B, "decode"))
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    cache = {n: jax.random.normal(k, s.shape, s.dtype)
             for (n, s), k in zip(spec.items(), keys)}
    toks = jax.random.randint(keys[2], (B, STEPS), 0, cfg.vocab)
    lens = (jnp.array([3, 0, 11, 20], jnp.int32) if per_slot
            else jnp.array(5, jnp.int32))
    got_logits, got_cache = _steps(model, params, cache, lens, toks)
    want_logits, want_cache = _steps(ref, params, cache, lens, toks)
    for got, want in zip(got_logits, want_logits):
        np.testing.assert_array_equal(got, want)
    for n in ("k", "v"):
        np.testing.assert_array_equal(got_cache[n], want_cache[n])
    # the rows landed where the lengths say, and nothing else moved
    hot = np.zeros(spec["k"].shape[1:], bool)
    for t in range(STEPS):
        pos = np.broadcast_to(np.asarray(lens) + t, (B,))
        hot[np.arange(B), ..., pos] = True
    stale = np.asarray(cache["k"])
    assert not np.array_equal(got_cache["k"][:, hot], stale[:, hot])
    np.testing.assert_array_equal(got_cache["k"][:, ~hot], stale[:, ~hot])


def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


def test_layer_scan_moves_no_cache():
    """The layer scan takes the cache as a constant it reads in place, and
    neither carries nor stacks anything the size of one layer's cache: it
    stacks only the new rows, which one pass writes after it."""
    cfg = get_config("smollm-360m").reduced()
    model = DecoderLM(cfg, RunConfig(layer_mode="scan"))
    spec = model.cache_specs(ShapeSpec("t", SMAX, B, "decode"))
    batch = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
             "cache_len": jax.ShapeDtypeStruct((B,), jnp.int32)}
    jaxpr = jax.make_jaxpr(model.decode_step)(
        model.param_specs(), spec, batch).jaxpr
    layer = [e for e in _scans(jaxpr) if e.params["length"] == cfg.n_layers]
    assert len(layer) == 1
    scan = layer[0]
    layer_size = int(np.prod(spec["k"].shape[1:]))
    n_consts = scan.params["num_consts"]
    consts = [v.aval.shape for v in scan.invars[:n_consts]]
    assert consts.count(spec["k"].shape) == 2          # k and v, read in place
    n_carry = scan.params["num_carry"]
    xs = [v.aval.shape for v in scan.invars[n_consts + n_carry:]]
    assert spec["k"].shape not in xs                   # only the weights
    moved = scan.invars[n_consts:n_consts + n_carry] + scan.outvars
    assert all(int(np.prod(v.aval.shape)) < layer_size for v in moved)
    rows = (cfg.n_layers, B, cfg.n_kv_heads, cfg.resolved_head_dim)
    assert [v.aval.shape for v in scan.outvars].count(rows) == 2


# positions as a function of the cache's length: scalar, then one a slot
POSITIONS = {
    "first": lambda s: 0,
    "last": lambda s: s - 1,
    "past_end": lambda s: s,
    "per_slot": lambda s: [0, s - 1, s, 7],
    "per_slot_outside": lambda s: [-1, 3, s + 4, s - 1],
    "per_slot_past_end": lambda s: [s] * B,
    "per_slot_windows": lambda s: [127, 128, 255, 300],
}


@pytest.mark.parametrize("smax", [128, 384])
@pytest.mark.parametrize("pos", list(POSITIONS.values()), ids=list(POSITIONS))
def test_kv_write_kernel_matches_with_row(pos, smax):
    """The Pallas kernel (interpreted) puts each slot's rows where
    ``with_row`` selects them, bit for bit, across windows of 128 positions
    and blocks of 8 layers; a position outside the cache writes nothing."""
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    cache = jax.random.normal(keys[0], (16, B, 2, 8, smax), jnp.bfloat16)
    rows = jax.random.normal(keys[1], (16, B, 2, 8), jnp.bfloat16)
    pos = jnp.asarray(pos(smax), jnp.int32)
    got = np.asarray(write_rows_pallas(cache, rows, pos, interpret=True))
    np.testing.assert_array_equal(got, np.asarray(L.with_row(cache, rows,
                                                             pos)))
    inside = (np.asarray(pos) >= 0) & (np.asarray(pos) < smax)
    moved = ~np.all(got == np.asarray(cache), axis=(0, 2, 3, 4))
    np.testing.assert_array_equal(moved, np.broadcast_to(inside, (B,)))


@pytest.mark.parametrize("arch", ["smollm-360m", "qwen3-32b",
                                  "kimi-k2-1t-a32b"])
def test_init_cache_gives_k_and_v_their_own_buffers(arch):
    """A call that donates the cache refuses one buffer handed in twice."""
    model = DecoderLM(get_config(arch).reduced(), RunConfig())
    cache = model.init_cache(ShapeSpec("t", SMAX, B, "decode"))
    assert (cache["k"].unsafe_buffer_pointer()
            != cache["v"].unsafe_buffer_pointer())


@pytest.mark.parametrize("arch", ["smollm-360m", "qwen3-32b",
                                  "kimi-k2-1t-a32b"])
def test_donating_executable_matches_undonated(arch):
    """Six steps of the batcher's signature through the served executable,
    which donates its cache: each call deletes the cache it was given, and
    the logits and the final cache equal the undonated step's."""
    cfg = get_config(arch).reduced()
    entry = ExecutableCache().get(cfg, RunConfig(q_chunk=8, kv_chunk=8))
    model = entry.model
    params = model.init_params(jax.random.PRNGKey(0))
    shape = ShapeSpec("t", SMAX, B, "decode")
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, STEPS), 0,
                              cfg.vocab)
    lens = jnp.array([3, 0, 11, SMAX - STEPS], jnp.int32)
    want_logits, want_cache = _steps(model, params, model.init_cache(shape),
                                     lens, toks)
    cache = model.init_cache(shape)
    for t in range(STEPS):
        given = cache
        logits, cache = entry.decode(params, given, {
            "tokens": toks[:, t:t + 1], "cache_len": lens + t})
        assert all(a.is_deleted() for a in jax.tree.leaves(given))
        np.testing.assert_array_equal(np.asarray(logits), want_logits[t])
    for n in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cache[n]), want_cache[n])

