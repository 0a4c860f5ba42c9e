"""Plain float32 reference of the served payload, kept with the benchmark.

A llama-style decoder (RMSNorm, rotary positions, grouped-query attention,
SwiGLU, tied embeddings) written straight from the published description in
``jax.numpy``, with no cache, batching or kernels, and every matmul at the
highest precision. It imports nothing of the program. The weights are made
from the replica's seed by the deployment's recipe (``init_params``), in the
served dtype, then widened to float32.

``forward(..., fp8=True)`` is the control: the same pass with every matmul
input rounded to float8 e4m3 (per-tensor scales), the precision step below
the served bfloat16.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
LAYER_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
              "w_down")


def _layer_shapes(a: Dict) -> Dict[str, tuple]:
    d, hd, f = a["hidden_size"], a["head_dim"], a["intermediate_size"]
    hq, hkv = a["num_attention_heads"], a["num_key_value_heads"]
    return {"ln1": (d,), "ln2": (d,), "wq": (d, hq * hd),
            "wk": (d, hkv * hd), "wv": (d, hkv * hd), "wo": (hq * hd, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


@partial(jax.jit, static_argnums=(1, 2))
def _init(seed_key, arch_items: tuple, dtype: str):
    a = dict(arch_items)
    dt = jnp.dtype(dtype)
    n = a["num_hidden_layers"]
    keys = jax.random.split(seed_key, 8)
    layers = {}
    for i, (name, shape) in enumerate(_layer_shapes(a).items()):
        if name.startswith("ln"):
            layers[name] = jnp.ones((n,) + shape, jnp.float32)
            continue
        w = jax.random.normal(jax.random.fold_in(keys[0], i), (n,) + shape)
        layers[name] = (w / math.sqrt(shape[-2])).astype(dt)
    embed = jax.random.normal(keys[2], (a["vocab_size"], a["hidden_size"]))
    return {"embed": (embed * 0.02).astype(dt),
            "final_norm": jnp.ones((a["hidden_size"],), jnp.float32),
            "layers": layers}


def init_params(seed: int, arch: Dict, dtype: str) -> Dict:
    """Weights of the replica seeded ``seed``: each projection normal with
    std 1/sqrt(fan_in), the embedding normal with std 0.02, norms at one;
    made in ``dtype`` and returned widened to float32."""
    p = _init(jax.random.PRNGKey(seed), tuple(sorted(arch.items())), dtype)
    return jax.tree.map(lambda x: x.astype(jnp.float32), p)


def _fp8(x, axis=None):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, x, w, fp8):
    if fp8:
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum(spec, x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    s, d = x.shape[0], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs       # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnums=(2, 3))
def _forward(params, tokens, arch_items: tuple, fp8: bool):
    a = dict(arch_items)
    hq, hkv = a["num_attention_heads"], a["num_key_value_heads"]
    hd, eps, theta = a["head_dim"], a["rms_norm_eps"], a["rope_theta"]
    s = tokens.shape[0]
    x = params["embed"][tokens]                                    # (S, D)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, w):
        h = _rms(x, w["ln1"], eps)
        q = _mm("sd,dh->sh", h, w["wq"], fp8).reshape(s, hq, hd)
        k = _mm("sd,dh->sh", h, w["wk"], fp8).reshape(s, hkv, hd)
        v = _mm("sd,dh->sh", h, w["wv"], fp8).reshape(s, hkv, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, hq // hkv, axis=1)       # query head j reads kv j // g
        v = jnp.repeat(v, hq // hkv, axis=1)
        sc = _mm("qhd,khd->hqk", q, k, fp8) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        o = _mm("hqk,khd->qhd", p, v, fp8).reshape(s, hq * hd)
        x = x + _mm("sh,hd->sd", o, w["wo"], fp8)
        h = _rms(x, w["ln2"], eps)
        g = _mm("sd,df->sf", h, w["w_gate"], fp8)
        u = _mm("sd,df->sf", h, w["w_up"], fp8)
        x = x + _mm("sf,fd->sd", jax.nn.silu(g) * u, w["w_down"], fp8)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"], eps)
    return _mm("sd,vd->sv", x, params["embed"], fp8)


def forward(params: Dict, tokens: Sequence[int], arch: Dict,
            fp8: bool = False) -> np.ndarray:
    """Logits (len(tokens), vocab) in float32. Lengths are padded to a
    multiple of 128 (causal attention leaves the real rows unchanged), so
    the compile is reused across requests."""
    n = len(tokens)
    padded = np.zeros((-(-n // 128) * 128,), np.int32)
    padded[:n] = tokens
    out = _forward(params, jnp.asarray(padded), tuple(sorted(arch.items())),
                   fp8)
    return np.asarray(out[:n], np.float32)


def token_gaps(logits: np.ndarray, prompt_len: int,
               served: Sequence[int]) -> np.ndarray:
    """How far below the reference's best logit each served token lies.
    ``logits`` are the reference's over ``prompt + served[:-1]``: the row
    at ``prompt_len - 1 + i`` is the one greedy decoding read for
    ``served[i]``."""
    rows = logits[prompt_len - 1: prompt_len - 1 + len(served)]
    picked = rows[np.arange(len(served)), np.asarray(served)]
    return rows.max(axis=-1) - picked


def control_gaps(ref_logits: np.ndarray, low_logits: np.ndarray,
                 prompt_len: int, n: int) -> np.ndarray:
    """The control's reading at each of the ``n`` served positions: the
    reference's gap for the token that the lower precision puts first."""
    lo = prompt_len - 1
    pick = low_logits[lo:lo + n].argmax(axis=-1)
    return token_gaps(ref_logits, prompt_len, list(pick))


def check(samples: List[Dict], arch: Dict, dtype: str,
          control: bool = False) -> Dict[str, float]:
    """Run the reference over each sample (``seed``, ``prompt``, ``tokens``:
    the served tokens) and return the widest gap of a served token, with
    the control's widest gap too when ``control`` is set. Samples are
    grouped by replica seed, so each replica's weights are made once."""
    widest, widest_ctl, n_tok = 0.0, 0.0, 0
    with jax.default_matmul_precision("highest"):
        for seed in sorted({s["seed"] for s in samples}):
            params = init_params(seed, arch, dtype)
            for s in (x for x in samples if x["seed"] == seed):
                seq = list(s["prompt"]) + list(s["tokens"][:-1])
                ref = forward(params, seq, arch)
                widest = max(widest, float(token_gaps(
                    ref, len(s["prompt"]), s["tokens"]).max()))
                n_tok += len(s["tokens"])
                if control:
                    low = forward(params, seq, arch, fp8=True)
                    widest_ctl = max(widest_ctl, float(control_gaps(
                        ref, low, len(s["prompt"]), len(s["tokens"])).max()))
            del params
    out = {"logit_gap": widest, "tokens_checked": n_tok}
    if control:
        out["control_logit_gap"] = widest_ctl
    return out
