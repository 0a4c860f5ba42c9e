"""The llama decoder: RMSNorm, rotary positions, grouped-query attention,
SwiGLU, tied embeddings (smollm-360m).

The reference is written straight from the published description in
``jax.numpy``, with no cache, batching or kernels, and every matmul at the
highest precision. It imports nothing of the program. The weights are made
from the replica's seed by the deployment's recipe (``init_params``), in the
served dtype, then widened to float32. ``forward(..., fp8=True)`` is the
control: the same pass with every matmul input rounded to float8 e4m3.

The step counts (``step_bytes``, ``step_flops``) count what the algorithm
needs, not what today's program moves: a step reads every weight once, reads
the keys and values of the positions below each active slot's cache length,
and writes one new key and value row per active slot.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import mm

KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "num_hidden_layers",
        "vocab_size", "rope_theta", "rms_norm_eps")
BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 512}
# widest logit gap at TINY on the CPU, over eight seeds of 48 served tokens:
# served bf16 0 to 0.0036, the fp8 control 0.020 to 0.111, every token
# changed 0.73 to 0.93
TINY_LIMIT = 0.01
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "head_dim", "vocab_size", "rms_norm_eps")
LAYER_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
              "w_down")


def program_config(base, cfg: Dict):
    """The program's ``ArchConfig`` for the configuration file: the
    registered architecture with every size the file states."""
    if cfg.get("hidden_act", "silu") != "silu" or not cfg.get(
            "tie_word_embeddings", True):
        raise ValueError("the reference covers SwiGLU with tied embeddings")
    return dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]), tie_embeddings=True,
        dtype=cfg["dtype"])


# -- the reference -------------------------------------------------------------

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


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    s, d = x.shape[0], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs       # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def decoder(params, tokens, a: Dict, fp8: bool):
    """The decoder stack up to the final norm: hidden states (S, D), traced
    inside the caller's ``jit``."""
    hq, hkv = a["num_attention_heads"], a["num_key_value_heads"]
    hd, eps, theta = a["head_dim"], a["rms_norm_eps"], a["rope_theta"]
    s = tokens.shape[0]
    x = params["embed"][tokens]                                    # (S, D)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, w):
        h = _rms(x, w["ln1"], eps)
        q = mm("sd,dh->sh", h, w["wq"], fp8).reshape(s, hq, hd)
        k = mm("sd,dh->sh", h, w["wk"], fp8).reshape(s, hkv, hd)
        v = mm("sd,dh->sh", h, w["wv"], fp8).reshape(s, hkv, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, hq // hkv, axis=1)       # query head j reads kv j // g
        v = jnp.repeat(v, hq // hkv, axis=1)
        sc = mm("qhd,khd->hqk", q, k, fp8) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        o = mm("hqk,khd->qhd", p, v, fp8).reshape(s, hq * hd)
        x = x + mm("sh,hd->sd", o, w["wo"], fp8)
        h = _rms(x, w["ln2"], eps)
        g = mm("sd,df->sf", h, w["w_gate"], fp8)
        u = mm("sd,df->sf", h, w["w_up"], fp8)
        x = x + mm("sf,fd->sd", jax.nn.silu(g) * u, w["w_down"], fp8)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _rms(x, params["final_norm"], eps)


@partial(jax.jit, static_argnums=(2, 3))
def _forward(params, tokens, arch_items: tuple, fp8: bool):
    x = decoder(params, tokens, dict(arch_items), fp8)
    return mm("sd,vd->sv", x, params["embed"], fp8)


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


# -- what a decode step needs ---------------------------------------------------

def matmul_weights(a: Dict) -> int:
    """Weights that take part in a matmul per token: every projection of
    every layer and the (tied) output head."""
    d, f, hd = a["hidden_size"], a["intermediate_size"], a["head_dim"]
    hq, hkv = a["num_attention_heads"], a["num_key_value_heads"]
    per_layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f
    return a["num_hidden_layers"] * per_layer + a["vocab_size"] * d


def norm_weights(a: Dict) -> int:
    return (2 * a["num_hidden_layers"] + 1) * a["hidden_size"]


def token_flops(a: Dict, ctx: int) -> int:
    """FLOPs to decode one token that attends to ``ctx`` positions: two per
    matmul weight, and QK^T plus AV over the context in every layer."""
    attn = 4 * a["num_hidden_layers"] * a["num_attention_heads"] \
        * a["head_dim"] * ctx
    return 2 * matmul_weights(a) + attn


def kv_row_bytes(a: Dict, dtype: str) -> int:
    """Bytes of one position's keys and values over all layers."""
    return 2 * a["num_hidden_layers"] * a["num_key_value_heads"] \
        * a["head_dim"] * BYTES[dtype]


def step_bytes(a: Dict, dtype: str, cache_lens: Iterable[int]) -> int:
    """Bytes one decode step needs: the weights once (norm scales in
    float32), the cache below each active slot's length, one new row per
    active slot."""
    lens = list(cache_lens)
    row = kv_row_bytes(a, dtype)
    return (matmul_weights(a) * BYTES[dtype] + norm_weights(a) * 4
            + row * sum(lens) + row * len(lens))


def step_flops(a: Dict, cache_lens: Iterable[int]) -> int:
    """FLOPs of one decode step over its active slots (each attends to its
    cache and its new row)."""
    return sum(token_flops(a, n + 1) for n in cache_lens)
