"""The correctness check: served tokens against a plain float32 reference.

The reference itself is the configuration's payload (``bench/payloads/``,
the module that the harness loaded): its weights made from the replica's
seed, and its logits over a prompt and the served tokens. It imports
nothing of the program. This module reads how far below the
reference's best logit each served token lies.

The control is the payload's pass with every matmul input rounded to float8
e4m3 (per-tensor scales, ``mm(..., fp8=True)``), the precision step below
the served bfloat16.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x, axis=None):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(spec, x, w, fp8):
    """A payload's matmul: ``einsum`` at the highest precision, its inputs
    rounded to float8 e4m3 for the control."""
    if fp8:
        x, w = _fp8(x), _fp8(w)
    return jnp.einsum(spec, x, w, precision=HI)


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


def check(samples: List[Dict], payload, arch: Dict, dtype: str,
          control: bool = False) -> Dict[str, float]:
    """Run the reference over each sample (``seed``, ``prompt``, ``tokens``:
    the served tokens) and return the widest gap of a served token, with
    the control's widest gap too when ``control`` is set. Samples are
    grouped by replica seed, so each replica's weights are made once. The
    weights and logits are those of ``payload``, the configuration's
    payload module."""
    widest, widest_ctl, n_tok = 0.0, 0.0, 0
    with jax.default_matmul_precision("highest"):
        for seed in sorted({s["seed"] for s in samples}):
            params = payload.init_params(seed, arch, dtype)
            for s in (x for x in samples if x["seed"] == seed):
                seq = list(s["prompt"]) + list(s["tokens"][:-1])
                ref = payload.forward(params, seq, arch)
                widest = max(widest, float(token_gaps(
                    ref, len(s["prompt"]), s["tokens"]).max()))
                n_tok += len(s["tokens"])
                if control:
                    low = payload.forward(params, seq, arch, fp8=True)
                    widest_ctl = max(widest_ctl, float(control_gaps(
                        ref, low, len(s["prompt"]), len(s["tokens"])).max()))
            del params
    out = {"logit_gap": widest, "tokens_checked": n_tok}
    if control:
        out["control_logit_gap"] = widest_ctl
    return out
