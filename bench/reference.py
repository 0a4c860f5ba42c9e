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


QUANTILES = (0.5, 0.9, 0.99, 1.0)


def at(gaps: np.ndarray, q: float) -> float:
    """The q-quantile of a pool of gaps, an observed gap (numpy's
    ``method="higher"``): at 1.0 the widest."""
    return float(np.quantile(gaps, q, method="higher"))


def spread(gaps: np.ndarray) -> Dict:
    """How a pool of served tokens' gaps is spread, for the log: the widest,
    the share of tokens off the reference's best (gap above 0) and the
    ``QUANTILES``."""
    return {"widest": float(gaps.max()),
            "share_off": float(np.mean(gaps > 0)),
            "quantiles": {q: at(gaps, q) for q in QUANTILES}}


def check(samples: List[Dict], payload, arch: Dict, dtype: str,
          control: bool = False, quantile: float = 1.0) -> Dict:
    """Run the reference over each sample (``seed``, ``prompt``, ``tokens``:
    the served tokens) and pool the gap of every served token. Returns the
    ``quantile`` of that pool as ``logit_gap`` (``at``: at the default 1.0,
    the widest gap), ``tokens_checked``, and the pool's ``spread`` as
    ``program``; with ``control`` set, the same of the control's pool as
    ``control_logit_gap`` and ``control``.

    A quantile q under a limit L holds exactly when at most
    floor((1 - q)(n - 1)) of the n pooled tokens lie more than L below the
    reference's best logit: about a share 1 - q of them. A configuration
    that names q < 1 states, in its ``reference.witnesses``, its program's
    and its control's readings at that q, the control's failing L.

    Samples are grouped by replica seed, so each replica's weights are made
    once. The weights and logits are those of ``payload``, the
    configuration's payload module."""
    gaps, ctl = [], []
    with jax.default_matmul_precision("highest"):
        for seed in sorted({s["seed"] for s in samples}):
            params = payload.init_params(seed, arch, dtype)
            for s in (x for x in samples if x["seed"] == seed):
                seq = list(s["prompt"]) + list(s["tokens"][:-1])
                ref = payload.forward(params, seq, arch)
                gaps.append(token_gaps(ref, len(s["prompt"]), s["tokens"]))
                if control:
                    low = payload.forward(params, seq, arch, fp8=True)
                    ctl.append(control_gaps(ref, low, len(s["prompt"]),
                                            len(s["tokens"])))
            del params
    pooled = np.concatenate(gaps)
    out = {"logit_gap": at(pooled, quantile), "tokens_checked": len(pooled),
           "program": spread(pooled)}
    if control:
        pooled = np.concatenate(ctl)
        out["control_logit_gap"] = at(pooled, quantile)
        out["control"] = spread(pooled)
    return out
