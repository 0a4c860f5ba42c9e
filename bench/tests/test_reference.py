"""The reference and its control at a tiny size: the weights it makes from
a replica's seed are the program's, the served tokens sit within the limit,
and the fp8 control reads above it."""
import dataclasses

import numpy as np

from bench import reference
from bench.tests.tiny import TINY_ARCH, TINY_LIMIT

ARCH = dict(TINY_ARCH, rope_theta=10000.0, rms_norm_eps=1e-6,
            dtype="bfloat16")


def _program_replica(seed):
    from repro.configs import get_config
    from repro.serving.engine import Replica

    cfg = dataclasses.replace(
        get_config("smollm-360m"), n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab=512, head_dim=16, dtype="bfloat16")
    return Replica(cfg, rng_seed=seed, max_seq=64)


def test_reference_weights_are_the_replicas():
    rep = _program_replica(5)
    ref = reference.init_params(5, ARCH, "bfloat16")
    got = rep.params
    np.testing.assert_array_equal(np.asarray(ref["embed"]),
                                  np.asarray(got["embed"], np.float32))
    for k in reference.LAYER_KEYS:
        np.testing.assert_array_equal(
            np.asarray(ref["layers"][k]),
            np.asarray(got["layers"][k], np.float32), err_msg=k)


def _served(rep, prompts, n_new):
    from repro.serving.engine import ContinuousBatcher

    b = ContinuousBatcher(rep, max_slots=len(prompts))
    ids = [b.add_request(p, n_new) for p in prompts]
    done = b.run_until_done()
    return [done[i] for i in ids]


def test_served_within_limit_and_control_above_it():
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 512, size=n).tolist() for n in (5, 9, 14, 20)]
    rep = _program_replica(3)
    served = _served(rep, prompts, 12)
    samples = [{"seed": 3, "prompt": p, "tokens": t}
               for p, t in zip(prompts, served)]
    out = reference.check(samples, ARCH, "bfloat16", control=True)
    assert out["tokens_checked"] == 48
    assert out["logit_gap"] <= TINY_LIMIT
    assert out["control_logit_gap"] > TINY_LIMIT


def test_gap_reads_what_greedy_read():
    logits = np.array([[0.0, 1.0, 0.5], [2.0, 0.0, 1.5], [0.0, 0.0, 3.0]])
    # prompt of 2: rows 1 and 2 chose served[0] and served[1]
    np.testing.assert_allclose(reference.token_gaps(logits, 2, [0, 2]),
                               [0.0, 0.0])
    np.testing.assert_allclose(reference.token_gaps(logits, 2, [2, 1]),
                               [0.5, 3.0])
