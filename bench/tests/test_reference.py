"""The reference and its control at a tiny size: the weights that the llama
payload makes from a replica's seed are the program's, the served tokens sit
within the limit, and the fp8 control reads above it."""
import dataclasses

import numpy as np

from bench import reference
from bench.payloads import llama

ARCH = dict(llama.TINY, rope_theta=10000.0, rms_norm_eps=1e-6,
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
    ref = llama.init_params(5, ARCH, "bfloat16")
    got = rep.params
    np.testing.assert_array_equal(np.asarray(ref["embed"]),
                                  np.asarray(got["embed"], np.float32))
    for k in llama.LAYER_KEYS:
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
    out = reference.check(samples, llama, ARCH, "bfloat16", control=True)
    assert out["tokens_checked"] == 48
    assert out["logit_gap"] <= llama.TINY_LIMIT
    assert out["control_logit_gap"] > llama.TINY_LIMIT


def test_gap_reads_what_greedy_read():
    logits = np.array([[0.0, 1.0, 0.5], [2.0, 0.0, 1.5], [0.0, 0.0, 3.0]])
    # prompt of 2: rows 1 and 2 chose served[0] and served[1]
    np.testing.assert_allclose(reference.token_gaps(logits, 2, [0, 2]),
                               [0.0, 0.0])
    np.testing.assert_allclose(reference.token_gaps(logits, 2, [2, 1]),
                               [0.5, 3.0])


# The reference's readings of SEED's requests on the tiny llm.saturate
# configuration, as the reference read them before it moved into the llama
# payload file (the same code, run on the commit before the move)
SEED = 2147495000
PINNED = {"logit_gap": 0.0032381415367126465, "tokens_checked": 96,
          "control_logit_gap": 0.09582939743995667}


def test_reference_reads_as_before_it_moved(tree):
    """Eight requests served by two replicas of the tiny configuration,
    checked by the reference that the configuration's payload holds."""
    import json

    import pytest

    from bench.harness import arch_of, load_payload
    from repro.configs import get_config
    from repro.serving.engine import Replica

    cfg = json.loads((tree / "bench" / "configs" / "llm-smollm-360m.json")
                     .read_text())
    payload = load_payload(tree, cfg)
    arch = arch_of(cfg, payload)
    prog = payload.program_config(get_config(cfg["arch"]), cfg)
    rng = np.random.default_rng(SEED)
    samples = []
    for seed in (1, 2):
        rep = Replica(prog, rng_seed=seed, max_seq=cfg["cluster"]["max_seq"])
        prompts = [rng.integers(1, arch["vocab_size"], size=n).tolist()
                   for n in (5, 9, 14, 20)]
        samples += [{"seed": seed, "prompt": p, "tokens": t}
                    for p, t in zip(prompts, _served(rep, prompts, 12))]
    out = reference.check(samples, payload, arch, cfg["dtype"],
                          control=True)
    assert out == pytest.approx(PINNED, rel=1e-6, abs=0)
