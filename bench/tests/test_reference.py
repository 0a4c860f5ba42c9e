"""The reference and its control at a tiny size: the weights that the llama
payload makes from a replica's seed are the program's, the served tokens sit
within the limit, and the fp8 control reads above it."""
import dataclasses

import numpy as np
import pytest

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
    # at the default quantile, 1.0, the pooled gaps read the widest gap:
    # the same numbers, bit for bit, as the running maximum that they replace
    assert {k: out[k] for k in PINNED} == PINNED
    assert out["program"]["widest"] == out["logit_gap"]
    assert out["control"]["widest"] == out["control_logit_gap"]


# -- the quantile that a configuration names ---------------------------------

class Given:
    """A payload whose logits are given. The reference puts token 0 first at
    1.0 over token 1 at every position, so a served 1 lies 1.0 below its
    best; the control puts token 1 first where ``control_off`` says, by
    the sequence it reads."""

    def __init__(self, control_off):
        self.control_off = control_off
        self.made = []

    def init_params(self, seed, arch, dtype):
        self.made.append(seed)
        return seed

    def forward(self, params, tokens, arch, fp8=False):
        rows = np.tile(np.float32([1.0, 0.0]), (len(tokens), 1))
        if fp8:
            rows[self.control_off[tuple(tokens)], 1] = 2.0
        return rows


def _given(off, control_off, seeds=(1, 2), per=40):
    """Samples of ``per`` served tokens, a one-token prompt each, spread
    over ``seeds`` in turn; a token served as 1 where ``off`` is set, and
    the control's first choice at that position off where ``control_off``
    is set. Both are over the pooled tokens, in sample order."""
    samples, ctl = [], {}
    for k in range(len(off) // per):
        part = slice(k * per, (k + 1) * per)
        tokens = np.asarray(off[part], int).tolist()
        prompt = [k + 2]
        ctl[tuple(prompt + tokens[:-1])] = np.flatnonzero(control_off[part])
        samples.append({"seed": seeds[k % len(seeds)], "prompt": prompt,
                        "tokens": tokens})
    return samples, Given(ctl)


def _off(n, idx):
    out = np.zeros(n, bool)
    out[list(idx)] = True
    return out


# 320 served tokens spread as a routed-expert model's are in bf16, where a
# near-tie among the routing scores tips one token far off: the program 35
# off the reference's best, the fp8 control 179; here each off by 1.0
PROGRAM_OFF = _off(320, range(9, 320, 9))
CONTROL_OFF = _off(320, range(179))


@pytest.mark.parametrize("quantile, program, control", [
    (1.0, 1.0, 1.0),
    (0.8, 0.0, 1.0),
    (0.5, 0.0, 1.0),
    (0.4, 0.0, 0.0),
])
def test_quantile_of_the_pooled_gaps(quantile, program, control):
    assert PROGRAM_OFF.sum() == 35 and CONTROL_OFF.sum() == 179
    samples, payload = _given(PROGRAM_OFF, CONTROL_OFF)
    out = reference.check(samples, payload, {}, "bfloat16", control=True,
                          quantile=quantile)
    assert out["tokens_checked"] == 320
    assert (out["logit_gap"], out["control_logit_gap"]) == (program, control)
    assert out["program"]["share_off"] == 35 / 320
    assert out["control"]["share_off"] == 179 / 320
    assert out["program"]["widest"] == out["control"]["widest"] == 1.0
    # each replica's weights are made once
    assert payload.made == [1, 2]


def test_gaps_are_pooled_across_replicas():
    """All 35 tokens off on one replica: 35 of its 160 (over a fifth), but
    35 of the 320 pooled, which the 0.8 quantile passes."""
    off = np.zeros(320, bool)
    seed_2 = np.concatenate([np.arange(k * 40, (k + 1) * 40)
                             for k in (1, 3, 5, 7)])
    off[seed_2[:35]] = True
    samples, payload = _given(off, CONTROL_OFF)
    assert {s["seed"] for s in samples if any(s["tokens"])} == {2}
    alone = [s for s in samples if s["seed"] == 2]
    assert reference.check(alone, payload, {}, "bfloat16",
                           quantile=0.8)["logit_gap"] == 1.0
    assert reference.check(samples, payload, {}, "bfloat16",
                           quantile=0.8)["logit_gap"] == 0.0


@pytest.mark.parametrize("n, quantile", [(320, 0.8), (320, 0.99), (97, 0.75),
                                         (7, 0.5), (1, 0.3)])
def test_quantile_bounds_the_share_of_tokens_off(n, quantile):
    """The docstring's reading of a quantile: under a limit it holds exactly
    when at most floor((1 - q)(n - 1)) of n gaps lie above the limit. The
    quantile is an observed gap."""
    rng = np.random.default_rng(n)
    gaps = np.round(rng.exponential(0.1, n), 2) * (rng.random(n) < 0.5)
    read = reference.at(gaps, quantile)
    assert read in gaps
    allowed = int(np.floor((1 - quantile) * (n - 1)))
    ordered = np.sort(gaps)
    between = (ordered[:-1] + ordered[1:]) / 2
    for limit in np.unique(np.concatenate([gaps, between, [0.0, 1.0]])):
        holds = read <= limit
        assert holds == (np.sum(gaps > limit) <= allowed), limit


@pytest.mark.parametrize("quantile", [0, 1.5, "x", True, -0.2],
                         ids=["zero", "above_one", "text", "bool", "negative"])
def test_quantile_outside_zero_one_is_refused(tree, quantile):
    from bench.harness import BenchError, Cell
    from bench.tests.tiny import _edit

    def name(c):
        c["reference"]["quantile"] = quantile
        return c
    _edit(tree / "bench" / "configs" / "llm-smollm-360m.json", name)
    with pytest.raises(BenchError, match="llm-smollm-360m.json"):
        Cell.load(tree, "llm.saturate")


@pytest.mark.parametrize("quantile", [None, 1, 0.75])
def test_cell_reads_its_quantile(tree, quantile):
    from bench.harness import Cell
    from bench.tests.tiny import _edit

    def name(c):
        if quantile is not None:
            c["reference"]["quantile"] = quantile
        return c
    _edit(tree / "bench" / "configs" / "llm-smollm-360m.json", name)
    assert Cell.load(tree, "llm.saturate").quantile == \
        (1.0 if quantile is None else quantile)
