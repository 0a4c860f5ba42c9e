"""Every cell runs end to end at a tiny size on the CPU, through the same
harness as on the chip; the command itself refuses to run without a TPU."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

from bench.tests.conftest import REPO

CELLS = [w["name"] for w in
         json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(rehearse, workload):
    out = rehearse(workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    want = {m["name"] for m in spec["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"


def test_traced_run_reports_host_side_layer_metrics(rehearse):
    out = rehearse("llm.saturate", trace=True)
    assert out["correct"]
    # the CPU trace has no TPU plane: device metrics are left out, not 0
    assert "decode_step_roofline" not in out["metrics"]
    assert "device_idle_share.saturate" not in out["metrics"]
    assert out["metrics"]["batcher_step_ms.saturate"]["value"] > 0
    assert out["metrics"]["decode_mfu"]["value"] > 0


def test_command_refuses_without_tpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench" / "run.py"), "--workload",
         "llm.saturate", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path)})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_command_refuses_in_a_bare_directory(tmp_path):
    """Only BENCHMARK.json and bench/ present: the program is missing."""
    import shutil

    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "llm.saturate",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=tmp_path,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path)})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_new_mix_is_new_files_only(tree, rehearse):
    """A cell with a mix the benchmark has never seen, added as one data
    file and one BENCHMARK.json entry: no other file changes."""
    mix = {"kind": "open_poisson", "schedule_seed": 3,
           "params": {"rate": 6.0,
                      "prompt": {"dist": "uniform", "min": 3, "max": 9},
                      "output": {"dist": "uniform", "min": 4, "max": 10}}}
    (tree / "bench" / "traffic" / "throwaway.json").write_text(
        json.dumps(mix))
    spec = json.loads((tree / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "llm.throwaway",
                              "config": "llm-smollm-360m",
                              "traffic": "throwaway", "chips": 1,
                              "why": "test"})
    p50 = [m for m in spec["end_to_end"] if m["name"] == "invoke_p50_ms"]
    if p50:
        p50[0]["workloads"].append("llm.throwaway")
    else:
        spec["end_to_end"].append({"name": "invoke_p50_ms", "unit": "ms",
                                   "better": "lower", "bound": 0.25,
                                   "source": "host_clock",
                                   "workloads": ["llm.throwaway"]})
    (tree / "BENCHMARK.json").write_text(json.dumps(spec))
    out = rehearse("llm.throwaway")
    assert out["correct"]
    assert set(out["metrics"]) == {"setup_s", "invoke_p50_ms"}


@pytest.mark.parametrize("payload", [None, "nowhere"],
                         ids=["no_key", "no_module"])
def test_configuration_without_its_payload_is_refused(tree, payload):
    from bench.harness import BenchError, Cell
    from bench.tests.tiny import _edit

    def name(c):
        c.pop("payload")
        return c if payload is None else dict(c, payload=payload)
    _edit(tree / "bench" / "configs" / "llm-smollm-360m.json", name)
    with pytest.raises(BenchError, match="llm-smollm-360m" if payload is None
                       else "nowhere.py"):
        Cell.load(tree, "llm.saturate")


# A payload of another arithmetic than llama's: the same decoder, with an
# untied output head made as the program makes ``lm_head``. BROKEN forgets
# that head and reads the logits through the embedding, as llama does.
THROWAWAY = '''
import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.payloads import llama
from bench.reference import mm

BROKEN = {broken}
KEYS, TINY, WIDTHS = llama.KEYS, llama.TINY, llama.WIDTHS
step_bytes, step_flops = llama.step_bytes, llama.step_flops
# logit gap of the untied head at TINY on the CPU, at the quantile its
# configuration names (0.75), over twelve seeds: served bf16 0 on every seed
# (widest 0 to 0.035: its logits are some six times the tied head's, and so
# are its gaps), BROKEN 0.577 to 0.695 (widest 0.698 to 0.971)
TINY_LIMIT = 0.1


def program_config(base, cfg):
    tied = llama.program_config(base, dict(cfg, tie_word_embeddings=True))
    return dataclasses.replace(tied, tie_embeddings=False)


def init_params(seed, arch, dtype):
    d, v = arch["hidden_size"], arch["vocab_size"]
    key = jax.random.split(jax.random.PRNGKey(seed), 8)[3]
    head = (jax.random.normal(key, (d, v)) * (1.0 / math.sqrt(d)))
    return dict(llama.init_params(seed, arch, dtype),
                lm_head=head.astype(dtype).astype(jnp.float32))


@partial(jax.jit, static_argnums=(2, 3))
def _forward(params, tokens, arch_items, fp8):
    x = llama.decoder(params, tokens, dict(arch_items), fp8)
    head = params["embed"].T if BROKEN else params["lm_head"]
    return mm("sd,dv->sv", x, head, fp8)


def forward(params, tokens, arch, fp8=False):
    n = len(tokens)
    padded = np.zeros((-(-n // 128) * 128,), np.int32)
    padded[:n] = tokens
    out = _forward(params, jnp.asarray(padded), tuple(sorted(arch.items())),
                   fp8)
    return np.asarray(out[:n], np.float32)
'''



def _files(root):
    return {p.relative_to(root): p.read_bytes() for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "broken"])
def test_new_configuration_is_new_files_only(tree, rehearse, monkeypatch,
                                             broken):
    """A configuration of an architecture the benchmark has never seen,
    added as its payload file, its configuration file naming it, and
    entries in BENCHMARK.json: no other file changes. It is judged at the
    quantile it names, which the CPU form keeps. With the payload's logits
    broken, the same cell is not correct."""
    from bench import reference
    from bench.tests.tiny import tiny_config

    before = _files(tree)
    (tree / "bench" / "payloads" / "throwaway.py").write_text(
        THROWAWAY.format(broken=broken))
    cfg = json.loads((tree / "bench" / "configs"
                      / "llm-smollm-360m.json").read_text())
    cfg.update(name="llm-throwaway", payload="throwaway",
               tie_word_embeddings=False)
    cfg["reference"]["quantile"] = 0.75
    path = tree / "bench" / "configs" / "llm-throwaway.json"
    path.write_text(json.dumps(cfg))
    tiny_config(tree, path)
    ref = json.loads(path.read_text())["reference"]
    assert (ref["limit"], ref["quantile"]) == (0.1, 0.75)
    spec = json.loads((tree / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "llm-throwaway",
                            "file": "bench/configs/llm-throwaway.json",
                            "source": "test", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "throwaway.saturate",
                              "config": "llm-throwaway",
                              "traffic": "closed_48", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "tokens_per_s":
            m["workloads"].append("throwaway.saturate")
    (tree / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _files(tree)
    assert {k for k in before if before[k] != after[k]} == \
        {Path("BENCHMARK.json")}

    judged = []
    check = reference.check

    def spy(*args, **kw):
        judged.append(kw["quantile"])
        return check(*args, **kw)
    monkeypatch.setattr(reference, "check", spy)
    out = rehearse("throwaway.saturate")
    assert judged == [0.75]
    assert out["correct"] is not broken, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "tokens_per_s"}
