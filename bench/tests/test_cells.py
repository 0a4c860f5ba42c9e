"""Every cell runs end to end at a tiny size on the CPU, through the same
harness as on the chip; the command itself refuses to run without a TPU."""
import json
import subprocess
import sys

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
