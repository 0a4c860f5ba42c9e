"""CPU rehearsal of chip_smoke.py: its serving routine at a reduced size, and
its refusal to report anything without a TPU."""
import dataclasses
import importlib.util
from pathlib import Path

import pytest

from repro.configs import get_config

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

# smollm's family and GQA ratio at a CPU size, in the served dtype
TINY_BF16 = dataclasses.replace(
    get_config("smollm-360m").reduced(n_layers=2, d_model=96, n_heads=6,
                                      n_kv_heads=2, d_ff=128, vocab=128),
    dtype="bfloat16")
SMALL = dict(n_requests=6, prompt_lens=(4, 12), max_new=5, max_seq=64,
             max_slots=4)


def test_serve_reduced_config_serves_all_and_matches_reference():
    out = chip_smoke.serve(TINY_BF16, **SMALL)
    assert out["requests"] == 6
    assert out["tokens"] == 6 * 5
    assert out["decode_steps"] > 0
    assert len(out["cold_create_ms"]) == 1       # one compile, then warm
    assert out["n_reference"] == 2
    assert out["max_rel_err"] <= chip_smoke.REF_TOL


def test_serve_fails_when_reference_disagrees():
    with pytest.raises(chip_smoke.SmokeFailure, match="float32 reference"):
        chip_smoke.serve(TINY_BF16, tol=0.0, **SMALL)


def test_main_exits_nonzero_without_tpu(capsys):
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert "no TPU found" in err
    assert out == ""                     # no result line, no JSON
