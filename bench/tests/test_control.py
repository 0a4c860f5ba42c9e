"""The control, put in the program's place, fails the committed limit at the
cells' widths: the tokens that an fp8 pass of the reference puts first go
through the same checks as the served tokens and ``correct`` comes out
false, while the program's own tokens pass. Depth is cut to two layers so
that a CPU test can hold it; the chip readings at full depth are in
PERF.md."""
import json

import pytest

from bench.tests.conftest import REPO
from bench.tests.tiny import _edit

CONFIG = "bench/configs/llm-smollm-360m.json"
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "head_dim", "vocab_size", "rms_norm_eps")


@pytest.fixture
def wide(tree):
    """The tiny tree with the configuration's own widths, epsilon and
    limit, at two layers."""
    src = json.loads((REPO / CONFIG).read_text())

    def widen(c):
        c.update({k: src[k] for k in WIDTHS})
        c["num_hidden_layers"] = 2
        # the closed loop's first requests, all that a CPU serves in time
        c["reference"] = {"tokens": 16, "limit": src["reference"]["limit"]}
        return c
    _edit(tree / CONFIG, widen)
    return tree


@pytest.mark.parametrize("control", [False, True],
                         ids=["program", "control"])
def test_control_fails_the_limit_that_the_program_meets(wide, rehearse,
                                                        control):
    out = rehearse("llm.saturate", control=control)
    gap, limit = out["checks"]["logit_gap"]
    assert out["checks"]["tokens_checked"][0] >= \
        out["checks"]["tokens_checked"][1]
    if control:
        assert out["correct"] is False and gap > limit, out["checks"]
    else:
        assert out["correct"] is True and gap <= limit, out["checks"]
