"""The control, put in the program's place, fails the committed limit at the
cells' widths: the tokens that an fp8 pass of the reference puts first go
through the same checks as the served tokens and ``correct`` comes out
false, while the program's own tokens pass. The widths are the
configuration's own (its payload's ``WIDTHS``); the rest stays at the
payload's ``TINY`` sizes, such as a depth that a CPU test can hold. The chip
readings at full size are in PERF.md."""
import json

import pytest

from bench.harness import load_payload
from bench.tests.conftest import REPO
from bench.tests.tiny import _edit

CONFIG = "bench/configs/llm-smollm-360m.json"


@pytest.fixture
def wide(tree):
    """The tiny tree with the configuration's own widths and reference
    block (its limit, and its quantile where it names one)."""
    src = json.loads((REPO / CONFIG).read_text())
    widths = load_payload(tree, src).WIDTHS

    def widen(c):
        c.update({k: src[k] for k in widths})
        # the closed loop's first requests, all that a CPU serves in time
        c["reference"] = dict(src["reference"], tokens=16)
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
