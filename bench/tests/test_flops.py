"""``bench/flops.py`` against hand counts for smollm-360m."""
import json

from bench import flops
from bench.harness import arch_of
from bench.tests.conftest import REPO

ARCH = arch_of(json.loads(
    (REPO / "bench" / "configs" / "llm-smollm-360m.json").read_text()))


def test_weights_match_the_published_parameter_count():
    # per layer: q 960x960, k and v 960x320, o 960x960, gate/up/down
    # 3x960x2560 = 9,830,400; 32 layers plus the tied 49,152 x 960 head
    assert flops.matmul_weights(ARCH) == 32 * 9_830_400 + 47_185_920
    # 2 norms a layer and the final one, 960 each: 361,821,120 in all
    assert flops.matmul_weights(ARCH) + flops.norm_weights(ARCH) \
        == 361_821_120


def test_token_flops():
    # 2 per matmul weight, plus QK^T and AV: 4 x 32 layers x 15 heads x 64
    assert flops.token_flops(ARCH, 0) == 723_517_440
    assert flops.token_flops(ARCH, 1000) == 723_517_440 + 122_880 * 1000
    assert flops.step_flops(ARCH, [9, 99]) == \
        flops.token_flops(ARCH, 10) + flops.token_flops(ARCH, 100)


def test_step_bytes():
    # K and V of one position: 2 x 32 layers x 5 heads x 64 x 2 bytes
    assert flops.kv_row_bytes(ARCH, "bfloat16") == 40_960
    weights = 361_758_720 * 2 + 62_400 * 4
    assert flops.step_bytes(ARCH, "bfloat16", []) == weights
    assert flops.step_bytes(ARCH, "bfloat16", [100, 200]) == \
        weights + 40_960 * 300 + 40_960 * 2
