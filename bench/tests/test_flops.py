"""The llama payload's step counts against hand counts for smollm-360m, and
the per-layer readers counting through the payload that the run loaded."""
import json
from types import SimpleNamespace

from bench.harness import Record, Step, _load_module, arch_of
from bench.payloads import llama
from bench.tests.conftest import REPO

ARCH = arch_of(json.loads(
    (REPO / "bench" / "configs" / "llm-smollm-360m.json").read_text()), llama)


def test_weights_match_the_published_parameter_count():
    # per layer: q 960x960, k and v 960x320, o 960x960, gate/up/down
    # 3x960x2560 = 9,830,400; 32 layers plus the tied 49,152 x 960 head
    assert llama.matmul_weights(ARCH) == 32 * 9_830_400 + 47_185_920
    # 2 norms a layer and the final one, 960 each: 361,821,120 in all
    assert llama.matmul_weights(ARCH) + llama.norm_weights(ARCH) \
        == 361_821_120


def test_token_flops():
    # 2 per matmul weight, plus QK^T and AV: 4 x 32 layers x 15 heads x 64
    assert llama.token_flops(ARCH, 0) == 723_517_440
    assert llama.token_flops(ARCH, 1000) == 723_517_440 + 122_880 * 1000
    assert llama.step_flops(ARCH, [9, 99]) == \
        llama.token_flops(ARCH, 10) + llama.token_flops(ARCH, 100)


def test_step_bytes():
    # K and V of one position: 2 x 32 layers x 5 heads x 64 x 2 bytes
    assert llama.kv_row_bytes(ARCH, "bfloat16") == 40_960
    weights = 361_758_720 * 2 + 62_400 * 4
    assert llama.step_bytes(ARCH, "bfloat16", []) == weights
    assert llama.step_bytes(ARCH, "bfloat16", [100, 200]) == \
        weights + 40_960 * 300 + 40_960 * 2


def _reader(metric):
    return _load_module(REPO / "bench" / "metrics" / f"{metric}.py",
                        metric).read


def test_flops_reads_the_payload_that_arch_names():
    """The readers take the step counts from ``Record.payload``, with the
    record's ``arch``: a payload of other counts gives other readings."""
    seen = []

    def count(name, n):
        def f(arch, *args):
            seen.append((name, arch))
            return n
        return f
    stub = SimpleNamespace(step_flops=count("flops", 2e9),
                           step_bytes=count("bytes", 4e8))
    rec = Record(seconds=51.0, arch=ARCH, payload=stub,
                 peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
                 steps=[Step(1.0, 1.5, [9], 1), Step(2.0, 2.5, [99], 1)],
                 spans={"trace": [(0.0, 10.0)]},
                 trace={"modules": {"jit_decode_step": (2, 0.05)}})
    assert _reader("decode_mfu")(rec) == 100.0 * 2 * 2e9 / 1e13
    # the step needs max(4e8 / 1e11, 2e9 / 1e12) = 4 ms, run 2 x in 50 ms
    assert _reader("decode_step_roofline")(rec) == 100.0 * 0.004 * 2 / 0.05
    assert {n for n, _ in seen} == {"flops", "bytes"}
    assert all(a is ARCH for _, a in seen)


def test_decode_mfu_counts_the_traced_span_only():
    """A traced run's steps after the profiler stopped, and the rest of the
    window, are left out of the FLOPs and of the time alike."""
    read = _reader("decode_mfu")
    rec = Record(seconds=51.0, arch=ARCH, payload=llama,
                 peaks={"bf16_flops_per_s": 1e12},
                 steps=[Step(1.0, 1.5, [9], 1), Step(9.0, 9.9, [99], 1),
                        Step(9.9, 10.1, [5], 1), Step(20.0, 20.1, [7], 1)],
                 spans={"trace": [(0.0, 10.0)]})
    assert read(rec) == 100.0 * (llama.step_flops(ARCH, [9])
                                 + llama.step_flops(ARCH, [99])) / 1e13
    assert read(Record(seconds=51.0, arch=ARCH, payload=llama,
                       peaks=rec.peaks,
                       steps=rec.steps)) is None
