"""Run one benchmark cell on the chip this process finds.

    python bench/run.py --workload llm.saturate --seed 1 --seconds 30 --trace 0

The cell, its configuration, traffic mix and metrics are read from
``BENCHMARK.json`` at the root of the checkout. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``), then
``checks``, each number compared with its limit. The same checks are the last
lines of standard error. It exits non-zero, with no result line, when JAX
finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
# the TPU runtime logs to /tmp/tpu_logs unless told otherwise: keep every
# write inside the checkout
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "bench" / "out" / "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the fp8 control in the program's place "
                         "(correct must come out false)")
    args = ap.parse_args(argv)
    from bench.harness import BenchError, run

    try:
        out = run(ROOT, args.workload, args.seed, args.seconds,
                  bool(args.trace), T_START, control=bool(args.control))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
