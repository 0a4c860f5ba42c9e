"""The benchmark harness reports a failed module through its exit code."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks import fig1_burst, run  # noqa: E402


def test_harness_exits_nonzero_when_a_module_fails(monkeypatch):
    def boom(reporter, quick=True):
        raise RuntimeError("module broke")

    monkeypatch.setattr(fig1_burst, "run", boom)
    monkeypatch.setattr(sys, "argv", ["run.py", "--only", "fig1"])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code and "fig1" in str(exc.value.code)
