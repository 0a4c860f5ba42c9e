"""A copy of the benchmark at a tiny size, for rehearsals on the CPU.

``make_tree(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` into ``tmp``
and rewrites each configuration there to its payload's tiny sizes and
limit (the payload file's ``TINY`` and ``TINY_LIMIT``) and each mix to short
requests, so that every cell runs in seconds through the same harness.
The CPU gets a made-up entry in the copied peaks table: numbers read against
it are rehearsals, never device metrics.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_LENGTHS = {
    "prompt": {"dist": "lognormal", "median": 8, "sigma": 0.6,
               "min": 2, "max": 24},
    "output": {"dist": "uniform", "min": 2, "max": 8}}


def _edit(path: Path, fn) -> None:
    path.write_text(json.dumps(fn(json.loads(path.read_text())), indent=1))


def tiny_config(root: Path, path: Path) -> None:
    """Rewrite the configuration file at ``path`` in the tree at ``root`` to
    its payload's ``TINY`` sizes and ``TINY_LIMIT``, and a small cluster.
    Of the ``reference`` block only ``tokens`` and ``limit`` change: its
    ``quantile`` stays the configuration's."""
    from bench.harness import load_payload

    def tiny(c):
        payload = load_payload(root, c)
        c.update(payload.TINY)
        c["cluster"].update(slots=4, max_seq=64)
        c["drain_cap_s"] = 20
        c["reference"].update(tokens=32, limit=payload.TINY_LIMIT)
        return c
    _edit(path, tiny)


def make_tree(tmp: Path) -> Path:
    root = Path(tmp) / "tree"
    root.mkdir(parents=True, exist_ok=True)
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__",
                                                  "tests"),
                    dirs_exist_ok=True)
    bench = root / "bench"

    for path in (bench / "configs").glob("*.json"):
        tiny_config(root, path)

    def tiny_mix(t):
        p = t["params"]
        for k in ("prompt", "output"):
            if k in p:
                p[k] = TINY_LENGTHS[k]
        if "clients" in p:
            p["clients"] = 6
        if "rate" in p:
            p["rate"] = 4.0
        if "timer" in p:
            p["timer"]["period_s"] = 1.5
            p["timer"]["phase_s"] = 0.2
            p["timer"]["functions"] = p["timer"]["functions"][:4]
        return t

    for path in (bench / "traffic").glob("*.json"):
        _edit(path, tiny_mix)
    _edit(bench / "peaks.json", lambda p: {**p, "cpu": {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
        "hbm_bytes": 1e10, "source": "made up for CPU rehearsals"}})
    return root


# cells whose data the benchmark keeps for later (PERF.md, Open questions):
# (configuration, mix, end-to-end metrics besides setup_s)
LATER = {
    "llm.steady": ("llm-smollm-360m", "poisson_steady",
                   ["invoke_p50_ms", "invoke_p95_ms"]),
    "faas.burst": ("faas-azure-smollm-360m", "azure_unison",
                   ["invoke_p50_ms", "invoke_p95_ms", "cold_invoke_p50_ms"]),
}


def add_cell(root: Path, name: str) -> str:
    """Name a cell of ``LATER`` in the tree's ``BENCHMARK.json``, as a
    later PR would: entries only, every file it reads already there."""
    config, traffic, metrics = LATER[name]
    path = root / "BENCHMARK.json"
    spec = json.loads(path.read_text())
    if name in {w["name"] for w in spec["workloads"]}:
        return name
    if config not in {c["name"] for c in spec["configs"]}:
        spec["configs"].append({"name": config,
                                "file": f"bench/configs/{config}.json",
                                "source": "", "reduced": [], "why": ""})
    spec["workloads"].append({"name": name, "config": config,
                              "traffic": traffic, "chips": 1, "why": ""})
    have = {m["name"]: m for m in spec["end_to_end"]}
    for m in metrics:
        if m not in have:
            have[m] = {"name": m, "unit": "ms", "better": "lower",
                       "bound": 0.25, "source": "host_clock",
                       "workloads": []}
            spec["end_to_end"].append(have[m])
        have[m]["workloads"].append(name)
    path.write_text(json.dumps(spec))
    return name
