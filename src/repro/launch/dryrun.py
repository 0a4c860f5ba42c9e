import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST run before any jax import: jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent (sharding
propagates, collectives legal, no compile-time OOM) and records the numbers
§Roofline consumes: memory_analysis, cost_analysis (FLOPs/bytes) and the
per-collective byte counts parsed from the optimized HLO.

Usage (one cell per process — keeps compiler memory bounded, enables
parallel sweeps on a real workstation):

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b \
        --shape train_4k --mesh single [--scan] [--out results/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]

Single-pod cells default to *exact-cost mode* (unrolled layer stack — XLA's
cost_analysis counts scan bodies once, unrolling makes FLOP/byte/collective
totals exact). Multi-pod cells default to scan mode: they exist to prove the
pod axis shards, the roofline table is single-pod (EXPERIMENTS.md §Dry-run).
"""
import argparse
import json
import re
import time
import traceback

from repro.launch.hlo_parse import parse_collectives  # noqa: E402


def _probe_variants(cfg):
    """Probe configs for exact cost extrapolation.

    XLA's cost_analysis counts a lax.scan body once, so the full-depth scan
    compile underreports FLOPs/bytes/collective counts. All our layer stacks
    are homogeneous, so per-device cost is exactly affine in the layer count:
    cost(L) = fixed + L * per_layer. We compile tiny *unrolled* probes (with
    full-window attention and unrolled recurrence chunks, so nothing hides in
    a loop body) and solve for (fixed, per_layer). Whisper has two stacks ->
    three probes. Returns (variants, solver) where variants is a list of
    (tag, cfg) and solver maps {tag: cost} -> extrapolated cost.
    """
    import dataclasses
    if cfg.family == "audio":
        e = cfg.enc_dec
        v = [("p11", dataclasses.replace(
                 cfg, n_layers=1,
                 enc_dec=dataclasses.replace(e, n_encoder_layers=1))),
             ("p21", dataclasses.replace(
                 cfg, n_layers=1,
                 enc_dec=dataclasses.replace(e, n_encoder_layers=2))),
             ("p12", dataclasses.replace(
                 cfg, n_layers=2,
                 enc_dec=dataclasses.replace(e, n_encoder_layers=1)))]

        def solve(c):
            f_enc = c["p21"] - c["p11"]
            f_dec = c["p12"] - c["p11"]
            fixed = c["p11"] - f_enc - f_dec
            return fixed + e.n_encoder_layers * f_enc + cfg.n_layers * f_dec
        return v, solve
    if cfg.family == "hybrid":
        k = cfg.ssm.attn_every
        v = [("p1", dataclasses.replace(cfg, n_layers=k)),
             ("p2", dataclasses.replace(cfg, n_layers=2 * k))]
        n_blocks = cfg.n_layers // k
    else:
        v = [("p1", dataclasses.replace(cfg, n_layers=1)),
             ("p2", dataclasses.replace(cfg, n_layers=2))]
        n_blocks = cfg.n_layers

    def solve(c):
        body = c["p2"] - c["p1"]
        fixed = c["p1"] - body
        return fixed + n_blocks * body
    return v, solve


def _collect(compiled) -> dict:
    ca = compiled.cost_analysis() or {}
    colls = parse_collectives(compiled.as_text())
    summary = {}
    for c in colls:
        s = summary.setdefault(c["kind"], {"count": 0, "bytes": 0})
        s["count"] += 1
        s["bytes"] += c["bytes"]
        s["group"] = c["group"] or s.get("group")
    return {"flops": ca.get("flops", 0.0),
            "bytes_accessed": ca.get("bytes accessed", 0.0),
            "collectives": summary}


def _parse_overrides(txt: str) -> dict:
    out = {}
    for kv in filter(None, txt.split(",")):
        k, v = kv.split("=")
        if v in ("true", "True"):
            v = True
        elif v in ("false", "False"):
            v = False
        elif v in ("none", "None"):
            v = None
        else:
            try:
                v = int(v)
            except ValueError:
                try:
                    v = float(v)
                except ValueError:
                    pass
        out[k] = v
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             exact_costs: bool, out_dir: str,
             run_overrides: dict | None = None, tag: str = "") -> dict:
    import jax
    from repro.configs import get_config, applicable_shapes
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_step, default_run_config
    from repro.configs.base import SHAPES

    cfg = get_config(arch)
    if shape_name not in applicable_shapes(cfg):
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "status": "skipped",
               "reason": "full-attention arch: long_500k inapplicable "
                         "(DESIGN.md §Arch-applicability)"}
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir,
                               f"{arch}_{shape_name}_{mesh_kind}.json"),
                  "w") as fh:
            json.dump(rec, fh, indent=1)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    shape = SHAPES[shape_name]
    t0 = time.time()
    run_overrides = run_overrides or {}
    if "data_axes" in run_overrides and isinstance(run_overrides["data_axes"], str):
        run_overrides["data_axes"] = tuple(run_overrides["data_axes"].split("+"))
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "n_devices": mesh.size, "exact_costs": exact_costs,
           "overrides": {k: str(v) for k, v in run_overrides.items()},
           "tag": tag}
    try:
        # -- full-depth compile: feasibility proof + memory analysis ------
        with jax.set_mesh(mesh):
            base_rc = default_run_config(mesh, shape, **run_overrides)
            fn, args, meta = build_step(arch, shape_name, mesh,
                                        run_cfg=base_rc)
            lowered = fn.lower(*args)
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)

        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
        }
        rec["cost_scan_raw"] = _collect(compiled)
        del compiled, lowered

        # -- probe compiles: exact per-layer costs, affine extrapolation ---
        if exact_costs:
            variants, solve = _probe_variants(cfg)
            probe_costs = {}
            seq_full = max(shape.seq_len if shape.kind != "decode" else 1, 1)
            for ptag, pcfg in variants:
                run_cfg = default_run_config(
                    mesh, shape, **dict(run_overrides, layer_mode="unroll",
                                        q_chunk=max(seq_full, 128),
                                        kv_chunk=max(seq_full, 128),
                                        seq_chunk=512))
                with jax.set_mesh(mesh):
                    pfn, pargs, _ = build_step(arch, shape_name, mesh,
                                               run_cfg=run_cfg,
                                               cfg_override=pcfg)
                    pcompiled = pfn.lower(*pargs).compile()
                probe_costs[ptag] = _collect(pcompiled)
                del pcompiled

            def solve_field(get):
                return solve({t: get(probe_costs[t]) for t in probe_costs})

            coll_kinds = set()
            for c in probe_costs.values():
                coll_kinds |= set(c["collectives"])
            rec["cost"] = {
                "flops": solve_field(lambda c: c["flops"]),
                "bytes_accessed": solve_field(lambda c: c["bytes_accessed"]),
                "collectives": {
                    k: {"bytes": solve_field(
                            lambda c: c["collectives"].get(k, {}).get("bytes", 0)),
                        "count": solve_field(
                            lambda c: c["collectives"].get(k, {}).get("count", 0)),
                        "group": max((c["collectives"].get(k, {}).get("group")
                                      or 0) for c in probe_costs.values())}
                    for k in coll_kinds},
                "method": "probe-extrapolated (exact for homogeneous stacks)",
            }
        else:
            rec["cost"] = dict(rec["cost_scan_raw"],
                               method="scan-raw (bodies counted once)")

        rec["status"] = "ok"
        rc = meta["run_cfg"]
        rec["run_cfg"] = {"layer_mode": rc.layer_mode,
                          "q_chunk": rc.q_chunk, "kv_chunk": rc.kv_chunk,
                          "seq_chunk": rc.seq_chunk,
                          "capacity_factor": rc.moe_capacity_factor}
        print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: OK "
              f"(compile {rec['compile_s']}s, "
              f"flops/dev {rec['cost']['flops']:.3e}, "
              f"temp {rec['memory']['temp_bytes']/2**30:.2f} GiB)")
        print(f"  memory_analysis: {ma}")
        print(f"  collectives: {rec['cost'].get('collectives')}")
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: FAIL {rec['error']}")

    os.makedirs(out_dir, exist_ok=True)
    suffix = f"_{tag}" if tag else ""
    fname = f"{arch}_{shape_name}_{mesh_kind}{suffix}.json"
    with open(os.path.join(out_dir, fname), "w") as fh:
        json.dump(rec, fh, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="")
    ap.add_argument("--shape", type=str, default="")
    ap.add_argument("--mesh", type=str, default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--scan", action="store_true",
                    help="force scan layer mode (fast compile, inexact costs)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", type=str, default="results/dryrun")
    ap.add_argument("--override", type=str, default="",
                    help="RunConfig overrides, e.g. sharded_decode=true")
    ap.add_argument("--tag", type=str, default="")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    from repro.configs import all_arch_names, applicable_shapes, get_config

    if args.all:
        cells = []
        for arch in all_arch_names():
            for shape in applicable_shapes(get_config(arch)):
                cells.append((arch, shape))
    else:
        cells = [(args.arch, args.shape)]

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for arch, shape in cells:
        for mesh_kind in meshes:
            exact = (mesh_kind == "single") and not args.scan
            fname = os.path.join(args.out, f"{arch}_{shape}_{mesh_kind}.json")
            if args.skip_existing and os.path.exists(fname):
                with open(fname) as fh:
                    if json.load(fh).get("status") in ("ok", "skipped"):
                        print(f"[dryrun] skip existing {fname}")
                        continue
            run_cell(arch, shape, mesh_kind, exact, args.out,
                     run_overrides=_parse_overrides(args.override),
                     tag=args.tag)


if __name__ == "__main__":
    main()
