"""Training launcher: any assigned arch, any mesh, checkpoint/restart.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \
        --steps 100 [--resume] [--zero1] [--grad-accum 4] [--compress]

Full-size configs lower onto the local mesh (use a TPU host); --reduced runs
the same code path with the smoke-test config (CPU-friendly). Checkpoints are
written via the elastic-reshard-capable store (train/checkpoint.py), so a
restart may use a different device count.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_config
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import default_run_config
from repro.models.api import build_model
from repro.train.checkpoint import (latest_step, restore_checkpoint,
                                    save_checkpoint)
from repro.train.data import ZipfLMStream
from repro.train.optimizer import adamw_init
from repro.train.train_step import make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--compress", action="store_true",
                    help="int8 stochastic-rounding gradient codec")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-dir", type=str, default="results/train_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(n_layers=4, d_model=128, n_heads=4, d_ff=384,
                          vocab=2048)
    mesh = make_local_mesh()
    with jax.set_mesh(mesh):
        run = default_run_config(
            mesh, None, q_chunk=64, kv_chunk=64, seq_chunk=16,
            grad_accum=args.grad_accum, use_zero1=args.zero1,
            grad_compress=args.compress)
        model = build_model(cfg, run)
        params = model.init_params(jax.random.PRNGKey(args.seed))
        opt = adamw_init(params)
        n = sum(x.size for x in jax.tree.leaves(params))
        print(f"[train] {cfg.name}: {n/1e6:.1f}M params on {mesh.shape} mesh")

        start = 0
        if args.resume and latest_step(args.ckpt_dir) is not None:
            (state, start) = restore_checkpoint(
                args.ckpt_dir, None, {"params": params, "opt": opt})
            params, opt = state["params"], state["opt"]
            print(f"[train] resumed from step {start}")

        step_fn = jax.jit(make_train_step(model, lr=args.lr))
        stream = ZipfLMStream(vocab=cfg.vocab, seq=args.seq,
                              batch=args.batch, seed=args.seed + 1)
        t0 = time.time()
        for i in range(start, start + args.steps):
            params, opt, m = step_fn(params, opt, stream.batch_at(i),
                                     jax.random.PRNGKey(i))
            if (i + 1) % args.ckpt_every == 0:
                save_checkpoint(args.ckpt_dir, i + 1,
                                {"params": params, "opt": opt},
                                async_save=True)
            if (i + 1) % 20 == 0:
                tps = args.batch * args.seq * 20 / (time.time() - t0)
                t0 = time.time()
                print(f"[train] step {i+1:5d} loss {float(m['loss']):.4f} "
                      f"gnorm {float(m['grad_norm']):.2f} {tps:,.0f} tok/s")
        save_checkpoint(args.ckpt_dir, start + args.steps,
                        {"params": params, "opt": opt})
        print(f"[train] done at step {start + args.steps}")


if __name__ == "__main__":
    main()
