"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Single-host execution with the real data pipeline and checkpoint/restart.
``--arch`` trains that model at its published widths and depth;
``--smoke`` trains its reduced smoke config.  At production scale the
same factories are consumed by the dry-run (``repro.launch.dryrun``) with
the 16x16 / 2x16x16 meshes — this CLI is the runnable end of the same
code path.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_arch_config, list_archs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.substrate.data import DataConfig, DataPipeline
from repro.training.checkpoint import Checkpointer
from repro.training.optim import OptimizerConfig
from repro.training.train import TrainConfig, init_state, make_train_step


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rhapsody-demo",
                    choices=list_archs() + ["rhapsody-demo"])
    ap.add_argument("--smoke", action="store_true",
                    help="use the arch's reduced smoke config instead")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--quantize-opt", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    cfg = get_arch_config(args.arch, smoke=args.smoke)
    api = get_model(cfg)
    opt = OptimizerConfig(lr=args.lr, warmup_steps=max(1, args.steps // 20),
                          decay_steps=args.steps,
                          quantize_states=args.quantize_opt)
    tcfg = TrainConfig(global_batch=args.batch, seq_len=args.seq,
                       microbatches=args.microbatches, optimizer=opt,
                       checkpoint_every=args.ckpt_every)
    data = DataPipeline(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                   global_batch=args.batch))
    ck = Checkpointer(args.ckpt_dir, keep=3) if args.ckpt_dir else None

    state, _ = init_state(jax.random.PRNGKey(0), api, cfg, opt)
    start = 0
    if args.resume and ck is not None:
        restored, start = ck.restore_latest({"state": state,
                                             "data": data.state()})
        if restored is not None:
            state = restored["state"]
            data.restore(jax.tree.map(int, restored["data"]))
            print(f"[train] resumed from step {start}")

    step_fn = make_train_step(api, cfg, tcfg)
    t0 = time.perf_counter()
    tokens_done = 0
    for i in range(start, args.steps):
        batch = data.next_batch()
        state, metrics = step_fn(state, batch)
        tokens_done += args.batch * args.seq
        if i % args.log_every == 0 or i == args.steps - 1:
            dt = time.perf_counter() - t0
            print(f"[train] step {i:5d} loss {float(metrics['loss']):.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"{tokens_done / max(dt, 1e-9):.0f} tok/s", flush=True)
        if ck is not None and (i + 1) % tcfg.checkpoint_every == 0:
            ck.save({"state": state, "data": data.state()}, i + 1)
    print(f"[train] done: {args.steps - start} steps, arch={cfg.name}")


if __name__ == "__main__":
    main()
