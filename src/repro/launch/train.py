"""Training launcher.

On real hardware this runs the full config on the production mesh; on this
CPU container use ``--reduced`` for an actually-executing run (the full
configs are exercised via launch/dryrun.py). Supports checkpoint/restart
(``--resume``), microbatching, remat, and int8 gradient compression over the
DP axis (``--grad-compression``, shard_map path).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \
      --steps 50 --resume --ckpt-dir /tmp/ckpt   # restart from latest
"""
from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.data.pipeline import TokenPipeline
from repro.distributed import sharding as shd
from repro.models.registry import get_model
from repro.training import checkpoint as ckpt
from repro.training.compression import compress_psum, ef_init
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import init_train_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = get_model(cfg)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5 + 1),
                          total_steps=args.steps)
    step_fn = make_train_step(model, opt_cfg, microbatches=args.microbatches,
                              remat=not args.no_remat)

    if args.grad_compression:
        step_fn = _wrap_with_compression(model, opt_cfg, args)

    step_fn = jax.jit(step_fn, donate_argnums=0)

    state = init_train_state(model, jax.random.PRNGKey(args.seed))
    start = 0
    if args.resume and args.ckpt_dir:
        last = ckpt.latest_step(args.ckpt_dir)
        if last is not None:
            like = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
            state, manifest = ckpt.restore(args.ckpt_dir, last, like)
            start = last
            print(f"resumed from step {last}")

    pipe = iter(TokenPipeline(cfg, args.batch, args.seq, seed=args.seed))
    t0 = time.time()
    for step in range(start, args.steps):
        batch = next(pipe)
        state, metrics = step_fn(state, batch)
        if (step + 1) % args.log_every == 0 or step == start:
            loss = float(metrics["loss"])
            tok_s = args.batch * args.seq * (step + 1 - start) / (time.time() - t0)
            print(f"step {step + 1:5d}  loss {loss:.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"lr {float(metrics['lr']):.2e}  tok/s {tok_s:,.0f}",
                  flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, step + 1, state,
                      extra={"arch": args.arch, "reduced": args.reduced})
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, args.steps, state,
                  extra={"arch": args.arch, "reduced": args.reduced})
    print("done")
    return state


def _wrap_with_compression(model, opt_cfg, args):
    """DP train step with int8 error-feedback gradient all-reduce inside
    shard_map (beyond-paper distributed-optimization option)."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh

    from repro.training.optimizer import adamw_update
    from repro.training.train_step import make_loss_fn

    mesh = make_mesh((jax.device_count(),), ("dp",))
    loss_fn = make_loss_fn(model, remat=not args.no_remat)

    def step(state, batch):
        def local(state, batch, residuals):
            (loss, extras), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state["params"], batch)
            grads, new_res = compress_psum(grads, residuals, "dp")
            new_params, new_opt, om = adamw_update(
                opt_cfg, state["params"], grads, state["opt"])
            loss = jax.lax.pmean(loss, "dp")
            return ({"params": new_params, "opt": new_opt, "ef": new_res},
                    {"loss": loss, **extras, **om})

        inner = jax.shard_map(
            local, mesh=mesh,
            in_specs=({"params": P(), "opt": P(), "ef": P()},
                      jax.tree.map(lambda _: P("dp"), batch), P()),
            out_specs=({"params": P(), "opt": P(), "ef": P()}, P()),
            check_vma=False)
        st = dict(state)
        residuals = st.pop("ef", None)
        if residuals is None:
            residuals = ef_init(state["params"])
        return inner(st, batch, residuals)

    return step


if __name__ == "__main__":
    main()
