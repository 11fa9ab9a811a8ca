"""End-to-end training driver.

Runs on whatever devices exist: single CPU (examples/smoke), a forced
multi-device host, or a real fleet.  Features: deterministic resumable
data, atomic checkpoints + auto-resume, straggler watchdog, optional
cross-pod int8 gradient compression.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --steps 200 --batch 8 --seq 256 --reduced --ckpt-dir runs/ckpt
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax

from repro.checkpoint import manager as ckpt
from repro.configs import ARCHS, get_config, reduced_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.compile_cache import init_compile_cache
from repro.launch.steps import make_train_step
from repro.models import model as M
from repro.optim import adamw
from repro.parallel import sharding as S
from repro.runtime.elastic import StepWatchdog


def build_mesh(args):
    n = len(jax.devices())
    if n == 1:
        return None
    model_par = min(args.model_parallel, n)
    from repro.launch.mesh import make_mesh
    return make_mesh((n // model_par, model_par), ("data", "model"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="qwen3-1.7b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--model-parallel", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-out", default="")
    args = ap.parse_args(argv)
    init_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    mesh = build_mesh(args)

    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=max(
        10, args.steps // 20), total_steps=args.steps)
    data = SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, seed=args.seed,
        n_image_tokens=cfg.n_image_tokens, d_image=cfg.d_image,
        d_frame=cfg.d_frame if cfg.enc_dec else 0))

    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    opt_state = adamw.init(opt_cfg, params)
    start_step = 0
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        state, manifest = ckpt.restore(args.ckpt_dir,
                                       {"params": params, "opt": opt_state})
        params, opt_state = state["params"], state["opt"]
        start_step = manifest["step"]
        print(f"[train] resumed from step {start_step}")

    step_fn = make_train_step(cfg, opt_cfg, mesh)
    if mesh is not None:
        pshard = S.params_shardings(cfg, mesh)
        oshard = {"m": pshard, "v": pshard,
                  "step": jax.sharding.NamedSharding(
                      mesh, jax.sharding.PartitionSpec())}
        step_fn = jax.jit(step_fn, in_shardings=(pshard, oshard, None),
                          out_shardings=(pshard, oshard, None),
                          donate_argnums=(0, 1))
        params = jax.device_put(params, pshard)
        opt_state = jax.device_put(opt_state, oshard)
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    watchdog = StepWatchdog()
    history = []
    t_start = time.time()
    for step in range(start_step, args.steps):
        batch = {k: jax.numpy.asarray(v)
                 for k, v in data.get_batch(step).items()}
        t0 = time.time()
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        metrics = {k: float(v) for k, v in metrics.items()}
        dt = time.time() - t0
        ev = watchdog.observe(step, dt)
        if ev is not None:
            print(f"[watchdog] straggler step {step}: {dt:.2f}s "
                  f"(median {ev.median:.2f}s)")
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"[train] step {step} loss={metrics['loss']:.4f} "
                  f"ce={metrics['ce']:.4f} gnorm={metrics['grad_norm']:.3f} "
                  f"lr={metrics['lr']:.2e} dt={dt:.2f}s", flush=True)
        history.append({"step": step, **metrics, "dt": dt})
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            ckpt.save(args.ckpt_dir, step + 1,
                      {"params": params, "opt": opt_state},
                      extras={"arch": args.arch, "reduced": args.reduced})
    total = time.time() - t_start
    print(f"[train] done: {args.steps - start_step} steps in {total:.1f}s; "
          f"loss {history[0]['loss']:.4f} → {history[-1]['loss']:.4f}")
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, args.steps,
                  {"params": params, "opt": opt_state},
                  extras={"arch": args.arch, "reduced": args.reduced})
    if args.metrics_out:
        os.makedirs(os.path.dirname(args.metrics_out) or ".", exist_ok=True)
        with open(args.metrics_out, "w") as f:
            json.dump(history, f)
    return history


if __name__ == "__main__":
    main()
