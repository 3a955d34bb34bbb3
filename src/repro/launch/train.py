"""Training driver with JIRIAF fault-tolerance semantics.

Runs a real training loop (reduced configs on CPU; production configs on a
TPU fleet) under a JRM walltime lease: checkpoints periodically AND inside
the §4.5.4 drain margin, survives --kill-at-step (simulated node failure:
process aborts; rerunning resumes from the latest checkpoint), and logs
through the Prometheus-analog registry.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --reduced \
      --steps 60 --batch 8 --seq 64 --devices 4 --mesh 2x2 \
      --ckpt-dir /tmp/ckpt [--kill-at-step 30] [--walltime 120]
"""
import argparse
import math
import os
import sys


def _pre_jax():
    # host-platform device count must be fixed before jax import; a TPU
    # platform ignores it and reports its own chips
    if "--devices" in sys.argv:
        n = sys.argv[sys.argv.index("--devices") + 1]
        os.environ.setdefault("XLA_FLAGS",
                              f"--xla_force_host_platform_device_count={n}")


_pre_jax()

import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402
import numpy as np                            # noqa: E402

from repro.checkpoint import checkpointer as ckpt           # noqa: E402
from repro.configs.base import ShapeConfig, get_config      # noqa: E402
from repro.core.jrm import start_vk                         # noqa: E402
from repro.core.metrics import Registry                     # noqa: E402
from repro.data.pipeline import DataConfig, SyntheticDataset  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh                     # noqa: E402
from repro.launch.steps import make_train_cell              # noqa: E402
from repro.models import model_api as MA                    # noqa: E402
from repro.optim import adamw                               # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--mesh", default="")            # e.g. "2x2"
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--kill-at-step", type=int, default=-1)
    ap.add_argument("--walltime", type=float, default=0.0)
    ap.add_argument("--step-seconds", type=float, default=1.0,
                    help="simulated seconds per step for the lease clock")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args(argv)

    n_dev = len(jax.devices())
    if args.devices > n_dev:
        ap.error(f"--devices {args.devices}: the {jax.default_backend()} "
                 f"platform has {n_dev}")
    dims = tuple(int(x) for x in args.mesh.split("x")) if args.mesh else ()
    if math.prod(dims) > n_dev:
        ap.error(f"--mesh {args.mesh} needs {math.prod(dims)} devices; the "
                 f"{jax.default_backend()} platform has {n_dev}")
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeConfig("cli", "train", args.seq, args.batch)

    mesh = None
    if dims:
        axes = ("data", "model")[:len(dims)] if len(dims) == 2 else \
            ("pod", "data", "model")[:len(dims)]
        mesh = make_mesh(dims, axes)

    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=5,
                                total_steps=max(args.steps, 10))
    cell = make_train_cell(cfg, shape, mesh, opt_cfg=opt_cfg,
                           microbatches=args.microbatches)
    step_fn = cell.jit()

    mod = MA.get_module(cfg)
    node = start_vk("jrm-train-0", walltime=args.walltime, now=0.0,
                    nodetype="tpu" if mesh else "cpu")
    reg = Registry()

    # ----- init or resume -----
    start_step = 0
    params = mod.init(jax.random.PRNGKey(0), cfg)
    opt = adamw.init(params)
    data = SyntheticDataset(DataConfig(
        batch=args.batch, seq=args.seq, vocab=cfg.vocab,
        frontend_seq=cfg.frontend_seq, d_model=cfg.d_model))
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        (params, opt, dstate), meta = ckpt.restore(
            args.ckpt_dir, (params, opt, {"step": jnp.zeros((), jnp.int32)}))
        data.restore(dstate)
        start_step = int(meta["step"])
        print(f"[restore] resumed from step {start_step}")
    if mesh is not None:
        params = jax.tree.map(jax.device_put, params,
                              cell.in_shardings[0])
        opt = jax.tree.map(jax.device_put, opt, cell.in_shardings[1])

    losses = []
    now = start_step * args.step_seconds
    for step in range(start_step, args.steps):
        now = step * args.step_seconds
        node.tick(now)
        if not node.ready:
            print(f"[lease] walltime expired at step {step}; stopping")
            break
        draining = node.draining(now)
        batch = data.next_batch(
            cell.in_shardings[2] if mesh is not None else None)
        if args.kill_at_step == step:
            print(f"[failure] simulated node loss at step {step}",
                  flush=True)
            os._exit(42)       # no checkpoint, no cleanup — a real crash
        params, opt, metrics = step_fn(params, opt, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        reg.gauge("train_loss").set(loss)
        reg.counter("train_steps_total").inc()
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e}")
        must_ckpt = (args.ckpt_dir and
                     (step % args.ckpt_every == args.ckpt_every - 1 or
                      draining or step == args.steps - 1))
        if must_ckpt:
            ckpt.save(args.ckpt_dir, step + 1,
                      (params, opt, {"step": jnp.asarray(data.step)}),
                      meta={"step": step + 1, "arch": args.arch})
            if draining:
                print(f"[drain] checkpointed at step {step + 1} inside "
                      f"walltime margin; exiting for requeue")
                break
    if len(losses) >= 10:
        first, last = np.mean(losses[:5]), np.mean(losses[-5:])
        print(f"loss {first:.4f} -> {last:.4f} "
              f"({'improved' if last < first else 'NOT improved'})")
    return losses


if __name__ == "__main__":
    main()
