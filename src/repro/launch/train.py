"""End-to-end trainer: checkpoint/restart, preemption handling, logging.

Runs the reduced configs on this CPU host end-to-end; the same driver lowers
the full configs on a production mesh (the dry-run proves those compile).

  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt --ckpt-every 50

The CNN family (googlenet) trains through the execution-plan path:
``--plan concurrent`` lowers the scheduler's co-execution groups to a
``core/plan.py`` Plan (grouped/stacked branch kernels etc.) packed at
forward+backward cost — the custom VJPs co-execute the mirrored grad
CoGroups (``backward_plan``), so ``--plan`` covers the train step's
backward half too.  ``--plan serial`` re-plans with concurrency off
(singleton groups, per-op-fastest algorithms — the paper's serial
baseline), ``--plan none`` is the plain XLA forward:

  PYTHONPATH=src python -m repro.launch.train --arch googlenet --reduced \
      --steps 20 --batch 4 --plan concurrent

Fault tolerance (DESIGN.md §6): atomic checkpoints every N steps including
the data-iterator state; ``--resume`` restarts exactly where a previous run
(or a preempted pod) stopped; SIGTERM triggers a final checkpoint before
exit (the preemption path at datacenter scale).
"""
from __future__ import annotations

import argparse
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_reduced
from repro.data import Pipeline, SyntheticImages, SyntheticLM
from repro.launch import runtime
from repro.launch import steps as ST
from repro.launch.mesh import make_local_mesh
from repro.models import cnn as CNN
from repro.models import transformer as T
from repro.sharding import specs as SH


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--impl", default="xla", choices=["xla", "pallas"])
    ap.add_argument("--plan", default="none",
                    choices=["none", "serial", "concurrent"],
                    help="CNN-family execution plan: lower the schedule to "
                         "core/plan.py ExecGroups (concurrent), keep it "
                         "serial, or bypass planning (none)")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    runtime.enable_compile_cache()
    print(f"[train] {runtime.device_line()}", flush=True)
    run(args)
    return 0


def run(args) -> list[float]:
    """Train for ``args.steps`` steps; returns the per-step losses (each
    taken before that step's update)."""
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    mesh = make_local_mesh()
    print(f"[train] {cfg.name}: N={cfg.param_count()/1e6:.2f}M params, "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    is_cnn = getattr(cfg, "family", "lm") == "cnn"
    key = jax.random.PRNGKey(args.seed)
    params = CNN.init_params(cfg, key) if is_cnn else T.init_params(cfg, key)
    tc = ST.train_config_for(cfg)
    opt = ST.make_optimizer(cfg, tc)
    opt = type(opt)(**{**opt.__dict__, "lr": args.lr,
                       "total": args.steps, "warmup": max(args.steps // 20, 1)})
    opt_state = opt.init(params)

    if is_cnn:
        source = SyntheticImages(cfg.img, cfg.num_classes, args.batch,
                                 seed=args.seed)
    else:
        source = SyntheticLM(cfg.vocab, args.seq, args.batch, seed=args.seed)
    pipe = Pipeline(source)
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if args.resume and mgr and mgr.latest_step() is not None:
        template = {"params": params, "opt": opt_state,
                    "data": {"step": np.zeros((), np.int64)}}
        state, manifest = mgr.restore(template)
        params, opt_state = state["params"], state["opt"]
        pipe.restore({"step": int(state["data"]["step"])})
        start = manifest["step"]
        print(f"[train] resumed from step {start}")

    if is_cnn:
        if args.impl != "xla":
            print(f"[train] --impl {args.impl} ignored for CNN arch "
                  "(kernel choice comes from the plan)")
        plan = None
        if args.plan != "none":
            # train=True: pack + budget-check groups at fwd+bwd cost —
            # the plan covers the whole training step, not just forward
            plan, _ = CNN.plan_cnn(cfg, args.batch,
                                   concurrent=args.plan == "concurrent",
                                   train=True)
            chunked = [(g.ops[0], g.chunks) for g in plan.groups
                       if g.chunks > 1]
            print(f"[train] plan: modes={plan.mode_counts()} "
                  f"modeled_makespan={plan.makespan * 1e3:.3f} ms "
                  f"smem_chunked={chunked}")
            bwd = plan.context.get("backward")
            if bwd is not None:
                print(f"[train] backward plan: modes={bwd.mode_counts()} "
                      f"modeled_makespan={bwd.makespan * 1e3:.3f} ms "
                      f"xla_fallbacks={len(bwd.groups_of_mode('xla'))}")
        step_fn = ST.make_cnn_train_step(cfg, opt, plan=plan)
    else:
        if args.plan != "none":
            print(f"[train] --plan {args.plan} ignored for non-CNN arch")
        step_fn = ST.make_train_step(cfg, opt, impl=args.impl, remat=False)
    jitted = jax.jit(step_fn, donate_argnums=(0, 1))

    stop = {"now": False}

    def _sigterm(signum, frame):   # preemption: checkpoint + clean exit
        stop["now"] = True
    signal.signal(signal.SIGTERM, _sigterm)

    def save(step):
        if mgr:
            mgr.save(step, {"params": params, "opt": opt_state,
                            "data": {"step": np.int64(pipe.step)}},
                     extra={"arch": cfg.name})

    losses = []
    t0 = time.time()
    with SH.activations_on(mesh):
        for step in range(start, args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
            params, opt_state, metrics = jitted(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
            if (step + 1) % args.log_every == 0:
                dt = (time.time() - t0) / args.log_every
                print(f"step {step+1:5d} loss={losses[-1]:.4f} "
                      f"lr={float(metrics['lr']):.2e} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"{dt*1e3:.0f} ms/step", flush=True)
                t0 = time.time()
            if mgr and (step + 1) % args.ckpt_every == 0:
                save(step + 1)
            if stop["now"]:
                print("[train] SIGTERM -> checkpoint + exit")
                save(step + 1)
                return losses
    if mgr:
        save(args.steps)
    first = np.mean(losses[:10]) if len(losses) >= 10 else losses[0]
    last = np.mean(losses[-10:])
    print(f"[train] done. loss {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")
    return losses


if __name__ == "__main__":
    sys.exit(main())
