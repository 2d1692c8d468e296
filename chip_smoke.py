"""Bring-up smoke on one TPU v5e chip: full-width GoogLeNet serving and
training through the repository's own entry points, each checked against
the plain XLA reference.

    python chip_smoke.py

One process, no children.  In order:

  device  prints platform / device_kind / count and exits nonzero unless
          this is the v5e ``core.cost_model`` describes with the Pallas
          kernels compiled (``kernels.ops.default_interpret()`` False);
  serve   ``launch.serve.serve_cnn_metrics`` on ``googlenet`` (224x224,
          published widths, seeded random weights): bucket ladder
          {1, 2, 4, 8}, 16 seeded requests, chained plans, plan cache at
          hit rate 1.0.  Then bucket 8 with 5 valid images against
          ``CNN.forward`` (XLA, highest matmul precision), and the traced
          forward's pallas_call count against what the plan implies,
          SMEM chunks included (no group degraded to XLA);
  train   3 steps of ``launch/train.py --arch googlenet --plan concurrent
          --batch 16``: finite losses, and the step-0 loss against the
          ``plan=None`` (XLA) loss on the same params and batch.

Nothing is caught around a phase: any failure exits nonzero.  Timings
printed here are this smoke's wall clock (compiles included where said),
not benchmark numbers.  The last line of stdout is the JSON result.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy as np

# Logits tolerance, as max|kernel - ref| / max|ref| over the valid rows.
# The kernels take f32 operands and accumulate in f32, so they differ
# from the highest-precision XLA reference by summation order only: the
# planned forward in interpret mode misses by 3e-7 (reduced googlenet,
# XLA:CPU).  The same forward with bf16 weights and images misses by
# 6e-3 (full googlenet, XLA:CPU), so 1e-4 fails a bf16 kernel path.
LOGITS_RTOL = 1e-4
# Step-0 loss tolerance, relative.  At init the logits are ~0.05, so the
# cross-entropy sits near ln(1000) and barely moves with precision (bf16:
# 3e-6 at full size) — the logits check above is the precision check.
# This one catches a planned step that trains a different function:
# even all-zero logits move the loss by 2e-4.  f32 planned vs XLA: 2e-7
# (reduced googlenet, XLA:CPU).
LOSS_RTOL = 1e-5

SERVE_BUCKETS = [1, 2, 4, 8]


def _say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def _fail(msg: str) -> None:
    _say(f"FAIL: {msg}")
    sys.exit(1)


def _rel(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / np.abs(b).max())


def check_device():
    from repro.launch.runtime import device_info
    info = device_info()
    _say(f"device: {info}")
    if info["platform"] != "tpu":
        _fail(f"no TPU: JAX runs on {info['platform']!r}")
    from repro.core import cost_model as cm
    from repro.kernels.ops import default_interpret
    if info["kind"] != cm.DEVICE_KIND:
        _fail(f"device_kind {info['kind']!r} is not the "
              f"{cm.DEVICE_KIND!r} the cost model describes")
    if default_interpret():
        _fail("Pallas kernels would run in interpret mode")
    return info


def serve_phase() -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import plan_cache
    from repro.core.launch_count import count_launches
    from repro.launch.serve import serve_cnn_metrics
    from repro.launch.steps import make_cnn_serve_step
    from repro.models import cnn as CNN

    cfg = get_config("googlenet")
    t0 = time.perf_counter()
    m = serve_cnn_metrics(cfg, max_images=8, num_requests=16, seed=0)
    wall = time.perf_counter() - t0
    _say(f"serve: {m['requests']} requests, {m['images']} images in "
         f"{m['dispatches']} dispatches over buckets {m['buckets']}, "
         f"plan cache {m['plan_cache']}")
    _say(f"serve timings (this smoke, not a benchmark): loop incl. "
         f"bucket compiles {wall:.1f} s; warm request p50 "
         f"{m['p50_ms']:.1f} ms p99 {m['p99_ms']:.1f} ms, dispatch p50 "
         f"{m['dispatch_p50_ms']:.1f} ms")
    if m["buckets"] != SERVE_BUCKETS or m["requests"] != 16 \
            or m["plan_cache"]["hit_rate"] != 1.0:
        _fail(f"serving did not run the expected ladder/requests: {m}")

    entry = plan_cache.cached_cnn_plan(cfg, 8, chain_modules=True)
    plan = entry.plan
    chunks = [(g.ops[0], g.chunks, g.chunk_rows) for g in plan.groups
              if g.chunks > 1]
    _say(f"bucket 8 plan: modes {plan.mode_counts()}, SMEM chunks "
         f"{chunks}")
    if any(g.ops != ("input",) and not g.mode.startswith("grouped")
           for g in plan.groups):
        _fail("the bucket-8 plan leaves ops outside the grouped launches")
    params = CNN.init_params(cfg, jax.random.PRNGKey(0))  # serve's seed
    rng = np.random.default_rng(1)
    imgs = np.zeros((8,) + cfg.img, np.float32)
    imgs[:5] = rng.normal(size=(5,) + cfg.img)
    imgs = jnp.asarray(imgs)
    t0 = time.perf_counter()
    got = np.asarray(entry.executable(params, imgs, jnp.int32(5)))
    t_got = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        ref = np.asarray(jax.jit(lambda p, x: CNN.forward(p, cfg, x))(
            params, imgs[:5]))
        t_ref = time.perf_counter() - t0
    err = _rel(got[:5], ref)
    _say(f"bucket 8, 5 valid images vs XLA reference: max rel err "
         f"{err:.3e} (tolerance {LOGITS_RTOL:.0e}); timings (this smoke): "
         f"cached executable {t_got:.3f} s, reference incl. compile "
         f"{t_ref:.1f} s")
    if not np.isfinite(got[:5]).all() or err > LOGITS_RTOL:
        _fail("bucket-8 logits disagree with the XLA reference")

    counts = count_launches(make_cnn_serve_step(cfg, plan), params, imgs,
                            jnp.int32(5))
    implied = sum(g.chunks for g in plan.groups
                  if g.mode.startswith("grouped"))
    _say(f"traced forward: {counts}; plan implies {implied} pallas_call "
         f"launches")
    if counts.get("pallas_call", 0) != implied:
        _fail("a planned group did not run as its grouped launch(es)")


def train_phase() -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.data import Pipeline, SyntheticImages
    from repro.launch import train
    from repro.models import cnn as CNN

    argv = ["--arch", "googlenet", "--plan", "concurrent", "--steps", "3",
            "--batch", "16", "--log-every", "1", "--seed", "0"]
    t0 = time.perf_counter()
    losses = train.run(train.parse_args(argv))
    wall = time.perf_counter() - t0
    _say(f"train: losses {losses}; wall incl. plan + compile "
         f"{wall:.1f} s (this smoke; step 1 of the ms/step lines above "
         f"includes the compile)")
    if len(losses) != 3 or not all(math.isfinite(x) for x in losses):
        _fail("training did not produce 3 finite losses")

    cfg = get_config("googlenet")
    params = CNN.init_params(cfg, jax.random.PRNGKey(0))  # train's seed
    batch = next(Pipeline(SyntheticImages(cfg.img, cfg.num_classes, 16,
                                          seed=0)))
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    with jax.default_matmul_precision("highest"):
        ref = float(jax.jit(lambda p, b: CNN.loss_fn(p, cfg, b)[0])(
            params, batch))
    err = abs(losses[0] - ref) / abs(ref)
    _say(f"step-0 loss {losses[0]!r} vs plan=None {ref!r}: rel err "
         f"{err:.3e} (tolerance {LOSS_RTOL:.0e})")
    if err > LOSS_RTOL:
        _fail("planned step-0 loss disagrees with the plan=None loss")


def main() -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "src"))
    try:
        from repro.launch import runtime
    except ImportError as e:
        _fail(f"the repository's src/ is not beside this script ({e})")
    info = check_device()
    _say(f"compile cache: {runtime.enable_compile_cache()}")
    t0 = time.perf_counter()
    serve_phase()
    _say(f"serve phase done in {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    train_phase()
    _say(f"train phase done in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
