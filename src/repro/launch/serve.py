"""Serving drivers — transformer decode AND planned-CNN continuous batching.

Two serving paths share this module:

  transformer (``--arch llama3-8b ...``): prefill (cache fill) + decode
  steps (one token per step, greedy) with a KV cache.  The same
  ``decode_step`` lowers at production shapes in the dry-run
  (decode_32k / long_500k cells).

      PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b \\
          --reduced --batch 4 --prompt-len 32 --gen 32

  CNN (``--arch googlenet ...``): continuous-batching inference on the
  PLANNED executor — the paper's co-execution thesis applied where Opara
  aims it (small ragged inference batches).  Requests are split into
  chunks of at most ``max_images`` (an oversized request spans several
  dispatches — no image is silently dropped), admitted deadline- and
  size-aware (an EDF anchor plus a greedy fill that minimizes the
  dispatch's ``cost_model.padded_m_factor`` — padding waste, not queue
  order, decides who rides along), padded up to an M-bucket from the
  cost model's ladder (``cost_model.serve_buckets`` — bucket granularity
  is a modeled decision: pow2 image counts, merged where bm-alignment
  makes the padding free), and each bucket dispatches through ONE cached
  plan + offset tables + jitted executable (``core.plan_cache``).  The
  ragged ``valid_images`` operand is a traced i32 scalar, so every
  request mix in a bucket re-enters the same trace; the grouped-family
  kernels — INCLUDING the chained cross-module launch — mask the
  padded-M tail in-kernel (dead M-blocks skipped as no-op waves, live
  tails zero-stored).  A warm request pays zero lowering, zero
  ``_plan_tiles*`` rebuilds and zero re-tracing — the driver warms every
  bucket once, resets the cache counters, and asserts the measured
  stream runs at hit rate 1.0.  ``CNNServer`` is the one engine for
  this path (split, admit, pack, run), with a profiler span around each
  phase and around the transfer, launch and device wait of each
  dispatch.  Latency is attributed per REQUEST (queue wait + dispatch
  wall, completion of the LAST chunk for split requests); p50/p99 are
  request-level percentiles with the sample count reported alongside,
  and the raw dispatch-wall percentiles keep their own ``dispatch_*``
  keys (``serve_cnn_metrics`` — the numbers
  ``benchmarks/run.py`` records into BENCH_plan.json).

      PYTHONPATH=src python -m repro.launch.serve --arch googlenet \\
          --reduced --requests 12 --max-images 4
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced
from repro.launch import runtime
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as T
from repro.sharding import specs as SH

# importlib, not ``from repro.kernels import grouped_matmul``: the
# package re-exports a FUNCTION of that name which shadows the submodule
# attribute.  Module scope, NOT inside CNNServer.run — the
# import-machinery lookup has no business riding the per-dispatch hot loop.
_gmm = importlib.import_module("repro.kernels.grouped_matmul")


def _bucket_for(n: int, ladder: list[int]) -> int:
    for b in ladder:
        if n <= b:
            return b
    return ladder[-1]


def _split_request(rid: int, imgs, deadline: float, max_images: int):
    """Chunk one request into admission units of <= max_images images.
    Every submitted image lands in exactly one chunk — an oversized
    request spans several dispatches instead of being truncated."""
    return [{"rid": rid, "imgs": imgs[o:o + max_images],
             "deadline": deadline}
            for o in range(0, imgs.shape[0], max_images)]


def _admit(pending, max_images: int, ladder, rows_per_image: int, pmf):
    """Pick the next co-batch from ``pending`` chunks (mutates it).

    EDF anchor: the earliest-deadline chunk always dispatches next — a
    latency guarantee no packing heuristic may trade away.  Fill: among
    chunks that still fit under ``max_images``, greedily admit whichever
    minimizes the resulting dispatch's padded-M factor, stopping when no
    candidate improves on the current factor (a rider that bumps the
    bucket would pay more padding than it removes).  Ties fall to the
    earlier deadline via the stable sort.
    """
    pending.sort(key=lambda c: c["deadline"])
    batch = [pending.pop(0)]
    total = batch[0]["imgs"].shape[0]

    def factor(n):
        return pmf(n * rows_per_image,
                   _bucket_for(n, ladder) * rows_per_image)

    while True:
        cands = [c for c in pending
                 if total + c["imgs"].shape[0] <= max_images]
        if not cands:
            break
        best = min(cands,
                   key=lambda c: factor(total + c["imgs"].shape[0]))
        if factor(total + best["imgs"].shape[0]) > factor(total):
            break
        # identity removal — list.remove would == -compare image arrays
        pending.pop(next(i for i, c in enumerate(pending) if c is best))
        batch.append(best)
        total += best["imgs"].shape[0]
    return batch, total


class CNNServer:
    """The stream-serving engine of a planned CNN: requests split into
    chunks as they arrive (``split``), co-batches admitted from the queue
    (``admit``), packed into the smallest bucket of the ladder that holds
    them (``pack``) and run through the bucket's cached plan and jitted
    executable (``run``).

    Each phase is a host span on the profiler's clock
    (``jax.profiler.TraceAnnotation``, inert without a profiler session):
    ``serve.admit``, ``serve.pack`` and ``serve.dispatch``, which holds
    ``serve.h2d`` (the packed images' transfer, until it is on the device),
    ``serve.launch`` (the executable's call, which returns once the work is
    enqueued) and ``serve.wait`` (until the logits are ready); in set-up,
    ``serve.lower`` and ``serve.warm`` per bucket.

    ``counters`` counts the stream since ``reset_counters``: dispatches
    per bucket, valid and padded images, chunks admitted, the longest
    queue met at admission, and the host-clock seconds spent in the
    transfer, the launch and the wait.  ``setup`` keeps per bucket the
    seconds of lowering and of the warm dispatch, the packed input's
    bytes on the host and on the device, and what the bucket's chained
    launches do, read off their offset tables as the step is traced:
    ``chained_steps``, grid steps by lhs source (``x``, ``ring``,
    ``panel``), and ``ring_window_builds`` (dense counts: a ragged
    dispatch skips its dead blocks' steps).
    """

    def __init__(self, cfg, params, max_images: int, *,
                 chain_modules: bool = True, interpret=None):
        from repro.core import cost_model as CM
        from repro.core import plan_cache
        from repro.launch.steps import make_cnn_serve_step

        h, w, _c = cfg.img
        self.cfg, self.params, self.max_images = cfg, params, max_images
        self.rows_per_image = h * w
        self.ladder = CM.serve_buckets(max_images, h * w)
        self._pmf = CM.padded_m_factor
        self._plan_cache = plan_cache
        self.setup = {"lower_s": {}, "warm_s": {}, "input_host_bytes": {},
                      "input_device_bytes": {}, "chained_steps": {},
                      "ring_window_builds": {}}
        self.entries, self._steps = {}, {}
        for b in self.ladder:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("serve.lower", bucket=b):
                entry = plan_cache.cached_cnn_plan(
                    cfg, b, chain_modules=chain_modules)
            self.setup["lower_s"][b] = time.perf_counter() - t0
            self._steps[b] = make_cnn_serve_step(cfg, entry.plan,
                                                 interpret=interpret)
            if entry.executable is None:
                entry.executable = jax.jit(self._steps[b])
            self.entries[b] = entry
        self.reset_counters()

    def reset_counters(self) -> None:
        self.counters = {"dispatches": {b: 0 for b in self.ladder},
                         "valid_images": 0, "padded_images": 0,
                         "chunks_admitted": 0, "pending_max": 0,
                         "h2d_s": 0.0, "launch_s": 0.0, "wait_s": 0.0}

    def split(self, rid: int, imgs, deadline: float):
        return _split_request(rid, imgs, deadline, self.max_images)

    def admit(self, pending):
        """The next co-batch from ``pending`` (mutated), by ``_admit``."""
        n = len(pending)
        with jax.profiler.TraceAnnotation("serve.admit", pending=n):
            batch, total = _admit(pending, self.max_images, self.ladder,
                                  self.rows_per_image, self._pmf)
        self.counters["chunks_admitted"] += len(batch)
        self.counters["pending_max"] = max(self.counters["pending_max"], n)
        return batch, total

    def pack(self, arrs):
        """``arrs`` in order in a zero-filled float32 array of the smallest
        bucket that holds them: (images, bucket, valid images)."""
        with jax.profiler.TraceAnnotation("serve.pack"):
            n = sum(r.shape[0] for r in arrs)
            bucket = _bucket_for(n, self.ladder)
            imgs = np.zeros((bucket,) + tuple(self.cfg.img), np.float32)
            off = 0
            for r in arrs:
                imgs[off:off + r.shape[0]] = r
                off += r.shape[0]
        return imgs, bucket, n

    def run(self, imgs, bucket: int, n: int):
        """The logits of a packed bucket, ready on the device."""
        entry = self.entries[bucket]
        c = self.counters
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(
                "serve.dispatch", seq=sum(c["dispatches"].values()),
                bucket=bucket, valid=n):
            with jax.profiler.TraceAnnotation("serve.h2d"):
                x = jax.block_until_ready(jnp.asarray(imgs))
            t1 = time.perf_counter()
            # record which device offset tables this entry's executable
            # touches and pin them to the entry (first dispatch only): the
            # plan cache's LRU eviction unpins them, so table memory tracks
            # LIVE entries, not everything ever traced
            with _gmm._device_table.recording() as touched:
                with jax.profiler.TraceAnnotation("serve.launch"):
                    logits = entry.executable(self.params, x, jnp.int32(n))
                t2 = time.perf_counter()
                with jax.profiler.TraceAnnotation("serve.wait"):
                    jax.block_until_ready(logits)
        t3 = time.perf_counter()
        self._plan_cache.attach_tables(entry, touched)
        # the phases on the host's clock as well, so that an untraced
        # dispatch splits too: a traced one carries the profiler's cost
        c["h2d_s"] += t1 - t0
        c["launch_s"] += t2 - t1
        c["wait_s"] += t3 - t2
        c["dispatches"][bucket] += 1
        c["valid_images"] += n
        c["padded_images"] += bucket - n
        return logits

    def warm(self) -> None:
        """One full dispatch per bucket: traces, compiles or loads each
        bucket's executable and pins its offset tables.  Leaves the
        counters to the caller to reset."""
        h, w, c = self.cfg.img
        for b in self.ladder:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("serve.warm", bucket=b), \
                    _gmm.chained_steps_recording() as rec:
                imgs, bucket, n = self.pack([np.zeros((b, h, w, c),
                                                      np.float32)])
                self.run(imgs, bucket, n)
            self.setup["warm_s"][b] = time.perf_counter() - t0
            self.setup["input_host_bytes"][b] = imgs.nbytes
            self.setup["input_device_bytes"][b] = \
                jnp.asarray(imgs).on_device_size_in_bytes()
            if not rec["launches"] and any(
                    g.mode == "grouped_chained"
                    for g in self.entries[b].plan.groups):
                # the executable was traced before this engine's warm-up:
                # trace the step again, abstractly, to count
                with _gmm.chained_steps_recording() as rec:
                    jax.eval_shape(self._steps[b], self.params,
                                   jax.ShapeDtypeStruct(imgs.shape,
                                                        imgs.dtype),
                                   jax.ShapeDtypeStruct((), jnp.int32))
            self.setup["ring_window_builds"][b] = rec["window_builds"]
            self.setup["chained_steps"][b] = {
                k: rec[k] for k in ("x", "ring", "panel")}


def serve_cnn_metrics(cfg, *, max_images: int = 4, num_requests: int = 12,
                      seed: int = 0, chain_modules: bool = True,
                      interpret=None) -> dict:
    """Run the continuous-batching loop on ``cfg`` and return metrics.

    Synthetic seeded request stream: each request carries
    1..max_images+1 images (the +1 deliberately exercises the oversized
    path) and a deadline drawn from the same rng; every request is
    submitted at once and served through one ``CNNServer``.  Warmup
    dispatches one batch per ladder bucket (populating plan cache, device
    offset tables and jit traces), then counters reset and the measured
    stream must re-lower nothing: the cache still holds every bucket's
    entry afterwards.

    Latency is per REQUEST: completion of its last chunk minus
    submission, i.e. queue wait + dispatch wall.  ``p50_ms``/``p99_ms``
    are request-level (``latency_samples`` counts them); the dispatch
    walls keep their own ``dispatch_p50_ms``/``dispatch_p99_ms``.
    """
    from repro.core import cost_model as CM
    from repro.core import plan_cache
    from repro.models import cnn as CNN

    h, w, c = cfg.img
    rng = np.random.default_rng(seed)
    params = CNN.init_params(cfg, jax.random.PRNGKey(seed))
    engine = CNNServer(cfg, params, max_images,
                       chain_modules=chain_modules, interpret=interpret)

    # request stream: image counts in [1, max_images + 1] — the +1 makes
    # oversized requests (must split, never truncate) part of every run
    sizes = rng.integers(1, max_images + 2, size=num_requests)
    deadlines = rng.uniform(0.05, 0.5, size=num_requests)
    requests = [rng.normal(size=(int(s), h, w, c)).astype(np.float32)
                for s in sizes]

    engine.warm()
    plan_cache.reset()          # counters only; entries stay warm
    engine.reset_counters()

    pending = []
    for rid, (r, dl) in enumerate(zip(requests, deadlines)):
        pending.extend(engine.split(rid, r, float(dl)))
    chunks_left = {rid: sum(1 for c_ in pending if c_["rid"] == rid)
                   for rid in range(num_requests)}
    submitted_images = int(sum(sizes))

    dispatch_s, waste = [], []
    done_at: dict[int, float] = {}
    served_images = 0
    t_start = time.perf_counter()
    while pending:
        batch, _total = engine.admit(pending)
        imgs, bucket, n = engine.pack([c_["imgs"] for c_ in batch])
        t0 = time.perf_counter()
        engine.run(imgs, bucket, n)
        t_end = time.perf_counter()
        dispatch_s.append(t_end - t0)
        served_images += n
        waste.append(CM.padded_m_factor(n * h * w, bucket * h * w))
        for c_ in batch:
            chunks_left[c_["rid"]] -= 1
            if chunks_left[c_["rid"]] == 0:
                done_at[c_["rid"]] = t_end
    wall = time.perf_counter() - t_start

    assert len(done_at) == num_requests and served_images == \
        submitted_images, "a submitted image never reached a launch"
    # the stream ran on the entries the engine resolved at its start; the
    # cache must still hold each one (one hit per bucket), none re-lowered
    # or evicted
    held = [plan_cache.cached_cnn_plan(cfg, b, chain_modules=chain_modules)
            is e for b, e in engine.entries.items()]
    stats = plan_cache.stats()
    assert all(held) and stats["misses"] == 0 \
        and stats["hit_rate"] == 1.0, (
            f"warm serving path re-lowered a plan: {stats}")
    req_ms = np.asarray([done_at[r] - t_start
                         for r in range(num_requests)]) * 1e3
    disp_ms = np.asarray(dispatch_s) * 1e3
    return {
        "arch": cfg.name,
        "buckets": engine.ladder,
        "requests": int(num_requests),
        "dispatches": len(dispatch_s),
        "images": int(served_images),
        "images_submitted": submitted_images,
        "qps": float(num_requests / wall),
        "images_per_s": float(served_images / wall),
        # request-level latency: queue wait + dispatch wall, last chunk
        # for split requests
        "p50_ms": float(np.percentile(req_ms, 50)),
        "p99_ms": float(np.percentile(req_ms, 99)),
        "latency_samples": int(req_ms.size),
        "dispatch_p50_ms": float(np.percentile(disp_ms, 50)),
        "dispatch_p99_ms": float(np.percentile(disp_ms, 99)),
        "padded_m_factor_mean": float(np.mean(waste)),
        "plan_cache": stats,
        # per-ladder planlint coverage: a bucket's entry is verified when
        # its lowering ran analysis.verify_plan with zero findings
        # (pytest / REPRO_PLANLINT=1 — see plan._verify_requested)
        "plans_verified": sum(1 for e in engine.entries.values()
                              if e.verified),
    }


def _serve_cnn(args) -> int:
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    m = serve_cnn_metrics(cfg, max_images=args.max_images,
                          num_requests=args.requests, seed=args.seed)
    print(f"[serve] {m['arch']}: {m['requests']} requests "
          f"({m['images']} images) in {m['dispatches']} dispatches, "
          f"buckets {m['buckets']}")
    print(f"[serve] qps {m['qps']:.2f} ({m['images_per_s']:.2f} img/s), "
          f"request p50 {m['p50_ms']:.1f} ms / p99 {m['p99_ms']:.1f} ms "
          f"(n={m['latency_samples']}), dispatch p50 "
          f"{m['dispatch_p50_ms']:.1f} ms, padded-M waste "
          f"x{m['padded_m_factor_mean']:.2f}")
    print(f"[serve] plan cache: {m['plan_cache']}")
    return 0


def _serve_transformer(args) -> int:
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    mesh = make_local_mesh()
    key = jax.random.PRNGKey(args.seed)
    params = T.init_params(cfg, key)

    b = args.batch
    total = args.prompt_len + args.gen
    prompts = jax.random.randint(jax.random.fold_in(key, 1),
                                 (b, args.prompt_len), 0, cfg.vocab)
    cache = T.init_cache(cfg, b, total)
    extra = None
    context = None
    if cfg.frontend == "frame":
        extra = jax.random.normal(
            jax.random.fold_in(key, 2),
            (b, cfg.enc_context_len, cfg.d_model)) * 0.02
    if cfg.frontend == "patch":
        extra = jax.random.normal(
            jax.random.fold_in(key, 2),
            (b, cfg.frontend_len, cfg.d_model)) * 0.02

    prefill = jax.jit(lambda p, t, c, e: T.prefill(p, cfg, t, c,
                                                   extra_embeds=e))
    decode = jax.jit(lambda p, c, t, pos, ctx: T.decode_step(
        p, cfg, c, t, pos, context=ctx))

    with SH.activations_on(mesh):
        if cfg.enc_dec:
            context = jax.jit(
                lambda p, e: T._encoder(cfg, p, e))(params, extra)
            extra_for_prefill = extra
        else:
            extra_for_prefill = extra
        t0 = time.time()
        logits, cache = prefill(params, prompts, cache, extra_for_prefill)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        t_prefill = time.time() - t0
        out = [tok]
        t0 = time.time()
        for i in range(args.gen - 1):
            pos = jnp.int32(args.prompt_len + i)
            logits, cache = decode(params, cache, tok, pos, context)
            tok = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)[:, None]
            out.append(tok)
        dt = time.time() - t0
        toks = np.concatenate([np.asarray(t) for t in out], axis=1)
    print(f"[serve] {cfg.name}: prefill {args.prompt_len} tok in "
          f"{t_prefill*1e3:.0f} ms; {args.gen-1} decode steps at "
          f"{dt/(args.gen-1)*1e3:.1f} ms/tok (batch {b})")
    print("[serve] sample:", toks[0, :16].tolist())
    assert toks.shape == (b, args.gen) and (toks >= 0).all() \
        and (toks < cfg.vocab).all()
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=12,
                    help="CNN path: synthetic request count")
    ap.add_argument("--max-images", type=int, default=4,
                    help="CNN path: max images per request/co-batch")
    args = ap.parse_args(argv)
    runtime.enable_compile_cache()
    print(f"[serve] {runtime.device_line()}", flush=True)

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if getattr(cfg, "family", "") == "cnn":
        return _serve_cnn(args)
    return _serve_transformer(args)


if __name__ == "__main__":
    sys.exit(main())
