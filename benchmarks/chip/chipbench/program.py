"""The harness's door into the program under test: its serving entry
points, called as its own launch scripts call them.  The program's config
comes from the configuration's module (``program_config``).

Only ``dispatch`` repeats program logic: the per-dispatch packing of
``launch/serve.py:serve_cnn_metrics``, which has no public entry that takes
requests as they arrive.
"""
from __future__ import annotations

import contextlib
import importlib
import time

import numpy as np


def serve_ladder(max_images: int, rows_per_image: int) -> list[int]:
    from repro.core import cost_model
    return cost_model.serve_buckets(max_images, rows_per_image)


class Server:
    """The serving path of ``launch/serve.py``: one cached chained plan and
    jitted executable per bucket of the ladder, requests split into chunks
    and admitted by ``launch.serve._admit``."""

    def __init__(self, cfg, params, max_images: int, *,
                 chain_modules: bool = True, wrap=None, interpret=None):
        import jax
        from repro.core import cost_model, plan_cache
        from repro.launch import serve, steps
        self._jax = jax
        self._serve = serve
        self._plan_cache = plan_cache
        self._pmf = cost_model.padded_m_factor
        self._gmm = importlib.import_module(
            "repro.kernels.grouped_matmul")
        self.cfg, self.params, self.max_images = cfg, params, max_images
        h, w, _c = cfg.img
        self.rows_per_image = h * w
        self.ladder = serve_ladder(max_images, h * w)
        self.entries, self.executables = {}, {}
        for b in self.ladder:
            entry = plan_cache.cached_cnn_plan(cfg, b,
                                               chain_modules=chain_modules)
            if entry.executable is None:
                entry.executable = jax.jit(steps.make_cnn_serve_step(
                    cfg, entry.plan, interpret=interpret))
            self.entries[b] = entry
            # ``wrap`` (tests only) puts a fault under the jit
            self.executables[b] = entry.executable if wrap is None else \
                jax.jit(wrap(steps.make_cnn_serve_step(
                    cfg, entry.plan, interpret=interpret)))

    def warm(self) -> None:
        """One dispatch per bucket: traces, compiles or loads each bucket's
        executable and pins its offset tables."""
        h, w, c = self.cfg.img
        for b in self.ladder:
            self.dispatch([np.zeros((b, h, w, c), np.float32)])
        self._plan_cache.reset()          # counters only

    def split(self, rid: int, imgs, due: float):
        return self._serve._split_request(rid, imgs, due, self.max_images)

    def admit(self, pending):
        return self._serve._admit(pending, self.max_images, self.ladder,
                                  self.rows_per_image, self._pmf)

    def bucket_for(self, n: int) -> int:
        return self._serve._bucket_for(n, self.ladder)

    def dispatch(self, arrs, span=contextlib.nullcontext):
        """Pack ``arrs`` into the smallest bucket that holds them, run the
        bucket's executable and wait for its logits.  Returns (logits,
        seconds from the call to ready, bucket, images)."""
        n = sum(r.shape[0] for r in arrs)
        bucket = self.bucket_for(n)
        entry = self.entries[bucket]
        h, w, c = self.cfg.img
        with span("bench.pack"):
            imgs = np.zeros((bucket, h, w, c), np.float32)
            off = 0
            for r in arrs:
                imgs[off:off + r.shape[0]] = r
                off += r.shape[0]
        t0 = time.perf_counter()
        with span("bench.dispatch"):
            with self._gmm._device_table.recording() as touched:
                logits = self.executables[bucket](self.params,
                                          self._jax.numpy.asarray(imgs),
                                          self._jax.numpy.int32(n))
                self._jax.block_until_ready(logits)
        wall = time.perf_counter() - t0
        self._plan_cache.attach_tables(entry, touched)
        return logits, wall, bucket, n

    def cache_stats(self) -> dict:
        return self._plan_cache.stats()
