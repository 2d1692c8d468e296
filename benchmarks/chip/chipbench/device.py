"""The device a run stands on: which it is, where its compiled programs are
kept, and how many it compiles."""
from __future__ import annotations

import os
import pathlib

from chipbench import peaks as peaks_lib


class NoChip(RuntimeError):
    """JAX finds no accelerator the benchmark can measure."""


def describe(chips: int) -> dict:
    """Names the device, and refuses anything but enough TPU chips whose
    ``device_kind`` is in the peaks table.  Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX runs on {info['platform']!r}")
    peaks_lib.peaks_for(info["kind"])
    if info["count"] < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX finds "
                     f"{info['count']}")
    from repro.kernels.ops import default_interpret
    if default_interpret():
        raise NoChip("the Pallas kernels would run in interpret mode")
    return info


def use_compile_cache(root) -> str:
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    points, else the fixed ``<checkout>/.jax_cache``.  Every program is
    kept, however fast it compiled, so that a second run compiles
    nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        pathlib.Path(root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts, from JAX's own monitoring events, the programs compiled
    afresh (persistent-cache misses), those loaded from the cache, and
    every backend compile request, hit or miss."""

    MISS = "/jax/compilation_cache/cache_misses"
    HIT = "/jax/compilation_cache/cache_hits"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.counts = {"compiled": 0, "from_cache": 0, "backend": 0}

    def _event(self, name, **_kw):
        if name == self.MISS:
            self.counts["compiled"] += 1
        elif name == self.HIT:
            self.counts["from_cache"] += 1

    def _duration(self, name, _secs, **_kw):
        if name == self.BACKEND:
            self.counts["backend"] += 1

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring
        monitoring.unregister_event_listener(self._event)
        monitoring.unregister_event_duration_listener(self._duration)
        return False

    def snapshot(self) -> dict:
        return dict(self.counts)


def peak_memory_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")
