"""What every entry point does before it builds a model: say which device
JAX runs on, and place the persistent compilation cache.

Nothing here runs at import: the drivers (``launch/train.py``,
``launch/serve.py``) and ``chip_smoke.py`` call these from ``main``.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the repository checkout (src/repro/launch/runtime.py -> checkout root)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def device_info() -> dict:
    """The device as JAX reports it: platform, device_kind, count."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_line() -> str:
    """One line naming the device — and saying so when the Pallas kernels
    will run in interpret mode, which is what they do off a TPU."""
    from repro.kernels.ops import default_interpret
    d = device_info()
    mode = "Pallas interpret mode" if default_interpret() \
        else "compiled Pallas kernels"
    return (f"device: {d['platform']} {d['kind']!r} x{d['count']} "
            f"({mode})")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing here overrides it.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` — the path is part of what a later run looks
    up, so it must not move between runs."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
