"""Traced-jaxpr launch counter — what actually compiles, not what eager ran.

The eager ``KERNEL_LAUNCHES`` probe in ``kernels/grouped_matmul.py`` counts
wrapper invocations; under ``jit`` that tells you nothing about surviving
XLA fallbacks.  This counter walks the jaxpr of a traced callable
(recursively, through pjit/custom-vjp/scan sub-jaxprs at any depth) and
counts the equations that become device launches a plan claims to have
deleted:

  pallas_call         — our kernels (one launch each)
  conv_general_dilated — an XLA convolution survived the GEMM lowering
  reduce_window_*     — a standalone pooling primitive survived absorption
  concatenate         — a join / packing copy survived epilogue-concat

``launches_per_forward`` on a plan is the pallas_call count PLUS the
surviving fallbacks — the honest per-direction launch total the ISSUE's
ceiling gates (and the chained plan's <= 12 claim) are measured by.
"""
from __future__ import annotations

import jax

# primitive name -> report key
COUNTED = {
    "pallas_call": "pallas_call",
    "conv_general_dilated": "conv",
    "reduce_window": "reduce_window",
    "reduce_window_max": "reduce_window",
    "reduce_window_min": "reduce_window",
    "reduce_window_sum": "reduce_window",
    "concatenate": "concatenate",
}


def walk_eqns(jaxpr):
    """Yield every equation of ``jaxpr`` and, recursively, of every
    sub-jaxpr reachable through its params (pjit's ``jaxpr``, custom-vjp
    call_jaxpr, scan/cond/checkpoint bodies, ...) — the traversal both
    this counter and the planlint fallback lint
    (``analysis/fallbacks.py``) are built on."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from walk_eqns(sub)


def _walk(jaxpr, counts: dict) -> None:
    for eqn in walk_eqns(jaxpr):
        key = COUNTED.get(eqn.primitive.name)
        if key is not None:
            counts[key] = counts.get(key, 0) + 1


def _subjaxprs(v):
    """Yield every Jaxpr reachable from one params value (pjit's ``jaxpr``,
    custom-vjp call_jaxpr, scan/cond branches, ...)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    if isinstance(v, ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from _subjaxprs(x)


def count_launches(fn, *args, **kwargs) -> dict:
    """Trace ``fn(*args, **kwargs)`` and return the counted-primitive
    histogram plus its ``total`` — the per-direction launch number the
    CI ceiling gates pin.  ``fn`` is traced, never executed."""
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    counts: dict = {}
    _walk(closed.jaxpr, counts)
    counts["total"] = sum(v for k, v in counts.items() if k != "total")
    return counts


def count_grad_launches(loss_fn, *args, **kwargs) -> dict:
    """Launch histogram of the BACKWARD half: trace grad of ``loss_fn``
    wrt its first argument and subtract nothing — the counted total is
    fwd+bwd of the differentiated computation, so callers wanting the
    backward-only number subtract their ``count_launches`` forward total
    (see ``launches_per_direction``)."""
    g = jax.grad(lambda *a: loss_fn(*a, **kwargs))
    closed = jax.make_jaxpr(g)(*args)
    counts: dict = {}
    _walk(closed.jaxpr, counts)
    counts["total"] = sum(v for k, v in counts.items() if k != "total")
    return counts


def launches_per_direction(loss_fn, *args, **kwargs) -> tuple[int, int]:
    """(launches_per_forward, launches_per_backward) of a scalar loss.

    Forward = traced ``loss_fn``; backward = traced ``grad(loss_fn)``
    minus the forward residual recomputation is NOT separable in a jaxpr,
    so the backward number is the grad trace's total minus the forward
    total — the launches the backward half ADDS, which is the quantity
    the mirrored backward plan prices."""
    fwd = count_launches(loss_fn, *args, **kwargs)["total"]
    both = count_grad_launches(loss_fn, *args, **kwargs)["total"]
    return fwd, max(both - fwd, 0)
