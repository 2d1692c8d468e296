"""Compute/communication overlap primitives (shard_map + ppermute rings).

GSPMD emits all-gather/reduce-scatter as monolithic ops that serialize with
compute.  These ring variants split the collective into per-step chunks and
interleave a partial matmul with each ``ppermute`` hop — the standard
"collective matmul" (Wang et al.) that hides TP communication under MXU
work.  They are the §Perf levers for the collective-bound cells.

  matmul_allgather_x(x_local, w_local, axis):
      y = allgather_M(x) @ w       (x row-sharded on M, w col-sharded on N)
      overlap: each ring step matmuls the chunk that just arrived.
  matmul_reducescatter(x_local, w_full_rows, axis):
      y_scattered = reduce_scatter_M(x_partial @ w)  done chunkwise so the
      partial-sum hop overlaps the next chunk's matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _ring_allgather_matmul_local(x_local, w_local, *, axis: str, p: int):
    """Per-device body: x_local (m, K), w_local (K, n_local).
    Computes allgather(x) @ w_local => (M, n_local), overlapped.
    ``p`` is the static ring size (mesh.shape[axis] — jax.lax.axis_size is
    not available on older jax, and the perm lists need a Python int)."""
    idx = jax.lax.axis_index(axis)
    m = x_local.shape[0]

    def step(carry, _):
        buf, out, i = carry
        # compute with the chunk currently held (originated at idx - i)
        src = (idx - i) % p
        partial = buf @ w_local                       # (m, n_local)
        out = jax.lax.dynamic_update_slice(out, partial, (src * m, 0))
        # pass the chunk along the ring (overlaps next matmul on TPU)
        buf = jax.lax.ppermute(buf, axis,
                               [(j, (j + 1) % p) for j in range(p)])
        return (buf, out, i + 1), None

    out0 = jnp.zeros((m * p, w_local.shape[1]), x_local.dtype)
    (buf, out, _), _ = jax.lax.scan(step, (x_local, out0, 0), None, length=p)
    return out


def matmul_allgather_x(x, w, mesh, axis: str = "model"):
    """x: (M, K) sharded on M over ``axis``; w: (K, N) sharded on N.
    Returns (M, N) sharded on N (replicated on M)."""
    fn = jax.shard_map(
        functools.partial(_ring_allgather_matmul_local, axis=axis,
                          p=mesh.shape[axis]),
        mesh=mesh,
        in_specs=(P(axis, None), P(None, axis)),
        out_specs=P(None, axis), check_vma=False)
    return fn(x, w)


def _ring_reducescatter_matmul_local(x_local, w_local, *, axis: str,
                                     p: int):
    """Per-device body: x_local (M, k_local) k-sharded, w_local (k_local, N).
    y = reduce-scatter_M( sum_k x_k @ w_k ): returns (M/p, N) shard."""
    idx = jax.lax.axis_index(axis)
    m = x_local.shape[0]
    ms = m // p

    def step(carry, i):
        acc, _ = carry
        # the accumulator currently held here is homed at (idx - i): add
        # this device's contribution to that output shard
        dst = (idx - i) % p
        xc = jax.lax.dynamic_slice(x_local, (dst * ms, 0),
                                   (ms, x_local.shape[1]))
        partial = xc @ w_local                         # (ms, N)
        acc = acc + partial
        acc_next = jax.lax.ppermute(
            acc, axis, [(j, (j + 1) % p) for j in range(p)])
        return (acc_next, 0), None

    acc0 = jnp.zeros((ms, w_local.shape[1]),
                     jnp.promote_types(x_local.dtype, jnp.float32))
    (acc, _), _ = jax.lax.scan(step, (acc0, 0), jnp.arange(p))
    return acc.astype(x_local.dtype)


def matmul_reducescatter(x, w, mesh, axis: str = "model"):
    """x: (M, K) sharded on K over ``axis``; w: (K, N) sharded on K.
    Returns y = x @ w reduce-scattered over M: (M, N) with M sharded."""
    fn = jax.shard_map(
        functools.partial(_ring_reducescatter_matmul_local, axis=axis,
                          p=mesh.shape[axis]),
        mesh=mesh,
        in_specs=(P(None, axis), P(axis, None)),
        out_specs=P(axis, None), check_vma=False)
    return fn(x, w)
