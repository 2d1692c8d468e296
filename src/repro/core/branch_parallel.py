"""Branch-parallel execution — the paper's concurrency, TPU-native.

Three execution modes for a fork/join of independent branches (paper Sec 2):

  stacked  — same-shape branch GEMMs fused into ONE Pallas kernel with a
             branch grid axis (``kernels.branch_matmul``): the intra-chip
             analogue of intra-SM sharing (DMA of branch g+1 overlaps MXU
             of branch g).
  spatial  — inter-chip spatial partitioning via ``shard_map`` over the
             ``model`` mesh axis: the axis is factored into
             (branch-group, within-group batch shard); each chip computes
             one branch on a fraction of the batch; a single all-gather
             joins.  This is the paper's inter-SM partitioning realized on
             hardware that actually exposes partitioning (C5's complaint
             about CUDA does not apply to a TPU mesh).
  xla      — emit branches independently inside one jit and let XLA's
             scheduler interleave them (the "trust the framework" baseline).

All modes require branches with identical output shapes (pad-and-slice for
heterogeneous Inception widths happens in the model layer).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import branch_matmul as stacked_matmul


@dataclasses.dataclass
class Branches:
    """Model-definition combinator: a fork of independent branch callables
    whose outputs are joined by ``combine`` ('concat' | 'sum' | 'stack')."""
    fns: Sequence[Callable]
    combine: str = "concat"
    name: str = "branches"


def _join(ys: list[jax.Array], combine: str) -> jax.Array:
    if combine == "concat":
        return jnp.concatenate(ys, axis=-1)
    if combine == "sum":
        out = ys[0]
        for y in ys[1:]:
            out = out + y
        return out
    if combine == "stack":
        return jnp.stack(ys, axis=0)
    raise ValueError(combine)


def run_xla(branches: Branches, x: jax.Array) -> jax.Array:
    return _join([f(x) for f in branches.fns], branches.combine)


def run_stacked_matmul(x: jax.Array, ws: jax.Array, combine: str = "concat",
                       interpret: bool | None = None) -> jax.Array:
    """Fused same-shape branch projections: x (M, K), ws (G, K, N)."""
    g = ws.shape[0]
    xs = jnp.broadcast_to(x[None], (g, *x.shape))
    ys = stacked_matmul(xs, ws, interpret=interpret)  # (G, M, N)
    return _join(list(ys), combine)


def run_spatial(branches: Branches, x: jax.Array, mesh: jax.sharding.Mesh,
                axis: str = "model") -> jax.Array:
    """Spatial partitioning over ``axis``: branch g on chips
    [g*W, (g+1)*W), each chip handling 1/W of the local batch.

    x: (B, ...) — batch leading.  Output joined on all chips (replicated
    along ``axis``).
    """

    fns = list(branches.fns)
    g = len(fns)
    m = mesh.shape[axis]
    assert m % g == 0, f"{g} branches must divide mesh axis {axis}={m}"
    w = m // g

    def local(xl):
        idx = jax.lax.axis_index(axis)
        grp, within = idx // w, idx % w
        bl = xl.shape[0]
        assert bl % w == 0, f"local batch {bl} not divisible by {w}"
        sub = jax.lax.dynamic_slice_in_dim(xl, within * (bl // w), bl // w, 0)
        y_sub = jax.lax.switch(grp, fns, sub)      # (bl/w, ...out)
        gath = jax.lax.all_gather(y_sub, axis)     # (M, bl/w, ...out)
        # device m = grp*W + within holds batch rows [within*bl/w, ...):
        # (G, W, bl/w, ...) reshapes straight to (G, bl, ...) in batch order
        ys = gath.reshape(g, bl, *y_sub.shape[1:])
        return _join(list(ys), branches.combine)

    in_spec = P(*([None] * x.ndim))
    # Trace one branch to get the output rank for the replicated out_spec.
    out_shape = jax.eval_shape(fns[0], jax.ShapeDtypeStruct(
        (x.shape[0],) + x.shape[1:], x.dtype))
    out_rank = len(out_shape.shape)
    out_spec = P(*([None] * out_rank))
    return jax.shard_map(local, mesh=mesh, in_specs=(in_spec,),
                         out_specs=out_spec, check_vma=False)(x)


def run(branches: Branches, x: jax.Array, *, mode: str = "xla",
        mesh: jax.sharding.Mesh | None = None, axis: str = "model"):
    if mode == "spatial":
        assert mesh is not None, "spatial mode needs a mesh"
        return run_spatial(branches, x, mesh, axis)
    return run_xla(branches, x)
