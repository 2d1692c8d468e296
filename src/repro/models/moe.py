"""Mixture-of-Experts layer: top-k router + capacity-bounded sort dispatch.

Experts ARE the paper's independent branches: E disjoint GEMM chains forked
by the router and joined by the weighted combine.  At mesh scale they are
spatially partitioned (expert dim sharded over the ``model`` axis = the
paper's inter-SM partitioning, one expert group per chip group); intra-chip
the E-leading einsum is exactly the stacked branch-GEMM pattern of
``kernels/branch_matmul``.

Dispatch is sort-based with a static capacity (GShard/Switch family), done
PER BATCH ROW so every sort/scatter is local to a data shard — a global
token sort would force cross-device sorting and SPMD full-rematerialization
(observed: 424 GB/device temp on the 398B config before this formulation).
FLOPs scale with top_k (not E); tokens over capacity are dropped (standard)
and counted in aux stats.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.sharding import constrain


def moe_init(key, d: int, f: int, n_experts: int, *, shared_f: int = 0,
             gated: bool = True, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    std = d ** -0.5
    p = {
        "router": L.normal_init(ks[0], (d, n_experts), std, dtype),
        "w_in": L.normal_init(ks[1], (n_experts, d, f), std, dtype),
        "w_out": L.normal_init(ks[2], (n_experts, f, d), f ** -0.5, dtype),
    }
    if gated:
        p["w_gate"] = L.normal_init(ks[3], (n_experts, d, f), std, dtype)
    if shared_f:
        p["shared"] = L.mlp_init(ks[4], d, shared_f, gated=gated, dtype=dtype)
    return p


def moe_apply(params, x, *, top_k: int, capacity_factor: float = 1.25,
              activation: str = "silu", impl: str = "einsum",
              interpret: bool | None = None):
    """x: (B, S, D) -> (out (B, S, D), aux dict).

    ``impl`` picks the expert engine: ``"einsum"`` is the capacity-padded
    E-leading stacked einsum (the oracle — FLOPs spent on every empty
    capacity slot), ``"grouped"`` packs routed tokens into per-expert
    ragged segments and runs ONE ``grouped_matmul_experts`` launch per
    direction (FLOPs scale with routed tokens).  Both share ``_route``,
    so routing, drops and the combine scatter are identical — the
    grouped path reproduces the einsum path for routed tokens exactly.
    The shard_map perf paths (``moe_local``/``moe_ep``) always use the
    einsum core; ``impl`` applies to the single-mesh path.

    Under the ``moe_local`` perf option (requires replicated expert params,
    i.e. dp_over_model), the whole dispatch/combine runs inside shard_map
    per data shard: sorts/scatters become chip-local, eliminating the
    GSPMD scatter-add all-reduce (measured 4.3 GB x n_layers on granite)."""
    assert impl in ("einsum", "grouped"), impl
    from repro.sharding import specs as SH
    mesh = getattr(SH._CTX, "mesh", None)
    if SH.perf_option("moe_local") and mesh is not None:
        from jax.sharding import PartitionSpec as P
        dp = SH.logical_axes(mesh, "dp")
        dp_size = 1
        for a in (dp if isinstance(dp, tuple) else (dp,) if dp else ()):
            dp_size *= mesh.shape[a]
        if dp and x.shape[0] % dp_size == 0:
            def local(p, xl):
                with SH.activations_on(None):   # no GSPMD constraints inside
                    out, aux = _moe_apply_core(p, xl, top_k=top_k,
                                               capacity_factor=capacity_factor,
                                               activation=activation)
                aux = {k: jax.lax.pmean(v, dp) if jnp.ndim(v) == 0 else v
                       for k, v in aux.items()}
                return out, aux

            fn = jax.shard_map(local, mesh=mesh,
                               in_specs=(P(), P(dp, None, None)),
                               out_specs=(P(dp, None, None), P()),
                               check_vma=False)
            return fn(params, x)

    # moe_ep: expert-parallel local dispatch — experts stay sharded over the
    # ``model`` axis (the paper's spatial branch partitioning); each chip
    # routes its data shard locally, computes ONLY its local experts, and a
    # single psum over ``model`` joins the branches.  Eliminates the GSPMD
    # gather/scatter all-reduces (measured ~600 GB/step on jamba train_4k).
    e_total = params["router"].shape[1]
    if SH.perf_option("moe_ep") and mesh is not None \
            and "model" in mesh.axis_names \
            and e_total % mesh.shape["model"] == 0 \
            and "shared" not in params:
        from jax.sharding import PartitionSpec as P
        dp = SH.logical_axes(mesh, "dp")
        dp_size = 1
        for a in (dp if isinstance(dp, tuple) else (dp,) if dp else ()):
            dp_size *= mesh.shape[a]
        if dp and x.shape[0] % dp_size == 0:
            e_local = e_total // mesh.shape["model"]

            def local_ep(p, xl):
                off = jax.lax.axis_index("model") * e_local
                with SH.activations_on(None):   # no GSPMD constraints inside
                    out, aux = _moe_apply_core(
                        p, xl, top_k=top_k, capacity_factor=capacity_factor,
                        activation=activation, expert_offset=off,
                        n_global_experts=e_total)
                out = jax.lax.psum(out, "model")        # join the branches
                aux = {k: (jax.lax.pmean(jax.lax.pmean(v, dp), "model")
                           if jnp.ndim(v) == 0 else v)
                       for k, v in aux.items()}
                return out, aux

            pspec = {"router": P(), "w_in": P("model", None, None),
                     "w_out": P("model", None, None)}
            if "w_gate" in params:
                pspec["w_gate"] = P("model", None, None)
            fn = jax.shard_map(local_ep, mesh=mesh,
                               in_specs=(pspec, P(dp, None, None)),
                               out_specs=(P(dp, None, None), P()),
                               check_vma=False)
            return fn(params, x)

    if impl == "grouped":
        return _moe_apply_grouped(params, x, top_k=top_k,
                                  capacity_factor=capacity_factor,
                                  activation=activation, interpret=interpret)
    return _moe_apply_core(params, x, top_k=top_k,
                           capacity_factor=capacity_factor,
                           activation=activation)


def moe_capacity(sk: int, capacity_factor: float, e_route: int) -> int:
    """Static per-(row, expert) capacity (GShard family): ceil to a
    multiple of 8 once past 8, never above S*k.  Shared by the dispatch,
    the plan pricing and the bench so the einsum engine's padded-slot
    denominator is the one the kernel path was actually compared to."""
    cap = int(-(-sk * capacity_factor // e_route))
    return max(1, min(-(-cap // 8) * 8 if cap >= 8 else cap, sk))


def _route(params, x, *, top_k: int, capacity_factor: float,
           expert_offset=0, n_global_experts: int | None = None):
    """Router + per-row sort-based dispatch shared by BOTH expert engines.

    Returns everything dispatch-order-dependent so the einsum and grouped
    paths see identical token ordering, identical drops and identical
    combine indices — the equivalence guarantee between the two engines
    reduces to the expert GEMMs themselves."""
    b, s, d = x.shape
    e = params["w_in"].shape[0]                # local experts to compute
    e_route = n_global_experts or e            # global routing space

    logits = jnp.einsum("bsd,de->bse", x, params["router"])
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, ids = jax.lax.top_k(probs, top_k)                    # (B, S, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)

    sk = s * top_k
    cap = moe_capacity(sk, capacity_factor, e_route)
    flat_e = ids.reshape(b, sk)                             # (B, S*k)
    flat_t = jnp.broadcast_to(
        jnp.repeat(jnp.arange(s), top_k)[None], (b, sk))
    flat_w = w.reshape(b, sk)
    order = jnp.argsort(flat_e, axis=1, stable=True)
    se = jnp.take_along_axis(flat_e, order, axis=1)
    st = jnp.take_along_axis(flat_t, order, axis=1)
    sw = jnp.take_along_axis(flat_w, order, axis=1)
    first = jax.vmap(
        lambda row: jnp.searchsorted(row, row, side="left"))(se)
    pos = jnp.arange(sk)[None] - first                      # rank in expert
    keep = pos < cap
    se_local = se - expert_offset                           # window shift
    in_window = (se_local >= 0) & (se_local < e)
    keep = keep & in_window
    brow = jnp.broadcast_to(jnp.arange(b)[:, None], (b, sk))
    return (probs, flat_e, se_local, st, sw, pos, keep, in_window, cap,
            brow, e, e_route, sk)


def _moe_aux(probs, flat_e, keep, in_window, brow, *, e, e_route, cap):
    """Switch load-balancing loss + drop/padding stats (shared)."""
    b, sk = flat_e.shape
    me = probs.mean((0, 1))                                 # (E_route,)
    ce = jnp.zeros((b, e_route), jnp.float32).at[brow, flat_e].add(1.0)
    ce = ce.sum(0) / (b * sk)
    aux_loss = e_route * jnp.sum(me * ce)
    n_window = jnp.maximum(in_window.sum().astype(jnp.float32), 1.0)
    kept = keep.sum().astype(jnp.float32)
    dropped = 1.0 - kept / n_window
    slots = float(b * e * cap)                 # the einsum engine's M rows
    return {"aux_loss": aux_loss, "drop_fraction": dropped,
            "capacity": cap,
            "padded_slot_fraction": (slots - kept) / slots}


def _moe_apply_core(params, x, *, top_k: int, capacity_factor: float = 1.25,
                    activation: str = "silu", expert_offset=0,
                    n_global_experts: int | None = None):
    """Batched-over-B dispatch/expert/combine (vmap-free sorts/gathers).

    With ``expert_offset``/``n_global_experts`` set (moe_ep shard_map path),
    routing runs over the GLOBAL expert space but only experts in the local
    window [offset, offset + E_local) are dispatched/computed; the caller
    psums the partial outputs over the expert axis."""
    b, s, d = x.shape
    (probs, flat_e, se_local, st, sw, pos, keep, in_window, cap, brow,
     e, e_route, sk) = _route(params, x, top_k=top_k,
                              capacity_factor=capacity_factor,
                              expert_offset=expert_offset,
                              n_global_experts=n_global_experts)
    slot = jnp.where(keep, se_local * cap + pos, e * cap)   # sentinel E*cap

    disp = jnp.full((b, e * cap + 1), s, jnp.int32)         # s -> zero row
    disp = disp.at[brow, slot].set(
        jnp.where(keep, st, s).astype(jnp.int32), mode="drop")
    xpad = jnp.concatenate([x, jnp.zeros((b, 1, d), x.dtype)], axis=1)
    xe = jnp.take_along_axis(
        xpad, disp[:, : e * cap, None], axis=1).reshape(b, e, cap, d)
    xe = constrain(xe, "dp", "tp", None, None)

    # ---- expert branches (stacked GEMMs over the expert axis) --------------
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[activation]
    h = jnp.einsum("becd,edf->becf", xe, params["w_in"])
    if "w_gate" in params:
        h = act(jnp.einsum("becd,edf->becf", xe, params["w_gate"])) * h
    else:
        h = act(h)
    h = constrain(h, "dp", "tp", None, None)
    ye = jnp.einsum("becf,efd->becd", h, params["w_out"])   # (B, E, C, D)
    ye = constrain(ye, "dp", "tp", None, None)

    # ---- weighted combine ---------------------------------------------------
    ypad = jnp.concatenate(
        [ye.reshape(b, e * cap, d),
         jnp.zeros((b, 1, d), ye.dtype)], axis=1)           # (B, E*C+1, D)
    contrib = jnp.take_along_axis(ypad, slot[..., None], axis=1) \
        * sw[..., None].astype(ye.dtype)                    # (B, S*k, D)
    out = jnp.zeros((b, s, d), ye.dtype).at[brow, st].add(contrib)

    if "shared" in params:
        out = out + L.mlp(params["shared"], x, activation).astype(out.dtype)

    aux = _moe_aux(probs, flat_e, keep, in_window, brow,
                   e=e, e_route=e_route, cap=cap)
    return out.reshape(b, s, d).astype(x.dtype), aux


def _moe_apply_grouped(params, x, *, top_k: int,
                       capacity_factor: float = 1.25,
                       activation: str = "silu",
                       interpret: bool | None = None):
    """Routed tokens packed into block-aligned per-expert segments of ONE
    (MBS*bm, D) buffer, expert compute in ONE ``grouped_matmul_experts``
    launch per direction.

    The pack permutation is a second stable argsort (by expert id, drops
    sorted last) on top of ``_route``'s per-row order; ``pp`` maps each
    routed assignment to its pack row and its inverse gathers the combine
    contributions, so combine indices and values match the einsum engine
    element-for-element (drops hit the appended zero row in both)."""
    from repro.kernels import ops as kops
    b, s, d = x.shape
    (probs, flat_e, se_local, st, sw, pos, keep, in_window, cap, brow,
     e, e_route, sk) = _route(params, x, top_k=top_k,
                              capacity_factor=capacity_factor)
    n = b * sk                                 # total routed assignments
    bm = kops.moe_block_m(n, e)
    n_pack = kops.moe_static_blocks(n, e, bm) * bm

    ge = jnp.where(keep, se_local, e).reshape(-1)           # drops -> E
    order2 = jnp.argsort(ge, stable=True)                   # global by expert
    sge = ge[order2]
    counts = jnp.zeros((e,), jnp.int32).at[sge].add(1, mode="drop")
    firstq = jnp.searchsorted(sge, sge, side="left")
    rank = jnp.arange(n, dtype=jnp.int32) - firstq          # rank in expert
    rowoff = kops.expert_row_offsets(counts, bm)
    pp_sorted = jnp.where(
        sge < e, rowoff[jnp.clip(sge, 0, e - 1)] + rank,
        n_pack).astype(jnp.int32)                           # drops -> trash
    pp = jnp.zeros((n,), jnp.int32).at[order2].set(pp_sorted)

    keep_f = keep.reshape(-1)
    fi = (brow * s + st).reshape(-1)                        # flat token idx
    dispv = jnp.full((n_pack + 1,), b * s, jnp.int32).at[pp].set(
        jnp.where(keep_f, fi, b * s).astype(jnp.int32), mode="drop")
    xflat = jnp.concatenate(
        [x.reshape(b * s, d), jnp.zeros((1, d), x.dtype)])  # b*s -> zeros
    xpk = xflat[dispv[:n_pack]]
    swpk = jnp.zeros((n_pack + 1,), jnp.float32).at[pp].set(
        jnp.where(keep_f, sw.reshape(-1), 0.0), mode="drop")[:n_pack]

    ypk = kops.grouped_matmul_experts(
        xpk, swpk, params["w_in"], params["w_out"], params.get("w_gate"),
        counts, activation=activation, bm=bm, interpret=interpret)

    ypad = jnp.concatenate([ypk, jnp.zeros((1, d), ypk.dtype)])
    contrib = ypad[pp].reshape(b, sk, d)       # drops gather the zero row
    out = jnp.zeros((b, s, d), ypk.dtype).at[brow, st].add(contrib)

    if "shared" in params:
        out = out + L.mlp(params["shared"], x, activation).astype(out.dtype)

    aux = _moe_aux(probs, flat_e, keep, in_window, brow,
                   e=e, e_route=e_route, cap=cap)
    return out.reshape(b, s, d).astype(x.dtype), aux


def build_moe_graph(*, b: int, s: int, d: int, f: int, e: int, top_k: int,
                    capacity_factor: float, gated: bool = True,
                    shared_f: int = 0, dtype_bytes: int = 4):
    """Op-graph view of one MoE layer for the plan layer: the router
    matmul forks into E independent expert chains (in/gate/out matmuls at
    the einsum engine's per-expert M = B*cap — the fork the scheduler
    sees; the grouped lowering re-prices them as ONE ragged launch) and
    the weighted combine joins them.  The optional shared MLP rides
    alongside the routed experts."""
    from repro.core.graph import Op, OpGraph

    g = OpGraph()
    sk = s * top_k
    cap = moe_capacity(sk, capacity_factor, e)
    g.add(Op.make("moe_router", "matmul", dtype_bytes, m=b * s, k=d, n=e))
    expert_ops = []
    for i in range(e):
        deps = ["moe_router"]
        g.add(Op.make(f"expert{i}_in", "matmul", dtype_bytes,
                      m=b * cap, k=d, n=f), deps)
        expert_ops.append(f"expert{i}_in")
        if gated:
            g.add(Op.make(f"expert{i}_gate", "matmul", dtype_bytes,
                          m=b * cap, k=d, n=f), deps)
            expert_ops.append(f"expert{i}_gate")
        g.add(Op.make(f"expert{i}_out", "matmul", dtype_bytes,
                      m=b * cap, k=f, n=d),
              [f"expert{i}_in"] + ([f"expert{i}_gate"] if gated else []))
        expert_ops.append(f"expert{i}_out")
    g.add(Op.make("moe_combine", "pointwise", dtype_bytes,
                  elements=b * sk * d), expert_ops)
    if shared_f:
        g.add(Op.make("shared_in", "matmul", dtype_bytes,
                      m=b * s, k=d, n=shared_f))
        if gated:
            g.add(Op.make("shared_gate", "matmul", dtype_bytes,
                          m=b * s, k=d, n=shared_f))
        g.add(Op.make("shared_out", "matmul", dtype_bytes,
                      m=b * s, k=shared_f, n=d),
              ["shared_in"] + (["shared_gate"] if gated else []))
    return g
