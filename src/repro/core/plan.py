"""Executable plan IR — the lowering layer between Schedule and kernels.

``core/scheduler.py`` decides WHAT co-executes (CoGroups + per-op
algorithms); this module decides HOW: ``lower()`` turns each CoGroup into an
``ExecGroup`` with a concrete execution mode and ``run_plan`` /
``execute_plan`` actually run it.  This is the piece the paper says
frameworks are missing — they model inter-op parallelism but launch
kernels serially — and the piece Opara-style systems add: an operator
execution plan compiled from the DAG.

Modes (mirroring ``core/branch_parallel.py``):

  grouped — branches expressible as shared-M GEMMs with *per-branch*
            (K_g, N_g) — ragged 1x1 widths, and K×K convs through their
            im2col view — run as ONE Pallas kernel over a flattened tile
            grid with a scalar-prefetched offset table and the bias+ReLU
            epilogue fused in-kernel (``kernels/grouped_matmul.py``).  No
            pad-to-max-N waste, no post-kernel HBM round-trip.
  grouped_concat — a grouped group that ABSORBS the fork/join concat its
            branches feed: the epilogue writes each branch's tiles
            straight into its slice of the join's [M, sum N_g] layout
            (``grouped_matmul_concat``), join inputs produced by earlier
            groups are copied in as passthrough column slices, and the
            standalone join op disappears from the plan.  The grad group
            mirrors as ONE combined dx+dw/db launch whose packing slices
            the joint cotangent directly.
  grouped_pooled — a grouped group that ABSORBS the maxpool op(s) feeding
            its branches: the launch's offset table gains per-branch pool
            descriptors and the kernel maxes raw-input tap tiles into a
            VMEM pooled-lhs scratch before each M-block's GEMM steps
            (``grouped_matmul_pooled``) — the pooled activation never
            round-trips HBM and the standalone ``reduce_window`` launch
            disappears from the plan.  A ``grouped_concat`` group absorbs
            pools the same way (mode stays grouped_concat, its ``pools``
            recorded), so a pool-proj branch rides the single
            pool+GEMM+epilogue+concat launch.  The grad group mirrors as
            the same ONE combined launch, the pooling cotangent scattered
            through the first-argmax window mask in its unpacking.
  grouped_chained — cross-MODULE streaming (opt-in via
            ``lower(chain_modules=True)``): a module's quad group, the
            concat-pair riding on its reductions, and stem conv runs
            merge into ONE launch running their phases in a lag-1 wave
            schedule (``grouped_matmul_chained``).  Phase p+1 branches
            ring-consume phase p's freshly computed row blocks from VMEM
            (K*K convs as K^2 shifted tap-GEMMs), the join never
            materializes — the launch's padded panels flow to the NEXT
            chained launch as a ``ChainPanels`` value addressed in place
            by panel lhs-source descriptors — and the grad group mirrors
            as one combined dx+dw/db launch per phase in reverse order.
  grouped_experts — an MoE layer's E expert chains (the router's fork)
            run as ONE per-expert-ragged grouped launch per direction
            (``kernels.grouped_matmul_experts``): each expert owns its
            routed token count M_g via the dynamic block-meta prefetch,
            the router's gating weights and activation fuse into the
            epilogue, and FLOPs scale with routed tokens instead of the
            einsum engine's E*capacity slots (``lower_moe``).
  stacked — same-GEMM-shape branches fuse into ONE Pallas kernel with a
            branch grid axis (``kernels/branch_matmul.py``); heterogeneous
            output widths are padded to a common N and sliced back.  Kept
            for uniform shapes, where the padding-waste term vanishes.
  fused   — a compute-bound GEMM paired with a memory-bound streamed
            reduction co-execute in one grid (``kernels/fused_branches.py``)
            so the reduction's HBM bytes ride under the GEMM's MXU work.
  spatial — branches run on disjoint chips of a mesh's ``model`` axis via
            ``core.branch_parallel.run_spatial`` (needs a mesh, branch
            count dividing the axis, and identical output shapes).
  serial  — one op after another with the scheduler-chosen per-op
            algorithms (the algorithms-dict path ``models/cnn.py::forward``
            has always had); also the fallback when budgets are infeasible.
  xla     — emit the ops together inside one jit and trust XLA to
            interleave them (the framework baseline the paper critiques).

Mode choice delegates to ``cost_model.group_execution_time`` (the same
judgement the scheduler packs with); ``lower`` re-checks the
workspace/VMEM budgets (paper C2) — a group whose combined footprint no
longer fits is demoted to ``serial`` — and upgrades to ``spatial`` when a
mesh makes that faster than any single-chip mode.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.analysis import budgets as _budgets
from repro.core import cost_model as cm
from repro.core.graph import OpGraph
from repro.core.scheduler import Schedule

MODES = ("grouped", "grouped_concat", "grouped_pooled", "grouped_chained",
         "grouped_experts", "stacked", "fused", "spatial", "serial", "xla")


@dataclasses.dataclass(frozen=True)
class ExecGroup:
    """One schedulable unit of the executable plan."""
    mode: str                      # one of MODES
    ops: tuple[str, ...]
    algorithms: dict[str, str]     # op -> algorithm (serial fallback path)
    modeled_time: float            # cost-model makespan under ``mode``
    reason: str = ""               # why ``mode`` was chosen (debugging)
    join: str = ""                 # grouped_concat: the absorbed join op
    # absorbed maxpools: (branch op, pool op) pairs — the branch's lhs is
    # pooled in-launch from the pool op's input (grouped_pooled, and
    # grouped_concat groups whose branches pool)
    pools: tuple[tuple[str, str], ...] = ()
    # grouped_chained: the launch's phase structure — one tuple of op
    # names per phase (the join, if any, rides ``join`` and appears in
    # ``ops`` but not in ``chain``).  Phase p+1 branches whose producer
    # sits in phase p consume it through the in-kernel VMEM ring.
    chain: tuple[tuple[str, ...], ...] = ()
    # SMEM chunking: each grouped-family launch of the group (both
    # directions) runs as ``chunks`` launches over image-aligned row
    # chunks of at most ``chunk_rows`` rows, so that every launch's offset
    # table fits the chip's SMEM (``analysis.budgets.chunk_rows``).
    # 0 / 1 = one launch over all M.
    chunk_rows: int = 0
    chunks: int = 1

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"unknown mode {self.mode}")


@dataclasses.dataclass
class ChainPanels:
    """The composite value a chained launch leaves in ``env``: the padded
    per-phase output panels of ``grouped_matmul_chained`` plus the
    (panel, col-block base, true width) segment layout of the logical
    join, in join order.  The next chained launch consumes it IN PLACE
    (panel lhs-source descriptors, or a per-segment pooled fold) — no
    concat, no reshape; any non-chained consumer materializes it to NHWC
    through ``_env_val`` (one concatenate: exactly the join the chain
    otherwise deleted)."""
    panels: tuple                       # padded (Mp, ncb*blk) arrays
    segments: tuple[tuple[int, int, int], ...]   # (panel, col block, n)
    m: int                              # true rows (B*H*W)
    h: int
    w: int
    blk: int = 128

    @property
    def width(self) -> int:
        return sum(n for _, _, n in self.segments)


@dataclasses.dataclass
class Plan:
    """Ordered ExecGroups + the context needed to execute them."""
    groups: list[ExecGroup]
    context: dict = dataclasses.field(default_factory=dict)

    @property
    def makespan(self) -> float:
        return sum(g.modeled_time for g in self.groups)

    @property
    def algorithms(self) -> dict[str, str]:
        out: dict[str, str] = {}
        for g in self.groups:
            out.update(g.algorithms)
        return out

    def mode_counts(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for g in self.groups:
            out[g.mode] = out.get(g.mode, 0) + 1
        return out

    def groups_of_mode(self, mode: str) -> list[ExecGroup]:
        return [g for g in self.groups if g.mode == mode]


# ---------------------------------------------------------------------------
# lowering
# ---------------------------------------------------------------------------

# (M, K, N) GEMM view of an op — matmuls verbatim, convs via im2col; the
# shared definition lives next to the times it feeds.
_gemm_shape = cm.gemm_shape


def _spatial_ok(graph: OpGraph, ops, mesh) -> bool:
    """Branches with one shared producer and identical output element
    counts, dividing the mesh's model axis."""
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return False
    if mesh.shape["model"] % len(ops) != 0 or mesh.shape["model"] < len(ops):
        return False
    preds = [graph.pred[op.name] for op in ops]
    if any(len(p) != 1 for p in preds) or len({tuple(sorted(p))
                                               for p in preds}) != 1:
        return False
    outs = set()
    for op in ops:
        p = op.p
        if op.kind == "conv2d":
            s = p.get("stride", 1)
            outs.add((p["n"], -(-p["h"] // s), -(-p["w"] // s), p["k"]))
        elif op.kind == "matmul":
            outs.add((p["m"], p["n"]))
        else:
            return False
    return len(outs) == 1


def _absorb_concat_joins(graph: OpGraph,
                         groups: list[ExecGroup]) -> list[ExecGroup]:
    """Fuse fork/join concats into the grouped launches that feed them.

    A grouped group absorbs a join when (a) the join is the ONLY consumer
    of every op in the group (their outputs exist solely to be
    concatenated), (b) the join is a pointwise op lowered as its own
    singleton group later in the plan, and (c) every OTHER join input is
    produced by an earlier group (those arrive as passthrough column
    slices).  The merged ``grouped_concat`` group prices at
    ``cost_model.group_execution_time(..., join=...)`` — branch slices
    leave the kernel inside the join buffer, so only the passthrough
    columns keep a copy cost — and the standalone join group is dropped.
    """
    out: list[ExecGroup | None] = list(groups)
    for idx, g in enumerate(out):
        if g is None or g.mode not in ("grouped", "grouped_pooled") \
                or len(g.ops) < 2:
            continue
        succs = {s for n in g.ops for s in graph.succ[n]}
        if len(succs) != 1:
            continue
        (jname,) = succs
        jop = graph.ops.get(jname)
        if jop is None or jop.kind != "pointwise":
            continue
        if any(graph.succ[n] != {jname} for n in g.ops):
            continue
        jidx = next((k for k, gg in enumerate(out)
                     if gg is not None and gg.ops == (jname,)), None)
        if jidx is None or jidx < idx:
            continue
        produced = {n for gg in out[:idx] if gg is not None for n in gg.ops}
        produced.update(n for n in graph.ops if not graph.pred[n])
        if not all(p in produced for p in graph.pred[jname] - set(g.ops)):
            continue
        ops = [graph.ops[n] for n in g.ops]
        profs = [cm.profile(op, g.algorithms[op.name]) for op in ops]
        mode, t = cm.group_execution_time(ops, profs, join=jop)
        if mode != "grouped_concat" \
                or t >= g.modeled_time + out[jidx].modeled_time:
            continue
        algs = dict(g.algorithms)
        algs.update(out[jidx].algorithms)
        out[idx] = ExecGroup(
            "grouped_concat", g.ops + (jname,), algs, t,
            "fused epilogue-concat: branch slices land in the join "
            "buffer in-kernel", join=jname, pools=g.pools)
        out[jidx] = None
    return [g for g in out if g is not None]


def _absorb_pools(graph: OpGraph, groups: list[ExecGroup], *,
                  hbm_budget: float = cm.HBM_BYTES * 0.25,
                  vmem_budget: float = cm.VMEM_BYTES) -> list[ExecGroup]:
    """Stream standalone maxpool ops through the grouped launches that
    consume them (the pool analogue of ``_absorb_concat_joins``).

    A maxpool singleton group is absorbed when EVERY consumer of the pool
    is a GEMM-viewed branch of a LATER grouped-family group and none of
    those branches already pools another input — each consuming group
    then gains a per-branch ``pools`` descriptor (its launch pools the
    pool op's RAW input in-kernel: tap tiles maxed into the pooled-lhs
    scratch, see ``kernels/grouped_matmul.py``) and the standalone
    ``reduce_window`` group is dropped.  The fused rider is ZERO
    (``cost_model.pool_profile`` — the tap reads stream through the
    launch's existing lhs DMA and the pooled activation never touches
    HBM), so absorption wins by exactly the pool group's makespan; a
    consuming STACKED group is re-priced onto the grouped kernel (the
    pad-to-max kernel has no pool stage), which must still beat keeping
    the pool standalone.  Consumers may span several groups — an
    inter-module pool feeding two launches is pooled by each (recomputed
    taps instead of a materialized pooled tensor; recompute is free under
    the rider model, the ROADMAP's hw-calibration caveat applies).

    The pooled launch's footprint is re-checked against the C2 budgets
    ``lower`` gated the unpooled group on: the tap-expanded X stack packs
    up to ``POOL_TAP_LIMIT`` tap tiles per pooled lhs tile (extra HBM
    workspace; past the limit the taps fold at pack time and add
    nothing), and the pooled-lhs scratch claims VMEM — a pool whose
    absorption would bust a consuming group's budget stays standalone."""
    out: list[ExecGroup | None] = list(groups)
    for idx, pg in enumerate(out):
        if pg is None or len(pg.ops) != 1:
            continue
        (pname,) = pg.ops
        pop = graph.ops.get(pname)
        if pop is None or pop.kind != "maxpool":
            continue
        consumers = sorted(graph.succ[pname])
        if not consumers:
            continue
        targets: dict[int, list[str]] = {}
        ok = True
        for c in consumers:
            j = next((k for k, gg in enumerate(out)
                      if gg is not None and c in gg.ops), None)
            if (j is None or j <= idx
                    or out[j].mode not in ("grouped", "grouped_pooled",
                                           "grouped_concat", "stacked")
                    or _gemm_shape(graph.ops[c]) is None
                    # the branch must read the pool as its ONLY input (its
                    # gemm_x maps each raw tap view single-argument) and a
                    # branch can absorb at most one pool chain
                    or graph.pred[c] != {pname}
                    or any(b == c for b, _ in out[j].pools)):
                ok = False
                break
            targets.setdefault(j, []).append(c)
        if not ok:
            continue
        # price every affected group first — absorption is all-or-nothing
        # across the pool's consumers (a partially absorbed pool would
        # still have to launch standalone), and the win check aggregates:
        # dropping the pool group saves its makespan exactly ONCE, so the
        # SUM of repriced-group increases (stacked consumers moving onto
        # the grouped kernel) must stay below it
        repriced: dict[int, ExecGroup] = {}
        delta = 0.0
        for j, branches in targets.items():
            gg = out[j]
            # C2 re-check on the WHOLE pooled launch (pools already
            # absorbed into this group included); ``include_gemm_ws``
            # prices the grouped kernel's im2col patch buffers even when
            # a join op rides in the group, matching the gate ``lower``
            # applied to the unpooled group
            fp = _budgets.group_footprint(
                graph, gg.ops, gg.algorithms, include_gemm_ws=True,
                pools=tuple(gg.pools) + tuple((b, pname)
                                              for b in branches))
            if not fp.fits(hbm_budget, vmem_budget):
                ok = False
                break
            mode, t, reason = gg.mode, gg.modeled_time, gg.reason
            if gg.mode == "stacked":
                branch_ops = [graph.ops[n] for n in gg.ops]
                t = cm.grouped_time(branch_ops)
                mode = "grouped_pooled"
                reason = ("pool absorption: stacked branches take the "
                          "grouped kernel (the pooled lhs needs its "
                          "pool stage)")
                delta += t - gg.modeled_time
            elif gg.mode == "grouped":
                mode = "grouped_pooled"
                reason = ("in-kernel pre-GEMM maxpool: pooled lhs "
                          "streams from raw-input tap tiles")
            algs = dict(gg.algorithms)
            algs.update(pg.algorithms)   # the pool's choice survives
            repriced[j] = ExecGroup(
                mode, gg.ops, algs, t, reason, join=gg.join,
                pools=gg.pools + tuple((b, pname) for b in branches))
        if not ok or delta >= pg.modeled_time:
            continue
        for j, gg in repriced.items():
            out[j] = gg
        out[idx] = None
    return [g for g in out if g is not None]


def _chain_feasible(graph: OpGraph, phase0: list[str], branches: list[str],
                    join: str, *, block: int = 128) -> bool:
    """Geometry/topology gates for merging a quad group (phase 0) with the
    grouped_concat pair (phase 1) feeding off it into ONE chained launch:

      * every phase-1 branch is a stride-1 conv whose single producer is a
        phase-0 op and whose halo fits the ring window — the kernel loads
        row blocks i-1/i/i+1 into a (3*bm, blk) window and slices at
        bm+delta, so |delta| = (kh//2)*W + kw//2 must stay <= bm (= block);
      * phase-0 ops read no phase-0 op (the wave schedule runs a phase's
        branches at the same lag — intra-phase chaining has no ring slot);
      * nothing escapes the launch: every phase-0 output is consumed only
        by phase-1 branches or the join, and the join reads only in-launch
        branches (the ChainPanels segments must all come from this launch);
      * one shared GEMM M across every branch of both phases (the wave
        schedule advances all phases over the same row blocks).
    """
    qset, bset = set(phase0), set(branches)
    for b in branches:
        op = graph.ops.get(b)
        preds = graph.pred[b]
        if (op is None or op.kind != "conv2d"
                or op.p.get("stride", 1) != 1
                or len(preds) != 1 or not preds <= qset):
            return False
        halo = (op.p.get("kh", 1) // 2) * op.p["w"] + op.p.get("kw", 1) // 2
        if halo > block:
            return False
    for n in phase0:
        if graph.pred[n] & qset:
            return False
        if not graph.succ[n] <= bset | {join}:
            return False
    if not graph.pred[join] <= qset | bset:
        return False
    ms = {(_gemm_shape(graph.ops[n]) or (None,))[0] for n in phase0 + branches}
    return None not in ms and len(ms) == 1


def _chain_budgets_ok(graph: OpGraph, phases: list[list[str]], ring, *,
                      hbm_budget: float, vmem_budget: float,
                      block: int = 128, pools=(),
                      train: bool = False) -> bool:
    """C2 re-check on the chained launch: the HBM workspace of its
    chained-priced GEMM lowering (ring consumers drop their patch buffer —
    their lhs never exists outside VMEM) plus the launch's ring scratch
    against the VMEM budget: 3 wave slots and a (3*bm, blk) shift window
    per ring column, and the f32 accumulator.  The footprint itself comes
    from ``analysis.budgets.chained_footprint``.  SMEM: a one-image chunk
    of the launch (and, ``train``, of its backward) must fit — chunking
    splits anything larger (``analysis.budgets.chunk_rows``)."""
    if not _budgets.chained_footprint(graph, phases, ring,
                                      block=block).fits(hbm_budget,
                                                        vmem_budget):
        return False
    probe = ExecGroup("grouped_chained", tuple(n for ph in phases
                                                for n in ph), {}, 0.0,
                      pools=tuple(pools), chain=tuple(map(tuple, phases)))
    try:
        _budgets.chunk_rows(graph, probe, _directions(train))
    except ValueError:
        return False
    return True


def _directions(train: bool) -> tuple[str, ...]:
    return ("fwd", "bwd") if train else ("fwd",)


def _smem_chunks(graph: OpGraph, groups: list[ExecGroup], *,
                 train: bool) -> list[ExecGroup]:
    """Size every grouped-family group's SMEM chunks — the last lowering
    pass, once each group's launch family (pooled, concat, chained) is
    final.  A launch whose offset table would overflow SMEM splits into
    image-aligned M-chunks (``ExecGroup.chunk_rows``/``chunks``), priced
    as extra launches (``cost_model.chunked_time``); ``train`` sizes them
    for the backward launches too.  A group in which one image's table
    alone overflows is budget-infeasible, like a VMEM overflow: serial
    (chained groups never get here — ``_chain_budgets_ok`` refuses)."""
    out = []
    for g in groups:
        if g.mode not in ("grouped", "grouped_pooled", "grouped_concat",
                          "grouped_chained"):
            out.append(g)
            continue
        try:
            rows, m = _budgets.chunk_rows(graph, g, _directions(train))
        except ValueError as e:
            assert g.mode != "grouped_chained", (g.ops, e)
            profs = [cm.profile(graph.ops[n], g.algorithms[n])
                     for n in g.ops]
            out.append(ExecGroup("serial", g.ops, g.algorithms,
                                 cm.serial_time(profs),
                                 f"budget-infeasible (SMEM: {e})",
                                 pools=g.pools))
            continue
        if rows < m:
            n = -(-m // rows)
            g = dataclasses.replace(
                g, chunk_rows=rows, chunks=n,
                modeled_time=cm.chunked_time(g.modeled_time, n),
                reason=f"{g.reason}; SMEM: {n} launches of <= {rows} rows")
        out.append(g)
    return out


def _chain_modules(graph: OpGraph, groups: list[ExecGroup], *,
                   hbm_budget: float = cm.HBM_BYTES * 0.25,
                   vmem_budget: float = cm.VMEM_BYTES,
                   block: int = 128, train: bool = False) -> list[ExecGroup]:
    """Chain grouped launches ACROSS module boundaries (the cross-module
    streaming pass, after ``_absorb_pools`` + ``_absorb_concat_joins``).

    Two rewrites, both producing ``grouped_chained`` groups that execute
    as ONE ``grouped_matmul_chained`` launch (kernels/grouped_matmul.py)
    running their phases in a lag-1 wave schedule — phase p+1 consumes
    phase p's freshly computed row blocks from an in-kernel VMEM ring,
    never touching HBM for that lhs:

      A. a quad group (grouped/grouped_pooled — e.g. an inception module's
         1x1/r3/r5/pp) merges with the grouped_concat pair riding on its
         reductions (3x3/5x5 + join) into a two-phase launch.  The join
         vanishes entirely: the launch's padded per-phase panels ARE the
         module output (a ``ChainPanels`` value), consumed in place by the
         next chained launch via panel lhs-source descriptors — the
         concat/copy the epilogue-concat mode still paid is gone.
      B. maximal runs of singleton serial conv groups (the stem) fold into
         one multi-phase launch, each conv a phase ring-consuming its
         predecessor — K*K convs stream as K^2 shifted tap-GEMMs.

    Gates: ``_chain_feasible`` (topology + ring-halo geometry),
    ``_chain_budgets_ok`` (C2), and a strict modeled win vs the groups
    merged (``cost_model.chained_time`` — co-execution over all phases
    with ring lhs traffic dropped, stretched by the wave-schedule fill
    factor).  Impl-level requirements (bias+ReLU epilogue, chain_geom)
    are the executor's to verify — a chained group whose bindings don't
    carry them degrades per-op like every other mode."""
    out: list[ExecGroup | None] = list(groups)
    # --- pass A: quad + pair -> one two-phase chained launch -------------
    for idx in range(len(out)):
        q = out[idx]
        if q is None or q.mode not in ("grouped", "grouped_pooled"):
            continue
        match = None
        for jdx in range(idx + 1, len(out)):
            pg = out[jdx]
            if pg is None or pg.mode != "grouped_concat" or not pg.join:
                continue
            branches = [n for n in pg.ops if n != pg.join]
            if {p for n in branches for p in graph.pred[n]} <= set(q.ops):
                match = (jdx, pg, branches)
                break
        if match is None:
            continue
        jdx, pg, branches = match
        if not _chain_feasible(graph, list(q.ops), branches, pg.join,
                               block=block):
            continue
        phases = [list(q.ops), branches]
        ring = frozenset(branches)
        if not _chain_budgets_ok(graph, phases, ring,
                                 hbm_budget=hbm_budget,
                                 vmem_budget=vmem_budget, block=block,
                                 pools=q.pools + pg.pools, train=train):
            continue
        phase_ops = [[graph.ops[n] for n in ph] for ph in phases]
        t = cm.chained_time(phase_ops, ring)
        if t >= q.modeled_time + pg.modeled_time:
            continue
        algs = dict(q.algorithms)
        algs.update(pg.algorithms)
        out[idx] = ExecGroup(
            "grouped_chained", q.ops + pg.ops, algs, t,
            "cross-module chain: reduction outputs stream to the K*K "
            "convs through the VMEM ring and the module output stays a "
            "panel composite (no join, no concat)",
            join=pg.join, pools=q.pools + pg.pools,
            chain=(tuple(q.ops), tuple(branches)))
        out[jdx] = None
    out = [g for g in out if g is not None]
    # --- pass B: serial conv runs -> one multi-phase chained launch ------
    sidx: dict[str, int] = {}
    for i, g in enumerate(out):
        if g.mode == "serial" and len(g.ops) == 1:
            op = graph.ops.get(g.ops[0])
            if op is not None and op.kind == "conv2d" \
                    and _gemm_shape(op) is not None:
                sidx[g.ops[0]] = i
    dead: set[int] = set()
    used: set[str] = set()
    for name in list(sidx):
        if name in used:
            continue
        run = [name]
        cur = name
        while True:
            succ = graph.succ[cur]
            if len(succ) != 1:
                break
            (nxt,) = succ
            if nxt not in sidx or nxt in used or graph.pred[nxt] != {cur}:
                break
            opn = graph.ops[nxt]
            if opn.p.get("stride", 1) != 1:
                break
            halo = (opn.p.get("kh", 1) // 2) * opn.p["w"] \
                + opn.p.get("kw", 1) // 2
            if halo > block:
                break
            if _gemm_shape(opn)[0] != _gemm_shape(graph.ops[cur])[0]:
                break
            run.append(nxt)
            cur = nxt
        used.update(run)
        if len(run) < 2:
            continue
        phases = [[n] for n in run]
        ring = frozenset(run[1:])
        if not _chain_budgets_ok(graph, phases, ring,
                                 hbm_budget=hbm_budget,
                                 vmem_budget=vmem_budget, block=block,
                                 train=train):
            continue
        phase_ops = [[graph.ops[n]] for n in run]
        t = cm.chained_time(phase_ops, ring)
        base = sum(out[sidx[n]].modeled_time for n in run)
        if t >= base:
            continue
        algs: dict[str, str] = {}
        for n in run:
            algs.update(out[sidx[n]].algorithms)
        out[sidx[run[0]]] = ExecGroup(
            "grouped_chained", tuple(run), algs, t,
            "serial-conv chain: each conv a phase ring-consuming its "
            "predecessor (K*K convs as K^2 shifted tap-GEMMs)",
            chain=tuple((n,) for n in run))
        dead.update(sidx[n] for n in run[1:])
    return [g for i, g in enumerate(out) if g is not None and i not in dead]


def _verify_requested(verify) -> bool:
    """planlint default: explicit flag wins; otherwise on under pytest or
    ``REPRO_PLANLINT=1`` (CI), off in production lowering paths."""
    if verify is not None:
        return bool(verify)
    import os
    return (os.environ.get("REPRO_PLANLINT") == "1"
            or "PYTEST_CURRENT_TEST" in os.environ)


def _maybe_verify(plan: Plan, graph: OpGraph | None, verify) -> Plan:
    """Run ``analysis.verify_plan`` on a freshly lowered plan when
    requested; raise ``PlanVerificationError`` on findings, stamp
    ``context["verified"]`` on success (what ``plan_cache`` records)."""
    if not _verify_requested(verify):
        return plan
    from repro import analysis
    findings = analysis.verify_plan(plan, graph)
    if findings:
        raise analysis.PlanVerificationError(findings)
    plan.context["verified"] = True
    return plan


def lower(graph: OpGraph, schedule: Schedule, *, mesh=None,
          hbm_budget: float = cm.HBM_BYTES * 0.25,
          vmem_budget: float = cm.VMEM_BYTES, train: bool = False,
          fuse_concat: bool = True, fuse_pool: bool = True,
          chain_modules: bool = False, verify: bool | None = None) -> Plan:
    """Lower a Schedule to an executable Plan.

    Mode choice per CoGroup: budget-infeasible or singleton -> serial;
    otherwise ``cost_model.group_execution_time`` picks the realizable
    single-chip mode (grouped ragged branch GEMM / stacked uniform-shape /
    fused complementary pair / xla interleave) at its modeled makespan,
    and a mesh upgrades same-output branches to ``spatial`` when the
    chip-split beats every single-chip mode.  ``fuse_pool`` (default)
    then streams each standalone maxpool through the grouped launch(es)
    consuming it (``_absorb_pools`` -> ``grouped_pooled`` / pooled
    groups — zero standalone ``reduce_window`` ops on the fused path),
    and ``fuse_concat`` (default) absorbs each fork/join concat into the
    grouped launch feeding it (``_absorb_concat_joins`` ->
    ``grouped_concat`` groups — zero standalone join ops).

    ``train=True`` additionally checks the C2 budgets against the
    group's backward profiles (each direction on its own — forward and
    backward are sequential launches, so their footprints never
    co-reside): a training step realizes the grad CoGroup of every
    co-executed group through its custom VJP (see ``backward_plan``), so
    a group whose backward footprint doesn't fit must run serial in BOTH
    directions — the mirrored plan never takes a co-execution decision
    the backward can't honor.
    """
    _REASON = {
        "grouped": "ragged shared-M GEMM branches -> grouped kernel "
                   "(uniform-K shared-X branches dedup to one wide GEMM "
                   "at execution)",
        "stacked": "same-shape GEMM branches",
        "fused": "compute+memory complementary pair",
        "xla": "heterogeneous group -> XLA interleave",
    }
    groups: list[ExecGroup] = []
    for cg in schedule.groups:
        ops = [graph.ops[n] for n in cg.ops]
        profs = [cm.profile(op, cg.algorithms[op.name]) for op in ops]
        # the footprint computation lives in ``analysis.budgets`` — it
        # prices the serial fallback AND (for a multi-op all-GEMM group)
        # the GEMM lowering's im2col patch buffers, whichever is larger
        feasible = _budgets.group_footprint(
            graph, cg.ops, cg.algorithms).fits(hbm_budget, vmem_budget)
        if train and feasible:
            # forward and backward are separate sequential launches whose
            # footprints never co-reside: each direction must fit the
            # budgets on its own (not their sum)
            feasible = _budgets.group_footprint(
                graph, cg.ops, cg.algorithms,
                direction="bwd").fits(hbm_budget, vmem_budget)
        if len(ops) == 1:
            mode, t, reason = "serial", cm.serial_time(profs), "singleton"
        elif cg.serialized or not feasible:
            mode, t = "serial", cm.serial_time(profs)
            reason = "budget-infeasible (C2 fallback)"
        else:
            mode, t = cm.group_execution_time(ops, profs)
            reason = _REASON[mode]
            if _spatial_ok(graph, ops, mesh):
                t_sp = cm.spatial_time(profs, mesh.shape["model"])
                if t_sp < t:
                    mode, t = "spatial", t_sp
                    reason = "branches fit the mesh model axis"
        groups.append(ExecGroup(mode, tuple(cg.ops), dict(cg.algorithms),
                                t, reason))
    if fuse_pool:
        groups = _absorb_pools(graph, groups, hbm_budget=hbm_budget,
                               vmem_budget=vmem_budget)
    if fuse_concat:
        groups = _absorb_concat_joins(graph, groups)
    if chain_modules:
        # cross-module streaming (opt-in): chain the absorbed launches —
        # quad + concat-pair pairs and serial conv runs — into
        # grouped_chained groups (see ``_chain_modules``)
        groups = _chain_modules(graph, groups, hbm_budget=hbm_budget,
                                vmem_budget=vmem_budget, train=train)
    groups = _smem_chunks(graph, groups, train=train)
    plan = Plan(groups, context={"mesh": mesh, "graph": graph,
                                 "budgets": {"hbm": hbm_budget,
                                             "vmem": vmem_budget,
                                             "smem": cm.SMEM_PREFETCH_BYTES,
                                             "train": train}})
    return _maybe_verify(plan, graph, verify)


# ---------------------------------------------------------------------------
# backward-plan lowering
# ---------------------------------------------------------------------------

def backward_plan(graph: OpGraph, plan: Plan, *,
                  hbm_budget: float = cm.HBM_BYTES * 0.25,
                  vmem_budget: float = cm.VMEM_BYTES,
                  verify: bool | None = None) -> Plan:
    """Derive the mirrored backward Plan from a lowered forward plan.

    The backward graph of a fork/join network is the forward graph
    reversed — the same CoGroups in mirrored order — and autodiff of
    ``run_plan`` realizes exactly that structure: a co-executed forward
    group pulls all its cotangents back through ONE custom VJP, so each
    forward ExecGroup becomes one grad ExecGroup (ops ``grad:<name>``)
    whose mode is what that VJP launches:

      grouped -> grouped   ONE combined launch: masked dx + dw/db over a
                           concatenated two-phase offset table
                           (``grouped_matmul_bwd``) — zero XLA fallbacks
                           and a single kernel per grad CoGroup.
      grouped_concat -> grouped_concat   the same combined launch; the
                           joint cotangent is sliced straight into its
                           packing, so the standalone join backward
                           (split) disappears with its forward.
      grouped_pooled -> grouped_pooled   the same combined launch; pooled
                           branches' lhs fold at pack time and the
                           pooling cotangent scatters through the
                           first-argmax window mask in the unpacking, so
                           the standalone pool backward disappears with
                           its forward (absorbed pools mirror as
                           ``grad:`` pools on the grad group).
      stacked -> stacked   ``branch_matmul``'s VJP runs the stacked
                           kernel on the backward GEMMs.
      serial  -> serial    per-op VJPs (convs take the stride-aware
                           GEMM-view backward ``models/cnn.py`` binds).
      fused / spatial -> serial   those VJPs pull back per-op through XLA.
      xla     -> xla       XLA interleaves the grad ops as it likes.

    The same C2 safety net applies: a grad group whose summed backward
    profiles exceed the budgets is priced serial (``lower(train=True)``
    makes the demotion bidirectional, so the mirror stays faithful).
    Makespans come from ``cost_model.group_execution_time_bwd`` /
    ``backward_profiles``.  The returned Plan is the lowering + pricing
    artifact for the training step's backward half — mode counts,
    ``Plan.makespan``, the benchmarks' modeled columns; execution flows
    through the VJPs of the forward plan, not through ``run_plan``.
    """
    _REASON = {
        "grouped": "mirror: ONE combined masked-dx + dw/db launch",
        "grouped_concat": "mirror: ONE combined launch, joint cotangent "
                          "sliced straight into its packing",
        "grouped_pooled": "mirror: ONE combined launch, pooling cotangent "
                          "scattered through the argmax mask in its "
                          "unpacking",
        "grouped_chained": "mirror: reverse-phase chain — ONE combined "
                           "masked-dx + dw/db launch per phase",
        "stacked": "mirror: stacked kernel VJP on the backward GEMMs",
        "serial": "per-op VJPs",
        "fused": "fused VJP pulls back per-op",
        "spatial": "spatial VJP pulls back per-op",
        "xla": "forward group already XLA-interleaved",
    }
    groups: list[ExecGroup] = []
    for g in reversed(plan.groups):
        ops = [graph.ops[n] for n in g.ops]
        bprofs = [p for op in ops
                  for p in cm.backward_profiles(
                      op, g.algorithms.get(op.name)
                      or cm.best_algorithm(op)[0])]
        # same accounting as ``lower(train=True)``'s gate — the shared
        # ``analysis.budgets`` computation keeps the mirror faithful
        feasible = _budgets.group_footprint(
            graph, g.ops, g.algorithms,
            direction="bwd").fits(hbm_budget, vmem_budget)
        if g.mode == "grouped_concat" and feasible:
            branch_ops = [op for op in ops if op.name != g.join]
            mode, t = cm.group_execution_time_bwd(
                branch_ops, g.algorithms, mode="grouped_concat",
                join=graph.ops[g.join])
            reason = _REASON[mode]
        elif g.mode == "grouped_chained" and feasible and g.chain:
            # the chained VJP mirrors the chain in REVERSE phase order —
            # one combined grouped launch per phase (a ring consumer's lhs
            # cotangent seeds the producer phase's dy, so phases cannot
            # backward-co-execute with each other)
            phase_ops = [[graph.ops[n] for n in ph] for ph in g.chain]
            mode, t = "grouped_chained", cm.chained_time_bwd(phase_ops,
                                                             g.algorithms)
            reason = _REASON[mode]
        elif g.mode in ("grouped", "grouped_pooled", "stacked") and feasible:
            mode, t = cm.group_execution_time_bwd(ops, g.algorithms,
                                                  mode=g.mode)
            reason = _REASON[mode]
        elif g.mode == "xla":
            mode, t = "xla", cm.xla_interleave_time(bprofs)
            reason = _REASON["xla"]
        else:
            mode, t = "serial", sum(p.time for p in bprofs)
            reason = ("budget-infeasible (C2 fallback)"
                      if g.mode in ("grouped", "grouped_concat",
                                    "grouped_pooled", "grouped_chained",
                                    "stacked")
                      else _REASON[g.mode])
        if mode != "serial" and g.chunks > 1:
            # the VJP's launches split at the forward's chunk rows
            t = cm.chunked_time(t, g.chunks)
            reason = f"{reason}; SMEM: {g.chunks} launches per direction"
        groups.append(ExecGroup(
            mode, tuple(f"grad:{n}" for n in g.ops),
            {f"grad:{n}": a for n, a in g.algorithms.items()}, t, reason,
            chunk_rows=g.chunk_rows if mode != "serial" else 0,
            chunks=g.chunks if mode != "serial" else 1,
            join=f"grad:{g.join}" if g.join else "",
            pools=tuple((f"grad:{b}", f"grad:{p}") for b, p in g.pools),
            chain=tuple(tuple(f"grad:{n}" for n in ph)
                        for ph in reversed(g.chain)) if g.chain else ()))
    bwd = Plan(groups, context={"forward": plan, "graph": graph,
                                "budgets": {**plan.context.get("budgets",
                                                               {}),
                                            "hbm": hbm_budget,
                                            "vmem": vmem_budget}})
    return _maybe_verify(bwd, graph, verify)


# ---------------------------------------------------------------------------
# execution
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class OpImpl:
    """Executable binding of one graph op (built by the model layer).

    ``fn(*dep_arrays, algorithm=...)`` is the universal path (serial / xla
    groups).  The optional views unlock the co-execution kernels:

      gemm_x/gemm_w/gemm_post — the op as ``post(x2d @ w)`` with
          x2d (M, K) from the deps and w (K, N): grouped + stacked + fused
          modes.  For a K×K conv, gemm_x is the im2col patch view.
      gemm_x_key — opt-in hashable token identifying the gemm_x
          *transform*: two impls with equal (deps, gemm_x_key) promise to
          produce the identical x2d.  When every branch of a grouped
          group shares one (deps, key) and one K, the executor dedups the
          shared X into ONE wide GEMM (weights concatenated along N — a
          single X read); the ragged kernel stays for mixed-K groups.
          ``None`` (the default) never dedups.
      gemm_bias/gemm_relu/gemm_reshape — split epilogue for grouped mode:
          when every branch provides bias + ReLU + a pure reshape, the
          grouped kernel fuses bias+ReLU in-kernel (no HBM round-trip)
          and only ``gemm_reshape`` runs outside.  ``gemm_post`` remains
          the out-of-kernel epilogue for stacked/fused and the non-fused
          grouped fallback — providing both must be equivalent.
      stream_z/stream_post — the op as ``post(silu(z).sum(0))`` with
          z (R, C) from the deps: the streamed branch of fused mode.
      pool_chain — maxpool ops only: the ((window, stride), ...) chain.
          What lets a grouped launch ABSORB the pool (grouped_pooled /
          pooled grouped_concat): the executor expands the pool's raw
          input into tap views (``kernels.pool_tap_views``) and the
          consuming branch's ``gemm_x`` maps each view; ``fn`` stays the
          standalone ``reduce_window`` chain (serial/degrade baseline).
      chain_geom — convs only: (kh, kw, stride, cin, oh, ow), the raw
          spatial geometry a ``grouped_chained`` launch needs to build
          ring tap-GEMM descriptors, panel-block weight layouts and the
          border masks — information ``gemm_x``'s closure hides.
    """
    deps: tuple[str, ...]
    fn: Callable[..., Any]
    gemm_x: Callable[..., Any] | None = None
    gemm_x_key: Any = None
    gemm_w: Any = None
    gemm_post: Callable[..., Any] | None = None
    gemm_bias: Any = None
    gemm_relu: bool = False
    gemm_reshape: Callable[..., Any] | None = None
    stream_z: Callable[..., Any] | None = None
    stream_post: Callable[..., Any] | None = None
    pool_chain: tuple | None = None
    chain_geom: tuple | None = None


def _materialize_chain(v: ChainPanels):
    """NHWC composite of a ChainPanels — the ONE concatenate a chained
    launch deleted, paid back only when a non-chained consumer (degrade
    path, custom graphs) actually needs the assembled tensor."""
    parts = [v.panels[p][:v.m, cb * v.blk: cb * v.blk + n]
             for p, cb, n in v.segments]
    x2 = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)
    return x2.reshape(-1, v.h, v.w, x2.shape[-1])


def _env_val(env: dict, d: str):
    """Read ``env[d]``, materializing (and caching back) a ChainPanels for
    consumers that expect the plain NHWC value."""
    v = env[d]
    if isinstance(v, ChainPanels):
        v = _materialize_chain(v)
        env[d] = v
    return v


def _dep_args(impl: OpImpl, env: dict):
    return [_env_val(env, d) for d in impl.deps]


def _has_gemm_views(impl: OpImpl) -> bool:
    return (impl.gemm_x is not None and impl.gemm_w is not None
            and impl.gemm_post is not None)


def _has_stream_views(impl: OpImpl) -> bool:
    return impl.stream_z is not None and impl.stream_post is not None


def _stacked_runnable(group: ExecGroup, impls, pending) -> bool:
    """All ops unseeded and every impl carries the GEMM views the stacked
    kernel needs — ``lower`` decides modes from the graph alone, so fn-only
    ``OpImpl`` bindings (the model-agnostic path) must fall back here."""
    return (len(pending) == len(group.ops)
            and all(_has_gemm_views(impls[n]) for n in group.ops))


def _grouped_fusable(impls, names) -> bool:
    """Every branch carries the split epilogue -> bias+ReLU fuse in-kernel."""
    return all(impls[n].gemm_bias is not None and impls[n].gemm_relu
               and impls[n].gemm_reshape is not None for n in names)


def _grouped_runnable(group: ExecGroup, impls, pending) -> bool:
    if len(pending) != len(group.ops):
        return False
    if not all(impls[n].gemm_x is not None and impls[n].gemm_w is not None
               for n in group.ops):
        return False
    return _grouped_fusable(impls, group.ops) or all(
        impls[n].gemm_post is not None for n in group.ops)


def _pools_runnable(group: ExecGroup, impls, env) -> bool:
    """Every absorbed pool has a chain-carrying impl whose raw input is
    already materialized — else the group degrades (the pools run
    standalone via their ``fn`` and the branches read them normally)."""
    for _b, p in group.pools:
        pimpl = impls.get(p)
        if pimpl is None or pimpl.pool_chain is None \
                or len(pimpl.deps) != 1 or pimpl.deps[0] not in env:
            return False
    return True


def _branch_lhs(group: ExecGroup, impls, env, names):
    """Per-branch GEMM lhs: a plain 2D array, or — for a pool-absorbed
    branch — the tuple of raw-input tap views (each mapped through the
    branch's own ``gemm_x``) the pooled launch maxes in-kernel.

    Tap views are built ONCE per absorbed pool op and shared by every
    branch pooling it (the memo role the deleted ``memo1`` pre-transform
    helper played, now at tap granularity).  A chain whose expansion
    exceeds ``POOL_TAP_LIMIT`` folds HERE, before the per-tap ``gemm_x``
    mapping — max commutes with the gather/reshape views, so folding
    early is value- and gradient-identical while never materializing the
    (e.g. 81-view) expansion the kernel wrapper would immediately fold
    anyway."""
    from repro.kernels.grouped_matmul import (POOL_TAP_LIMIT,
                                              pool_from_taps,
                                              pool_tap_views)
    pools = dict(group.pools)
    views: dict[str, Any] = {}
    xs = []
    for n in names:
        impl = impls[n]
        if n in pools:
            pname = pools[n]
            if pname not in views:
                pimpl = impls[pname]
                vs = pool_tap_views(_env_val(env, pimpl.deps[0]),
                                    pimpl.pool_chain)
                views[pname] = pool_from_taps(vs) \
                    if len(vs) > POOL_TAP_LIMIT else vs
            v = views[pname]
            xs.append(impl.gemm_x(v) if not isinstance(v, list)
                      else tuple(impl.gemm_x(t) for t in v))
        else:
            xs.append(impl.gemm_x(*_dep_args(impl, env)))
    return xs


def _grouped_concat_runnable(group: ExecGroup, impls, env, pending) -> bool:
    """The absorbed-join launch needs: every branch with GEMM views AND
    the split in-kernel epilogue (the output goes straight into the join
    buffer — there is no out-of-kernel ``gemm_post`` stage to run), the
    join impl with its 2D->NHWC ``gemm_reshape`` view, and every
    passthrough join input already in ``env``."""
    if len(pending) != len(group.ops) or not group.join \
            or group.join not in impls:
        return False
    jimpl = impls[group.join]
    branches = [n for n in group.ops if n != group.join]
    if jimpl.gemm_reshape is None or not set(branches) <= set(jimpl.deps):
        return False
    if not all(impls[n].gemm_x is not None and impls[n].gemm_w is not None
               for n in branches):
        return False
    return _grouped_fusable(impls, branches) and all(
        d in env for d in jimpl.deps if d not in branches)


def _fused_runnable(group: ExecGroup, impls, pending) -> bool:
    if len(pending) != len(group.ops):
        return False
    gemm = [n for n in group.ops if _has_gemm_views(impls[n])]
    stream = [n for n in group.ops if _has_stream_views(impls[n])]
    return len(gemm) == 1 and len(stream) == 1 and gemm[0] != stream[0]


def _run_stacked(group: ExecGroup, impls: dict[str, OpImpl], env: dict,
                 interpret):
    """Pad-to-max stacking: every branch is padded to the widest (K, N)
    so the uniform-shape branch kernel applies — the baseline the grouped
    mode exists to beat on ragged branches."""
    from repro.kernels import branch_matmul  # padded (G,M,K)x(G,K,N) wrapper
    xs, ws, ns = [], [], []
    for name in group.ops:
        impl = impls[name]
        xs.append(impl.gemm_x(*_dep_args(impl, env)))
        ws.append(impl.gemm_w)
        ns.append(impl.gemm_w.shape[1])
    k_max = max(w.shape[0] for w in ws)
    n_max = max(ns)
    xs = [jnp.pad(x, ((0, 0), (0, k_max - x.shape[1]))) for x in xs]
    ws = [jnp.pad(w, ((0, k_max - w.shape[0]), (0, n_max - w.shape[1])))
          for w in ws]
    ys = branch_matmul(jnp.stack(xs), jnp.stack(ws), interpret=interpret)
    for i, name in enumerate(group.ops):
        impl = impls[name]
        env[name] = impl.gemm_post(ys[i][:, :ns[i]])


def _shared_x_wide(impls, names) -> bool:
    """Shared-input X dedup condition (ROADMAP item): every branch reads
    the SAME GEMM lhs — one (deps, gemm_x_key) bucket, opt-in via the
    key — with one K, so the group is a single wide GEMM along N."""
    i0 = impls[names[0]]
    if i0.gemm_x_key is None:
        return False
    if any(impls[n].deps != i0.deps or impls[n].gemm_x_key != i0.gemm_x_key
           for n in names):
        return False
    return len({impls[n].gemm_w.shape[0] for n in names}) == 1


def _dedup_buckets(impls, names, pools) -> list[list[str]]:
    """Order-preserving PARTIAL shared-X dedup: branches with equal
    (deps, gemm_x_key, K, absorbed pool) promise the identical GEMM lhs
    and bucket together — each multi-branch bucket becomes one wide
    sub-GEMM of the launch (lhs read once, weights concatenated along N)
    while the remaining singletons ride the same launch as ragged
    branches.  Generalizes ``_shared_x_wide``'s all-or-nothing condition:
    e.g. an inception quad's 1x1/r3/r5 trio dedups even though the
    pool-proj branch reads a different (pooled) input.  ``gemm_x_key is
    None`` (the default) never buckets."""
    buckets: list[list[str]] = []
    keyof: dict = {}
    for n in names:
        i = impls[n]
        key = None if i.gemm_x_key is None else (
            i.deps, i.gemm_x_key, i.gemm_w.shape[0], pools.get(n))
        if key is not None and key in keyof:
            buckets[keyof[key]].append(n)
        else:
            if key is not None:
                keyof[key] = len(buckets)
            buckets.append([n])
    return buckets


def _valid_rows(xs, valid_images, batch):
    """Per-group ragged-M row count: ``valid_images`` requests pack
    contiguously at the head of the batch axis, and every lhs of a group
    has M = batch * rows_per_image for ITS spatial extent — so the true
    row count is ``valid_images * (M // batch)``.  None when the launch
    is not ragged.

    Every lhs must agree on M and M must divide by ``batch`` — a silent
    floor here would hand the kernel a cutoff that splits an image and
    the masked launch would serve truncated rows as if they were real.
    """
    if valid_images is None:
        return None
    ms = {(x[0] if isinstance(x, (list, tuple)) else x).shape[0]
          for x in xs}
    if len(ms) != 1:
        raise ValueError(
            f"ragged group mixes lhs row counts {sorted(ms)} — "
            "valid-row masking needs one M per launch")
    return _valid_rows_from_m(ms.pop(), valid_images, batch)


def _valid_rows_from_m(m, valid_images, batch):
    """``_valid_rows`` from a known M (the chained path carries M as a
    python int rather than arrays)."""
    if valid_images is None:
        return None
    if m % batch != 0:
        raise ValueError(
            f"lhs M={m} is not a multiple of batch={batch} — "
            "rows_per_image would be fractional, so an image-aligned "
            "ragged cutoff cannot exist")
    return valid_images * (m // batch)


def _run_grouped(group: ExecGroup, impls: dict[str, OpImpl], env: dict,
                 interpret, valid_images=None, batch=None):
    # ragged, fused epilogue; pooled branches hand the launch their tap
    # views and the kernel's pool stage folds them (grouped_matmul_pooled
    # delegates to the plain grouped kernel when nothing pools)
    from repro.kernels.ops import grouped_matmul_pooled
    names = group.ops
    pools = dict(group.pools)
    cr = group.chunk_rows or None
    fusable = _grouped_fusable(impls, names)
    buckets = _dedup_buckets(impls, names, pools)
    if len(buckets) < len(names):
        # shared-lhs buckets concatenate weights along N into ONE wide
        # sub-GEMM — the shared input is read (and, when pooled, tap-
        # folded) once per bucket instead of once per branch, and the
        # wide GEMM's VJP keeps the backward deduped too (one dx, one
        # wide dw/db, split by the concat's own pullback).  Singleton
        # buckets stay ragged branches of the SAME launch.
        xs = [_branch_lhs(group, impls, env, bk[:1])[0] for bk in buckets]
        mv = _valid_rows(xs, valid_images, batch)
        ws_b = [impls[bk[0]].gemm_w if len(bk) == 1 else
                jnp.concatenate([impls[n].gemm_w for n in bk], axis=1)
                for bk in buckets]
        if fusable:
            bs_b = [impls[bk[0]].gemm_bias if len(bk) == 1 else
                    jnp.concatenate([impls[n].gemm_bias for n in bk])
                    for bk in buckets]
            ys = grouped_matmul_pooled(xs, ws_b, bs_b, relu=True,
                                       m_valid=mv, interpret=interpret,
                                       chunk_rows=cr)
        else:
            ys = grouped_matmul_pooled(xs, ws_b, m_valid=mv,
                                       interpret=interpret, chunk_rows=cr)
        for bk, y in zip(buckets, ys):
            off = 0
            for n in bk:
                sl = y[:, off:off + impls[n].gemm_w.shape[1]]
                env[n] = impls[n].gemm_reshape(sl) if fusable \
                    else impls[n].gemm_post(sl)
                off += impls[n].gemm_w.shape[1]
        return
    ws = [impls[n].gemm_w for n in names]
    xs = _branch_lhs(group, impls, env, names)
    mv = _valid_rows(xs, valid_images, batch)
    if fusable:
        ys = grouped_matmul_pooled(xs, ws,
                                   [impls[n].gemm_bias for n in names],
                                   relu=True, m_valid=mv,
                                   interpret=interpret, chunk_rows=cr)
        for n, y in zip(names, ys):
            env[n] = impls[n].gemm_reshape(y)
    else:
        ys = grouped_matmul_pooled(xs, ws, m_valid=mv, interpret=interpret,
                                   chunk_rows=cr)
        for n, y in zip(names, ys):
            env[n] = impls[n].gemm_post(y)


def _chained_runnable(group: ExecGroup, impls, env, pending) -> bool:
    """The chained launch needs every phase op bound with the in-kernel
    epilogue (bias+ReLU is hardcoded in the chained kernel), its raw conv
    geometry (``chain_geom``) and a single dep that is either an earlier
    phase (ring), an absorbed pool, or already materialized; the join (if
    any) must read only in-launch ops.  Anything missing degrades the
    whole group to the per-op path."""
    if len(pending) != len(group.ops) or not group.chain:
        return False
    names = [n for ph in group.chain for n in ph]
    if set(group.ops) - set(names) - ({group.join} if group.join else set()):
        return False
    pools = dict(group.pools)
    opset = set(names)
    for n in names:
        impl = impls.get(n)
        if impl is None or impl.chain_geom is None or impl.gemm_w is None \
                or impl.gemm_bias is None or not impl.gemm_relu \
                or len(impl.deps) != 1:
            return False
        d = impl.deps[0]
        if d not in opset and n not in pools and d not in env:
            return False
    if group.join:
        jimpl = impls.get(group.join)
        if jimpl is None or set(jimpl.deps) - opset:
            return False
    return _pools_runnable(group, impls, env)


def _pool_fold(v, chain):
    """Maxpool ``chain`` applied to an NHWC array or — per segment, since
    pooling commutes with the channel concat — to a ChainPanels composite,
    packed back into ONE dense (B*OH*OW, C) lhs with dynamic_update_slice:
    no concatenate, no standalone reduce_window."""
    from repro.kernels.ops import pool_from_taps, pool_tap_views
    if not isinstance(v, ChainPanels):
        p = pool_from_taps(pool_tap_views(v, chain))
        return p.reshape(-1, p.shape[-1])
    segs = []
    for pidx, cb, n in v.segments:
        seg = v.panels[pidx][:v.m, cb * v.blk: cb * v.blk + n]
        p = pool_from_taps(pool_tap_views(seg.reshape(-1, v.h, v.w, n),
                                          chain))
        segs.append(p.reshape(-1, n))
    out = jnp.zeros((segs[0].shape[0], sum(s.shape[1] for s in segs)),
                    segs[0].dtype)
    off = 0
    for s in segs:
        out = jax.lax.dynamic_update_slice(out, s, (0, off))
        off += s.shape[1]
    return out


def _panel_desc(v: ChainPanels):
    """Panel lhs-source descriptors of a ChainPanels consumed IN PLACE:
    one (panel, col block) per padded block in segment (= join) order,
    plus the true-channel row range of the consumer's weight each block
    covers (block rows past a segment's true width meet zero weight
    rows, so the panels' zero-padded columns contribute nothing)."""
    blocks, ranges = [], []
    coff = 0
    for pidx, cb, n in v.segments:
        nbb = -(-n // v.blk)
        for j in range(nbb):
            blocks.append((pidx, cb + j))
            lo = coff + j * v.blk
            ranges.append((lo, min(coff + n, lo + v.blk)))
        coff += n
    return blocks, ranges


def _pad_w_dense(wmat, blk):
    """Row-pad a dense (K, N) weight to the k-step grid (ceil(K/blk)*blk
    rows) — the layout matching a dense x lhs's padded col blocks."""
    kb = -(-wmat.shape[0] // blk)
    return jnp.pad(wmat, ((0, kb * blk - wmat.shape[0]), (0, 0)))


def _pack_w_blocks(wmat, ranges, blk):
    """Weight rows rearranged to panel-descriptor k-step order: block s
    holds ``wmat[lo:hi]`` at its top (zero rows elsewhere), matching the
    consumed panel block's true channels."""
    buf = jnp.zeros((len(ranges) * blk, wmat.shape[1]), wmat.dtype)
    for s, (lo, hi) in enumerate(ranges):
        buf = jax.lax.dynamic_update_slice(buf, wmat[lo:hi], (s * blk, 0))
    return buf


def _pack_w_ring(wmat, kh, kw, cin, nrc, blk):
    """Ring-consumer weight in tap-major/ring-col-minor k-step order: the
    (C, KH, KW)-ordered im2col weight ``wmat`` strided-sliced per tap
    (rows dh*kw+dw :: kh*kw give w[dh, dw]) and laid out per ring column
    block — the order ``_chain_ksteps`` emits the tap-GEMMs in."""
    buf = jnp.zeros((kh * kw * nrc * blk, wmat.shape[1]), wmat.dtype)
    s = 0
    for dh in range(kh):
        for dw in range(kw):
            tap = jax.lax.slice(wmat, (dh * kw + dw, 0), wmat.shape,
                                (kh * kw, 1))          # (cin, nout)
            for j in range(nrc):
                lo = j * blk
                if lo < cin:
                    buf = jax.lax.dynamic_update_slice(
                        buf, tap[lo:min(lo + blk, cin)], (s * blk, 0))
                s += 1
    return buf


def _panel_index(panels: list, arr) -> int:
    for i, p in enumerate(panels):
        if p is arr:
            return i
    panels.append(arr)
    return len(panels) - 1


def _run_grouped_chained(group: ExecGroup, impls: dict[str, OpImpl],
                         env: dict, interpret, valid_images=None,
                         batch=None):
    """Execute a ``grouped_chained`` group as ONE multi-phase launch.

    Per-branch lhs sources, in preference order:
      ring   — dep is an earlier phase of THIS launch: the kernel streams
               the producer's row-block panels through the VMEM ring
               (K*K convs as K^2 shifted tap-GEMMs; weights repacked
               tap-major by ``_pack_w_ring``).
      pooled — dep is an absorbed pool: the pool folds OUTSIDE the kernel
               (``_pool_fold``, per ChainPanels segment — max commutes
               with the channel concat) into one dense lhs.
      panel  — dep is the PREVIOUS chained launch's ChainPanels and the
               conv is pointwise: lhs-source descriptors address the
               producer's padded panels in place (zero copies; weights
               repacked per block by ``_pack_w_blocks``).
      x      — anything else: the branch's own ``gemm_x`` view (the stem
               head's strided im2col, custom graphs), packed by the
               kernel wrapper.

    The launch's padded output panels become a ``ChainPanels`` env value
    under the join's name (or the last phase op's, stem chains) — the
    module boundary never materializes."""
    from repro.kernels.ops import grouped_matmul_chained
    blk = 128
    pools = dict(group.pools)
    opset = {n for ph in group.chain for n in ph}
    consumed = {impls[n].deps[0] for ph in group.chain for n in ph
                if impls[n].deps[0] in opset}
    ring_cols: dict[str, tuple] = {}
    nxt = 0
    for ph in group.chain:
        for n in ph:
            if n in consumed:
                nbb = -(-impls[n].gemm_w.shape[1] // blk)
                ring_cols[n] = tuple(range(nxt, nxt + nbb))
                nxt += nbb
    pooled: dict[str, Any] = {}
    for _b, pname in group.pools:
        if pname not in pooled:
            pimpl = impls[pname]
            pooled[pname] = _pool_fold(env[pimpl.deps[0]],
                                       pimpl.pool_chain)
    panels: list = []
    phase_dicts = []
    m = None
    geom = None
    for ph in group.chain:
        brs = []
        for n in ph:
            impl = impls[n]
            kh, kw, stride, cin, oh, ow = impl.chain_geom
            wmat = impl.gemm_w
            d = impl.deps[0]
            if d in opset:
                rcs = ring_cols[d]
                src = ("ring", kh, kw, rcs)
                wpk = _pack_w_ring(wmat, kh, kw, cin, len(rcs), blk)
            elif n in pools:
                x2d = pooled[pools[n]]
                src, wpk, m = ("x", [x2d]), _pad_w_dense(wmat, blk), \
                    x2d.shape[0]
            else:
                v = env[d]
                if isinstance(v, ChainPanels) and (kh, kw) == (1, 1) \
                        and stride == 1:
                    blocks, ranges = _panel_desc(v)
                    used = sorted({p for p, _ in blocks})
                    if len(used) <= 2:     # kernel addresses <= 2 panels
                        remap = {p: _panel_index(panels, v.panels[p])
                                 for p in used}
                        src = ("panel", [(remap[p], cb)
                                         for p, cb in blocks])
                        wpk, m = _pack_w_blocks(wmat, ranges, blk), v.m
                    else:
                        x2d = _materialize_chain(v).reshape(v.m, -1)
                        src, wpk, m = ("x", [x2d]), \
                            _pad_w_dense(wmat, blk), v.m
                else:
                    x2d = impl.gemm_x(_env_val(env, d))
                    src, wpk, m = ("x", [x2d]), _pad_w_dense(wmat, blk), \
                        x2d.shape[0]
            if geom is None:
                geom = (oh, ow)
            brs.append({"n": wmat.shape[1], "w": wpk, "b": impl.gemm_bias,
                        "src": src, "ring_write": ring_cols.get(n)})
        phase_dicts.append(brs)
    assert m is not None and geom is not None, group.ops
    mv = _valid_rows_from_m(m, valid_images, batch)
    outs = grouped_matmul_chained(phase_dicts, m=m, h=geom[0], w=geom[1],
                                  panels=tuple(panels), block=blk,
                                  m_valid=mv, interpret=interpret,
                                  chunk_rows=group.chunk_rows or None)
    lay: dict[str, tuple[int, int, int]] = {}
    for p, ph in enumerate(group.chain):
        cb = 0
        for n in ph:
            nout = impls[n].gemm_w.shape[1]
            lay[n] = (p, cb, nout)
            cb += -(-nout // blk)
    if group.join:
        out_name = group.join
        order = list(impls[group.join].deps)
    else:
        out_name = group.chain[-1][-1]
        order = [out_name]
    env[out_name] = ChainPanels(
        panels=tuple(outs), segments=tuple(lay[n] for n in order),
        m=m, h=geom[0], w=geom[1], blk=blk)


def _run_grouped_concat(group: ExecGroup, impls: dict[str, OpImpl], env: dict,
                        interpret, valid_images=None, batch=None):
    """Fused epilogue-concat execution: the grouped kernel writes every
    in-launch branch's bias+ReLU output straight into its slice of the
    join's (M, sum N_g) buffer; join inputs produced by EARLIER groups
    (e.g. the 1x1/pool-proj outputs of an inception quad) are copied in
    as passthrough column slices.  Only the join gets an env entry — the
    absorption condition guarantees the join is every in-launch branch's
    sole consumer, so their standalone outputs would be dead values (and
    materializing them would be exactly the per-branch round-trip this
    mode deletes)."""
    from repro.kernels.ops import (grouped_block_shape,
                                   grouped_matmul_pooled_concat)
    jimpl = impls[group.join]
    branches = [n for n in group.ops if n != group.join]
    offs: dict[str, int] = {}
    widths: dict[str, int] = {}
    off = 0
    for d in jimpl.deps:
        w = impls[d].gemm_w.shape[1] if d in branches \
            else _env_val(env, d).shape[-1]
        offs[d], widths[d] = off, w
        off += w
    order = [d for d in jimpl.deps if d in branches]
    xs = _branch_lhs(group, impls, env, order)
    ws = [impls[n].gemm_w for n in order]
    x0 = xs[0][0] if isinstance(xs[0], tuple) else xs[0]
    # the PADDED join buffer (compact=False): branch g's true columns sit
    # at the cumulative padded base, so the join assembles as ONE
    # concatenate of passthrough segments and (maximal) buffer slices —
    # strictly less copying than per-branch outputs + a standalone concat
    # (pooled branches ride the same launch via their tap views)
    y2d = grouped_matmul_pooled_concat(
        xs, ws, [impls[n].gemm_bias for n in order],
        offsets=[offs[n] for n in order], total=off, relu=True,
        compact=False, m_valid=_valid_rows(xs, valid_images, batch),
        interpret=interpret, chunk_rows=group.chunk_rows or None)
    bn = grouped_block_shape(
        x0.shape[0], [(w.shape[0], w.shape[1]) for w in ws],
        x0.dtype).bn
    pbase = {}
    base = 0
    for n, w in zip(order, ws):
        pbase[n] = base
        base += -(-w.shape[1] // bn) * bn
    segs: list = []       # (lo, hi) buffer slices interleaved with pt 2D
    for d in jimpl.deps:
        if d in branches:
            lo, hi = pbase[d], pbase[d] + widths[d]
            if segs and isinstance(segs[-1], tuple) and segs[-1][1] == lo:
                segs[-1] = (segs[-1][0], hi)       # extend a contiguous run
            else:
                segs.append((lo, hi))
        else:
            segs.append(_env_val(env, d).reshape(-1, widths[d])
                        .astype(y2d.dtype))
    parts = [y2d[:, s[0]:s[1]] if isinstance(s, tuple) else s for s in segs]
    joined = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)
    env[group.join] = jimpl.gemm_reshape(joined)


def _run_fused(group: ExecGroup, impls: dict[str, OpImpl], env: dict,
               interpret):
    from repro.kernels.ops import fused_gemm_reduce  # padded wrapper
    gemm = [n for n in group.ops if _has_gemm_views(impls[n])]
    stream = [n for n in group.ops if _has_stream_views(impls[n])]
    assert len(gemm) == 1 and len(stream) == 1, group.ops
    gi, si = impls[gemm[0]], impls[stream[0]]
    x2d = gi.gemm_x(*_dep_args(gi, env))
    z = si.stream_z(*_dep_args(si, env))
    c, r = fused_gemm_reduce(x2d, gi.gemm_w, z, interpret=interpret)
    env[gemm[0]] = gi.gemm_post(c)
    env[stream[0]] = si.stream_post(r)


def _run_spatial_group(group: ExecGroup, impls: dict[str, OpImpl], env: dict,
                       mesh):
    from repro.core import branch_parallel as bp
    dep = impls[group.ops[0]].deps[0]
    fns = [impls[n].fn for n in group.ops]
    br = bp.Branches(fns, combine="stack")
    ys = bp.run_spatial(br, _env_val(env, dep), mesh)    # (G, B, ...)
    for i, name in enumerate(group.ops):
        env[name] = ys[i]


def _scope(group: ExecGroup, executed: str | None = None, *, op=None):
    """Provenance scope for everything a group (or one serial/degraded
    op) emits: ``analysis/fallbacks.py`` attributes surviving fallback
    primitives in a traced plan to these ``jax.named_scope`` tags, so a
    zero-fallback gate reports WHICH op regressed instead of a bare
    count.  ``/`` nests scopes in a jaxpr name stack, so op names
    sanitize to ``.``."""
    mode = executed or group.mode
    tag = (op if op is not None else group.ops[0]).replace("/", ".")
    return jax.named_scope(f"plan[{mode}:{tag}]")


def run_plan(impls: dict[str, OpImpl], env: dict, plan: Plan, *,
             mesh=None, interpret=None, timings: dict | None = None,
             valid_images=None) -> dict:
    """Execute a lowered plan over ``impls``; returns the op->value env.

    ``env`` seeds graph sources (ops with no deps / externally computed
    values); seeded ops are never recomputed in any mode.  A co-execution
    group (stacked / fused) whose impls lack the gemm/stream views — or
    that is partially seeded — degrades to the per-op xla path rather than
    failing: ``lower`` picks modes from the graph alone and cannot see the
    bindings.  ``timings``, when a dict, collects eager per-mode wall time
    {mode: seconds} — only meaningful outside jit; degraded groups are
    keyed ``"<mode>->xla"`` so they never masquerade as the co-execution
    kernel they skipped.

    ``valid_images`` (python int or traced i32 scalar) makes every
    grouped/pooled/concat launch ragged-M: requests pack contiguously at
    the head of the batch axis and only the first ``valid_images`` images
    are real — each launch masks its padded-M tail in-kernel (zero-stored
    epilogue rows past the group's true row count).  Inference-only (the
    ragged kernels bypass the custom VJPs), and requires
    ``plan.context["batch"]`` (the bucket size the plan was lowered for).
    Batch elements never mix inside a launch (im2col, pooling and ring
    taps are image-local by the border masks), so the first
    ``valid_images`` outputs are exactly the dense run's.  Chained groups
    mask too: the launch skips M-blocks past the cutoff as no-op waves
    (dead blocks run zero GEMM/ring/pool steps) and zero-stores the live
    tail block, so the next launch's panel descriptors and ring taps read
    clean producer slots instead of relying on the caller to drop
    garbage.
    """
    import time as _time
    import jax as _jax

    mesh = mesh if mesh is not None else plan.context.get("mesh")
    batch = plan.context.get("batch")
    if valid_images is not None:
        assert batch is not None, \
            "valid_images needs plan.context['batch'] (the bucket size)"
    for group in plan.groups:
        t0 = _time.perf_counter() if timings is not None else 0.0
        pending = [n for n in group.ops if n not in env]
        if not pending:
            continue
        executed = group.mode
        if group.mode in ("grouped", "grouped_pooled") \
                and _grouped_runnable(group, impls, pending) \
                and _pools_runnable(group, impls, env):
            with _scope(group):
                _run_grouped(group, impls, env, interpret,
                             valid_images=valid_images, batch=batch)
        elif group.mode == "grouped_concat" and _grouped_concat_runnable(
                group, impls, env, pending) \
                and _pools_runnable(group, impls, env):
            with _scope(group):
                _run_grouped_concat(group, impls, env, interpret,
                                    valid_images=valid_images, batch=batch)
        elif group.mode == "grouped_chained" and _chained_runnable(
                group, impls, env, pending):
            with _scope(group):
                _run_grouped_chained(group, impls, env, interpret,
                                     valid_images=valid_images,
                                     batch=batch)
        elif group.mode == "stacked" and _stacked_runnable(group, impls,
                                                           pending):
            with _scope(group):
                _run_stacked(group, impls, env, interpret)
        elif group.mode == "fused" and _fused_runnable(group, impls,
                                                       pending):
            with _scope(group):
                _run_fused(group, impls, env, interpret)
        elif group.mode == "spatial" and len(pending) == len(group.ops):
            with _scope(group):
                _run_spatial_group(group, impls, env, mesh)
        else:
            # serial: scheduler-chosen per-op algorithm kernels.
            # xla: native ops emitted together; XLA interleaves.  Also the
            # degraded path for co-execution groups (see docstring).
            if group.mode not in ("serial", "xla"):
                executed = f"{group.mode}->xla"
            # a degraded pooled group must first materialize its absorbed
            # pools (the plan dropped their standalone groups): run each
            # pool op's fn — the reduce_window baseline — so the branch
            # fns can read their declared deps
            for _b, p in group.pools:
                if p in env:
                    continue
                pimpl = impls.get(p)
                if pimpl is None:
                    raise KeyError(
                        f"absorbed pool op {p!r} has no OpImpl: a degraded "
                        f"pooled group runs the pool's fn to materialize "
                        f"its branches' input — pool ops ride group.pools "
                        f"(not group.ops), so bind an impl for {p!r} too")
                with _scope(group, executed, op=p):
                    env[p] = pimpl.fn(*_dep_args(pimpl, env))
            for name in pending:
                impl = impls[name]
                alg = group.algorithms.get(name) if group.mode == "serial" \
                    else "xla"
                with _scope(group, executed, op=name):
                    env[name] = impl.fn(*_dep_args(impl, env),
                                        algorithm=alg)
        if timings is not None:
            vals = []
            for n in group.ops:
                v = env.get(n)
                if isinstance(v, ChainPanels):
                    vals.extend(v.panels)
                elif v is not None:
                    vals.append(v)
            _jax.block_until_ready(vals)
            timings[executed] = timings.get(executed, 0.0) \
                + (_time.perf_counter() - t0)
    return env


def execute_plan(params, x, plan: Plan, *, mesh=None, interpret=None,
                 valid_images=None):
    """Entry point for the repo's native subject: run a plan produced by
    ``models.cnn.plan_cnn`` on images ``x`` with CNN ``params``.

    Model-agnostic execution (custom graphs) goes through ``run_plan`` with
    explicit ``OpImpl`` bindings instead.  ``valid_images`` as in
    ``run_plan`` (ragged-M serving batches; inference-only).
    """
    cfg = plan.context.get("cfg")
    if cfg is None:
        raise ValueError("plan has no cfg context — produce it with "
                         "models.cnn.plan_cnn, or use run_plan directly")
    from repro.models import cnn
    return cnn.forward_plan(params, cfg, x, plan, mesh=mesh,
                            interpret=interpret,
                            valid_images=valid_images)


# ---------------------------------------------------------------------------
# MoE lowering: the expert fork as ONE grouped-family launch
# ---------------------------------------------------------------------------

def lower_moe(graph: OpGraph, *, b: int, s: int, d: int, f: int, e: int,
              top_k: int, capacity_factor: float, gated: bool = True,
              shared_f: int = 0, bm: int | None = None,
              dtype_bytes: int = 4, verify: bool | None = None) -> Plan:
    """Lower one MoE layer's op graph (``models.moe.build_moe_graph``) to
    a Plan whose expert fork is a single ``grouped_experts`` ExecGroup.

    The E expert chains the graph exposes as 3E (2E ungated) independent
    matmuls at the einsum engine's padded M = B*cap collapse into ONE
    per-expert-ragged launch per direction; the group's ``modeled_time``
    is ``cost_model.moe_grouped_profile`` over the static routed-token
    grid, and ``reason`` records the pricing against the capacity-padded
    einsum and the pad-to-max stacked baselines so the decision is
    auditable from the plan alone.  Router / combine / shared-MLP ops
    stay serial groups (they are the fork and join, not branches)."""
    from repro.models.moe import moe_capacity

    if bm is None:
        from repro.kernels import moe_block_m
        bm = moe_block_m(b * s * top_k, e)
    sk = s * top_k
    cap = moe_capacity(sk, capacity_factor, e)
    n_slots = b * sk
    times = cm.moe_dispatch_times(n_slots, b, cap, e, d, f, gated=gated,
                                  bm=bm, dtype_bytes=dtype_bytes)

    expert_ops = tuple(n for n in graph.ops if n.startswith("expert"))
    assert len(expert_ops) == (3 if gated else 2) * e, expert_ops
    groups = [
        ExecGroup("serial", ("moe_router",), {"moe_router": "mxu128"},
                  cm.profile(graph.ops["moe_router"], "mxu128").time),
        ExecGroup(
            "grouped_experts", expert_ops, {}, times["grouped"],
            reason=(f"{len(expert_ops)} expert GEMMs -> 1 ragged launch: "
                    f"grouped {times['grouped'] * 1e6:.2f}us vs einsum "
                    f"{times['einsum'] * 1e6:.2f}us vs stacked "
                    f"{times['stacked'] * 1e6:.2f}us")),
        ExecGroup("serial", ("moe_combine",), {"moe_combine": "vpu"},
                  cm.profile(graph.ops["moe_combine"], "vpu").time),
    ]
    if shared_f:
        shared_ops = tuple(n for n in graph.ops if n.startswith("shared"))
        sprofs = [cm.profile(graph.ops[n], "mxu128") for n in shared_ops]
        groups.append(ExecGroup("serial", shared_ops,
                                {n: "mxu128" for n in shared_ops},
                                cm.serial_time(sprofs)))
    ctx = {"moe": {"b": b, "s": s, "d": d, "f": f, "e": e, "top_k": top_k,
                   "capacity_factor": capacity_factor, "gated": gated,
                   "shared_f": shared_f, "bm": bm, "cap": cap,
                   "n_slots": n_slots, "times": times}}
    ctx["graph"] = graph
    return _maybe_verify(Plan(groups, ctx), graph, verify)
