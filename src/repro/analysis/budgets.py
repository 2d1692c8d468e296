"""planlint static footprints — ONE C2 budget computation per ExecGroup.

Before this module, the HBM-workspace + VMEM footprint a co-executed
group must fit under was computed in three near-duplicate places (and a
fourth for the backward mirror): ``plan.lower``'s feasibility gate,
``plan._absorb_pools``'s pooled-launch re-check and
``plan._chain_budgets_ok``'s ring-scratch check — implementations that
have already drifted once (PR 5's review notes).  All of them now call
the two functions here, and so does ``analysis.verify_plan`` when it
re-derives a lowered plan's footprint and checks it against the budgets
the plan was lowered under (``Plan.context["budgets"]``).

The accounting, in one place:

  base profiles    the chosen-algorithm ``cost_model.profile`` rows —
                   the serial fallback's footprint.
  GEMM workspace   a multi-op all-GEMM group executes the GEMM lowering,
                   whose im2col patch buffers can exceed the serial
                   fallback's workspace — the gate takes the max.
  pool riders      an absorbed pool packs up to ``POOL_TAP_LIMIT`` tap
                   tiles per pooled-lhs tile into the X stack
                   ((taps-1) * M * K extra workspace bytes per pooled
                   branch) and claims one pooled-lhs VMEM scratch
                   (128^2 blocks over the widest pooled K).
  backward         each direction launches sequentially, so the
                   backward footprint is gated on its own (summed
                   ``cost_model.backward_profiles``), never added to
                   the forward's.
  chained          ``cost_model.chained_profiles`` workspace (ring
                   consumers drop their patch buffer) plus what the
                   launch holds in VMEM at its M-block
                   (``kernels.grouped_matmul.chained_vmem_bytes`` at
                   ``chained_block_m``): its tiles, the f32
                   accumulator and the ring scratch, 3 wave slots and a
                   (3*bm, blk) shift window per ring column.
  SMEM             every grouped-family launch prefetches its offset
                   table (one column per grid step, linear in M) into
                   the chip's SMEM (``cost_model.SMEM_PREFETCH_BYTES``).
                   ``chunk_rows`` sizes the image-aligned M-chunks a
                   group's launches split into so each table fits, and
                   ``group_smem_bytes`` prices them with the kernels' own
                   ``launch_smem_bytes``.
"""
from __future__ import annotations

import dataclasses
import importlib

from repro.core import cost_model as cm

BLK = 128


@dataclasses.dataclass(frozen=True)
class Footprint:
    """A group's static C2 footprint: HBM workspace + VMEM residency."""
    workspace_bytes: float
    vmem_bytes: float

    def fits(self, hbm_budget: float, vmem_budget: float) -> bool:
        return (self.workspace_bytes <= hbm_budget
                and self.vmem_bytes <= vmem_budget)


def tap_count(pool_op) -> int:
    """Tap tiles per pooled-lhs tile: the product of the pool chain's
    squared windows, folded to 1 past ``POOL_TAP_LIMIT`` (the packer
    folds the taps at pack time instead of expanding the X stack)."""
    from repro.kernels.grouped_matmul import POOL_TAP_LIMIT
    t = 1
    for win, _s in pool_op.p["chain"]:
        t *= win * win
    return t if t <= POOL_TAP_LIMIT else 1


def group_footprint(graph, names, algorithms, *, pools=(),
                    direction: str = "fwd",
                    include_gemm_ws: bool | None = None) -> Footprint:
    """The static footprint of one ExecGroup.

    ``names``/``algorithms`` identify the ops and their chosen
    algorithms; ``pools`` is the group's ``(branch, pool)`` rider list;
    ``direction="bwd"`` prices the mirrored backward launch instead
    (summed ``backward_profiles``, algorithm falling back to
    ``best_algorithm`` when the group never chose one — matching
    ``backward_plan``).  ``include_gemm_ws`` forces the GEMM-lowering
    workspace max on (pooled re-checks price the grouped kernel even
    when a join op rides in the group); ``None`` applies it exactly when
    ``lower`` would — a multi-op group of GEMM-viewed ops.
    """
    ops = [graph.ops[n] for n in names]
    if direction == "bwd":
        bprofs = [p for op in ops
                  for p in cm.backward_profiles(
                      op, algorithms.get(op.name)
                      or cm.best_algorithm(op)[0])]
        return Footprint(sum(p.workspace_bytes for p in bprofs),
                         sum(p.vmem_bytes for p in bprofs))
    base = [cm.profile(op, algorithms[op.name]) for op in ops]
    ws = sum(p.workspace_bytes for p in base)
    vmem = sum(p.vmem_bytes for p in base)
    if include_gemm_ws is None:
        include_gemm_ws = (len(ops) > 1
                           and all(cm.gemm_shape(op) is not None
                                   for op in ops))
    if include_gemm_ws:
        ws = max(ws, sum(p.workspace_bytes for p in cm.gemm_profiles(ops)))
    extra_ws, extra_vmem = 0.0, 0.0
    for b, pn in pools:
        s = cm.gemm_shape(graph.ops[b])
        extra_ws += (tap_count(graph.ops[pn]) - 1) \
            * s[0] * s[1] * graph.ops[b].dtype_bytes
        extra_vmem = max(extra_vmem, -(-s[1] // 128) * 128 * 128 * 4)
    return Footprint(ws + extra_ws, vmem + extra_vmem)


def chained_footprint(graph, phases, ring, *, pools=()) -> Footprint:
    """The static footprint of one chained launch: chained-priced GEMM
    workspace (ring consumers' lhs never exists outside VMEM) plus what
    the launch holds in VMEM at the M-block it runs at — its tiles, the
    f32 accumulator and, per ring column, 3 wave slots and a (3*bm, blk)
    shift window (``chained_vmem_bytes``).  Raises ValueError on a chain
    ``chained_spec`` finds malformed."""
    ops = [graph.ops[n] for ph in phases for n in ph]
    profs = cm.chained_profiles(ops, ring)
    bm, _mb, spec, _oh, ow, _nring, problems = chained_spec(graph, phases,
                                                            pools)
    if spec is None:
        raise ValueError("; ".join(msg for _, msg in problems))
    vmem = _gm().chained_vmem_bytes(spec, bm, ow,
                                    max(op.dtype_bytes for op in ops))
    return Footprint(sum(p.workspace_bytes for p in profs),
                     sum(p.vmem_bytes for p in profs) + vmem)


# ---------------------------------------------------------------------------
# SMEM: offset-table chunking
# ---------------------------------------------------------------------------

def _gm():
    # importlib, not ``from repro.kernels import grouped_matmul``: the
    # package re-exports a FUNCTION of that name which shadows the
    # submodule attribute
    return importlib.import_module("repro.kernels.grouped_matmul")


def _ceil(a: int, b: int) -> int:
    return -(-a // b)


def _strip(n: str) -> str:
    return n[5:] if n.startswith("grad:") else n


def chained_spec(graph, chain, pools=()):
    """Rebuild the hashable chained-launch spec ``_chain_static`` would
    produce, from the graph alone.  ``chain`` is the group's phases of op
    names, ``pools`` its (branch, pool) riders.  Returns (bm, mb, spec,
    oh, ow, nring, problems) — the launch's M-block
    (``chained_block_m``) and M-block count, spec None (and bm, mb
    None) when the chain is malformed, and ``problems`` a list of
    (checker, message) pairs.

    A branch whose lhs comes from OUTSIDE the launch is specced as a
    packed-x source: the panel-descriptor block numbering needs the
    executor's env, and the wave / ring schedule is invariant to the lhs
    source tag.  Its k-step count is what the executor's panel source
    takes when the lhs is a previous launch's join — one step per
    128-column block of each join segment — which bounds the dense x
    source's from above, so SMEM chunks sized from it fit either."""
    chain = [[_strip(n) for n in ph] for ph in chain]
    opset = {n for ph in chain for n in ph}
    pools = {_strip(b): _strip(p) for b, p in pools}
    out = []

    def dep_of(n):
        preds = sorted(graph.pred[n])
        if n in pools:
            return pools[n]
        if len(preds) != 1:
            out.append(("schema", f"chained op {n} has {len(preds)} preds "
                        "— a chain branch streams exactly one lhs"))
            return None
        return preds[0]

    def outside_ksteps(op, d, k):
        kb = _ceil(k, BLK)
        dop = graph.ops.get(d)
        if (op.p.get("kh", 1), op.p.get("kw", 1), op.p.get("stride", 1)) \
                != (1, 1, 1) or dop is None or dop.kind != "pointwise":
            return kb
        segs = [cm.gemm_shape(graph.ops[p]) for p in graph.pred[d]]
        if not segs or None in segs:
            return kb
        return max(kb, sum(_ceil(s[2], BLK) for s in segs))

    consumed = []
    for ph in chain:
        for n in ph:
            d = dep_of(n)
            if d is not None and d in opset and d not in consumed:
                consumed.append(d)
    ring_cols: dict[str, tuple] = {}
    nxt = 0
    for d in consumed:
        nbb = _ceil(cm.gemm_shape(graph.ops[d])[2], BLK)
        ring_cols[d] = tuple(range(nxt, nxt + nbb))
        nxt += nbb
    nring = max(nxt, 1)

    first = graph.ops[chain[0][0]]
    stride0 = first.p.get("stride", 1)
    oh = _ceil(first.p["h"], stride0)
    ow = _ceil(first.p["w"], stride0)
    ms = {cm.gemm_shape(graph.ops[n])[0] for ph in chain for n in ph}
    if len(ms) != 1:
        out.append(("schema", f"chained phases disagree on shared M: "
                    f"{sorted(ms)} — the wave schedule advances all phases "
                    "over one row space"))
        return None, None, None, oh, ow, nring, out
    m = ms.pop()

    spec = []
    for ph in chain:
        pspec = []
        for n in ph:
            op = graph.ops[n]
            _, kk, nn = cm.gemm_shape(op)
            nbb = _ceil(nn, BLK)
            d = dep_of(n)
            if d in opset:
                kh, kw = op.p.get("kh", 1), op.p.get("kw", 1)
                if op.p.get("stride", 1) != 1:
                    out.append(("schema", f"ring consumer {n} has stride "
                                f"{op.p['stride']} — the shifted-window "
                                "ring only streams stride-1 taps"))
                    return None, None, None, oh, ow, nring, out
                taps = []
                for dh in range(kh):
                    for dw in range(kw):
                        delta = (dh - kh // 2) * ow + (dw - kw // 2)
                        if abs(delta) > BLK:
                            out.append(("bounds", f"ring consumer {n} halo "
                                        f"{delta} exceeds {BLK} rows (W={ow}, "
                                        f"k={kh}x{kw}) — chain-ineligible "
                                        "geometry"))
                            return None, None, None, oh, ow, nring, out
                        taps.append((delta, dh - kh // 2, dw - kw // 2))
                src = ("ring", (tuple(taps), ring_cols[d]))
            elif n in pools:
                src = ("x", _ceil(kk, BLK))
            else:
                src = ("x", outside_ksteps(op, d, kk))
            pspec.append((src[0], src[1], nbb,
                          tuple(ring_cols.get(n, ()))))
        spec.append(tuple(pspec))
    spec = tuple(spec)
    bm = _gm().chained_block_m(spec, m, ow, max(
        graph.ops[n].dtype_bytes for ph in chain for n in ph))
    return bm, _ceil(m, bm), spec, oh, ow, nring, out


def group_launches(graph, g, directions=("fwd",)):
    """Every grouped-family launch shape one ExecGroup makes in
    ``directions``: a list of (m, bm, per_block, mrow_slots, fixed) —
    the arguments ``kernels.grouped_matmul.launch_smem_bytes`` prices a
    launch with (``per_block``: the table family's (rows, steps) for one
    M-block).  Branch geometry is per branch with f32 blocks — the most
    grid steps the executor can take (bf16 may widen blocks, and
    shared-lhs dedup merges branches into fewer steps), so chunks sized
    from it fit every launch the group actually makes."""
    gm = _gm()
    launches = []
    if g.mode == "grouped_chained":
        bm, _mb, spec, _oh, _ow, _nring, problems = chained_spec(
            graph, g.chain, g.pools)
        if spec is None:
            raise ValueError("; ".join(msg for _, msg in problems))
        m = cm.gemm_shape(graph.ops[_strip(g.chain[0][0])])[0]
        if "fwd" in directions:
            launches.append((m, bm, gm._plan_tiles_chained(1, spec).shape,
                             len(spec), ((2,),)))
        if "bwd" in directions:
            # the VJP's ONE combined dx + dW/db launch per phase, each
            # branch's lhs packed to its k-steps' 128-column blocks
            for pspec in spec:
                kbs = tuple(len(gm._chain_ksteps(tag, src))
                            for tag, src, _nbb, _rw in pspec)
                nbs = tuple(nbb for _t, _s, nbb, _rw in pspec)
                launches.append((m, BLK, gm._plan_tiles_bwd(1, kbs, nbs)
                                 .shape, 0, ()))
        return launches
    names = [_strip(n) for n in g.ops if n != g.join]
    shapes = [cm.gemm_shape(graph.ops[n]) for n in names]
    m = shapes[0][0]
    kns = [(k, n) for _, k, n in shapes]
    bl = gm.grouped_block_shape(m, kns, "float32")
    if "fwd" in directions:
        pools = {_strip(b): _strip(p) for b, p in g.pools}
        taps = tuple(tap_count(graph.ops[pools[n]]) if n in pools else 1
                     for n in names)
        kbs = tuple(_ceil(k, bl.bk) for k, _ in kns)
        nbs = tuple(_ceil(n, bl.bn) for _, n in kns)
        if any(t > 1 for t in taps):
            per = gm._plan_tiles_pooled(1, kbs, nbs, taps, bool(g.join))
        elif g.join:
            per = gm._plan_tiles_concat(1, kbs, nbs)
        else:
            per = gm._plan_tiles(1, kbs, nbs)
        launches.append((m, bl.bm, per.shape, 1, ()))
    if "bwd" in directions:
        b = bl.bm if bl.bm == bl.bn == bl.bk else BLK
        kbs = tuple(_ceil(k, b) for k, _ in kns)
        nbs = tuple(_ceil(n, b) for _, n in kns)
        launches.append((m, b, gm._plan_tiles_bwd(1, kbs, nbs).shape, 0,
                         ()))
    return launches


def _image_rows(graph, g, m: int) -> int:
    """Rows of one image in the group's GEMM row space (conv ops: M over
    the batch); row-local matmul groups chunk at 128-row blocks."""
    op = graph.ops[_strip(g.ops[0])]
    return m // op.p["n"] if op.kind == "conv2d" else BLK


def chunk_rows(graph, g, directions=("fwd",)) -> tuple[int, int]:
    """(rows per launch, M) for an ExecGroup: the widest image-aligned
    chunk whose launches fit SMEM in every one of ``directions``, or M
    when one launch fits.  Raises ValueError when one image's table
    alone exceeds SMEM."""
    gm = _gm()
    launches = group_launches(graph, g, directions)
    m = launches[0][0]
    unit = _image_rows(graph, g, m)
    return min(gm.smem_chunk_rows(m, bm, per, unit=unit, mrow_slots=ms,
                                  fixed=fx)
               for m, bm, per, ms, fx in launches), m


def group_smem_bytes(graph, g, rows: int, directions=("fwd",)) -> int:
    """Prefetch bytes of the group's largest launch when each launch
    covers at most ``rows`` rows."""
    gm = _gm()
    return max(gm.launch_smem_bytes(per, _ceil(min(rows, m), bm), ms, fx)
               for m, bm, per, ms, fx in group_launches(graph, g,
                                                         directions))
