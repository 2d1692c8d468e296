"""planlint ``verify_plan`` — re-derive a lowered plan's launch geometry
and prove every static invariant without executing a kernel.

For each co-executed ExecGroup the verifier reconstructs the launch
geometry the executor would hand the kernel wrappers — (M, K, N) per
branch via ``cost_model.gemm_shape``, blocks via ``grouped_block_shape``,
pool tap counts via ``analysis.budgets.tap_count``, the chained phase
spec via the same rules ``_chain_static`` applies — then builds the REAL
offset table with the kernel's own ``_plan_tiles*`` planner and checks
it against the independent schema/replay implementations in
``analysis.tables`` plus the happens-before analysis in
``analysis.hazards``, and re-prices the group's C2 footprint against the
budgets the plan was lowered under (``plan.context["budgets"]``).

Two deliberate normalizations (the invariants checked are unaffected):

  * a chained branch whose lhs comes from OUTSIDE the launch (a previous
    launch's panel composite, a materialized env value) is specced as a
    packed-x source (``analysis.budgets.chained_spec``) — the
    panel-descriptor block numbering needs the executor's env, which a
    static pass does not have, and the wave / ring schedule is invariant
    to the lhs source tag;
  * ragged-M serving launches are verified at the full bucket M — the
    offset table is identical for every request mix in the bucket, and
    the chained masked obligations (mrow slot addressing, in-image tap
    identity) are checked for ALL image-aligned cutoffs at once by
    ``hazards.check_chained_masked``.

An SMEM-chunked group is verified one chunk at a time: its tables are
built at ``ExecGroup.chunk_rows``, and every launch's prefetch must fit
the SMEM budget (``analysis.budgets.group_smem_bytes``).

Geometry checks are memoized: plans re-lower the same shapes constantly
(every pytest case, every serve bucket) and the tables are pure
functions of the geometry key.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.analysis import Finding
from repro.analysis import budgets as _budgets
from repro.analysis import hazards, tables
from repro.core import cost_model as cm

BLK = 128


def _gm():
    # importlib, not ``from repro.kernels import grouped_matmul``: the
    # package re-exports a FUNCTION of that name which shadows the
    # submodule attribute once ``__init__`` finishes
    import importlib
    return importlib.import_module("repro.kernels.grouped_matmul")


#: modes whose groups carry a scalar-prefetch offset table to verify
TABLE_MODES = ("grouped", "grouped_pooled", "grouped_concat",
               "grouped_chained", "grouped_experts")


def _ceil(a: int, b: int) -> int:
    return -(-a // b)


def _strip(n: str) -> str:
    return n[5:] if n.startswith("grad:") else n


def _dtype_of(op):
    return jnp.bfloat16 if op.dtype_bytes == 2 else jnp.float32


def _findings(raw, fam, where):
    return [Finding(kind, fam, where, msg) for kind, msg in raw]


# ---------------------------------------------------------------------------
# memoized geometry checks (pure functions of the geometry key)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=4096)
def _checked_plain(mb, kbs, nbs, concat):
    gm = _gm()
    tab = (gm._plan_tiles_concat(mb, kbs, nbs) if concat
           else gm._plan_tiles(mb, kbs, nbs))
    return tuple(tables.check_plain(tab, mb, kbs, nbs, concat=concat))


@functools.lru_cache(maxsize=4096)
def _checked_pooled(mb, kbs, nbs, taps, concat):
    gm = _gm()
    tab = gm._plan_tiles_pooled(mb, kbs, nbs, taps, concat)
    return tuple(tables.check_pooled(tab, mb, kbs, nbs, taps, concat))


@functools.lru_cache(maxsize=4096)
def _checked_bwd(mb, kbs, nbs):
    gm = _gm()
    tab = gm._plan_tiles_bwd(mb, kbs, nbs)
    return tuple(tables.check_bwd(tab, mb, kbs, nbs))


@functools.lru_cache(maxsize=1024)
def _checked_chained(mb, spec, h, w, nring, bm):
    gm = _gm()
    tab = gm._plan_tiles_chained(mb, spec)
    raw = list(tables.check_chained(tab, mb, spec))
    raw += hazards.check_chained_schedule(tab, mb, len(spec), h=h, w=w,
                                          bm=bm, nring=nring)
    raw += hazards.check_chained_masked(tab, mb, len(spec), h=h, w=w)
    return tuple(raw)


@functools.lru_cache(maxsize=256)
def _checked_experts(mbs, db, fb, gated):
    gm = _gm()
    raw = list(tables.check_experts(
        gm._plan_tiles_experts(mbs, db, fb, gated), mbs, db, fb, gated))
    raw += tables.check_experts_bwd(
        gm._plan_tiles_experts_bwd(mbs, db, fb, gated), mbs, db, fb, gated)
    return tuple(raw)


# ---------------------------------------------------------------------------
# per-group verification
# ---------------------------------------------------------------------------

def _branch_geometry(graph, names, where):
    """Shared-M (M, [(K, N)...], dtype) of a branch group, or
    (None, findings) when the geometry is inconsistent."""
    shapes = []
    for n in names:
        s = cm.gemm_shape(graph.ops[_strip(n)])
        if s is None:
            return None, [Finding("schema", "group", where,
                                  f"branch {n} has no GEMM view — it "
                                  "cannot ride a grouped launch")]
        shapes.append(s)
    ms = {s[0] for s in shapes}
    if len(ms) != 1:
        return None, [Finding("schema", "group", where,
                              f"branches disagree on shared M: {sorted(ms)}"
                              " — a grouped launch needs one row space")]
    dt = _dtype_of(graph.ops[_strip(names[0])])
    return (ms.pop(), [(k, n) for _, k, n in shapes], dt), []


def _verify_grouped(graph, g, where, direction):
    grouped_block_shape = _gm().grouped_block_shape
    names = [n for n in g.ops if n != g.join] if g.join else list(g.ops)
    geom, out = _branch_geometry(graph, names, where)
    if geom is None:
        return out
    m, kns, dt = geom
    pools = {b: p for b, p in g.pools}
    taps = tuple(_budgets.tap_count(graph.ops[_strip(pools[n])])
                 if n in pools else 1 for n in names)
    rows = min(g.chunk_rows or m, m)      # each SMEM chunk's own table
    if direction == "bwd":
        # the combined masked-dx + dw/db launch: ONE uniform block size
        bl = grouped_block_shape(m, kns, dt)
        b = bl.bm if bl.bm == bl.bn == bl.bk else BLK
        mb = _ceil(rows, b)
        kbs = tuple(_ceil(k, b) for k, _ in kns)
        nbs = tuple(_ceil(n, b) for _, n in kns)
        raw = _checked_bwd(mb, kbs, nbs)
        return out + _findings(raw, "grouped-bwd", where)
    bl = grouped_block_shape(m, kns, dt)
    mb = _ceil(rows, bl.bm)
    kbs = tuple(_ceil(k, bl.bk) for k, _ in kns)
    nbs = tuple(_ceil(n, bl.bn) for _, n in kns)
    concat = bool(g.join)
    if any(t > 1 for t in taps):
        raw = _checked_pooled(mb, kbs, nbs, taps, concat)
        fam = "pooled-concat" if concat else "pooled"
    elif concat:
        raw = _checked_plain(mb, kbs, nbs, True)
        fam = "concat"
    else:
        raw = _checked_plain(mb, kbs, nbs, False)
        fam = "plain"
    out += _findings(raw, fam, where)
    if concat:
        # write-write tiling of the padded join panel (col-block space),
        # plus true-width coverage of the join against its declared size
        segs = []
        cb = 0
        for n, nb in zip(names, nbs):
            segs.append((cb, nb, n))
            cb += nb
        out += _findings(hazards.check_concat_segments(segs, cb),
                         "concat-panel", where)
        join_op = graph.ops[_strip(g.join)]
        if join_op.kind == "pointwise" and "elements" in join_op.p:
            total = join_op.p["elements"] // m
            in_launch = sum(n for _, n in kns)
            passthrough = sum(
                cm.gemm_shape(graph.ops[p])[2]
                for p in sorted(graph.pred[_strip(g.join)])
                if p not in {_strip(n) for n in names}
                and cm.gemm_shape(graph.ops[p]) is not None)
            if in_launch + passthrough != total:
                out.append(Finding(
                    "hazard", "concat-panel", where,
                    f"join {g.join} declares {total} columns but its "
                    f"writers cover {in_launch} in-launch + "
                    f"{passthrough} passthrough"))
    return out


def _verify_chained(graph, g, where, direction):
    if direction == "bwd":
        # reverse-phase mirror: ONE combined masked-dx + dw/db grouped
        # launch per phase — verify each phase's two-phase bwd table
        out = []
        for p, ph in enumerate(g.chain):
            sub = _verify_grouped(
                graph, type(g)("grouped", tuple(ph), g.algorithms, 0.0,
                               chunk_rows=g.chunk_rows),
                f"{where}/phase{p}", "bwd")
            out += sub
        return out
    bm, mb, spec, oh, ow, nring, raw = _budgets.chained_spec(
        graph, g.chain, g.pools)
    out = _findings(raw, "chained", where)
    if spec is None:
        return out
    if g.chunk_rows:
        mb = _ceil(g.chunk_rows, bm)      # each chunk's own launch table
    return out + _findings(_checked_chained(mb, spec, oh, ow, nring, bm),
                           "chained", where)


def _verify_experts(plan, where):
    moe_static_blocks = _gm().moe_static_blocks
    moe = plan.context.get("moe")
    if not moe:
        return [Finding("schema", "experts", where,
                        "grouped_experts group without plan.context"
                        "['moe'] — the static block grid is underivable")]
    mbs = moe_static_blocks(moe["n_slots"], moe["e"], moe["bm"])
    db, fb = _ceil(moe["d"], BLK), _ceil(moe["f"], BLK)
    raw = _checked_experts(mbs, db, fb, int(moe["gated"]))
    return _findings(raw, "experts", where)


def _verify_smem(graph, g, where, direction, budgets):
    """Every launch of the group — one per SMEM chunk — must prefetch no
    more than the SMEM budget, and the recorded chunk count must be the
    one its ``chunk_rows`` gives.  A backward is checked only for plans
    lowered for training (``lower(train=True)`` sizes the chunks for
    both directions; an inference plan's backward mirror is pricing)."""
    if direction == "bwd" and not budgets.get("train"):
        return []
    try:
        launches = _budgets.group_launches(graph, g, (direction,))
    except ValueError as e:
        return [Finding("schema", g.mode, where, str(e))]
    m = launches[0][0]
    rows = min(g.chunk_rows or m, m)
    out = []
    if g.chunks != _ceil(m, rows):
        out.append(Finding("bounds", g.mode, where,
                           f"{g.chunks} chunks recorded, but {rows}-row "
                           f"chunks of M={m} make {_ceil(m, rows)}"))
    need = _budgets.group_smem_bytes(graph, g, rows, (direction,))
    smem = budgets.get("smem", cm.SMEM_PREFETCH_BYTES)
    if need > smem:
        out.append(Finding("budget", g.mode, where,
                           f"a {rows}-row launch prefetches {need}B of "
                           f"offset tables into SMEM, over the {smem}B "
                           "budget — the chip's compiler refuses it; "
                           "chunk_rows should have split it"))
    return out


def _verify_budget(graph, g, where, direction, budgets):
    if not budgets:
        return []
    hbm, vmem = budgets["hbm"], budgets["vmem"]
    out = _verify_smem(graph, g, where, direction, budgets)
    if g.mode == "grouped_chained":
        if direction == "bwd":
            return out  # per-phase grouped launches, priced by the mirror
        chain = [[_strip(n) for n in ph] for ph in g.chain]
        opset = {n for ph in chain for n in ph}
        ring = frozenset(n for ph in chain for n in ph
                         if graph.pred[n] & opset)
        try:
            fp = _budgets.chained_footprint(graph, chain, ring,
                                            pools=g.pools)
        except ValueError:
            return out      # malformed: ``_verify_chained`` reports it
    else:
        names = tuple(_strip(n) for n in g.ops)
        algs = {_strip(k): v for k, v in g.algorithms.items()}
        fp = _budgets.group_footprint(
            graph, names, algs, direction=direction,
            pools=tuple((_strip(b), _strip(p)) for b, p in g.pools),
            include_gemm_ws=True if (direction == "fwd" and g.pools)
            else None)
    if not fp.fits(hbm, vmem):
        out.append(Finding("budget", g.mode, where,
                           f"footprint (ws={fp.workspace_bytes:.3g}B, "
                           f"vmem={fp.vmem_bytes:.3g}B) exceeds the lowered "
                           f"budgets (hbm={hbm:.3g}B, vmem={vmem:.3g}B) — "
                           "this group should have been priced serial"))
    return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def verify_plan(plan, graph=None):
    """Statically verify a lowered plan (see ``analysis.verify_plan``).

    ``graph`` defaults to the plan's own ``context["graph"]`` (stashed by
    ``lower`` / ``backward_plan``); a backward plan falls back to its
    forward plan's context.  Returns a list of ``Finding``."""
    fwd_ctx = plan.context.get("forward")
    if graph is None:
        graph = plan.context.get("graph")
    if graph is None and fwd_ctx is not None:
        graph = fwd_ctx.context.get("graph")
    budgets = plan.context.get("budgets")
    if budgets is None and fwd_ctx is not None:
        budgets = fwd_ctx.context.get("budgets")
    direction = "bwd" if any(n.startswith("grad:")
                             for g in plan.groups for n in g.ops) else "fwd"
    out: list[Finding] = []
    for gi, g in enumerate(plan.groups):
        if g.mode not in TABLE_MODES:
            continue
        where = f"group[{gi}] {g.mode}({', '.join(g.ops[:3])}" \
                + (", ..." if len(g.ops) > 3 else "") + ")"
        if g.mode == "grouped_experts":
            out += _verify_experts(plan, where)
            continue
        if graph is None:
            out.append(Finding("schema", "plan", where,
                               "no op graph available (pass one, or "
                               "lower the plan with a graph context) — "
                               "table checks skipped"))
            continue
        if g.mode == "grouped_chained":
            out += _verify_chained(graph, g, where, direction)
        else:
            out += _verify_grouped(graph, g, where, direction)
        out += _verify_budget(graph, g, where, direction, budgets)
    return out
