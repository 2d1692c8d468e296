"""Grouped ragged branch GEMM — co-execution without pad-to-max waste.

``branch_matmul`` (the stacked mode) batches G *same-shape* GEMMs on a
branch grid axis and pads heterogeneous widths to a common (K, N) — on
ragged Inception branches most of those MXU tiles multiply zeros.  This
kernel runs G GEMMs with *per-branch* (K_g, N_g) sharing one M (the
spatial-flattened activation rows every branch of a fork reads):

    y_g = epilogue(x_g @ w_g + b_g)          g = 0..G-1
    x_g: (M, K_g)   w_g: (K_g, N_g)   y_g: (M, N_g)

The grid is the *flattened union of every branch's tile grid* — one step
per (branch, row-block, col-block, k-block) — and a scalar-prefetched
int32 offset table (SMEM) tells each step which slots of the packed
operands it touches:

    row 0  xt     slot index into the packed X tile stack (T_x, bm, bk)
    row 1  wt     slot index into the packed W tile stack (T_w, bk, bn)
    row 2  bj     col-block index into the packed bias (1, sum Np_g)
    row 3  first  1 on a tile's first k-step (zero the accumulator)
    row 4  last   1 on a tile's last k-step (epilogue + store)
    row 5  ot     slot index into the packed output tile stack

k-steps of one output tile are consecutive grid steps, so the fp32
accumulator lives in VMEM scratch across them.  The bias + optional ReLU
epilogue is applied in-kernel at the last k-step — branch outputs leave
the kernel finished, with no post-kernel bias/activation round-trip.
The optional ``mask`` operand (tiled like X) zeroes LHS elements where
mask <= 0 before the dot: the fused-ReLU *cotangent* mask of the
backward pass, applied in-kernel instead of a separate XLA pass.
Per-branch dims pad only to the block alignment, never to the widest
branch: zero pad-to-max-N FLOPs.

``grouped_matmul_concat`` is the fused epilogue-concat variant: the same
kernel, but the scalar-prefetched table lays output slots out as the
fork/join's padded panel layout (m-outermost, per-branch column-block
offsets), so each branch's bias+ReLU epilogue stores its finished tile
directly into the branch's slice of the join buffer.  The per-branch
output buffers, their tile-stack unpacks, and the standalone
``concatenate`` join all disappear — one bulk layout pass plus a single
column gather (identity for bn-aligned widths) yields the true
``[M, sum N_g]`` join.

``grouped_matmul_pooled`` / ``grouped_matmul_pooled_concat`` stream a
branch's maxpool through the SAME launch as an in-kernel pre-GEMM stage:
the offset table gains a per-branch pool descriptor (rows 6-9 — derived
from the branch's (window, stride) chain) and the packed X stack holds,
for pooled branches, the pool-window *tap views* of the RAW input
(``pool_tap_views`` — shifted slices, pure layout like the im2col view,
never a ``reduce_window``).  Pool steps max tap tiles into a VMEM
pooled-lhs scratch; the GEMM steps of that M-block then draw their lhs
from the scratch — the pooled activation never round-trips HBM and the
standalone pooling launch disappears (cuDNN's pooling primitive, and the
last pre-GEMM round-trip of an inception module).

``grouped_matmul_dw`` is the mirrored backward-weight kernel: G
*transposed* GEMMs dw_g = x_g^T @ dy_g with per-branch (K_g, N_g)
outputs sharing the M contraction, db_g = sum_M dy_g reduced in the same
pass (accumulated on the first k-row, where each dy column block is
streamed in anyway, and stored at the last m-step).

``grouped_matmul_bwd`` merges the masked-dx pass and ``grouped_matmul_dw``
into ONE launch over a concatenated two-phase offset table: the dY and
mask tile stacks both phases read are identically tiled (bm, bn) blocks,
so they are packed once and shared — half the packing traffic of the
separate dx + dw launches, and the whole grad CoGroup of a grouped
branch group is a single kernel (the shape ``kernels/ops.py``'s VJPs
emit).

Block sizes default to ``grouped_block_shape`` (ROADMAP "block-size
tuning"): 256-row M-blocks once M > 16384, and 256-wide (bk, bn) weight
tiles for bf16 when every branch is already 256-aligned; the returned
``GroupedBlocks`` repr records the choice (``grouped_debug`` prints the
whole launch).

Every tensor operand is packed as a (T, block, block) tile stack —
branch g's X tiles occupy slots [xbase_g, xbase_g + mb * nkb_g), its
outputs [obase_g, obase_g + mb * npb_g), and so on — so each grid step
addresses *leading-dim* slots: contiguous for the TPU DMA engine and for
the interpret-mode emulation this repo tests under (block reads/writes
against a (M, sum K) matrix are strided in the lane dim and dominate the
emulated wall time).  Tiling X in and the output back out are pure
layout passes (zero FLOPs), fused by XLA around the kernel.

Like the rest of the zoo this runs under ``interpret=True`` on CPU; the
differentiable wrapper (custom VJP) lives in ``kernels/ops.py``.
"""
from __future__ import annotations

import contextlib
import functools
from collections import OrderedDict
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.tables import (
    GM_XT, GM_WT, GM_BJ, GM_FIRST, GM_LAST, GM_OT, GM_MI,
    GP_XT, GP_WT, GP_FIRST, GP_LAST, GP_OT,
    GP_POOL, GP_PFIRST, GP_PS, GP_UPOOL, GP_MI,
    DW_XT, DW_DYT, DW_FIRST, DW_LAST, DW_OT, DW_BJ, DW_DODB,
    BW_DYT, BW_ABT, BW_FIRST, BW_LAST, BW_OT, BW_DODB, BW_DW, BW_BJ,
    CH_I, CH_XT, CH_WT, CH_BJ, CH_FIRST, CH_LAST, CH_PH, CH_SRC,
    CH_PCA, CH_PCB, CH_RC, CH_DELTA, CH_DH, CH_DW, CH_RWC, CH_ROWS,
    EX_BI, EX_XT, EX_WH, EX_WO, EX_PH, EX_FIRST, EX_LAST,
    EX_HJ, EX_OT, EX_RES,
    EB_BI, EB_DYT, EB_XT, EB_WHT, EB_WOT, EB_RES, EB_PH, EB_FIRST,
    EB_LAST, EB_PJ, EB_DXOT, EB_DWH, EB_DWO,
    ch_out_i_row, ch_out_j_row, ch_mrow_row, chained_panel_stride,
    chained_step_counts, smem_bytes)
from repro.kernels.matmul import mxu_precision


# Eager kernel launches by wrapper name — the benchmark's
# launches-per-grad-CoGroup instrument (under jit the wrapper runs once
# at trace time, so only eager measurement is meaningful).
KERNEL_LAUNCHES: dict[str, int] = {}


def _count_launch(name: str) -> None:
    KERNEL_LAUNCHES[name] = KERNEL_LAUNCHES.get(name, 0) + 1


def reset_launch_counts() -> None:
    KERNEL_LAUNCHES.clear()


# What the chained launches traced inside ``chained_steps_recording()`` do,
# summed: the launches, their grid steps by lhs source and their
# ring-window builds, read off each launch's offset table
# (``tables.chained_step_counts``).
_CHAIN_RECORDERS: list[dict] = []


@contextlib.contextmanager
def chained_steps_recording():
    rec = {"launches": 0, "x": 0, "ring": 0, "panel": 0, "window_builds": 0}
    _CHAIN_RECORDERS.append(rec)
    try:
        yield rec
    finally:
        _CHAIN_RECORDERS.remove(rec)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile_stack(a2d, b0: int, b1: int):
    """(D0, D1) -> (D0/b0 * D1/b1, b0, b1) leading-dim tile stack,
    row-block major (the slot layout every kernel here addresses)."""
    d0, d1 = a2d.shape
    t = a2d.reshape(d0 // b0, b0, d1 // b1, b1).transpose(0, 2, 1, 3)
    return t.reshape(-1, b0, b1)


# ---------------------------------------------------------------------------
# block-size heuristic (ROADMAP "block-size tuning")
# ---------------------------------------------------------------------------

M_LARGE_ROWS = 16384     # B*OH*OW beyond which 256-row M-blocks pay off


class GroupedBlocks(NamedTuple):
    """Chosen (bm, bn, bk) with the reason — the kernel's debug repr."""
    bm: int
    bn: int
    bk: int
    note: str = "default 128^3"

    def __repr__(self):
        return (f"GroupedBlocks(bm={self.bm}, bn={self.bn}, bk={self.bk}, "
                f"note={self.note!r})")


def grouped_block_shape(m: int, kns, dtype=jnp.float32) -> GroupedBlocks:
    """Pick (bm, bn, bk) for a grouped launch over branch widths ``kns``
    = [(K_g, N_g)] sharing ``m`` rows.

    Large-M groups (M = B*OH*OW > 16384) take 256-row M-blocks — half
    the grid steps, twice the MXU work per DMA.  bf16 operands take
    256-wide (bk, bn) weight tiles whenever EVERY branch's K (resp. N)
    is already a multiple of 256, so the wider alignment adds zero pad
    FLOPs; a (256, 256) bf16 W tile plus the f32 accumulator still sit
    comfortably in VMEM.  f32 keeps 128 lanes (the MXU native tile).
    """
    notes = []
    bm, bn, bk = 128, 128, 128
    if m > M_LARGE_ROWS:
        bm = 256
        notes.append(f"M={m}>16k -> bm=256")
    if jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16):
        if all(n % 256 == 0 for _, n in kns):
            bn = 256
        if all(k % 256 == 0 for k, _ in kns):
            bk = 256
        if bn == 256 or bk == 256:
            notes.append(f"bf16 256-aligned -> (bk,bn)=({bk},{bn})")
    return GroupedBlocks(bm, bn, bk, "; ".join(notes) or "default 128^3")


def grouped_debug(xs, ws, *, bm=None, bn=None, bk=None) -> str:
    """Human-readable description of the launch ``grouped_matmul(xs, ws)``
    would make — branch count, shared M, dtype, chosen blocks (heuristic
    or explicit), and the flattened grid size."""
    m = xs[0].shape[0]
    kns = [(w.shape[0], w.shape[1]) for w in ws]
    blocks = grouped_block_shape(m, kns, xs[0].dtype)
    if not (bm is None and bn is None and bk is None):
        # mirror the kernels: explicit dims override, the rest still come
        # from the heuristic — the repr must report the ACTUAL launch
        blocks = GroupedBlocks(bm or blocks.bm, bn or blocks.bn,
                               bk or blocks.bk,
                               f"explicit over ({blocks.note})")
    mb = _round_up(m, blocks.bm) // blocks.bm
    steps = sum(mb * (_round_up(k, blocks.bk) // blocks.bk)
                * (_round_up(n, blocks.bn) // blocks.bn) for k, n in kns)
    return (f"grouped_matmul[G={len(ws)} M={m} "
            f"{jnp.dtype(xs[0].dtype).name} {blocks!r} grid={steps}]")


# ---------------------------------------------------------------------------
# forward kernel: y_g = epilogue(x_g @ w_g + b_g)
# ---------------------------------------------------------------------------

def _gmm_kernel(tab_ref, *refs, relu: bool, masked: bool,
                ragged: bool = False):
    if ragged:
        mrow_ref, *refs = refs
    if masked:
        x_ref, m_ref, w_ref, b_ref, o_ref, acc_ref = refs
    else:
        x_ref, w_ref, b_ref, o_ref, acc_ref = refs
    t = pl.program_id(0)

    @pl.when(tab_ref[GM_FIRST, t] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    if masked:
        x = jnp.where(m_ref[...] > 0, x, jnp.zeros_like(x))
    acc_ref[...] += jnp.dot(x, w_ref[...],
                            precision=mxu_precision(x.dtype),
                            preferred_element_type=jnp.float32)

    @pl.when(tab_ref[GM_LAST, t] == 1)
    def _store():
        y = acc_ref[...] + b_ref[...].astype(jnp.float32)
        if relu:
            y = jnp.maximum(y, 0.0)
        if ragged:
            # ragged-M epilogue mask: the table's M-block-index row picks
            # this tile's per-block valid-row count out of the second
            # prefetched scalar vector; rows at/past it store zeros (the
            # deterministic padded-M tail — same first-class in-kernel
            # masking as the ReLU cotangent's dY fold)
            valid = mrow_ref[tab_ref[GM_MI, t]]
            ri = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
            y = jnp.where(ri < valid, y, 0.0)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.lru_cache(maxsize=512)
def _plan_tiles(m_blocks: int, kbs: tuple[int, ...], nbs: tuple[int, ...]):
    """Offset table for the flattened grid (hashable block counts in,
    (7, T) int32 out) — pure shape bookkeeping, cached across traces.
    Row 6 is the step's M-block index — consumed only by ragged-M
    launches (the epilogue mask's index into the per-M-block valid-row
    vector); appended so rows 0-5 keep their positions for every
    existing consumer."""
    rows: list[list[int]] = [[], [], [], [], [], [], []]
    noff = xbase = wbase = obase = 0
    for nkb, npb in zip(kbs, nbs):
        for i in range(m_blocks):
            for j in range(npb):
                for kk in range(nkb):
                    rows[0].append(xbase + i * nkb + kk)
                    rows[1].append(wbase + kk * npb + j)
                    rows[2].append(noff + j)
                    rows[3].append(1 if kk == 0 else 0)
                    rows[4].append(1 if kk == nkb - 1 else 0)
                    rows[5].append(obase + i * npb + j)
                    rows[6].append(i)
        noff += npb
        xbase += m_blocks * nkb
        wbase += nkb * npb
        obase += m_blocks * npb
    return np.array(rows, np.int32)


class _DeviceTableCache:
    """Device-resident offset tables — hoisted: built and uploaded ONCE per
    tile-grid shape and reused across launches.  Re-uploading the table
    every call is what put the grouped backward behind stacked on host
    wall under the interpret emulation (BENCH ``bwd_wall_ordering_ok``
    regression).  ensure_compile_time_eval: a first call from inside a
    jit trace must still cache a CONCRETE device array, not a traced
    constant that would leak into later eager calls.

    Was a plain ``functools.lru_cache``; now a registry with PIN COUNTS so
    ``core.plan_cache`` eviction can release exactly the tables no live
    cache entry needs: a pinned key survives any recency pressure, an
    unpinned key falls off the LRU tail once ``maxsize`` unpinned entries
    accumulate, and ``unpin`` drops keys whose pin count hits zero.  The
    ``cache_info``/``cache_clear`` surface of the old lru_cache is kept —
    the identity regression tests probe it."""

    def __init__(self, maxsize: int = 512):
        self.maxsize = maxsize
        self._data: "OrderedDict[tuple, object]" = OrderedDict()
        self._pins: dict[tuple, int] = {}
        self._hits = self._misses = 0
        self._recorders: list[set] = []

    def __call__(self, builder, *args):
        key = (builder,) + tuple(args)
        for rec in self._recorders:
            rec.add(key)
        t = self._data.get(key)
        if t is not None:
            self._hits += 1
            self._data.move_to_end(key)
            return t
        self._misses += 1
        with jax.ensure_compile_time_eval():
            t = jnp.asarray(builder(*args))
        self._data[key] = t
        if len(self._data) > self.maxsize:
            for k in list(self._data):
                if len(self._data) <= self.maxsize:
                    break
                if self._pins.get(k, 0) == 0:
                    del self._data[k]
        return t

    @contextlib.contextmanager
    def recording(self):
        """Collect the table keys touched inside the block (the set a
        plan-cache entry pins as its live working set)."""
        rec: set = set()
        self._recorders.append(rec)
        try:
            yield rec
        finally:
            self._recorders.remove(rec)

    def pin(self, keys) -> None:
        for k in keys:
            self._pins[k] = self._pins.get(k, 0) + 1

    def unpin(self, keys) -> None:
        """Drop a pin per key; a key left with zero pins is released from
        the registry (plan-cache eviction -> its tables go too, unless a
        surviving entry still pins them)."""
        for k in keys:
            n = self._pins.get(k, 0) - 1
            if n > 0:
                self._pins[k] = n
            else:
                self._pins.pop(k, None)
                self._data.pop(k, None)

    def cache_info(self):
        return functools._CacheInfo(self._hits, self._misses, self.maxsize,
                                    len(self._data))

    def cache_clear(self):
        self._data.clear()
        self._pins.clear()
        self._hits = self._misses = 0


_device_table = _DeviceTableCache()


def _ragged_mrows(m_valid, mb: int, bm: int):
    """Per-M-block valid-row counts for a ragged-M launch: block i holds
    ``clip(m_valid - i*bm, 0, bm)`` true rows.  ``m_valid`` is the TOTAL
    true row count (requests pack contiguously along M, so raggedness is
    tail-only) — a python int or a traced i32 scalar: every request mix
    inside one padded-M bucket shares the same offset table and traced
    executable and differs only in this runtime vector, which rides the
    launch as a second scalar-prefetch operand."""
    mv = jnp.asarray(m_valid, jnp.int32)
    return jnp.clip(mv - jnp.arange(mb, dtype=jnp.int32) * bm, 0, bm)


def _ragged_index_maps(ragged: bool):
    """(tile index map builder, bias index map) for a grouped-family
    launch: ragged launches prefetch TWO scalar operands (table + valid
    rows), so every index map gains the trailing ``mrow`` argument."""
    if ragged:
        return (lambda row: (lambda t, tab, mrow, row=row:
                             (tab[row, t], 0, 0)),
                lambda t, tab, mrow: (0, tab[GM_BJ, t]))
    return (lambda row: (lambda t, tab, row=row: (tab[row, t], 0, 0)),
            lambda t, tab: (0, tab[GM_BJ, t]))


# ---------------------------------------------------------------------------
# SMEM: M-chunked launches
# ---------------------------------------------------------------------------
#
# A launch prefetches its whole offset table into SMEM (plus the ragged
# mrow vector and, chained, the dims pair), and the table has one column
# per grid step — linear in M.  Past the chip's 1 MiB of SMEM the compiler
# refuses the launch, so a launch whose table would not fit runs as
# several launches over row chunks, each with a table that fits.  Chunks
# need no halo: every grouped-family row is image-local (im2col rows,
# pool taps and the chained ring's border-masked taps never read another
# image), so chunk outputs stack along M, the backward's dW/db sum over
# chunks and a ragged ``m_valid`` clips per chunk.  The plan layer sizes
# the chunks (``ExecGroup.chunk_rows``) with these same functions;
# ``chunk_rows=None`` sizes them here.

def launch_smem_bytes(per_block, mb: int, mrow_slots: int = 1,
                      fixed=()) -> int:
    """SMEM bytes one launch over ``mb`` M-blocks prefetches: its offset
    table — ``per_block`` is the (rows, steps) shape of the family's
    table for ONE M-block, and every family's step count is linear in the
    M-block count — plus the ragged mrow vector (``mrow_slots`` per
    block; counted for dense launches too, so both chunk alike) and the
    ``fixed`` operands' shapes."""
    r, s = per_block
    return (smem_bytes((r, mb * s))
            + (smem_bytes((mrow_slots * mb,)) if mrow_slots else 0)
            + sum(smem_bytes(f) for f in fixed))


def _smem_budget() -> int:
    from repro.core import cost_model
    return cost_model.SMEM_PREFETCH_BYTES


def smem_chunk_rows(m: int, bm: int, per_block, *, unit: int,
                    mrow_slots: int = 1, fixed=()) -> int:
    """Rows per launch: all ``m`` when the whole launch's prefetch fits
    ``cost_model.SMEM_PREFETCH_BYTES``, else the largest multiple of
    ``unit`` rows (an image, or an M-block) whose launch fits.  Raises
    when one unit alone does not fit — no chunking makes that legal."""
    budget = _smem_budget()

    def fits(rows):
        return launch_smem_bytes(per_block, -(-rows // bm), mrow_slots,
                                 fixed) <= budget
    if fits(m):
        return m
    if not fits(unit):
        raise ValueError(
            f"one {unit}-row unit's offset table ({per_block[0]} rows x "
            f"{per_block[1]} steps per M-block) exceeds the "
            f"{budget}-byte SMEM prefetch budget")
    lo, hi = 1, -(-m // unit)            # lo units fit, hi do not
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if fits(mid * unit):
            lo = mid
        else:
            hi = mid
    return lo * unit


def _launch_rows(m: int, bm: int, per_block, chunk_rows, *, unit: int,
                 mrow_slots: int = 1, fixed=()) -> int:
    """Rows per launch for this call: the planned ``chunk_rows`` (which
    must fit SMEM), else ``smem_chunk_rows`` over ``unit``-row units."""
    if chunk_rows is None:
        return smem_chunk_rows(m, bm, per_block, unit=unit,
                               mrow_slots=mrow_slots, fixed=fixed)
    rows = min(int(chunk_rows), m)
    need = launch_smem_bytes(per_block, -(-rows // bm), mrow_slots, fixed)
    if need > _smem_budget():
        raise ValueError(
            f"planned chunk of {rows} rows needs {need} SMEM bytes; the "
            f"prefetch budget is {_smem_budget()}")
    return rows


def _row_spans(m: int, rows: int):
    return [(s, min(rows, m - s)) for s in range(0, m, rows)]


def _chunk_valid(m_valid, start: int, rows: int):
    if m_valid is None:
        return None
    return jnp.clip(jnp.asarray(m_valid, jnp.int32) - start, 0, rows)


def _stack_rows(parts, total: int | None = None):
    """Chunk outputs stacked along M into ``total`` rows (default: their
    sum; extra rows are zero) with dynamic_update_slice — no concatenate,
    which the traced launch counter charges as a join copy."""
    total = sum(p.shape[0] for p in parts) if total is None else total
    out = jnp.zeros((total,) + parts[0].shape[1:], parts[0].dtype)
    off = 0
    for p in parts:
        out = jax.lax.dynamic_update_slice(out, p,
                                           (off,) + (0,) * (p.ndim - 1))
        off += p.shape[0]
    return out


def _sum_parts(parts):
    """Per-chunk partial dW/db summed in f32, returned in their dtype."""
    acc = parts[0].astype(jnp.float32)
    for p in parts[1:]:
        acc = acc + p.astype(jnp.float32)
    return acc.astype(parts[0].dtype)


def grouped_matmul(xs, ws, bs=None, *, relu: bool = False, mask=None,
                   m_valid=None, bm: int | None = None, bn: int | None = None,
                   bk: int | None = None, interpret: bool = False,
                   chunk_rows: int | None = None):
    """[x_g @ w_g (+ b_g) (+ ReLU)] for ragged (K_g, N_g), one kernel.

    xs: G arrays (M, K_g) — shared M; ws: G arrays (K_g, N_g);
    bs: G arrays (N_g,) or None; mask: G arrays (M, K_g) or None —
    x_g is zeroed where mask_g <= 0 in-kernel (the ReLU cotangent mask
    of the backward dx GEMMs).  ``m_valid`` (python int or traced i32
    scalar) makes the launch ragged-M: rows at/past it are padding and
    the epilogue stores zeros there (``_ragged_mrows``) — the serving
    path's bucketed multi-request batches.  Block sizes default to
    ``grouped_block_shape``.  ``chunk_rows`` caps the rows per launch
    (SMEM chunking; None sizes it from the table).  Returns G arrays
    (M, N_g).
    """
    g = len(xs)
    assert g == len(ws) and g >= 1, (len(xs), len(ws))
    assert bs is None or len(bs) == g
    assert mask is None or len(mask) == g
    m = xs[0].shape[0]
    assert all(x.shape[0] == m for x in xs), [x.shape for x in xs]
    assert all(x.shape[1] == w.shape[0] for x, w in zip(xs, ws)), \
        [(x.shape, w.shape) for x, w in zip(xs, ws)]
    if bm is None or bn is None or bk is None:
        blocks = grouped_block_shape(
            m, [(w.shape[0], w.shape[1]) for w in ws], xs[0].dtype)
        bm, bn, bk = bm or blocks.bm, bn or blocks.bn, bk or blocks.bk
    mp = _round_up(m, bm)
    mb = mp // bm
    kps = [_round_up(x.shape[1], bk) for x in xs]
    nps = [_round_up(w.shape[1], bn) for w in ws]
    nsum = sum(nps)
    kbs = tuple(kp // bk for kp in kps)
    nbs = tuple(np_ // bn for np_ in nps)
    rows = _launch_rows(m, bm, _plan_tiles(1, kbs, nbs).shape, chunk_rows,
                        unit=bm)
    if rows < m:
        parts = [grouped_matmul(
            [x[s:s + r] for x in xs], ws, bs, relu=relu,
            mask=None if mask is None else [mk[s:s + r] for mk in mask],
            m_valid=_chunk_valid(m_valid, s, r), bm=bm, bn=bn, bk=bk,
            interpret=interpret, chunk_rows=r)
            for s, r in _row_spans(m, rows)]
        return [_stack_rows([p[i] for p in parts]) for i in range(g)]

    def pack_x(arrs):
        return jnp.concatenate(
            [_tile_stack(jnp.pad(a, ((0, mp - m), (0, kp - a.shape[1]))),
                         bm, bk)
             for a, kp in zip(arrs, kps)], axis=0)

    xpk = pack_x(xs)
    wpk = jnp.concatenate(
        [_tile_stack(jnp.pad(w, ((0, kp - w.shape[0]),
                                 (0, np_ - w.shape[1]))), bk, bn)
         for w, kp, np_ in zip(ws, kps, nps)], axis=0).astype(xpk.dtype)
    if bs is None:
        bpk = jnp.zeros((1, nsum), xpk.dtype)
    else:
        bpk = jnp.concatenate(
            [jnp.pad(b, (0, np_ - b.shape[0]))
             for b, np_ in zip(bs, nps)]).reshape(1, nsum).astype(xpk.dtype)

    _count_launch("grouped_matmul")
    tab = _device_table(_plan_tiles, mb, kbs, nbs)
    o_tiles = mb * sum(nbs)

    ragged = m_valid is not None
    ix, ixb = _ragged_index_maps(ragged)
    in_specs = [pl.BlockSpec((None, bm, bk), ix(GM_XT))]
    ins = [xpk]
    if mask is not None:
        assert all(mk.shape == x.shape for mk, x in zip(mask, xs)), \
            [(mk.shape, x.shape) for mk, x in zip(mask, xs)]
        in_specs.append(pl.BlockSpec((None, bm, bk), ix(GM_XT)))
        ins.append(pack_x(mask))
    in_specs += [
        pl.BlockSpec((None, bk, bn), ix(GM_WT)),
        pl.BlockSpec((1, bn), ixb),
    ]
    ins += [wpk, bpk]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 if ragged else 1,
        grid=(tab.shape[1],),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, bm, bn), ix(GM_OT)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    scalars = (tab, _ragged_mrows(m_valid, mb, bm)) if ragged else (tab,)
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, relu=relu, masked=mask is not None,
                          ragged=ragged),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((o_tiles, bm, bn), xs[0].dtype),
        interpret=interpret,
    )(*scalars, *ins)

    outs, obase = [], 0
    for w, np_ in zip(ws, nps):
        npb = np_ // bn
        tiles = out[obase:obase + mb * npb]
        y = tiles.reshape(mb, npb, bm, bn).transpose(0, 2, 1, 3)
        outs.append(y.reshape(mp, np_)[:m, :w.shape[1]])
        obase += mb * npb
    return outs


def grouped_matmul_ref(xs, ws, bs=None, *, relu: bool = False, mask=None,
                       m_valid=None):
    """Per-branch XLA oracle for tests/benchmarks.  ``m_valid`` mirrors
    the ragged-M launch: rows at/past it are zeroed in the output."""
    outs = []
    for i, (x, w) in enumerate(zip(xs, ws)):
        if mask is not None:
            x = jnp.where(mask[i] > 0, x, jnp.zeros_like(x))
        y = jnp.dot(x, w, preferred_element_type=jnp.float32)
        if bs is not None:
            y = y + bs[i].astype(jnp.float32)
        if relu:
            y = jnp.maximum(y, 0.0)
        if m_valid is not None:
            ri = jnp.arange(y.shape[0], dtype=jnp.int32)[:, None]
            y = jnp.where(ri < jnp.asarray(m_valid, jnp.int32), y, 0.0)
        outs.append(y.astype(x.dtype))
    return outs


# ---------------------------------------------------------------------------
# fused epilogue-concat: y_g tiles land in the join's [M, sum N_g] layout
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=512)
def _plan_tiles_concat(m_blocks: int, kbs: tuple[int, ...],
                       nbs: tuple[int, ...]):
    """Offset table for the fused-concat grid — the SAME six rows as
    ``_plan_tiles`` (the launch runs the unmodified ``_gmm_kernel``, so a
    grid step costs exactly what a plain grouped step costs), but ordered
    m-outermost with output slots laid out as the join's padded panel
    layout: slot = mi * sum(npb_g) + (colblock base of branch g) + j.
    One ``reshape . transpose . reshape`` then yields the whole
    (Mp, sum Np_g) padded join — no per-branch unpack — and a single
    column gather compacts away the per-branch block padding.  Row 6 is
    the appended M-block index (ragged-M epilogue mask; see
    ``_plan_tiles``)."""
    rows: list[list[int]] = [[] for _ in range(7)]
    xbases, wbases, cbases = [], [], []
    xb = wb = cb = 0
    for nkb, npb in zip(kbs, nbs):
        xbases.append(xb)
        wbases.append(wb)
        cbases.append(cb)
        xb += m_blocks * nkb
        wb += nkb * npb
        cb += npb
    ncbt = cb
    for i in range(m_blocks):
        for g, (nkb, npb) in enumerate(zip(kbs, nbs)):
            for j in range(npb):
                for kk in range(nkb):
                    rows[0].append(xbases[g] + i * nkb + kk)
                    rows[1].append(wbases[g] + kk * npb + j)
                    rows[2].append(cbases[g] + j)
                    rows[3].append(1 if kk == 0 else 0)
                    rows[4].append(1 if kk == nkb - 1 else 0)
                    rows[5].append(i * ncbt + cbases[g] + j)
                    rows[6].append(i)
    return np.array(rows, np.int32)


@functools.lru_cache(maxsize=512)
def _concat_gather_index(offsets: tuple[int, ...], ns: tuple[int, ...],
                         nps: tuple[int, ...], total: int):
    """Column map join-buffer -> padded-panel layout: true column
    offsets[g] + c reads padded column base_g + c; passthrough holes
    (columns no branch owns) read column 0 — placeholder values the
    caller's ``dynamic_update_slice`` overwrites."""
    idx = np.zeros(total, np.int32)
    base = 0
    for off, n, np_ in zip(offsets, ns, nps):
        idx[off:off + n] = base + np.arange(n, dtype=np.int32)
        base += np_
    with jax.ensure_compile_time_eval():
        return jnp.asarray(idx)


def grouped_matmul_concat(xs, ws, bs=None, *, offsets, total: int,
                          relu: bool = False, compact: bool = True,
                          m_valid=None, bm: int | None = None,
                          bn: int | None = None, bk: int | None = None,
                          interpret: bool = False,
                          chunk_rows: int | None = None):
    """[x_g @ w_g (+ b_g) (+ ReLU)] assembled into the fork/join's concat
    layout — ONE (M, total) output, branch g's columns at ``offsets[g]``.

    The launch IS a grouped launch (the unmodified ``_gmm_kernel`` —
    identical per-step cost), but its output slots are the join's padded
    panel layout, m-outermost: one bulk layout pass yields the whole
    (Mp, sum Np_g) padded join at once — the per-branch output buffers
    and their unpacks disappear — and one column gather compacts the
    per-branch block padding into the true [M, total] layout (for
    bn-aligned branch widths it degenerates to the identity).

    Columns of ``total`` not covered by any branch (passthrough slices of
    branch outputs computed by an EARLIER launch) carry placeholder
    values — the caller overwrites them (``core/plan.py`` uses
    ``lax.dynamic_update_slice``).  Returns the (M, total) join buffer.

    ``compact=False`` skips the gather and returns the PADDED
    (M, sum Np_g) join buffer instead — branch g's true columns at the
    cumulative padded base — for callers that splice the passthrough
    segments and strip the padding in one pass (``core/plan.py``'s
    grouped_concat executor); ``offsets``/``total`` then only fix the
    branch order.  ``m_valid`` and ``chunk_rows`` as in
    ``grouped_matmul`` (ragged-M epilogue mask: rows at/past it store
    zeros; SMEM chunking).
    """
    g = len(xs)
    assert g == len(ws) and g == len(offsets) and g >= 1
    assert bs is None or len(bs) == g
    m = xs[0].shape[0]
    assert all(x.shape[0] == m for x in xs), [x.shape for x in xs]
    assert all(x.shape[1] == w.shape[0] for x, w in zip(xs, ws))
    ns = [w.shape[1] for w in ws]
    segs = sorted(zip(offsets, ns))
    assert all(o1 >= o0 + n0 for (o0, n0), (o1, _) in zip(segs, segs[1:])) \
        and segs[-1][0] + segs[-1][1] <= total, (offsets, ns, total)
    if bm is None or bn is None or bk is None:
        blocks = grouped_block_shape(
            m, [(w.shape[0], w.shape[1]) for w in ws], xs[0].dtype)
        bm, bn, bk = bm or blocks.bm, bn or blocks.bn, bk or blocks.bk
    mp = _round_up(m, bm)
    mb = mp // bm
    kps = [_round_up(x.shape[1], bk) for x in xs]
    nps = [_round_up(n, bn) for n in ns]
    nsum = sum(nps)
    kbs = tuple(kp // bk for kp in kps)
    nbs = tuple(np_ // bn for np_ in nps)
    rows = _launch_rows(m, bm, _plan_tiles_concat(1, kbs, nbs).shape,
                        chunk_rows, unit=bm)
    if rows < m:
        return _stack_rows([grouped_matmul_concat(
            [x[s:s + r] for x in xs], ws, bs, offsets=offsets, total=total,
            relu=relu, compact=compact, m_valid=_chunk_valid(m_valid, s, r),
            bm=bm, bn=bn, bk=bk, interpret=interpret, chunk_rows=r)
            for s, r in _row_spans(m, rows)])

    xpk = jnp.concatenate(
        [_tile_stack(jnp.pad(x, ((0, mp - m), (0, kp - x.shape[1]))),
                     bm, bk)
         for x, kp in zip(xs, kps)], axis=0)
    wpk = jnp.concatenate(
        [_tile_stack(jnp.pad(w, ((0, kp - w.shape[0]),
                                 (0, np_ - w.shape[1]))), bk, bn)
         for w, kp, np_ in zip(ws, kps, nps)], axis=0).astype(xpk.dtype)
    if bs is None:
        bpk = jnp.zeros((1, nsum), xpk.dtype)
    else:
        bpk = jnp.concatenate(
            [jnp.pad(b, (0, np_ - b.shape[0]))
             for b, np_ in zip(bs, nps)]).reshape(1, nsum).astype(xpk.dtype)

    _count_launch("grouped_matmul_concat")
    tab = _device_table(_plan_tiles_concat, mb, kbs, nbs)
    ncbt = sum(nbs)

    ragged = m_valid is not None
    ix, ixb = _ragged_index_maps(ragged)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 if ragged else 1,
        grid=(tab.shape[1],),
        in_specs=[
            pl.BlockSpec((None, bm, bk), ix(GM_XT)),
            pl.BlockSpec((None, bk, bn), ix(GM_WT)),
            pl.BlockSpec((1, bn), ixb),
        ],
        out_specs=pl.BlockSpec((None, bm, bn), ix(GM_OT)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    scalars = (tab, _ragged_mrows(m_valid, mb, bm)) if ragged else (tab,)
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, relu=relu, masked=False,
                          ragged=ragged),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mb * ncbt, bm, bn), xs[0].dtype),
        interpret=interpret,
    )(*scalars, xpk, wpk, bpk)
    # m-outermost slots: ONE layout pass unpacks the padded join whole
    y2 = out.reshape(mb, ncbt, bm, bn).transpose(0, 2, 1, 3)
    y2 = y2.reshape(mp, ncbt * bn)[:m]
    if not compact:
        return y2
    idx = _concat_gather_index(tuple(int(o) for o in offsets), tuple(ns),
                               tuple(nps), int(total))
    return jnp.take(y2, idx, axis=1)


def grouped_matmul_concat_ref(xs, ws, bs=None, *, offsets, total: int,
                              relu: bool = False, m_valid=None):
    """Per-branch XLA oracle: scatter each branch's GEMM into the join
    layout (uncovered columns are zero here, unspecified in the kernel)."""
    m = xs[0].shape[0]
    out = jnp.zeros((m, total), xs[0].dtype)
    ys = grouped_matmul_ref(xs, ws, bs, relu=relu, m_valid=m_valid)
    for y, off in zip(ys, offsets):
        out = jax.lax.dynamic_update_slice(out, y, (0, off))
    return out


# ---------------------------------------------------------------------------
# pooled grouped launch: in-kernel maxpool as a pre-GEMM stage
# ---------------------------------------------------------------------------

def _tap_views_one(x, window: int, stride: int):
    """One SAME-padded maxpool stage as ``window**2`` shifted views of
    ``x`` (NHWC): view ``(dh, dw)`` holds, at output position (oh, ow),
    the input element the pool window reads at tap (dh, dw) — out-of-image
    taps are -inf (the max monoid identity, exactly ``reduce_window``'s
    SAME padding).  A pure pad+strided-slice layout pass: no
    ``reduce_window``, no compute beyond the pad."""
    b, h, w, c = x.shape
    oh, ow = -(-h // stride), -(-w // stride)
    ph = max((oh - 1) * stride + window - h, 0)
    pw = max((ow - 1) * stride + window - w, 0)
    plh, plw = ph // 2, pw // 2
    xp = jnp.pad(x, ((0, 0), (plh, ph - plh), (plw, pw - plw), (0, 0)),
                 constant_values=-np.inf)
    # lax.slice, not __getitem__: jnp's strided getitem lowers to a gather
    # whose index grid is built with a concatenate — a layout launch the
    # chained plan's launch-ceiling gate would count.
    return [jax.lax.slice(xp, (0, dh, dw, 0),
                          (b, dh + (oh - 1) * stride + 1,
                           dw + (ow - 1) * stride + 1, xp.shape[3]),
                          (1, stride, stride, 1))
            for dh in range(window) for dw in range(window)]


def pool_tap_views(x, chain):
    """A maxpool *chain* ``((window, stride), ...)`` applied to NHWC ``x``
    as a flat list of shifted views whose elementwise max IS the pooled
    output: ``max_t views[t] == maxpool_chain(x)``.

    Views are ordered so that a first-max-wins fold reproduces the
    cotangent routing of the XLA oracle exactly (``reduce_window``'s max
    grad sends ties to the first maximal tap in window scan order; for a
    chain, the OUTER pool's scatter runs first, so its taps are the major
    axis of the composed order)."""
    views = [x]
    for window, stride in chain:
        exp = [_tap_views_one(v, window, stride) for v in views]
        ntap = window * window
        views = [exp[i][e] for e in range(ntap) for i in range(len(exp))]
    return views


def pool_from_taps(taps):
    """Left-fold ``where(isnan(v) | (v > acc), v, acc)`` over tap views:
    values equal ``reduce_window`` max — including NaN propagation (a
    NaN tap poisons its windows, as XLA's max does; a bare ``v > acc``
    select would silently drop it) — and the select routing makes
    autodiff send tie cotangents to the FIRST maximal tap: bit-identical
    gradients to the XLA oracle on finite inputs (``lax.max``'s
    balanced-eq tie splitting would not be; under NaNs gradients are
    meaningless either way)."""
    acc = taps[0]
    for v in taps[1:]:
        acc = jnp.where(jnp.isnan(v) | (v > acc), v, acc)
    return acc


def pool_cotangent_taps(taps, pooled, d_pooled):
    """Scatter the pooled-lhs cotangent back onto the tap views through
    the first-argmax window mask: tap t receives ``d_pooled`` where it
    equals the pooled max AND no earlier tap does — the mask the combined
    backward launch's unpacking pass applies (elementwise, like the ReLU
    cotangent mask folded into its dY packing)."""
    assigned = jnp.zeros(pooled.shape, jnp.bool_)
    outs = []
    for v in taps:
        take = (v == pooled) & ~assigned
        assigned = assigned | take
        outs.append(jnp.where(take, d_pooled, jnp.zeros_like(d_pooled)))
    return outs


def _gmm_pooled_kernel(tab_ref, *refs, relu: bool, ragged: bool = False):
    """``_gmm_kernel`` plus the in-kernel pre-GEMM pool stage.  Pool steps
    (row 6) max one tap tile of the raw input into the pooled-lhs VMEM
    scratch slot ``ps`` (row 8; row 7 marks the first tap, which seeds the
    slot); GEMM steps with row 9 set draw their lhs from that slot instead
    of the X ref.  Everything else is the unmodified grouped step —
    including the ragged-M epilogue mask (row 10 = M-block index into the
    second prefetched scalar vector)."""
    if ragged:
        mrow_ref, *refs = refs
    x_ref, w_ref, b_ref, o_ref, acc_ref, pool_ref = refs
    t = pl.program_id(0)
    is_pool = tab_ref[GP_POOL, t] == 1
    ps = tab_ref[GP_PS, t]

    @pl.when(is_pool)
    def _pool():
        tile = x_ref[...].astype(jnp.float32)

        @pl.when(tab_ref[GP_PFIRST, t] == 1)
        def _seed():
            pool_ref[ps] = tile

        @pl.when(tab_ref[GP_PFIRST, t] == 0)
        def _max():
            # same NaN-propagating select as pool_from_taps (lax.max may
            # drop a NaN acc against a later finite tap on some backends)
            cur = pool_ref[ps]
            pool_ref[ps] = jnp.where(jnp.isnan(tile) | (tile > cur),
                                     tile, cur)

    @pl.when(~is_pool)
    def _gemm():
        @pl.when(tab_ref[GP_FIRST, t] == 1)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        x = jnp.where(tab_ref[GP_UPOOL, t] == 1,
                      pool_ref[ps].astype(x.dtype), x)
        acc_ref[...] += jnp.dot(x, w_ref[...],
                                precision=mxu_precision(x.dtype),
                                preferred_element_type=jnp.float32)

        @pl.when(tab_ref[GP_LAST, t] == 1)
        def _store():
            y = acc_ref[...] + b_ref[...].astype(jnp.float32)
            if relu:
                y = jnp.maximum(y, 0.0)
            if ragged:
                valid = mrow_ref[tab_ref[GP_MI, t]]
                ri = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
                y = jnp.where(ri < valid, y, 0.0)
            o_ref[...] = y.astype(o_ref.dtype)


@functools.lru_cache(maxsize=512)
def _plan_tiles_pooled(m_blocks: int, kbs: tuple[int, ...],
                       nbs: tuple[int, ...], taps: tuple[int, ...],
                       concat: bool):
    """Offset table for the pooled grouped grid — the per-branch pool
    descriptor the tentpole adds to the scalar-prefetch table.  ``taps[g]``
    is the branch's pool-window tap count (1 = unpooled; window and stride
    live in the tap-slot layout the packing derives from the branch's
    (window, stride) chain).  Branch g's packed X region holds, for every
    (row-block i, k-block kk), its ``taps[g]`` tap tiles consecutively;
    before an M-block's GEMM steps, one pool step per (kk, tap) maxes the
    taps into the pooled-lhs scratch slot kk.  ``concat=True`` lays output
    slots out as the join's padded panel layout, m-outermost
    (``_plan_tiles_concat``).  Rows:

        row 0  xt     slot into the packed X stack (pool step: the tap
                      tile; unpooled GEMM step: the lhs tile; pooled GEMM
                      step: the tile's first tap — fetched, unused)
        row 1  wt     slot into the packed W tile stack
        row 2  bj     col-block index into the packed bias
        row 3  first  1 on a tile's first k-step (zero the accumulator)
        row 4  last   1 on a tile's last k-step (epilogue + store)
        row 5  ot     output slot (pool steps: the upcoming tile's slot —
                      never stored, keeps the revisit window stable)
        row 6  pool   1 = pool step (max a tap tile into scratch)
        row 7  pfirst 1 on a tile's first tap (seed the scratch slot)
        row 8  ps     pooled-lhs scratch slot (the tile's k-block index)
        row 9  upool  1 = GEMM step draws its lhs from the scratch
        row 10 mi     M-block index (ragged-M epilogue mask; appended —
                      rows 0-9 keep their positions)
    """
    rows: list[list[int]] = [[] for _ in range(11)]
    # cbases doubles as the bias col-block offset: the packed bias and
    # the concat panel share one column-block numbering (like
    # _plan_tiles_concat's single accumulator)
    xbases, wbases, obases, cbases = [], [], [], []
    xb = wb = ob = cb = 0
    for nkb, npb, tp in zip(kbs, nbs, taps):
        xbases.append(xb)
        wbases.append(wb)
        obases.append(ob)
        cbases.append(cb)
        xb += m_blocks * nkb * tp
        wb += nkb * npb
        ob += m_blocks * npb
        cb += npb
    ncbt = cb

    def emit(g, i):
        nkb, npb, tp = kbs[g], nbs[g], taps[g]
        pooled = tp > 1
        first_ot = (i * ncbt + cbases[g]) if concat else (obases[g] + i * npb)
        if pooled:
            for kk in range(nkb):
                for t in range(tp):
                    rows[0].append(xbases[g] + (i * nkb + kk) * tp + t)
                    rows[1].append(wbases[g])
                    rows[2].append(cbases[g])
                    rows[3].append(0)
                    rows[4].append(0)
                    rows[5].append(first_ot)
                    rows[6].append(1)
                    rows[7].append(1 if t == 0 else 0)
                    rows[8].append(kk)
                    rows[9].append(0)
                    rows[10].append(i)
        for j in range(npb):
            for kk in range(nkb):
                rows[0].append(xbases[g] + (i * nkb + kk) * tp)
                rows[1].append(wbases[g] + kk * npb + j)
                rows[2].append(cbases[g] + j)
                rows[3].append(1 if kk == 0 else 0)
                rows[4].append(1 if kk == nkb - 1 else 0)
                rows[5].append((i * ncbt + cbases[g] + j) if concat
                               else (obases[g] + i * npb + j))
                rows[6].append(0)
                rows[7].append(0)
                # unpooled steps still read the scratch (both select arms
                # are fetched) — pin them to slot 0, always in bounds
                rows[8].append(kk if pooled else 0)
                rows[9].append(1 if pooled else 0)
                rows[10].append(i)

    if concat:
        for i in range(m_blocks):
            for g in range(len(kbs)):
                emit(g, i)
    else:
        for g in range(len(kbs)):
            for i in range(m_blocks):
                emit(g, i)
    return np.array(rows, np.int32)


# A single pool window keeps its taps as in-kernel pool steps; a chained
# pool (e.g. the (3,2)+(3,1) pool-proj of a pooled module) expands to
# window1^2 * window2^2 = 81 views, and 81 pool grid steps per (i, kk)
# tile cost more than they save (on hardware: more steps than the GEMM
# they feed; on the interpret emulation: each is a fully-charged grid
# step).  Past the limit the taps fold at PACK time instead — an
# elementwise max fused into the tile-stack layout pass, still zero
# reduce_window, still one launch, same VJP (the backward folds at pack
# time in all cases).  Heuristic knob in the grouped_block_shape spirit.
POOL_TAP_LIMIT = 16


def _branch_taps(xs, tap_limit: int | None = None):
    """Normalize xs entries: an array is one tap (unpooled); a list/tuple
    of tap arrays is a pooled branch — folded at pack time when its tap
    count exceeds ``tap_limit``.  Returns (tap lists, tap counts)."""
    limit = POOL_TAP_LIMIT if tap_limit is None else tap_limit
    tls, tns = [], []
    for x in xs:
        if isinstance(x, (list, tuple)):
            assert len(x) >= 1
            assert all(t.shape == x[0].shape for t in x)
            if len(x) > limit:
                tls.append([pool_from_taps(list(x))])
                tns.append(1)
            else:
                tls.append(list(x))
                tns.append(len(x))
        else:
            tls.append([x])
            tns.append(1)
    return tls, tns


def _pooled_launch(xs, ws, bs, *, relu, concat, offsets=None, total=None,
                   compact=True, m_valid=None, bm=None, bn=None, bk=None,
                   interpret=False, tap_limit=None, chunk_rows=None):
    """Shared implementation of the pooled grouped launch (plain and
    fused-concat output layouts)."""
    g = len(xs)
    assert g == len(ws) and g >= 1
    assert bs is None or len(bs) == g
    tls, tns = _branch_taps(xs, tap_limit)
    m = tls[0][0].shape[0]
    assert all(t.shape[0] == m for tl in tls for t in tl)
    assert all(tl[0].shape[1] == w.shape[0] for tl, w in zip(tls, ws))
    ns = [w.shape[1] for w in ws]
    if concat:
        assert offsets is not None and total is not None \
            and len(offsets) == g
        segs = sorted(zip(offsets, ns))
        assert all(o1 >= o0 + n0 for (o0, n0), (o1, _)
                   in zip(segs, segs[1:])) \
            and segs[-1][0] + segs[-1][1] <= total, (offsets, ns, total)
    if bm is None or bn is None or bk is None:
        blocks = grouped_block_shape(
            m, [(w.shape[0], w.shape[1]) for w in ws], tls[0][0].dtype)
        bm, bn, bk = bm or blocks.bm, bn or blocks.bn, bk or blocks.bk
    mp = _round_up(m, bm)
    mb = mp // bm
    kps = [_round_up(tl[0].shape[1], bk) for tl in tls]
    nps = [_round_up(n, bn) for n in ns]
    nsum = sum(nps)
    kbs = tuple(kp // bk for kp in kps)
    nbs = tuple(np_ // bn for np_ in nps)
    rows = _launch_rows(
        m, bm, _plan_tiles_pooled(1, kbs, nbs, tuple(tns), concat).shape,
        chunk_rows, unit=bm)
    if rows < m:
        parts = [_pooled_launch(
            [[t[s:s + r] for t in tl] if tn > 1 else tl[0][s:s + r]
             for tl, tn in zip(tls, tns)], ws, bs, relu=relu,
            concat=concat, offsets=offsets, total=total, compact=compact,
            m_valid=_chunk_valid(m_valid, s, r), bm=bm, bn=bn, bk=bk,
            interpret=interpret, tap_limit=tap_limit, chunk_rows=r)
            for s, r in _row_spans(m, rows)]
        if concat:
            return _stack_rows(parts)
        return [_stack_rows([p[i] for p in parts]) for i in range(g)]

    # X stack: branch g's region holds, tile by tile, its taps
    # consecutively — (i, kk)-tile slots [base + (i*nkb + kk)*taps, +taps)
    parts = []
    for tl, kp in zip(tls, kps):
        stacks = [_tile_stack(
            jnp.pad(t, ((0, mp - m), (0, kp - t.shape[1]))), bm, bk)
            for t in tl]
        if len(stacks) == 1:
            parts.append(stacks[0])
        else:
            # interleave taps per tile: (T_tiles, taps, bm, bk) flattened
            parts.append(jnp.stack(stacks, axis=1).reshape(-1, bm, bk))
    xpk = jnp.concatenate(parts, axis=0)
    wpk = jnp.concatenate(
        [_tile_stack(jnp.pad(w, ((0, kp - w.shape[0]),
                                 (0, np_ - w.shape[1]))), bk, bn)
         for w, kp, np_ in zip(ws, kps, nps)], axis=0).astype(xpk.dtype)
    if bs is None:
        bpk = jnp.zeros((1, nsum), xpk.dtype)
    else:
        bpk = jnp.concatenate(
            [jnp.pad(b, (0, np_ - b.shape[0]))
             for b, np_ in zip(bs, nps)]).reshape(1, nsum).astype(xpk.dtype)

    name = "grouped_matmul_pooled_concat" if concat \
        else "grouped_matmul_pooled"
    _count_launch(name)
    tab = _device_table(_plan_tiles_pooled, mb, kbs, nbs, tuple(tns),
                        concat)
    nkb_pool = max((kp // bk for kp, tn in zip(kps, tns) if tn > 1),
                   default=1)
    o_tiles = mb * sum(np_ // bn for np_ in nps)

    ragged = m_valid is not None
    ix, ixb = _ragged_index_maps(ragged)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 if ragged else 1,
        grid=(tab.shape[1],),
        in_specs=[
            pl.BlockSpec((None, bm, bk), ix(GP_XT)),
            pl.BlockSpec((None, bk, bn), ix(GP_WT)),
            pl.BlockSpec((1, bn), ixb),
        ],
        out_specs=pl.BlockSpec((None, bm, bn), ix(GP_OT)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((nkb_pool, bm, bk), jnp.float32)],
    )
    scalars = (tab, _ragged_mrows(m_valid, mb, bm)) if ragged else (tab,)
    out = pl.pallas_call(
        functools.partial(_gmm_pooled_kernel, relu=relu, ragged=ragged),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((o_tiles, bm, bn), tls[0][0].dtype),
        interpret=interpret,
    )(*scalars, xpk, wpk, bpk)

    if concat:
        ncbt = sum(np_ // bn for np_ in nps)
        y2 = out.reshape(mb, ncbt, bm, bn).transpose(0, 2, 1, 3)
        y2 = y2.reshape(mp, ncbt * bn)[:m]
        if not compact:
            return y2
        idx = _concat_gather_index(tuple(int(o) for o in offsets),
                                  tuple(ns), tuple(nps), int(total))
        return jnp.take(y2, idx, axis=1)
    outs, obase = [], 0
    for w, np_ in zip(ws, nps):
        npb = np_ // bn
        tiles = out[obase:obase + mb * npb]
        y = tiles.reshape(mb, npb, bm, bn).transpose(0, 2, 1, 3)
        outs.append(y.reshape(mp, np_)[:m, :w.shape[1]])
        obase += mb * npb
    return outs


def grouped_matmul_pooled(xs, ws, bs=None, *, relu: bool = False,
                          m_valid=None, bm: int | None = None,
                          bn: int | None = None, bk: int | None = None,
                          interpret: bool = False,
                          tap_limit: int | None = None,
                          chunk_rows: int | None = None):
    """[maxpool(x_g) @ w_g (+ b_g) (+ ReLU)] for ragged (K_g, N_g) in ONE
    launch, the maxpool computed IN-KERNEL as a pre-GEMM stage.

    ``xs[g]`` is either an (M, K_g) array (unpooled branch — a plain
    grouped lhs) or a sequence of (M, K_g) *tap views* of the raw input
    (``pool_tap_views``): the kernel maxes the tap tiles into a VMEM
    pooled-lhs scratch per the table's pool descriptor, so the pooled
    activation never materializes in HBM and no standalone pooling launch
    remains.  Branches whose tap count exceeds ``tap_limit`` (default
    ``POOL_TAP_LIMIT``) fold at pack time instead — see the constant's
    comment.  ``m_valid`` and ``chunk_rows`` as in ``grouped_matmul``
    (ragged-M epilogue mask; SMEM chunking).  With no pooled branch this
    is exactly ``grouped_matmul``.  Returns G arrays (M, N_g).
    """
    if all(not isinstance(x, (list, tuple)) for x in xs):
        return grouped_matmul(xs, ws, bs, relu=relu, m_valid=m_valid,
                              bm=bm, bn=bn, bk=bk, interpret=interpret,
                              chunk_rows=chunk_rows)
    return _pooled_launch(xs, ws, bs, relu=relu, concat=False,
                          m_valid=m_valid, bm=bm, bn=bn, bk=bk,
                          interpret=interpret, tap_limit=tap_limit,
                          chunk_rows=chunk_rows)


def grouped_matmul_pooled_concat(xs, ws, bs=None, *, offsets, total: int,
                                 relu: bool = False, compact: bool = True,
                                 m_valid=None, bm: int | None = None,
                                 bn: int | None = None,
                                 bk: int | None = None,
                                 interpret: bool = False,
                                 tap_limit: int | None = None,
                                 chunk_rows: int | None = None):
    """``grouped_matmul_concat`` with the in-kernel pool stage: pooled
    branches' epilogues land in the join's [M, total] layout like every
    other branch — one launch covers pooling, GEMMs, bias+ReLU AND the
    concat.  ``xs``/``compact``/``m_valid``/``chunk_rows`` semantics as
    in the pooled/concat wrappers.  With no pooled branch this is
    ``grouped_matmul_concat``."""
    if all(not isinstance(x, (list, tuple)) for x in xs):
        return grouped_matmul_concat(xs, ws, bs, offsets=offsets,
                                     total=total, relu=relu,
                                     compact=compact, m_valid=m_valid,
                                     bm=bm, bn=bn, bk=bk,
                                     interpret=interpret,
                                     chunk_rows=chunk_rows)
    return _pooled_launch(xs, ws, bs, relu=relu, concat=True,
                          offsets=offsets, total=total, compact=compact,
                          m_valid=m_valid, bm=bm, bn=bn, bk=bk,
                          interpret=interpret, tap_limit=tap_limit,
                          chunk_rows=chunk_rows)


def grouped_matmul_pooled_ref(xs, ws, bs=None, *, relu: bool = False,
                              m_valid=None):
    """Per-branch XLA oracle: fold each branch's taps, then plain GEMMs."""
    tls, tns = _branch_taps(xs)
    flat = [pool_from_taps(tl) if tn > 1 else tl[0]
            for tl, tn in zip(tls, tns)]
    return grouped_matmul_ref(flat, ws, bs, relu=relu, m_valid=m_valid)


def grouped_matmul_pooled_concat_ref(xs, ws, bs=None, *, offsets,
                                     total: int, relu: bool = False,
                                     m_valid=None):
    """Oracle for the pooled concat layout (uncovered columns zero)."""
    tls, tns = _branch_taps(xs)
    flat = [pool_from_taps(tl) if tn > 1 else tl[0]
            for tl, tn in zip(tls, tns)]
    return grouped_matmul_concat_ref(flat, ws, bs, offsets=offsets,
                                     total=total, relu=relu,
                                     m_valid=m_valid)


# ---------------------------------------------------------------------------
# backward-weight kernel: dw_g = x_g^T @ dy_g, db_g = sum_M dy_g
# ---------------------------------------------------------------------------

def _gmm_dw_kernel(tab_ref, *refs, masked: bool):
    if masked:
        x_ref, dy_ref, y_ref, dw_ref, db_ref, acc_ref, db_acc_ref = refs
    else:
        x_ref, dy_ref, dw_ref, db_ref, acc_ref, db_acc_ref = refs
    t = pl.program_id(0)
    dy = dy_ref[...]
    if masked:
        dy = jnp.where(y_ref[...] > 0, dy, jnp.zeros_like(dy))

    @pl.when(tab_ref[DW_FIRST, t] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((tab_ref[DW_FIRST, t] == 1) & (tab_ref[DW_DODB, t] == 1))
    def _init_db():
        db_acc_ref[...] = jnp.zeros_like(db_acc_ref)

    # x^T @ dy: contract the shared m-rows of both tiles -> (bk, bn)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], dy, dimension_numbers=(((0,), (0,)), ((), ())),
        precision=mxu_precision(dy.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(tab_ref[DW_DODB, t] == 1)
    def _acc_db():
        # db rides the first k-row, whose dy blocks are streamed in anyway
        db_acc_ref[...] += dy.astype(jnp.float32).sum(0, keepdims=True)

    @pl.when(tab_ref[DW_LAST, t] == 1)
    def _store():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)
        db_ref[...] = db_acc_ref[...]


@functools.lru_cache(maxsize=512)
def _plan_tiles_dw(m_blocks: int, kbs: tuple[int, ...], nbs: tuple[int, ...]):
    """Offset table for the dw grid — one step per (branch, col-block,
    k-row-block, m-step), m-steps consecutive so the fp32 (bk, bn)
    accumulator lives in VMEM scratch across them.  Column-major per
    branch (j outermost) so the db output block of column j is visited
    consecutively and holds its finished sum before the grid moves on.

        row 0  xt     slot into the packed X tile stack (T_x, bm, bk)
        row 1  dyt    slot into the packed dY tile stack (T_dy, bm, bn)
        row 2  first  1 on a tile's first m-step (zero the accumulators)
        row 3  last   1 on a tile's last m-step (store dw + db)
        row 4  ot     slot into the packed dW tile stack (T_w, bk, bn)
        row 5  bj     col-block index into the packed db (1, sum Np_g)
        row 6  dodb   1 on k-row 0 (the k-row that accumulates db)
    """
    rows: list[list[int]] = [[] for _ in range(7)]
    noff = xbase = dybase = wbase = 0
    for nkb, npb in zip(kbs, nbs):
        for j in range(npb):
            for ki in range(nkb):
                for mi in range(m_blocks):
                    rows[0].append(xbase + mi * nkb + ki)
                    rows[1].append(dybase + mi * npb + j)
                    rows[2].append(1 if mi == 0 else 0)
                    rows[3].append(1 if mi == m_blocks - 1 else 0)
                    rows[4].append(wbase + ki * npb + j)
                    rows[5].append(noff + j)
                    rows[6].append(1 if ki == 0 else 0)
        noff += npb
        xbase += m_blocks * nkb
        dybase += m_blocks * npb
        wbase += nkb * npb
    return np.array(rows, np.int32)


def grouped_matmul_dw(xs, dys, mask=None, *, bm: int | None = None,
                      bn: int | None = None, bk: int | None = None,
                      interpret: bool = False):
    """G transposed GEMMs dw_g = x_g^T @ dy_g with db_g = sum_M dy_g
    reduced in the same pass — the backward-weight half of a grouped
    branch group in ONE kernel.

    xs: G arrays (M, K_g) — the forward GEMM inputs (im2col patches for
    convs); dys: G arrays (M, N_g) — output cotangents; mask: optional G
    arrays (M, N_g) — dy_g is zeroed where mask_g <= 0 before BOTH the
    GEMM and the db reduction (the fused-ReLU cotangent mask, applied
    in-kernel).  Returns (dws, dbs): G arrays (K_g, N_g) in the input
    dtype and G float32 arrays (N_g,).
    """
    g = len(xs)
    assert g == len(dys) and g >= 1, (len(xs), len(dys))
    assert mask is None or len(mask) == g
    m = xs[0].shape[0]
    assert all(x.shape[0] == m and dy.shape[0] == m
               for x, dy in zip(xs, dys)), \
        [(x.shape, dy.shape) for x, dy in zip(xs, dys)]
    kns = [(x.shape[1], dy.shape[1]) for x, dy in zip(xs, dys)]
    if bm is None or bn is None or bk is None:
        blocks = grouped_block_shape(m, kns, xs[0].dtype)
        bm, bn, bk = bm or blocks.bm, bn or blocks.bn, bk or blocks.bk
    mp = _round_up(m, bm)
    mb = mp // bm
    kps = [_round_up(k, bk) for k, _ in kns]
    nps = [_round_up(n, bn) for _, n in kns]
    nsum = sum(nps)
    kbs = tuple(kp // bk for kp in kps)
    nbs = tuple(np_ // bn for np_ in nps)

    xpk = jnp.concatenate(
        [_tile_stack(jnp.pad(x, ((0, mp - m), (0, kp - x.shape[1]))),
                     bm, bk)
         for x, kp in zip(xs, kps)], axis=0)

    def pack_dy(arrs):
        return jnp.concatenate(
            [_tile_stack(jnp.pad(a, ((0, mp - m), (0, np_ - a.shape[1]))),
                         bm, bn)
             for a, np_ in zip(arrs, nps)], axis=0)

    ins = [xpk, pack_dy(dys).astype(xpk.dtype)]
    in_specs = [
        pl.BlockSpec((None, bm, bk), lambda t, tab: (tab[DW_XT, t], 0, 0)),
        pl.BlockSpec((None, bm, bn), lambda t, tab: (tab[DW_DYT, t], 0, 0)),
    ]
    if mask is not None:
        assert all(mk.shape == dy.shape for mk, dy in zip(mask, dys)), \
            [(mk.shape, dy.shape) for mk, dy in zip(mask, dys)]
        ins.append(pack_dy(mask))
        in_specs.append(
            pl.BlockSpec((None, bm, bn), lambda t, tab: (tab[DW_DYT, t], 0, 0)))

    _count_launch("grouped_matmul_dw")
    tab = _device_table(_plan_tiles_dw, mb, kbs, nbs)
    w_tiles = sum((kp // bk) * (np_ // bn) for kp, np_ in zip(kps, nps))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tab.shape[1],),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, bk, bn), lambda t, tab: (tab[DW_OT, t], 0, 0)),
            pl.BlockSpec((1, bn), lambda t, tab: (0, tab[DW_BJ, t])),
        ],
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32),
                        pltpu.VMEM((1, bn), jnp.float32)],
    )
    dwt, dbp = pl.pallas_call(
        functools.partial(_gmm_dw_kernel, masked=mask is not None),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((w_tiles, bk, bn), xs[0].dtype),
                   jax.ShapeDtypeStruct((1, nsum), jnp.float32)],
        interpret=interpret,
    )(tab, *ins)

    dws, dbs, wbase, noff = [], [], 0, 0
    for (k, n), kp, np_ in zip(kns, kps, nps):
        nkb, npb = kp // bk, np_ // bn
        tiles = dwt[wbase:wbase + nkb * npb]
        dw = tiles.reshape(nkb, npb, bk, bn).transpose(0, 2, 1, 3)
        dws.append(dw.reshape(kp, np_)[:k, :n])
        dbs.append(dbp[0, noff:noff + n])
        wbase += nkb * npb
        noff += np_
    return dws, dbs


def grouped_matmul_dw_ref(xs, dys, mask=None):
    """Per-branch XLA oracle: (dws, dbs) with the same mask semantics."""
    dws, dbs = [], []
    for i, (x, dy) in enumerate(zip(xs, dys)):
        if mask is not None:
            dy = jnp.where(mask[i] > 0, dy, jnp.zeros_like(dy))
        dws.append(jnp.dot(x.T, dy,
                           preferred_element_type=jnp.float32).astype(x.dtype))
        dbs.append(dy.astype(jnp.float32).sum(0))
    return dws, dbs


# ---------------------------------------------------------------------------
# combined backward: masked dx + dw/db in ONE launch (concatenated table)
# ---------------------------------------------------------------------------

def _gmm_bwd_kernel(tab_ref, dy_ref, ab_ref, o_ref, db_ref,
                    acc_ref, accb_ref):
    t = pl.program_id(0)
    is_dw = tab_ref[BW_DW, t] == 1
    first = tab_ref[BW_FIRST, t] == 1
    last = tab_ref[BW_LAST, t] == 1
    dodb = tab_ref[BW_DODB, t] == 1
    dy = dy_ref[...]          # pre-masked at pack time (ReLU cotangent)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # phase 0 — dx_g = dy_g @ w_g^T: ab is the W^T tile
    @pl.when(~is_dw)
    def _acc_dx():
        acc_ref[...] += jnp.dot(dy, ab_ref[...],
                                precision=mxu_precision(dy.dtype),
                                preferred_element_type=jnp.float32)

    # phase 1 — dw_g = x_g^T @ dy_g: ab is the X tile; db on k-row 0
    @pl.when(is_dw)
    def _acc_dw():
        acc_ref[...] += jax.lax.dot_general(
            ab_ref[...], dy, dimension_numbers=(((0,), (0,)), ((), ())),
            precision=mxu_precision(dy.dtype),
            preferred_element_type=jnp.float32)

    @pl.when(is_dw & first & dodb)
    def _init_db():
        accb_ref[...] = jnp.zeros_like(accb_ref)

    @pl.when(is_dw & dodb)
    def _acc_db():
        accb_ref[...] += dy.astype(jnp.float32).sum(0, keepdims=True)

    @pl.when(last)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    @pl.when(is_dw & last)
    def _store_db():
        db_ref[...] = accb_ref[...]


@functools.lru_cache(maxsize=512)
def _plan_tiles_bwd(m_blocks: int, kbs: tuple[int, ...],
                    nbs: tuple[int, ...]):
    """Concatenated two-phase offset table: every dx step, then every dw
    step, one flat grid over ONE uniform block size b = bm = bn = bk.
    Uniform blocks let both phases share one operand stack (W^T tiles ++
    X tiles), one output stack (dX tiles ++ dW tiles) and one fp32
    accumulator — vs separate per-phase operands, the interpret emulation
    (and a naive pipeline) moves one less input and one less output block
    per step.  Rows:

        row 0  dyt    slot into the packed dY tile stack (both phases)
        row 1  abt    slot into the shared W^T ++ X tile stack
        row 2  first  1 on a tile's first accumulation step
        row 3  last   1 on a tile's last step (store)
        row 4  ot     slot into the shared dX ++ dW output tile stack
        row 5  dodb   1 on k-row 0 of the dw phase (accumulates db)
        row 6  phase  0 = dx step, 1 = dw step
        row 7  bj     col-block index into the packed db (1, sum Np_g)
    """
    rows: list[list[int]] = [[] for _ in range(8)]
    xbases, dybases, wtbases, dxbases, dwbases, noffs = [], [], [], [], [], []
    xb = dyb = wtb = dxb = dwb = nb = 0
    for nkb, npb in zip(kbs, nbs):
        dybases.append(dyb)
        wtbases.append(wtb)
        dxbases.append(dxb)
        dyb += m_blocks * npb
        wtb += npb * nkb
        dxb += m_blocks * nkb
    for nkb, npb in zip(kbs, nbs):
        xbases.append(wtb + xb)         # X tiles follow ALL W^T tiles
        dwbases.append(dxb + dwb)       # dW tiles follow ALL dX tiles
        noffs.append(nb)
        xb += m_blocks * nkb
        dwb += nkb * npb
        nb += npb
    # dx phase: (branch, row-block, K col-block, N contraction-block)
    for g, (nkb, npb) in enumerate(zip(kbs, nbs)):
        for i in range(m_blocks):
            for kk in range(nkb):
                for j in range(npb):
                    rows[0].append(dybases[g] + i * npb + j)
                    rows[1].append(wtbases[g] + j * nkb + kk)
                    rows[2].append(1 if j == 0 else 0)
                    rows[3].append(1 if j == npb - 1 else 0)
                    rows[4].append(dxbases[g] + i * nkb + kk)
                    rows[5].append(0)
                    rows[6].append(0)
                    rows[7].append(0)
    # dw phase: (branch, N col-block, K row-block, m-step)
    for g, (nkb, npb) in enumerate(zip(kbs, nbs)):
        for j in range(npb):
            for ki in range(nkb):
                for mi in range(m_blocks):
                    rows[0].append(dybases[g] + mi * npb + j)
                    rows[1].append(xbases[g] + mi * nkb + ki)
                    rows[2].append(1 if mi == 0 else 0)
                    rows[3].append(1 if mi == m_blocks - 1 else 0)
                    rows[4].append(dwbases[g] + ki * npb + j)
                    rows[5].append(1 if ki == 0 else 0)
                    rows[6].append(1)
                    rows[7].append(noffs[g] + j)
    return np.array(rows, np.int32)


def grouped_matmul_bwd(xs, ws, dys, mask=None, *, block: int | None = None,
                       interpret: bool = False,
                       chunk_rows: int | None = None):
    """The whole grad CoGroup of a grouped branch group in ONE launch:
    dx_g = (dy_g ⊙ mask_g) @ w_g^T, dw_g = x_g^T @ (dy_g ⊙ mask_g),
    db_g = sum_M (dy_g ⊙ mask_g), over a concatenated two-phase offset
    table (``_plan_tiles_bwd``).

    The dY tile stack both phases read is packed ONCE — with the ReLU
    cotangent mask folded into the packing pass, so no mask operand rides
    the grid — and the W^T/X operands (resp. dX/dW outputs) share one
    tile stack over a single uniform block size: half the packing traffic
    of the separate dx + dw launches this replaces, and one block less in
    and out per grid step.

    xs: G arrays (M, K_g) — forward GEMM inputs; ws: G arrays (K_g, N_g);
    dys: G arrays (M, N_g); mask: optional G arrays (M, N_g) — the
    fused-ReLU cotangent mask (dy zeroed where mask <= 0, both phases).
    ``chunk_rows`` as in ``grouped_matmul``: SMEM chunks stack their dx
    rows and sum their dW/db.  Returns (dxs, dws, dbs): G×(M, K_g),
    G×(K_g, N_g) in the input dtype and G float32 (N_g,).
    """
    g = len(xs)
    assert g == len(ws) == len(dys) and g >= 1, (len(xs), len(ws), len(dys))
    assert mask is None or len(mask) == g
    m = xs[0].shape[0]
    assert all(x.shape[0] == m and dy.shape[0] == m
               and x.shape[1] == w.shape[0] and dy.shape[1] == w.shape[1]
               for x, w, dy in zip(xs, ws, dys)), \
        [(x.shape, w.shape, dy.shape) for x, w, dy in zip(xs, ws, dys)]
    kns = [(w.shape[0], w.shape[1]) for w in ws]
    if block is None:
        blocks = grouped_block_shape(m, kns, xs[0].dtype)
        # the shared operand/output stacks need ONE block size
        b = blocks.bm if blocks.bm == blocks.bn == blocks.bk else 128
    else:
        b = block
    mp = _round_up(m, b)
    mb = mp // b
    kps = [_round_up(k, b) for k, _ in kns]
    nps = [_round_up(n, b) for _, n in kns]
    nsum = sum(nps)
    kbs = tuple(kp // b for kp in kps)
    nbs = tuple(np_ // b for np_ in nps)
    rows = _launch_rows(m, b, _plan_tiles_bwd(1, kbs, nbs).shape,
                        chunk_rows, unit=b, mrow_slots=0)
    if rows < m:
        parts = [grouped_matmul_bwd(
            [x[s:s + r] for x in xs], ws, [dy[s:s + r] for dy in dys],
            None if mask is None else [mk[s:s + r] for mk in mask],
            block=b, interpret=interpret, chunk_rows=r)
            for s, r in _row_spans(m, rows)]
        return ([_stack_rows([p[0][i] for p in parts]) for i in range(g)],
                [_sum_parts([p[1][i] for p in parts]) for i in range(g)],
                [_sum_parts([p[2][i] for p in parts]) for i in range(g)])

    if mask is not None:
        assert all(mk.shape == dy.shape for mk, dy in zip(mask, dys))
        dys = [jnp.where(mk > 0, dy, jnp.zeros_like(dy))
               for mk, dy in zip(mask, dys)]
    dypk = jnp.concatenate(
        [_tile_stack(jnp.pad(dy, ((0, mp - m), (0, np_ - dy.shape[1]))),
                     b, b)
         for dy, np_ in zip(dys, nps)], axis=0)
    # shared second operand: every branch's W^T tiles, then every X's
    abpk = jnp.concatenate(
        [_tile_stack(jnp.pad(w.T, ((0, np_ - w.shape[1]),
                                   (0, kp - w.shape[0]))), b, b)
         for w, kp, np_ in zip(ws, kps, nps)]
        + [_tile_stack(jnp.pad(x, ((0, mp - m), (0, kp - x.shape[1]))),
                       b, b)
           for x, kp in zip(xs, kps)], axis=0).astype(dypk.dtype)

    _count_launch("grouped_matmul_bwd")
    tab = _device_table(_plan_tiles_bwd, mb, kbs, nbs)
    dx_tiles = mb * sum(kp // b for kp in kps)
    w_tiles = sum((kp // b) * (np_ // b) for kp, np_ in zip(kps, nps))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tab.shape[1],),
        in_specs=[
            pl.BlockSpec((None, b, b), lambda t, tab: (tab[BW_DYT, t], 0, 0)),
            pl.BlockSpec((None, b, b), lambda t, tab: (tab[BW_ABT, t], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, b, b), lambda t, tab: (tab[BW_OT, t], 0, 0)),
            pl.BlockSpec((1, b), lambda t, tab: (0, tab[BW_BJ, t])),
        ],
        scratch_shapes=[pltpu.VMEM((b, b), jnp.float32),
                        pltpu.VMEM((1, b), jnp.float32)],
    )
    ot, dbp = pl.pallas_call(
        _gmm_bwd_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((dx_tiles + w_tiles, b, b),
                                        xs[0].dtype),
                   jax.ShapeDtypeStruct((1, nsum), jnp.float32)],
        interpret=interpret,
    )(tab, dypk, abpk)

    dxs, dws, dbs = [], [], []
    dxbase, wbase, noff = 0, dx_tiles, 0
    for (k, n), kp, np_ in zip(kns, kps, nps):
        nkb, npb = kp // b, np_ // b
        xt = ot[dxbase:dxbase + mb * nkb]
        dx = xt.reshape(mb, nkb, b, b).transpose(0, 2, 1, 3)
        dxs.append(dx.reshape(mp, kp)[:m, :k])
        wt = ot[wbase:wbase + nkb * npb]
        dw = wt.reshape(nkb, npb, b, b).transpose(0, 2, 1, 3)
        dws.append(dw.reshape(kp, np_)[:k, :n])
        dbs.append(dbp[0, noff:noff + n])
        dxbase += mb * nkb
        wbase += nkb * npb
        noff += np_
    return dxs, dws, dbs


def grouped_matmul_bwd_ref(xs, ws, dys, mask=None):
    """Per-branch XLA oracle: (dxs, dws, dbs) with the same mask
    semantics as ``grouped_matmul_bwd``."""
    dxs, dws, dbs = [], [], []
    for i, (x, w, dy) in enumerate(zip(xs, ws, dys)):
        if mask is not None:
            dy = jnp.where(mask[i] > 0, dy, jnp.zeros_like(dy))
        dxs.append(jnp.dot(dy, w.T,
                           preferred_element_type=jnp.float32).astype(x.dtype))
        dws.append(jnp.dot(x.T, dy,
                           preferred_element_type=jnp.float32).astype(x.dtype))
        dbs.append(dy.astype(jnp.float32).sum(0))
    return dxs, dws, dbs


def grouped_matmul_flops(shapes, bm: int = 128, bn: int = 128,
                         bk: int = 128) -> tuple[int, int]:
    """(grouped, stacked) MXU FLOPs for branch GEMM shapes [(M, K_g, N_g)]:
    grouped pads per-branch to alignment; stacked additionally pads every
    branch to the widest (K, N) — the waste this kernel removes."""
    ms = {m for m, _, _ in shapes}
    assert len(ms) == 1, shapes
    mp = _round_up(ms.pop(), bm)
    kmax = max(_round_up(k, bk) for _, k, _ in shapes)
    nmax = max(_round_up(n, bn) for _, _, n in shapes)
    grouped = sum(2 * mp * _round_up(k, bk) * _round_up(n, bn)
                  for _, k, n in shapes)
    stacked = len(shapes) * 2 * mp * kmax * nmax
    return grouped, stacked


# ---------------------------------------------------------------------------
# chained multi-phase launch (cross-module streaming)
# ---------------------------------------------------------------------------
#
# ONE pallas_call executes a short CHAIN of grouped branch sets ("phases"):
# phase p's branches may draw their GEMM lhs from
#
#   src=0  the packed X tile stack (im2col / pooled-fold lhs prepped outside),
#   src=2  a VMEM ring holding the last 3 row-block panels a PRODUCER phase
#          of the same launch wrote — a KxK conv consumes them as K^2
#          shifted 1x1 tap-GEMMs with border masking, so the producer
#          activation never touches HBM.  The three panels are copied into
#          a per-ring-column window once per (phase, block), with the
#          block's (h, w) row coordinates; every tap then slices it,
#   src=3/4  a PANEL operand — the padded join buffer a PREVIOUS chained
#          launch emitted, consumed in place via a per-branch lhs-source
#          descriptor (panel id + column block) in the scalar-prefetch
#          table: join-chaining with no intervening concat/reshape.
#
# Phases run in a lag-1 wave schedule (wave w runs phase p's row block
# w - p, ascending p), so a ring consumer always finds producer blocks
# i-1, i, i+1 resident and un-overwritten (ring depth 3).  Each phase
# writes one output panel whose segments are its branches' padded column
# slabs — the layout the NEXT launch's panel descriptors address.
# The bias+ReLU epilogue is fused (chained branches must be relu convs).
# Each grid step does only its own source's work: the kernel switches on
# CH_SRC, and emits no code for a source the launch does not have.

# table rows are the CH_* constants in ``analysis.tables`` (plus 2 per
# phase via ch_out_i_row/ch_out_j_row: output row-block / col-block, kept
# on the "slot of the next write at step >= t" stability rule, which the
# x and panel tile rows follow too)


def _chain_ksteps(tag, src):
    """The ordered k-steps of one chained branch."""
    if tag == "x":
        return [("x", kk) for kk in range(src)]
    if tag == "panel":
        return [("panel", pc) for pc in src]
    taps, rcs = src
    return [("ring", (d, dh, dw, rc)) for (d, dh, dw) in taps for rc in rcs]


@functools.lru_cache(maxsize=512)
def _plan_tiles_chained(m_blocks: int, phases):
    """Offset table for a chained launch.  ``phases``: per phase a tuple of
    branch specs (tag, src, nbb, rwcs) with tag 'x' (src = k-block count),
    'panel' (src = ((panel, colblock), ...)) or 'ring' (src = (taps, ring
    cols), taps = ((delta, dh, dw), ...)); nbb = output n-blocks; rwcs =
    per-n-block ring write col (or ()).  The trailing ``ch_mrow_row``
    holds ``phase * m_blocks + block`` — the slot a ragged-M launch's
    prefetched per-phase mrow vector is read at; dense launches carry
    (and ignore) the same row, so one table serves both.  Pure shape
    bookkeeping, cached."""
    nph = len(phases)
    nrows = CH_ROWS + 2 * nph + 1
    pstride = chained_panel_stride(phases)
    info = []
    xbase = wbase = bbase = 0
    for phase in phases:
        pinfo = []
        ob = 0
        for (tag, src, nbb, rwcs) in phase:
            ksteps = _chain_ksteps(tag, src)
            pinfo.append((tag, src, nbb, rwcs, ksteps, xbase, wbase,
                          bbase, ob))
            if tag == "x":
                xbase += m_blocks * src
            wbase += len(ksteps) * nbb
            bbase += nbb
            ob += nbb
        info.append(pinfo)
    cols: list[list[int]] = []
    for wave in range(m_blocks + nph - 1):
        for p in range(nph):
            i = wave - p
            if not (0 <= i < m_blocks):
                continue
            for (tag, src, nbb, rwcs, ksteps, xb, wb, bb, ob) in info[p]:
                ns = len(ksteps)
                for j in range(nbb):
                    for s, (kt, kd) in enumerate(ksteps):
                        c = [0] * nrows
                        c[CH_I] = i
                        c[ch_mrow_row(nph)] = p * m_blocks + i
                        c[CH_WT] = wb + s * nbb + j
                        c[CH_BJ] = bb + j
                        c[CH_FIRST] = 1 if s == 0 else 0
                        c[CH_LAST] = 1 if s == ns - 1 else 0
                        c[CH_PH] = p
                        c[CH_RWC] = -1
                        if kt == "x":
                            c[CH_SRC] = 0
                            c[CH_XT] = xb + i * src + kd
                        elif kt == "panel":
                            pidx, cb = kd
                            c[CH_SRC] = 3 + pidx
                            c[CH_PCA if pidx == 0 else CH_PCB] = \
                                i * pstride + cb
                        else:
                            d, dh, dw, rc = kd
                            c[CH_SRC] = 2
                            c[CH_RC] = rc
                            c[CH_DELTA] = d
                            c[CH_DH] = dh
                            c[CH_DW] = dw
                        if c[CH_LAST]:
                            c[ch_out_i_row(p)] = i
                            c[ch_out_j_row(p)] = ob + j
                            if rwcs:
                                c[CH_RWC] = rwcs[j]
                        cols.append(c)
    # output stability: each phase's index rows = slot of the next write at
    # step >= t (single transition between consecutive writes; the final
    # write is the phase's last (row, col) slab, which is also the default)
    ncbs = [sum(br[2] for br in pinfo) for pinfo in info]
    for p in range(nph):
        nr, nc = ch_out_i_row(p), ch_out_j_row(p)
        nxt = (m_blocks - 1, ncbs[p] - 1)
        for c in reversed(cols):
            if c[CH_PH] == p and c[CH_LAST] == 1:
                nxt = (c[nr], c[nc])
            c[nr], c[nc] = nxt
    # input stability, the same rule: a step that reads no x tile (panel
    # A tile, panel B tile) holds the one the next reader reads, so the
    # pipeline fetches only tiles some step reads
    for row, src in ((CH_XT, 0), (CH_PCA, 3), (CH_PCB, 4)):
        nxt = next((c[row] for c in reversed(cols) if c[CH_SRC] == src), 0)
        for c in reversed(cols):
            if c[CH_SRC] == src:
                nxt = c[row]
            else:
                c[row] = nxt
    return np.array(cols, np.int32).T


@functools.lru_cache(maxsize=512)
def _chained_counts(m_blocks: int, phases):
    return chained_step_counts(_plan_tiles_chained(m_blocks, phases),
                               len(phases))


def _gmm_chained_kernel(*args, nphases: int, npanels: int, bm: int,
                        has_x: bool, nring: int, hwraps: int,
                        ragged: bool = False, debug_steps: bool = False):
    refs = iter(args)
    tab_ref = next(refs)
    mrow_ref = next(refs) if ragged else None
    dims_ref, x_ref, w_ref, b_ref = (next(refs) for _ in range(4))
    coord_ref = next(refs) if nring else None
    p_refs = [next(refs) for _ in range(npanels)]
    out_refs = [next(refs) for _ in range(nphases)]
    cnt_ref = next(refs) if debug_steps else None
    acc_ref = next(refs)
    if nring:
        ring_ref, win_ref, hw_ref, wkey_ref = refs
    t = pl.program_id(0)
    i = tab_ref[CH_I, t]
    src = tab_ref[CH_SRC, t]
    # the (phase, block) slot: ragged liveness, and the key of the ring
    # windows built for this (phase, block)
    slot = tab_ref[ch_mrow_row(nphases), t]
    # per-phase liveness: this (phase, block)'s true row count.  mrow == 0
    # means the block is entirely past m_valid and the whole wave is a
    # no-op guard — init, window build, GEMM, store and ring write all
    # skipped, never merely zeroed.
    mrow = mrow_ref[slot] if ragged else None
    live = (mrow > 0) if ragged else None

    def _reset():
        if debug_steps:
            cnt_ref[0, 0] = 0
            cnt_ref[0, 1] = 0
        for rc in range(nring):         # no window built yet
            wkey_ref[rc] = -1
    if debug_steps or nring:
        pl.when(t == 0)(_reset)

    def _acc(lhs):
        acc_ref[...] += jnp.dot(lhs, w_ref[...],
                                precision=mxu_precision(lhs.dtype),
                                preferred_element_type=jnp.float32)

    def _ring_step():
        rc = tab_ref[CH_RC, t]
        hd = dims_ref[0]
        wd = dims_ref[1]

        @pl.when(wkey_ref[rc] != slot)
        def _build():
            # producer row-block panels i-1, i, i+1 side by side, and the
            # (h, w) of each of block i's rows: row r = i*bm + k sits at
            # off + k in its image, off = i*bm mod h*w = h0*w + w0, and
            # the coordinate operand holds (u // w, u % w) of u = w0 + k
            wkey_ref[rc] = slot
            win_ref[rc, pl.ds(0, bm), :] = ring_ref[(i + 2) % 3, rc]
            win_ref[rc, pl.ds(bm, bm), :] = ring_ref[i % 3, rc]
            win_ref[rc, pl.ds(2 * bm, bm), :] = ring_ref[(i + 1) % 3, rc]
            off = jax.lax.rem(i * bm, hd * wd)
            code = coord_ref[pl.ds(jax.lax.rem(off, wd), bm), :]
            hh = jax.lax.div(off, wd) + (code >> 16)
            for _ in range(hwraps):
                hh = jnp.where(hh >= hd, hh - hd, hh)
            hw_ref[0] = hh
            hw_ref[1] = code & 0xFFFF
            if debug_steps:
                cnt_ref[0, 1] += 1

        shifted = win_ref[rc, pl.ds(bm + tab_ref[CH_DELTA, t], bm), :]
        dh = tab_ref[CH_DH, t]
        dw = tab_ref[CH_DW, t]
        hh = hw_ref[0]
        ww = hw_ref[1]
        valid = (hh >= -dh) & (hh < hd - dh) & (ww >= -dw) & (ww < wd - dw)
        _acc(jnp.where(valid, shifted, jnp.zeros_like(shifted)))

    def _body():
        if debug_steps:
            cnt_ref[0, 0] += 1

        @pl.when(tab_ref[CH_FIRST, t] == 1)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        if has_x:
            pl.when(src == 0)(lambda: _acc(x_ref[...]))
        if nring:
            pl.when(src == 2)(_ring_step)
        for pi, p_ref in enumerate(p_refs):
            pl.when(src == 3 + pi)(lambda p_ref=p_ref: _acc(p_ref[...]))

    def _store():
        bj = tab_ref[CH_BJ, t]
        y = jnp.maximum(
            acc_ref[...] + b_ref[bj, :].astype(jnp.float32)[None, :], 0.0)
        if ragged:
            # live tail block: exact zeros past the block's true rows, so
            # next-phase ring taps and next-launch panel descriptors read
            # clean producer slots
            ri = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
            y = jnp.where(ri < mrow, y, 0.0)
        y = y.astype(out_refs[0].dtype)
        ph = tab_ref[CH_PH, t]
        for p, o_ref in enumerate(out_refs):
            @pl.when(ph == p)
            def _(o_ref=o_ref):
                o_ref[...] = y

        if nring:
            rwc = tab_ref[CH_RWC, t]

            @pl.when(rwc >= 0)
            def _ring():
                ring_ref[i % 3, jnp.maximum(rwc, 0)] = y

    last = tab_ref[CH_LAST, t] == 1
    if ragged:
        pl.when(live)(_body)
        pl.when(last & live)(_store)
    else:
        _body()
        pl.when(last)(_store)


def _chain_coords(w: int, bm: int, blk: int):
    """The ring border mask's coordinate operand: row u < w + bm holds
    ``(u // w) << 16 | u % w`` in every lane."""
    u = np.arange(w + bm, dtype=np.int32)
    return np.repeat(((u // w) << 16 | u % w)[:, None], blk, axis=1)


def _chain_dims(h: int, w: int):
    return np.array([h, w], np.int32)


def chained_layout(phases, blk: int = 128):
    """Per-branch (phase, col base, n-blocks, true n) of the panel layout a
    chained launch emits — what the NEXT launch's panel descriptors (and
    the caller's output slicing) address."""
    out = []
    for p, phase in enumerate(phases):
        cb = 0
        for br in phase:
            nbb = -(-br["n"] // blk)
            out.append((p, cb, nbb, br["n"]))
            cb += nbb
    return out


def _chain_static(phases, blk, bm, wimg):
    """Hashable planner spec + validation for one chained launch."""
    spec = []
    for phase in phases:
        pspec = []
        for br in phase:
            nbb = -(-br["n"] // blk)
            tag = br["src"][0]
            if tag == "x":
                kbs = sum(-(-a.shape[1] // blk) for a in br["src"][1])
                src = kbs
            elif tag == "panel":
                src = tuple(br["src"][1])
            else:
                _, kh, kw, rcs = br["src"]
                taps = []
                for dh in range(kh):
                    for dw in range(kw):
                        d = (dh - kh // 2) * wimg + (dw - kw // 2)
                        assert abs(d) <= bm, (
                            f"halo {d} exceeds bm={bm} (W={wimg}, "
                            f"k={kh}x{kw}) — chain ineligible")
                        taps.append((d, dh - kh // 2, dw - kw // 2))
                src = (tuple(taps), tuple(rcs))
            rwcs = tuple(br.get("ring_write") or ())
            if rwcs:
                assert len(rwcs) == nbb, (rwcs, nbb)
            s = len(_chain_ksteps(tag, src))
            assert br["w"].shape[0] == s * blk, \
                (br["w"].shape, s, blk, "weight rows must be k-step-major")
            pspec.append((tag, src, nbb, rwcs))
        spec.append(tuple(pspec))
    return tuple(spec)


def grouped_matmul_chained(phases, *, m: int, h: int, w: int, panels=(),
                           block: int = 128, m_valid=None,
                           debug_steps: bool = False,
                           interpret: bool = False,
                           chunk_rows: int | None = None):
    """Execute a chain of grouped branch phases as ONE kernel.

    ``phases``: list of phases, each a list of branch dicts
      n     true output width
      w     (S*block, n) weight — rows in K-STEP-MAJOR order (one
            ``block``-row slab per k-step, zero-padded where the lhs slab
            is panel padding), S the branch's k-step count
      b     (n,) bias or None
      src   ('x', [2D (m, K_i) arrays])               packed-lhs branch
            ('panel', [(panel_idx, col_block), ...])  join-chained branch
            ('ring', kh, kw, (ring_cols...))          in-launch KxK conv
      ring_write  per-n-block ring col this branch's output feeds, or None

    ``panels``: previous-launch padded panels (rows >= m, cols a multiple
    of ``block``) consumed by 'panel' branches in place.  ``h``/``w`` are
    the shared spatial dims (m = B*h*w) the ring border mask decodes.

    Returns one padded (Mp, ncb_p * block) panel per phase; true values
    sit at [:m, col_base*block : col_base*block + n] per ``chained_layout``
    — padding columns are exactly zero (relu(0 + 0)).

    ``m_valid`` (python int or traced i32 scalar) makes the launch
    ragged-M: rows at/past it are padding.  The wave schedule SKIPS
    M-blocks entirely past ``m_valid`` (no-op guard — dead-block
    GEMM/ring steps never execute), live tail blocks mask their epilogue
    stores to exact zeros, and the per-phase liveness vector
    (``_ragged_mrows`` tiled per phase) rides the launch as a second
    scalar-prefetch operand.  ``m_valid`` must be image-aligned
    (a multiple of h*w): ring taps are image-local, so valid rows never
    read skipped blocks (``analysis.hazards.check_chained_masked``).
    Every request mix in one padded-M bucket shares the same offset
    table and traced executable.  Inference-only — the differentiable
    wrapper in ``kernels/ops.py`` rejects ragged chains from its VJP.

    ``debug_steps=True`` additionally returns the kernel's own counters
    (the skip instrument): ``(panels, counts)`` where ``counts`` is a
    (1, 2) i32, ``[0, 0]`` the grid steps that ran their body — dense
    launches count every step, ragged launches only live-block steps —
    and ``[0, 1]`` the ring windows built (``tables.chained_step_counts``
    gives both for the dense launch).

    ``chunk_rows`` caps the rows per launch (SMEM chunking, a multiple of
    the h*w image; None sizes it from the table): the chain then runs as
    one launch per image-aligned chunk, outputs stacked along M.
    """
    blk = block
    bm = blk
    mb = -(-m // bm)
    mp = mb * bm
    # dtype: follow the lhs operands
    dtype = None
    for phase in phases:
        for br in phase:
            if br["src"][0] == "x" and br["src"][1]:
                dtype = br["src"][1][0].dtype
    if dtype is None:
        dtype = panels[0].dtype if panels else phases[0][0]["w"].dtype
    spec = _chain_static(phases, blk, bm, w)
    nph = len(phases)
    hw = h * w
    rows = _launch_rows(m, bm, _plan_tiles_chained(1, spec).shape,
                        chunk_rows, unit=hw, mrow_slots=nph, fixed=((2,),))
    if rows < m:
        # image-aligned chunks: the ring's border mask decodes rows
        # relative to the launch, so every chunk must start an image
        if rows % hw:
            raise ValueError(f"chained chunk of {rows} rows is not a "
                             f"multiple of the {hw}-row image")
        parts = []
        for s, r in _row_spans(m, rows):
            sub = [[dict(br, src=("x", [a[s:s + r] for a in br["src"][1]]))
                    if br["src"][0] == "x" else br for br in phase]
                   for phase in phases]
            parts.append(grouped_matmul_chained(
                sub, m=r, h=h, w=w, panels=[pa[s:s + r] for pa in panels],
                block=block, m_valid=_chunk_valid(m_valid, s, r),
                debug_steps=debug_steps, interpret=interpret, chunk_rows=r))
        outs = [p[0] if debug_steps else p for p in parts]
        stacked = [_stack_rows([o[p][:r] for o, (_, r)
                                in zip(outs, _row_spans(m, rows))],
                               mb * bm) for p in range(nph)]
        if debug_steps:
            return stacked, sum(p[1] for p in parts)
        return stacked

    # ---- pack (dynamic_update_slice only: the chained path must emit no
    # concatenate primitives — the traced launch counter counts them) ----
    flat = [br for phase in phases for br in phase]
    flat_spec = [bs for pspec in spec for bs in pspec]
    tx = sum(mb * bs[1] for bs in flat_spec if bs[0] == "x")
    tw = sum(len(_chain_ksteps(bs[0], bs[1])) * bs[2] for bs in flat_spec)
    nb = sum(bs[2] for bs in flat_spec)
    xstack = jnp.zeros((max(tx, 1), bm, blk), dtype)
    wstack = jnp.zeros((tw, blk, blk), dtype)
    bstack = jnp.zeros((nb, blk), dtype)
    xbase = wbase = bbase = 0
    for br, (tag, src, nbb, _rw) in zip(flat, flat_spec):
        ksteps = _chain_ksteps(tag, src)
        s = len(ksteps)
        if tag == "x":
            kbs = src
            bb = jnp.zeros((mb, kbs, bm, blk), dtype)
            off = 0
            for a in br["src"][1]:
                kbi = -(-a.shape[1] // blk)
                ap = jnp.pad(a, ((0, mp - a.shape[0]),
                                 (0, kbi * blk - a.shape[1])))
                t4 = ap.reshape(mb, bm, kbi, blk).transpose(0, 2, 1, 3)
                bb = jax.lax.dynamic_update_slice(
                    bb, t4.astype(dtype), (0, off, 0, 0))
                off += kbi
            xstack = jax.lax.dynamic_update_slice(
                xstack, bb.reshape(-1, bm, blk), (xbase, 0, 0))
            xbase += mb * kbs
        wp = jnp.pad(br["w"], ((0, 0), (0, nbb * blk - br["n"])))
        t4 = wp.reshape(s, blk, nbb, blk).transpose(0, 2, 1, 3)
        wstack = jax.lax.dynamic_update_slice(
            wstack, t4.reshape(-1, blk, blk).astype(dtype), (wbase, 0, 0))
        wbase += s * nbb
        bias = br.get("b")
        if bias is not None:
            bp = jnp.pad(bias, (0, nbb * blk - br["n"]))
            bstack = jax.lax.dynamic_update_slice(
                bstack, bp.reshape(nbb, blk).astype(dtype), (bbase, 0))
        bbase += nbb
    pads = []
    for pa in panels:
        pr, pc = pa.shape
        assert pc % blk == 0, pa.shape
        pads.append(jnp.pad(pa, ((0, mp - pr), (0, 0))) if pr < mp
                    else pa[:mp])
    # the ring: none unless a branch reads it, else every column read or
    # written
    has_x = any(bs[0] == "x" for bs in flat_spec)
    reads = [c for bs in flat_spec if bs[0] == "ring" for c in bs[1][1]]
    writes = [c for bs in flat_spec for c in bs[3]]
    assert reads or not writes, "a ring write with no ring read"
    nring = 1 + max(reads + writes) if reads else 0

    _count_launch("grouped_matmul_chained")
    tab = _device_table(_plan_tiles_chained, mb, spec)
    dims = _device_table(_chain_dims, h, w)
    for rec in _CHAIN_RECORDERS:
        rec["launches"] += 1
        for k, v in _chained_counts(mb, spec).items():
            rec[k] += v

    ragged = m_valid is not None
    if ragged:
        # one liveness slot per (phase, block) — same per-block counts in
        # every phase (all phases share m), laid out phase-major to match
        # the table's ch_mrow_row slots.  broadcast+reshape, never
        # concatenate: the chained pack path must stay concat-free.
        mrows = jnp.broadcast_to(_ragged_mrows(m_valid, mb, bm)[None, :],
                                 (nph, mb)).reshape(nph * mb)

        def _im(fn):
            return lambda t, tab, mrow, dims: fn(t, tab, dims)
    else:
        def _im(fn):
            return lambda t, tab, dims: fn(t, tab, dims)

    in_specs = [
        pl.BlockSpec((None, bm, blk),
                     _im(lambda t, tab, dims: (tab[CH_XT, t], 0, 0))),
        pl.BlockSpec((None, blk, blk),
                     _im(lambda t, tab, dims: (tab[CH_WT, t], 0, 0))),
        pl.BlockSpec(memory_space=pltpu.VMEM),
    ]
    ins = [xstack, wstack, bstack]
    scratch = [pltpu.VMEM((bm, blk), jnp.float32)]
    hwraps = 0
    if nring:
        # the border mask's coordinate operand; scratch: the ring, a
        # window per ring column, the (h, w) of the rows of the block the
        # windows hold, and the (phase, block) each window was built for.
        # A row's h + u // w lies at most ``hwraps`` image heights past
        # its image's.
        in_specs.append(pl.BlockSpec(memory_space=pltpu.VMEM))
        ins.append(_device_table(_chain_coords, w, bm, blk))
        scratch += [pltpu.VMEM((3, nring, bm, blk), dtype),
                    pltpu.VMEM((nring, 3 * bm, blk), dtype),
                    pltpu.VMEM((2, bm, blk), jnp.int32),
                    pltpu.SMEM((nring,), jnp.int32)]
        hwraps = (h - 1 + (w + bm - 2) // w) // h
    pstride = chained_panel_stride(spec)
    for pi, pa in enumerate(pads):
        row = CH_PCA if pi == 0 else CH_PCB
        in_specs.append(pl.BlockSpec(
            (bm, blk), _im(lambda t, tab, dims, row=row:
                           (tab[row, t] // pstride, tab[row, t] % pstride))))
        ins.append(pa)
    ncbs = [sum(bs[2] for bs in pspec) for pspec in spec]
    out_specs = [
        pl.BlockSpec((bm, blk),
                     _im(lambda t, tab, dims, ri=ch_out_i_row(p),
                         rj=ch_out_j_row(p): (tab[ri, t], tab[rj, t])))
        for p in range(nph)
    ]
    out_shape = [jax.ShapeDtypeStruct((mp, ncb * blk), dtype)
                 for ncb in ncbs]
    if debug_steps:
        out_specs.append(pl.BlockSpec(
            (1, 2), _im(lambda t, tab, dims: (0, 0))))
        out_shape.append(jax.ShapeDtypeStruct((1, 2), jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 if ragged else 2,
        grid=(tab.shape[1],),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    scalars = (tab, mrows, dims) if ragged else (tab, dims)
    outs = pl.pallas_call(
        functools.partial(_gmm_chained_kernel, nphases=nph,
                          npanels=len(pads), bm=bm, has_x=has_x,
                          nring=nring, hwraps=hwraps, ragged=ragged,
                          debug_steps=debug_steps),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(*scalars, *ins)
    if debug_steps:
        return list(outs[:nph]), outs[nph]
    return list(outs)


def _shift_spatial(seg2d, m, h, w, dh, dw):
    """Zero-padded spatial shift of a (rows>=m, C) activation (m = B*h*w):
    row r of the result is row r + dh*w + dw where (h+dh, w+dw) stays in
    bounds, else 0 — the reference for one ring tap."""
    b = m // (h * w)
    img = seg2d[:m].reshape(b, h, w, -1)
    # pad + slice, not .at[].set: the scatter lowering builds its index
    # vector with concatenates that the launch counter would see.
    pb_h, pa_h = max(-dh, 0), max(dh, 0)
    pb_w, pa_w = max(-dw, 0), max(dw, 0)
    pimg = jnp.pad(img, ((0, 0), (pb_h, pa_h), (pb_w, pa_w), (0, 0)))
    out = jax.lax.slice(pimg, (0, pa_h, pa_w, 0),
                        (b, pa_h + h, pa_w + w, pimg.shape[3]))
    return out.reshape(m, -1)


def grouped_matmul_chained_ref(phases, *, m: int, h: int, w: int,
                               panels=(), block: int = 128):
    """XLA oracle for ``grouped_matmul_chained`` — same padded panels (true
    rows/cols; padding rows are zeros here, garbage in the kernel)."""
    blk = block
    mb = -(-m // blk)
    mp = mb * blk
    # ring col -> (producer phase, producer panel col block), from the
    # branches' ring_write descriptors — the mapping the kernel realizes
    # through its VMEM ring slots
    ringmap: dict[int, tuple[int, int]] = {}
    for p, phase in enumerate(phases):
        cb = 0
        for br in phase:
            nbb = -(-br["n"] // blk)
            for j, rc in enumerate(br.get("ring_write") or ()):
                ringmap[rc] = (p, cb + j)
            cb += nbb
    outs = []
    for phase in phases:
        segs = []
        for br in phase:
            nbb = -(-br["n"] // blk)
            tag = br["src"][0]
            if tag == "x":
                parts = []
                for a in br["src"][1]:
                    kbi = -(-a.shape[1] // blk)
                    parts.append(jnp.pad(
                        a, ((0, 0), (0, kbi * blk - a.shape[1]))))
                lhs = jnp.concatenate(parts, axis=1) if len(parts) > 1 \
                    else parts[0]
            elif tag == "panel":
                lhs = jnp.concatenate(
                    [panels[pidx][:m, cb * blk:(cb + 1) * blk]
                     for pidx, cb in br["src"][1]], axis=1)
            else:
                _, kh, kw, rcs = br["src"]
                taps = []
                for dh in range(kh):
                    for dw in range(kw):
                        for rc in rcs:
                            pp, pcb = ringmap[rc]
                            seg = outs[pp][:m, pcb * blk:(pcb + 1) * blk]
                            taps.append(_shift_spatial(
                                seg, m, h, w, dh - kh // 2, dw - kw // 2))
                lhs = jnp.concatenate(taps, axis=1)
            bias = br.get("b")
            y = lhs.astype(jnp.float32) @ br["w"].astype(jnp.float32)
            if bias is not None:
                y = y + bias.astype(jnp.float32)
            y = jnp.maximum(y, 0.0).astype(lhs.dtype)
            segs.append(jnp.pad(y, ((0, mp - m), (0, nbb * blk - br["n"]))))
        outs.append(jnp.concatenate(segs, axis=1))
    return outs


# ---------------------------------------------------------------------------
# per-expert ragged grouped GEMM: the MoE expert engine
# ---------------------------------------------------------------------------
#
# PR 7's raggedness is ONE shared M tail mask (requests pack contiguously,
# every branch sees the same m_valid).  MoE needs each branch (expert) g to
# own its routed token count M_g: tokens pack into per-expert block-aligned
# segments of a single (MBS*bm, D) buffer, the grid flattens over the ragged
# per-expert M-block counts, and the scalar-prefetch machinery splits into
#
#   static table (``_plan_tiles_experts``)  — per-step tile slots, phase and
#       first/last flags, scratch panel index.  Depends only on (MBS, DB,
#       FB, gated): every routing outcome reuses the SAME device table.
#   dynamic vector (``_expert_block_meta``)  — per-M-block expert id,
#       valid-row count (the per-branch ``_ragged_mrows``), and
#       first/last-block-of-expert flags, computed from the TRACED per-
#       expert counts.  Weight index maps do arithmetic on it
#       (``eid[bi] * tiles_per_expert + rel``), so which expert's tiles a
#       block fetches is a runtime decision inside a static grid.
#
# The static grid bound is MBS = floor(n_slots/bm) + E (each expert wastes
# at most one partial block, and every expert keeps >= 1 block so zero-token
# experts still store their — zero — dW tiles).  Blocks past the last live
# one ("dead tail") get eid = E-1, valid 0, zero packed rows: their stores
# are zeroed by the valid mask and their dW contributions are zero, so the
# combined backward's cross-block dW accumulation runs through them safely.
#
# The epilogue fuses the whole expert chain: H = act(X@Wg) * (X@Wi) (or
# act(X@Wi) ungated) through a VMEM panel, Y = (H@Wo) * sw with the router's
# combine weight sw row-scaled in-kernel and the per-block valid mask
# zeroing the tail — ONE launch per MoE layer per direction.

_MOE_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}


def moe_block_m(n_slots: int, e: int) -> int:
    """Packed M-block rows for the experts launch: the largest power of two
    <= clamp(n_slots/E, 8, 128) — full 128-row MXU tiles once the uniform
    per-expert count supports them, down to the f32 sublane floor of 8 for
    tiny batches (where one partial block per expert is the whole grid)."""
    per = max(n_slots // max(e, 1), 1)
    bm = 8
    while bm * 2 <= min(per, 128):
        bm *= 2
    return bm


def moe_static_blocks(n_slots: int, e: int, bm: int) -> int:
    """Static M-block bound for the experts grid: sum_g ceil(c_g/bm) <=
    floor(sum_g c_g / bm) + E for any routing outcome with sum c_g <=
    n_slots, and the +E also funds the >=1 block every expert keeps."""
    return n_slots // bm + e


def _expert_block_meta(counts, mbs: int, bm: int):
    """(4, MBS) int32 dynamic prefetch: rows [expert id, valid rows,
    first-block-of-expert, last-block-of-expert] per static M-block, from
    the TRACED per-expert routed counts.  Zero-token experts keep one
    block (valid 0); dead tail blocks take eid E-1 with valid 0."""
    counts = jnp.asarray(counts, jnp.int32)
    e = counts.shape[0]
    blocks = jnp.maximum(-(-counts // bm), 1)
    cum = jnp.cumsum(blocks)
    bi = jnp.arange(mbs, dtype=jnp.int32)
    eid = jnp.clip(jnp.searchsorted(cum, bi, side="right"),
                   0, e - 1).astype(jnp.int32)
    start = cum - blocks                          # first block of expert
    rel = bi - start[eid]
    mrows = jnp.clip(counts[eid] - rel * bm, 0, bm)
    febl = (bi == start[eid]).astype(jnp.int32)
    nxt = jnp.concatenate([eid[1:], jnp.full((1,), -1, jnp.int32)])
    lebl = (nxt != eid).astype(jnp.int32)
    return jnp.stack([eid, mrows, febl, lebl])


def expert_row_offsets(counts, bm: int):
    """(E,) packed-row offset of each expert's segment — the per-branch
    M-row offsets the dispatch scatters against (block-aligned so segment
    starts coincide with M-block starts)."""
    counts = jnp.asarray(counts, jnp.int32)
    blocks = jnp.maximum(-(-counts // bm), 1)
    return (jnp.cumsum(blocks) - blocks) * bm


@functools.lru_cache(maxsize=512)
def _plan_tiles_experts(mbs: int, db: int, fb: int, gated: int):
    """Static offset table for the experts forward, (10, T) int32.

    Per M-block i the steps run H phase (j over F-blocks, which over
    {in[, gate]}, k over D-blocks; accumulate X@W into the f32 acc, close
    each (j, which) tile into the VMEM H panel) then Y phase (c over
    D-blocks, j over F-blocks; accumulate Hpanel@Wout, close with the
    sw-scale + per-block valid mask epilogue).  Rows:

      0 bi      M-block index (keys the dynamic eid/mrows/sw lookups)
      1 xt      packed-X tile slot (held at last H value through Y)
      2 whrel   H-weight tile rel index: which*DB*FB + k*FB + j
      3 worel   Wout tile rel index: j*DB + c (held at next-use during H)
      4 phase   0 = H-in step, 1 = H-gate step, 2 = Y step
      5 first   1 on the tile's first accumulation step (zero the acc)
      6 last    1 on the tile's last accumulation step (close the tile)
      7 hj      F-block index (H panel scratch slot)
      8 ot      Y output tile slot i*DB + c (next-write during H)
      9 rres    residual (preact) output tile slot i*FB + j (next-write)
    """
    nw = 1 + gated
    rows: list[list[int]] = [[] for _ in range(10)]
    for i in range(mbs):
        for j in range(fb):
            for wch in range(nw):
                for k in range(db):
                    rows[0].append(i)
                    rows[1].append(i * db + k)
                    rows[2].append(wch * db * fb + k * fb + j)
                    rows[3].append(0)
                    rows[4].append(wch)
                    rows[5].append(1 if k == 0 else 0)
                    rows[6].append(1 if k == db - 1 else 0)
                    rows[7].append(j)
                    rows[8].append(i * db)
                    rows[9].append(i * fb + j)
        for c in range(db):
            for j in range(fb):
                rows[0].append(i)
                rows[1].append(i * db + db - 1)
                rows[2].append(0)
                rows[3].append(j * db + c)
                rows[4].append(2)
                rows[5].append(1 if j == 0 else 0)
                rows[6].append(1 if j == fb - 1 else 0)
                rows[7].append(j)
                rows[8].append(i * db + c)
                rows[9].append((i + 1) * fb if i + 1 < mbs
                               else i * fb + fb - 1)
    return np.array(rows, np.int32)


def _gmm_experts_kernel(tab_ref, dyn_ref, x_ref, wh_ref, wo_ref, sw_ref,
                        *rest, activation: str, gated: bool, train: bool):
    nres = (2 if gated else 1) if train else 0
    y_ref = rest[0]
    res_refs = rest[1:1 + nres]
    acc_ref, hin_s, hpost_s = rest[1 + nres:]
    t = pl.program_id(0)
    phase = tab_ref[EX_PH, t]
    last = tab_ref[EX_LAST, t] == 1
    hj = tab_ref[EX_HJ, t]
    dt = y_ref.dtype
    act = _MOE_ACTS[activation]

    @pl.when(tab_ref[EX_FIRST, t] == 1)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(phase < 2)
    def _h_step():
        acc_ref[...] += jnp.dot(x_ref[...], wh_ref[...],
                                precision=mxu_precision(dt),
                                preferred_element_type=jnp.float32)

    @pl.when(phase == 2)
    def _y_step():
        acc_ref[...] += jnp.dot(hpost_s[hj].astype(dt), wo_ref[...],
                                precision=mxu_precision(dt),
                                preferred_element_type=jnp.float32)

    @pl.when((phase == 0) & last)
    def _close_in():
        pre = acc_ref[...]
        if gated:
            hin_s[hj] = pre
        else:
            # oracle order: act applied to the dtype-cast preact
            hpost_s[hj] = act(pre.astype(dt)).astype(jnp.float32)
        if train:
            res_refs[0][...] = pre.astype(dt)

    if gated:
        @pl.when((phase == 1) & last)
        def _close_gate():
            pre_g = acc_ref[...]
            pre_i = hin_s[hj]
            # oracle order: h = act(gate preact) * in preact, in dtype
            h = act(pre_g.astype(dt)) * pre_i.astype(dt)
            hpost_s[hj] = h.astype(jnp.float32)
            if train:
                res_refs[1][...] = pre_g.astype(dt)

    @pl.when((phase == 2) & last)
    def _close_y():
        valid = dyn_ref[1, tab_ref[EX_BI, t]]
        y = acc_ref[...].astype(dt) * sw_ref[...][:, None].astype(dt)
        ri = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        y_ref[...] = jnp.where(ri < valid, y, jnp.zeros_like(y))


def _expert_wstack(w, d0p: int, d1p: int):
    """(E, D0, D1) expert weights -> per-expert (D0p/128 * D1p/128, 128,
    128) tile stacks, concatenated expert-major."""
    e, d0, d1 = w.shape
    wq = jnp.pad(w, ((0, 0), (0, d0p - d0), (0, d1p - d1)))
    return jnp.concatenate([_tile_stack(wq[g], 128, 128) for g in range(e)])


def _pack_rows(a2d, bm: int, d_pad: int):
    aq = jnp.pad(a2d, ((0, 0), (0, d_pad - a2d.shape[1])))
    return _tile_stack(aq, bm, 128)


def _unpack_rows(tiles, mbs: int, bm: int, nb: int, d: int):
    return tiles.reshape(mbs, nb, bm, 128).transpose(0, 2, 1, 3) \
        .reshape(mbs * bm, nb * 128)[:, :d]


def grouped_matmul_experts(xp, swp, w_in, w_out, w_gate, counts, *,
                           activation: str = "silu", train: bool = False,
                           bm: int | None = None, interpret: bool = False):
    """ONE launch over E expert chains with per-expert ragged M.

    xp     (MBS*bm, D)  tokens packed into block-aligned per-expert
                        segments (``expert_row_offsets``), zero elsewhere
    swp    (MBS*bm,)    f32 router combine weight per packed row (0 pad)
    w_in   (E, D, F);  w_out (E, F, D);  w_gate (E, D, F) or None
    counts (E,) i32     routed token count per expert — traced: every
                        routing outcome shares this trace and the static
                        offset table; only the dynamic (4, MBS) prefetch
                        vector changes
    train  also return the (MBS*bm, F) in/gate preacts (the combined
           backward's residuals)

    Returns y (MBS*bm, D) = act-gated expert chain output, row-scaled by
    swp, exact zeros at/past each block's valid count.
    """
    e, d, f = w_in.shape
    gated = w_gate is not None
    n_rows = xp.shape[0]
    bm = moe_block_m(n_rows, e) if bm is None else bm
    assert n_rows % bm == 0, (n_rows, bm)
    mbs = n_rows // bm
    dp_, fp_ = _round_up(d, 128), _round_up(f, 128)
    db, fb = dp_ // 128, fp_ // 128
    dt = xp.dtype

    x_tiles = _pack_rows(xp, bm, dp_)
    whs = []
    for g in range(e):
        whs.append(_expert_wstack(w_in[g:g + 1], dp_, fp_))
        if gated:
            whs.append(_expert_wstack(w_gate[g:g + 1], dp_, fp_))
    wh = jnp.concatenate(whs)
    wo = _expert_wstack(w_out, fp_, dp_)
    sw2 = jnp.asarray(swp, jnp.float32).reshape(mbs, bm)

    tab = _device_table(_plan_tiles_experts, mbs, db, fb, int(gated))
    dyn = _expert_block_meta(counts, mbs, bm)
    whpe, wope = (1 + int(gated)) * db * fb, fb * db

    in_specs = [
        pl.BlockSpec((None, bm, 128), lambda t, tab, dyn: (tab[EX_XT, t], 0, 0)),
        pl.BlockSpec((None, 128, 128),
                     lambda t, tab, dyn, s=whpe:
                     (dyn[0, tab[EX_BI, t]] * s + tab[EX_WH, t], 0, 0)),
        pl.BlockSpec((None, 128, 128),
                     lambda t, tab, dyn, s=wope:
                     (dyn[0, tab[EX_BI, t]] * s + tab[EX_WO, t], 0, 0)),
        pl.BlockSpec((None, bm), lambda t, tab, dyn: (tab[EX_BI, t], 0)),
    ]
    out_shape = [jax.ShapeDtypeStruct((mbs * db, bm, 128), dt)]
    out_specs = [pl.BlockSpec((None, bm, 128),
                              lambda t, tab, dyn: (tab[EX_OT, t], 0, 0))]
    if train:
        for _ in range(2 if gated else 1):
            out_shape.append(jax.ShapeDtypeStruct((mbs * fb, bm, 128), dt))
            out_specs.append(pl.BlockSpec(
                (None, bm, 128), lambda t, tab, dyn: (tab[EX_RES, t], 0, 0)))

    nw = 1 + int(gated)
    grid = (mbs * (nw * fb * db + db * fb),)
    fn = pl.pallas_call(
        functools.partial(_gmm_experts_kernel, activation=activation,
                          gated=gated, train=train),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((bm, 128), jnp.float32),
                            pltpu.VMEM((fb, bm, 128), jnp.float32),
                            pltpu.VMEM((fb, bm, 128), jnp.float32)]),
        out_shape=out_shape, interpret=interpret)
    _count_launch("grouped_matmul_experts")
    outs = fn(tab, dyn, x_tiles, wh, wo, sw2)
    y = _unpack_rows(outs[0], mbs, bm, db, d)
    if not train:
        return y
    res = [_unpack_rows(o, mbs, bm, fb, f) for o in outs[1:]]
    return (y, res[0], res[1] if gated else None)


@functools.lru_cache(maxsize=512)
def _plan_tiles_experts_bwd(mbs: int, db: int, fb: int, gated: int):
    """Static table for the ONE combined experts backward, (13, T) int32.

    Per M-block i (expert e = eid[i]), four phase types in order:
      A  dHpost_j = sum_c dYs(i,c) @ WoutT(e; c,j); at the last c derive
         the dHin/dGate cotangent panels and Hpost from the saved preacts
      B  dWout_acc[j*DB+c] += Hpost_j^T @ dYs(i,c) — zeroed on the
         DYNAMIC first-block-of-expert flag, stored on last-block (output
         slot eid*FB*DB + j*DB + c via index-map arithmetic): the dW
         accumulation crosses an expert's consecutive M-blocks
      C  dX(i,c) = sum_{which,j} dPanel[which*FB+j] @ WhT(e; which,j,c)
      D  dWh_acc[which*DB*FB + c*FB + j] += X(i,c)^T @ dPanel[which*FB+j]
         — same dynamic-flag accumulation as B

    Rows: 0 bi, 1 dyt, 2 xt, 3 whtrel, 4 wotrel, 5 rrest (saved-preact
    tile slot i*FB + j), 6 phase (0=A 1=B 2=C 3=D), 7 first, 8 last,
    9 pj (cotangent/Hpost panel slot: j in A/B, which*FB + j in C/D),
    10 dx out slot, 11 dWh rel (scratch slot AND output rel), 12 dWout
    rel (scratch slot AND output rel).  Unused operand rows hold a valid
    recent/next index so the block revisit semantics skip the refetch."""
    nw = 1 + gated
    rows: list[list[int]] = [[] for _ in range(13)]

    def emit(i, dyt, xt, whtrel, wotrel, rrest, phase, first, last, pj,
             dxot, dwhrel, dworel):
        vals = (i, dyt, xt, whtrel, wotrel, rrest, phase, first, last, pj,
                dxot, dwhrel, dworel)
        for r, v in zip(rows, vals):
            r.append(v)

    wot_hold = db * fb - 1
    for i in range(mbs):
        for j in range(fb):                    # A
            for c in range(db):
                emit(i, i * db + c, i * db, 0, c * fb + j, i * fb + j,
                     0, 1 if c == 0 else 0, 1 if c == db - 1 else 0,
                     j, i * db, 0, 0)
        for j in range(fb):                    # B
            for c in range(db):
                emit(i, i * db + c, i * db, 0, wot_hold, i * fb + j,
                     1, 0, 0, j, i * db, 0, j * db + c)
        for c in range(db):                    # C
            for wch in range(nw):
                for j in range(fb):
                    emit(i, i * db + db - 1, i * db,
                         wch * fb * db + j * db + c, wot_hold,
                         i * fb + fb - 1, 2,
                         1 if (wch == 0 and j == 0) else 0,
                         1 if (wch == nw - 1 and j == fb - 1) else 0,
                         wch * fb + j, i * db + c, 0, wot_hold)
        for wch in range(nw):                  # D
            for c in range(db):
                for j in range(fb):
                    emit(i, i * db + db - 1, i * db + c,
                         wch * fb * db, wot_hold, i * fb + fb - 1, 3,
                         0, 0, wch * fb + j, i * db + db - 1,
                         wch * db * fb + c * fb + j, wot_hold)
    return np.array(rows, np.int32)


def _gmm_experts_bwd_kernel(tab_ref, dyn_ref, x_ref, dy_ref, wht_ref,
                            wot_ref, hin_ref, *rest, activation: str,
                            gated: bool):
    if gated:
        gate_ref, *rest = rest
    dx_ref, dwh_ref, dwo_ref = rest[:3]
    acc_ref, dpan_s, hpost_s, dwo_acc, dwh_acc = rest[3:]
    t = pl.program_id(0)
    bi = tab_ref[EB_BI, t]
    phase = tab_ref[EB_PH, t]
    last = tab_ref[EB_LAST, t] == 1
    pj = tab_ref[EB_PJ, t]
    febl = dyn_ref[2, bi] == 1
    lebl = dyn_ref[3, bi] == 1
    dt = dx_ref.dtype
    act = _MOE_ACTS[activation]
    cdims = (((0,), (0,)), ((), ()))           # tile^T @ tile

    @pl.when(tab_ref[EB_FIRST, t] == 1)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(phase == 0)
    def _a_step():
        acc_ref[...] += jnp.dot(dy_ref[...], wot_ref[...],
                                precision=mxu_precision(dt),
                                preferred_element_type=jnp.float32)

    fb = dpan_s.shape[0] // (2 if gated else 1)

    @pl.when((phase == 0) & last)
    def _a_close():
        dh = acc_ref[...]
        pre_i = hin_ref[...].astype(jnp.float32)
        if gated:
            pre_g = gate_ref[...].astype(jnp.float32)
            actg, vjp_g = jax.vjp(act, pre_g)
            hpost_s[pj] = actg * pre_i
            dpan_s[pj] = dh * actg
            dpan_s[fb + pj] = vjp_g(dh * pre_i)[0]
        else:
            acti, vjp_i = jax.vjp(act, pre_i)
            hpost_s[pj] = acti
            dpan_s[pj] = vjp_i(dh)[0]

    @pl.when(phase == 1)
    def _b_step():
        slot = tab_ref[EB_DWO, t]

        @pl.when(febl)
        def _zero_b():
            dwo_acc[slot] = jnp.zeros_like(dwo_acc[slot])

        dwo_acc[slot] += jax.lax.dot_general(
            hpost_s[pj].astype(dt), dy_ref[...], cdims,
            precision=mxu_precision(dt),
            preferred_element_type=jnp.float32)

        @pl.when(lebl)
        def _store_b():
            dwo_ref[...] = dwo_acc[slot]

    @pl.when(phase == 2)
    def _c_step():
        acc_ref[...] += jnp.dot(dpan_s[pj].astype(dt), wht_ref[...],
                                precision=mxu_precision(dt),
                                preferred_element_type=jnp.float32)

    @pl.when((phase == 2) & last)
    def _c_close():
        valid = dyn_ref[1, bi]
        dx = acc_ref[...].astype(dt)
        ri = jax.lax.broadcasted_iota(jnp.int32, dx.shape, 0)
        dx_ref[...] = jnp.where(ri < valid, dx, jnp.zeros_like(dx))

    @pl.when(phase == 3)
    def _d_step():
        slot = tab_ref[EB_DWH, t]

        @pl.when(febl)
        def _zero_d():
            dwh_acc[slot] = jnp.zeros_like(dwh_acc[slot])

        dwh_acc[slot] += jax.lax.dot_general(
            x_ref[...], dpan_s[pj].astype(dt), cdims,
            precision=mxu_precision(dt),
            preferred_element_type=jnp.float32)

        @pl.when(lebl)
        def _store_d():
            dwh_ref[...] = dwh_acc[slot]


def _expert_wstack_t(w, d0p: int, d1p: int):
    """Transposed per-expert tile stacks: (E, D0, D1) -> tiles of W^T,
    expert-major, rel index r*D0B + c over the (D1p, D0p) transpose."""
    e = w.shape[0]
    wq = jnp.pad(w, ((0, 0), (0, d0p - w.shape[1]), (0, d1p - w.shape[2])))
    return jnp.concatenate(
        [_tile_stack(wq[g].T, 128, 128) for g in range(e)])


def grouped_matmul_experts_bwd(xp, dyp, w_in, w_out, w_gate, hinp, gatep,
                               counts, *, activation: str = "silu",
                               bm: int, interpret: bool = False):
    """ONE combined backward launch (dX + dW_in/dW_gate/dW_out) mirroring
    ``grouped_matmul_bwd``, over the per-expert ragged packing.

    ``dyp`` is the packed output cotangent with the router combine weight
    already folded in (dYs = dY * sw — the same cotangent-fold idiom as
    the ReLU mask); ``hinp``/``gatep`` are the forward's saved preacts.
    dW tiles accumulate in VMEM across each expert's consecutive M-blocks
    (zeroed/stored on the DYNAMIC first/last-block-of-expert prefetch
    flags) and come back f32.  There are no expert biases (``moe_init``),
    so the db third of the usual triple is vacuous."""
    e, d, f = w_in.shape
    gated = w_gate is not None
    n_rows = xp.shape[0]
    assert n_rows % bm == 0, (n_rows, bm)
    mbs = n_rows // bm
    dp_, fp_ = _round_up(d, 128), _round_up(f, 128)
    db, fb = dp_ // 128, fp_ // 128
    dt = xp.dtype
    nw = 1 + int(gated)

    x_tiles = _pack_rows(xp, bm, dp_)
    dy_tiles = _pack_rows(dyp.astype(dt), bm, dp_)
    hin_tiles = _pack_rows(hinp, bm, fp_)
    whts = []
    for g in range(e):
        whts.append(_expert_wstack_t(w_in[g:g + 1], dp_, fp_))
        if gated:
            whts.append(_expert_wstack_t(w_gate[g:g + 1], dp_, fp_))
    # per-expert layout [in tiles, gate tiles]: rel = which*FB*DB + j*DB+c
    wht = jnp.concatenate(whts)
    wot = _expert_wstack_t(w_out, fp_, dp_)     # W_out^T tiles: c*FB + j

    tab = _device_table(_plan_tiles_experts_bwd, mbs, db, fb, int(gated))
    dyn = _expert_block_meta(counts, mbs, bm)
    whtpe, wope = nw * fb * db, fb * db

    tile_ix = lambda row: (lambda t, tab, dyn, r=row: (tab[r, t], 0, 0))
    exp_ix = lambda row, s: (lambda t, tab, dyn, r=row, s=s:
                             (dyn[0, tab[EB_BI, t]] * s + tab[r, t], 0, 0))
    in_specs = [
        pl.BlockSpec((None, bm, 128), tile_ix(EB_XT)),       # X
        pl.BlockSpec((None, bm, 128), tile_ix(EB_DYT)),       # dYs
        pl.BlockSpec((None, 128, 128), exp_ix(EB_WHT, whtpe)),  # Wh^T
        pl.BlockSpec((None, 128, 128), exp_ix(EB_WOT, wope)),   # Wout^T
        pl.BlockSpec((None, bm, 128), tile_ix(EB_RES)),       # hin preact
    ]
    ins = [x_tiles, dy_tiles, wht, wot, hin_tiles]
    if gated:
        in_specs.append(pl.BlockSpec((None, bm, 128), tile_ix(EB_RES)))
        ins.append(_pack_rows(gatep, bm, fp_))

    out_shape = [
        jax.ShapeDtypeStruct((mbs * db, bm, 128), dt),           # dX
        jax.ShapeDtypeStruct((e * whtpe, 128, 128), jnp.float32),  # dWh
        jax.ShapeDtypeStruct((e * wope, 128, 128), jnp.float32),  # dWout
    ]
    out_specs = [
        pl.BlockSpec((None, bm, 128), tile_ix(EB_DXOT)),
        pl.BlockSpec((None, 128, 128), exp_ix(EB_DWH, whtpe)),
        pl.BlockSpec((None, 128, 128), exp_ix(EB_DWO, wope)),
    ]
    grid = (mbs * fb * db * (2 + 2 * nw),)
    fn = pl.pallas_call(
        functools.partial(_gmm_experts_bwd_kernel, activation=activation,
                          gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid,
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((bm, 128), jnp.float32),
                            pltpu.VMEM((nw * fb, bm, 128), jnp.float32),
                            pltpu.VMEM((fb, bm, 128), jnp.float32),
                            pltpu.VMEM((wope, 128, 128), jnp.float32),
                            pltpu.VMEM((whtpe, 128, 128), jnp.float32)]),
        out_shape=out_shape, interpret=interpret)
    _count_launch("grouped_matmul_experts_bwd")
    dx_t, dwh_t, dwo_t = fn(tab, dyn, *ins)

    dx = _unpack_rows(dx_t, mbs, bm, db, d)

    def _unstack_w(tiles, d0b, d1b, d0, d1):
        w = tiles.reshape(d0b, d1b, 128, 128).transpose(0, 2, 1, 3) \
            .reshape(d0b * 128, d1b * 128)
        return w[:d0, :d1]

    dwin = jnp.stack([_unstack_w(dwh_t[g * whtpe:g * whtpe + db * fb],
                                 db, fb, d, f) for g in range(e)])
    dwgate = None
    if gated:
        dwgate = jnp.stack(
            [_unstack_w(dwh_t[g * whtpe + db * fb:(g + 1) * whtpe],
                        db, fb, d, f) for g in range(e)])
    dwout = jnp.stack([_unstack_w(dwo_t[g * wope:(g + 1) * wope],
                                  fb, db, f, d) for g in range(e)])
    return dx, dwin, dwgate, dwout


def grouped_matmul_experts_ref(xp, swp, w_in, w_out, w_gate, counts, *,
                               activation: str = "silu", bm: int):
    """Per-expert XLA oracle on the packed layout: plain dense dots per
    expert (the same single-k-block f32 accumulation the kernel does for
    D, F <= 128), rows selected by the segment layout, sw row-scale, and
    exact zeros outside every expert's valid segment."""
    e, d, f = w_in.shape
    n_rows = xp.shape[0]
    act = _MOE_ACTS[activation]
    dt = xp.dtype
    offs = expert_row_offsets(counts, bm)
    counts = jnp.asarray(counts, jnp.int32)
    r = jnp.arange(n_rows)[:, None]
    y = jnp.zeros((n_rows, d), dt)
    for g in range(e):
        hin = (xp @ w_in[g])
        if w_gate is not None:
            h = act((xp @ w_gate[g]).astype(dt)) * hin.astype(dt)
        else:
            h = act(hin.astype(dt))
        yg = (h @ w_out[g]).astype(dt) * swp[:, None].astype(dt)
        seg = (r >= offs[g]) & (r < offs[g] + counts[g])
        y = jnp.where(seg, yg, y)
    return y


def grouped_matmul_experts_flops(n_slots: int, e: int, d: int, f: int, *,
                                 gated: bool, bm: int) -> int:
    """FLOPs of the static experts grid — scales with the routed budget
    n_slots plus at most one partial block per expert, NOT E*capacity."""
    mbs = moe_static_blocks(n_slots, e, bm)
    return 2 * mbs * bm * _round_up(d, 128) * _round_up(f, 128) \
        * (2 + int(gated))
