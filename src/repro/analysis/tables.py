"""planlint table schemas — named rows and static checkers for every
scalar-prefetch offset-table family the grouped kernels consume.

This module is the single source of truth for the table layouts: the
kernels in ``kernels/grouped_matmul.py`` import the ``*_ROWS`` row
constants below (no more magic ``tab[6, t]`` literals), and the verifier
in ``analysis.verify_plan`` replays every ``_plan_tiles*`` output
against the declarative checkers here — so kernel and verifier can
never disagree about what a row means.

Eight table families, one checker each:

  plain / concat   ``_plan_tiles`` / ``_plan_tiles_concat`` — (7, T)
                   branch-GEMM steps; the concat variant walks M-blocks
                   outermost and writes into one fused N-concatenated
                   output.                      -> ``check_plain``
  pooled           ``_plan_tiles_pooled`` — (11, T): in-kernel pool-tap
                   accumulation steps interleaved with GEMM steps that
                   read the pooled scratch.     -> ``check_pooled``
  dW               ``_plan_tiles_dw`` — (7, T): X^T @ dY accumulation
                   over M-blocks.               -> ``check_dw``
  backward 2-phase ``_plan_tiles_bwd`` — (8, T): every dX tile then
                   every dW tile in ONE launch. -> ``check_bwd``
  chained          ``_plan_tiles_chained`` — (_CH_ROWS + 2*P + 1, T):
                   the lag-1 wave schedule plus the trailing per-phase
                   valid-row metadata row (``ch_mrow_row`` — the slot a
                   ragged-M launch's prefetched mrow vector is read at,
                   so masked waves skip dead M-blocks).
                                                -> ``check_chained``
  experts fwd      ``_plan_tiles_experts`` — (10, T) per-expert-ragged
                   H then Y phases.             -> ``check_experts``
  experts bwd      ``_plan_tiles_experts_bwd`` — (13, T) A/B/C/D
                   phases (dHpost, dWout, dX, dWh).
                                                -> ``check_experts_bwd``

Every checker is pure numpy (this module imports NOTHING from the rest
of the package — the kernels import it, so it must stay leaf-level) and
returns a list of ``(kind, message)`` findings with ``kind`` in
``{"schema", "bounds"}``; an empty list means the table satisfies its
schema.  The checkers re-derive each column from a few anchor rows
(N-offset, M-block index, phase) and compare every other row, then
assert run discipline (first/last flags open and close accumulator runs
of exactly the right length) and coverage (every output tile produced
exactly once) — so mutating ANY single entry fires a finding: anchors
break the derived expectations, derived rows break the comparison,
flags break the run structure.
"""
from __future__ import annotations

import numpy as np

# ---------------------------------------------------------------------------
# row-name constants (imported by kernels/grouped_matmul.py)

#: plain + concat family — ``_plan_tiles`` / ``_plan_tiles_concat``
GM_XT, GM_WT, GM_BJ, GM_FIRST, GM_LAST, GM_OT, GM_MI = range(7)
GM_ROWS = 7

#: pooled family — ``_plan_tiles_pooled`` (rows 0-5 match plain; 6-10
#: add pool-step discipline and the ragged M-block row)
(GP_XT, GP_WT, GP_BJ, GP_FIRST, GP_LAST, GP_OT,
 GP_POOL, GP_PFIRST, GP_PS, GP_UPOOL, GP_MI) = range(11)
GP_ROWS = 11

#: dW family — ``_plan_tiles_dw``
DW_XT, DW_DYT, DW_FIRST, DW_LAST, DW_OT, DW_BJ, DW_DODB = range(7)
DW_ROWS = 7

#: combined-backward family — ``_plan_tiles_bwd`` (dx phase, dw phase)
BW_DYT, BW_ABT, BW_FIRST, BW_LAST, BW_OT, BW_DODB, BW_DW, BW_BJ = range(8)
BW_ROWS = 8

#: chained family — ``_plan_tiles_chained``; out-row helpers below.
#: ``CH_SRC`` is the step's lhs source (0 x tile, 2 ring window, 3 / 4
#: panel A / B).  ``CH_XT`` is an x-tile slot and ``CH_PCA`` / ``CH_PCB``
#: a panel tile, ``block * chained_panel_stride(spec) + col block``; a
#: step that reads no such operand carries the index of the next step
#: that does (the last one's past it), so the pipeline never fetches a
#: tile no step reads.
(CH_I, CH_XT, CH_WT, CH_BJ, CH_FIRST, CH_LAST, CH_PH, CH_SRC,
 CH_PCA, CH_PCB, CH_RC, CH_DELTA, CH_DH, CH_DW, CH_RWC) = range(15)
CH_ROWS = 15

#: experts forward family — ``_plan_tiles_experts``
(EX_BI, EX_XT, EX_WH, EX_WO, EX_PH, EX_FIRST, EX_LAST,
 EX_HJ, EX_OT, EX_RES) = range(10)
EX_ROWS = 10

#: experts backward family — ``_plan_tiles_experts_bwd``
(EB_BI, EB_DYT, EB_XT, EB_WHT, EB_WOT, EB_RES, EB_PH, EB_FIRST,
 EB_LAST, EB_PJ, EB_DXOT, EB_DWH, EB_DWO) = range(13)
EB_ROWS = 13


def ch_out_i_row(p: int) -> int:
    """Stability-backfilled output M-block row for chained phase ``p``."""
    return CH_ROWS + 2 * p


def ch_out_j_row(p: int) -> int:
    """Stability-backfilled output column row for chained phase ``p``."""
    return CH_ROWS + 2 * p + 1


def ch_mrow_row(nph: int) -> int:
    """Per-phase valid-row metadata row of a chained table (the LAST
    row, after all ``nph`` phases' output rows): step t holds
    ``phase * m_blocks + block`` — the slot of the prefetched per-phase
    mrow vector a ragged-M chained launch reads its liveness from.  A
    block with ``mrow == 0`` is entirely past ``m_valid`` and the wave
    becomes a no-op guard (GEMM/ring/pool steps never execute)."""
    return CH_ROWS + 2 * nph


def chained_panel_stride(spec) -> int:
    """Column blocks per panel row-block in a chained table's panel tile
    index: one more than the widest column block any panel branch of
    ``spec`` reads."""
    return 1 + max((cb for phase in spec for tag, src, _nbb, _rw in phase
                    if tag == "panel" for _p, cb in src), default=0)


def chained_step_counts(tab, nph: int) -> dict:
    """What one chained launch does, read off its offset table: grid
    steps by lhs source (``x``, ``ring``, ``panel``) and ``window_builds``,
    the ring windows the kernel assembles.  The kernel builds ring column
    ``rc``'s window on the first ring step of a (phase, block) that reads
    ``rc``, and every later tap of that (phase, block) reuses it.  Counts
    the dense launch: a ragged one skips its dead blocks' steps."""
    tab = np.asarray(tab)
    src = tab[CH_SRC]
    built: dict[int, int] = {}
    builds = 0
    for t in np.nonzero(src == 2)[0]:
        rc, key = int(tab[CH_RC, t]), int(tab[ch_mrow_row(nph), t])
        if built.get(rc) != key:
            built[rc] = key
            builds += 1
    return {"x": int((src == 0).sum()), "ring": int((src == 2).sum()),
            "panel": int((src >= 3).sum()), "window_builds": builds}


def smem_bytes(shape) -> int:
    """Bytes one int32 scalar-prefetch operand of ``shape`` occupies in
    TPU SMEM, padding included, as the v5e compiler lays it out (read off
    its allocation reports): a (R, T) table pads T to a multiple of 128
    words and R to 1, 2, 4 or a multiple of 8; a vector pads to a
    multiple of 1024 words."""
    if len(shape) == 1:
        return -(-shape[0] // 1024) * 1024 * 4
    r, c = shape
    rp = r if r <= 2 else 4 if r <= 4 else -(-r // 8) * 8
    return rp * (-(-c // 128) * 128) * 4


# ---------------------------------------------------------------------------
# shared helpers

def _runs(first, last, out, fam):
    """Split the step sequence into accumulator runs delimited by the
    first/last flags.  Returns a list of inclusive ``(lo, hi)`` spans,
    or ``None`` (with a finding appended) if the flags do not form a
    well-nested sequence of runs."""
    runs, open_ = [], None
    for t in range(first.shape[0]):
        f, l = int(first[t]), int(last[t])
        if f and open_ is not None:
            out.append(("schema",
                        f"{fam}: first flag at step {t} inside an open run"))
            return None
        if not f and open_ is None:
            out.append(("schema",
                        f"{fam}: step {t} belongs to no accumulator run"))
            return None
        if f:
            open_ = t
        if l:
            runs.append((open_, t))
            open_ = None
    if open_ is not None:
        out.append(("schema", f"{fam}: run opened at step {open_} "
                              "never sees a last flag"))
        return None
    return runs


def _group_of(base, v):
    """Index of the group whose [base[g], base[g+1]) span contains ``v``,
    or -1 if out of range.  ``base`` is a cumulative-offset array with a
    trailing total."""
    if v < 0 or v >= base[-1]:
        return -1
    return int(np.searchsorted(base, v, side="right") - 1)


def _check_exp(out, fam, tab, t, exp):
    """Compare every (row, expected) pair against column ``t``."""
    ok = True
    for row, want in exp.items():
        got = int(tab[row, t])
        if got != int(want):
            out.append(("schema", f"{fam}: row {row} at step {t} is "
                                  f"{got}, want {want}"))
            ok = False
    return ok


# ---------------------------------------------------------------------------
# plain / concat

def check_plain(tab, m_blocks, kbs, nbs, *, concat=False):
    """Validate a ``_plan_tiles`` (or ``_plan_tiles_concat``) table
    against the branch geometry: ``kbs[g]``/``nbs[g]`` are the K/N block
    counts of group ``g``, all groups share ``m_blocks`` M-blocks."""
    out = []
    fam = "concat" if concat else "plain"
    tab = np.asarray(tab)
    kbs, nbs = tuple(int(k) for k in kbs), tuple(int(n) for n in nbs)
    G = len(kbs)
    if tab.ndim != 2 or tab.shape[0] != GM_ROWS:
        out.append(("schema", f"{fam}: expected ({GM_ROWS}, T) table, "
                              f"got shape {tab.shape}"))
        return out
    T = m_blocks * sum(k * n for k, n in zip(kbs, nbs))
    if tab.shape[1] != T:
        out.append(("schema", f"{fam}: expected {T} steps, "
                              f"got {tab.shape[1]}"))
        return out
    cb = np.concatenate([[0], np.cumsum(nbs)])
    xb = np.concatenate([[0], np.cumsum([m_blocks * k for k in kbs])])
    wb = np.concatenate([[0], np.cumsum([k * n for k, n in zip(kbs, nbs)])])
    ob = np.concatenate([[0], np.cumsum([m_blocks * n for n in nbs])])
    ncbt = int(cb[-1])
    for row, nm in ((GM_FIRST, "first"), (GM_LAST, "last")):
        if not np.isin(tab[row], (0, 1)).all():
            out.append(("schema", f"{fam}: {nm}-flag row is not 0/1"))
            return out
    runs = _runs(tab[GM_FIRST], tab[GM_LAST], out, fam)
    if runs is None:
        return out
    seen = set()
    for lo, hi in runs:
        bj = int(tab[GM_BJ, lo])
        g = _group_of(cb, bj)
        if g < 0:
            out.append(("bounds", f"{fam}: N-offset {bj} at step {lo} "
                                  f"outside [0, {ncbt})"))
            continue
        j = bj - int(cb[g])
        i = int(tab[GM_MI, lo])
        if not 0 <= i < m_blocks:
            out.append(("bounds", f"{fam}: M-block {i} at step {lo} "
                                  f"outside [0, {m_blocks})"))
            continue
        nkb, npb = kbs[g], nbs[g]
        if hi - lo + 1 != nkb:
            out.append(("schema", f"{fam}: run at step {lo} has "
                                  f"{hi - lo + 1} k-steps, want {nkb}"))
            continue
        ot = (i * ncbt + int(cb[g]) + j) if concat \
            else (int(ob[g]) + i * npb + j)
        for kk, t in enumerate(range(lo, hi + 1)):
            _check_exp(out, fam, tab, t, {
                GM_XT: int(xb[g]) + i * nkb + kk,
                GM_WT: int(wb[g]) + kk * npb + j,
                GM_BJ: bj,
                GM_FIRST: int(kk == 0),
                GM_LAST: int(kk == nkb - 1),
                GM_OT: ot,
                GM_MI: i,
            })
        key = (g, i, j)
        if key in seen:
            out.append(("schema", f"{fam}: output tile {key} produced "
                                  "by two runs"))
        seen.add(key)
    want = m_blocks * sum(nbs)
    if len(seen) != want:
        out.append(("schema", f"{fam}: {len(seen)} distinct output tiles "
                              f"produced, want {want}"))
    return out


# ---------------------------------------------------------------------------
# pooled

def check_pooled(tab, m_blocks, kbs, nbs, taps, concat):
    """Validate a ``_plan_tiles_pooled`` table: ``taps[g] > 1`` marks a
    pooled group whose X tiles arrive pre-expanded ``taps[g]``-fold and
    are reduced into the pool scratch (slot = k-block) before the GEMM
    steps read them back (``GP_UPOOL``).  A sequential walk checks the
    scratch-slot ownership discipline on top of the per-step schema."""
    out = []
    fam = "pooled"
    tab = np.asarray(tab)
    kbs = tuple(int(k) for k in kbs)
    nbs = tuple(int(n) for n in nbs)
    taps = tuple(int(p) for p in taps)
    G = len(kbs)
    if tab.ndim != 2 or tab.shape[0] != GP_ROWS:
        out.append(("schema", f"{fam}: expected ({GP_ROWS}, T) table, "
                              f"got shape {tab.shape}"))
        return out
    T = m_blocks * sum(k * (tp if tp > 1 else 0) + k * n
                       for k, n, tp in zip(kbs, nbs, taps))
    if tab.shape[1] != T:
        out.append(("schema", f"{fam}: expected {T} steps, "
                              f"got {tab.shape[1]}"))
        return out
    xb = np.concatenate(
        [[0], np.cumsum([m_blocks * k * tp for k, tp in zip(kbs, taps)])])
    wb = np.concatenate([[0], np.cumsum([k * n for k, n in zip(kbs, nbs)])])
    ob = np.concatenate([[0], np.cumsum([m_blocks * n for n in nbs])])
    cb = np.concatenate([[0], np.cumsum(nbs)])
    ncbt = int(cb[-1])
    nkb_pool = max([k for k, tp in zip(kbs, taps) if tp > 1], default=1)

    owner = {}            # pool-scratch slot -> [x-tile base, taps done]
    open_tile = None      # the single (bm, bn) accumulator's owner
    next_kk = {}          # (g, i, j) -> next expected k-step
    seen = set()
    for t in range(T):
        pool = int(tab[GP_POOL, t])
        if pool not in (0, 1):
            out.append(("schema", f"{fam}: pool flag at step {t} not 0/1"))
            continue
        if pool:
            xt = int(tab[GP_XT, t])
            g = _group_of(xb, xt)
            if g < 0:
                out.append(("bounds", f"{fam}: pool X tile {xt} at step "
                                      f"{t} outside [0, {int(xb[-1])})"))
                continue
            tp, nkb, npb = taps[g], kbs[g], nbs[g]
            if tp <= 1:
                out.append(("schema", f"{fam}: pool step {t} reads the "
                                      f"unpooled group {g}"))
                continue
            rel = xt - int(xb[g])
            tap, idx = rel % tp, rel // tp
            i, kk = idx // nkb, idx % nkb
            first_ot = (i * ncbt + int(cb[g])) if concat \
                else (int(ob[g]) + i * npb)
            _check_exp(out, fam, tab, t, {
                GP_WT: int(wb[g]), GP_BJ: int(cb[g]), GP_FIRST: 0,
                GP_LAST: 0, GP_OT: first_ot, GP_PFIRST: int(tap == 0),
                GP_PS: kk, GP_UPOOL: 0, GP_MI: i,
            })
            ps = int(tab[GP_PS, t])
            if not 0 <= ps < nkb_pool:
                out.append(("bounds", f"{fam}: pool slot {ps} at step {t} "
                                      f"outside [0, {nkb_pool})"))
                continue
            if tap == 0:
                owner[ps] = [xt, 1]
            else:
                st = owner.get(ps)
                if st is None or xt != st[0] + st[1]:
                    out.append(("schema", f"{fam}: pool tap at step {t} "
                                          f"out of sequence for slot {ps}"))
                else:
                    st[1] += 1
        else:
            bj = int(tab[GP_BJ, t])
            g = _group_of(cb, bj)
            if g < 0:
                out.append(("bounds", f"{fam}: N-offset {bj} at step {t} "
                                      f"outside [0, {ncbt})"))
                continue
            j = bj - int(cb[g])
            i = int(tab[GP_MI, t])
            if not 0 <= i < m_blocks:
                out.append(("bounds", f"{fam}: M-block {i} at step {t} "
                                      f"outside [0, {m_blocks})"))
                continue
            tp, nkb, npb = taps[g], kbs[g], nbs[g]
            xt = int(tab[GP_XT, t])
            rel = xt - int(xb[g])
            if not (0 <= rel < m_blocks * nkb * tp and rel % tp == 0
                    and rel // tp // nkb == i):
                out.append(("schema", f"{fam}: GEMM X tile {xt} at step "
                                      f"{t} inconsistent with (g={g}, "
                                      f"i={i})"))
                continue
            kk = rel // tp % nkb
            pooled = tp > 1
            ot = (i * ncbt + int(cb[g]) + j) if concat \
                else (int(ob[g]) + i * npb + j)
            _check_exp(out, fam, tab, t, {
                GP_WT: int(wb[g]) + kk * npb + j,
                GP_FIRST: int(kk == 0), GP_LAST: int(kk == nkb - 1),
                GP_OT: ot, GP_PFIRST: 0,
                GP_PS: kk if pooled else 0,
                GP_UPOOL: int(pooled),
            })
            # accumulator-run discipline (one open tile at a time)
            want_kk = next_kk.get((g, i, j), 0)
            if kk != want_kk:
                out.append(("schema", f"{fam}: k-step {kk} at step {t} "
                                      f"for tile ({g}, {i}, {j}), "
                                      f"want {want_kk}"))
            next_kk[(g, i, j)] = kk + 1
            if kk == 0 and open_tile is not None:
                out.append(("schema", f"{fam}: GEMM run for tile "
                                      f"({g}, {i}, {j}) opens at step {t} "
                                      f"while {open_tile} is still open"))
            elif kk > 0 and open_tile != (g, i, j):
                out.append(("schema", f"{fam}: mid-run GEMM step {t} for "
                                      f"tile ({g}, {i}, {j}) does not own "
                                      "the accumulator"))
            open_tile = None if kk == nkb - 1 else (g, i, j)
            if pooled:
                st = owner.get(kk)
                if st is None or st != [int(xb[g]) + (i * nkb + kk) * tp,
                                        tp]:
                    out.append(("schema", f"{fam}: GEMM step {t} reads "
                                          f"pool slot {kk} before its "
                                          f"{tp} taps completed"))
            if kk == nkb - 1:
                key = (g, i, j)
                if key in seen:
                    out.append(("schema", f"{fam}: output tile {key} "
                                          "produced by two runs"))
                seen.add(key)
    want = m_blocks * sum(nbs)
    if len(seen) != want:
        out.append(("schema", f"{fam}: {len(seen)} distinct output tiles "
                              f"produced, want {want}"))
    return out


# ---------------------------------------------------------------------------
# dW

def check_dw(tab, m_blocks, kbs, nbs):
    """Validate a ``_plan_tiles_dw`` table: per group, each ``dW`` tile
    ``(ki, j)`` accumulates ``X^T @ dY`` over all ``m_blocks`` M-blocks
    in one run; ``DW_DODB`` marks the ``ki == 0`` runs that also reduce
    the bias gradient."""
    out = []
    fam = "dw"
    tab = np.asarray(tab)
    kbs, nbs = tuple(int(k) for k in kbs), tuple(int(n) for n in nbs)
    if tab.ndim != 2 or tab.shape[0] != DW_ROWS:
        out.append(("schema", f"{fam}: expected ({DW_ROWS}, T) table, "
                              f"got shape {tab.shape}"))
        return out
    T = m_blocks * sum(k * n for k, n in zip(kbs, nbs))
    if tab.shape[1] != T:
        out.append(("schema", f"{fam}: expected {T} steps, "
                              f"got {tab.shape[1]}"))
        return out
    xb = np.concatenate([[0], np.cumsum([m_blocks * k for k in kbs])])
    dyb = np.concatenate([[0], np.cumsum([m_blocks * n for n in nbs])])
    wb = np.concatenate([[0], np.cumsum([k * n for k, n in zip(kbs, nbs)])])
    cb = np.concatenate([[0], np.cumsum(nbs)])
    for row, nm in ((DW_FIRST, "first"), (DW_LAST, "last")):
        if not np.isin(tab[row], (0, 1)).all():
            out.append(("schema", f"{fam}: {nm}-flag row is not 0/1"))
            return out
    runs = _runs(tab[DW_FIRST], tab[DW_LAST], out, fam)
    if runs is None:
        return out
    seen = set()
    for lo, hi in runs:
        bj = int(tab[DW_BJ, lo])
        g = _group_of(cb, bj)
        if g < 0:
            out.append(("bounds", f"{fam}: N-offset {bj} at step {lo} "
                                  f"outside [0, {int(cb[-1])})"))
            continue
        j = bj - int(cb[g])
        nkb, npb = kbs[g], nbs[g]
        ot = int(tab[DW_OT, lo])
        ki = (ot - int(wb[g]) - j) // npb if npb else 0
        if not (0 <= ki < nkb and ot == int(wb[g]) + ki * npb + j):
            out.append(("bounds", f"{fam}: dW tile {ot} at step {lo} "
                                  f"inconsistent with (g={g}, j={j})"))
            continue
        if hi - lo + 1 != m_blocks:
            out.append(("schema", f"{fam}: run at step {lo} has "
                                  f"{hi - lo + 1} M-steps, want "
                                  f"{m_blocks}"))
            continue
        for mi, t in enumerate(range(lo, hi + 1)):
            _check_exp(out, fam, tab, t, {
                DW_XT: int(xb[g]) + mi * nkb + ki,
                DW_DYT: int(dyb[g]) + mi * npb + j,
                DW_FIRST: int(mi == 0),
                DW_LAST: int(mi == m_blocks - 1),
                DW_OT: ot, DW_BJ: bj,
                DW_DODB: int(ki == 0),
            })
        key = (g, ki, j)
        if key in seen:
            out.append(("schema", f"{fam}: dW tile {key} produced by "
                                  "two runs"))
        seen.add(key)
    want = sum(k * n for k, n in zip(kbs, nbs))
    if len(seen) != want:
        out.append(("schema", f"{fam}: {len(seen)} distinct dW tiles "
                              f"produced, want {want}"))
    return out


# ---------------------------------------------------------------------------
# combined backward (dx phase + dw phase, one launch)

def check_bwd(tab, m_blocks, kbs, nbs):
    """Validate a ``_plan_tiles_bwd`` table (uniform block): phase 0
    produces every ``dX`` tile (accumulating over N-blocks against
    ``W^T``), phase 1 every ``dW`` tile (accumulating over M-blocks
    against ``X``); the A-operand buffer holds all ``W^T`` tiles then
    all ``X`` tiles, the output buffer all ``dX`` then all ``dW``."""
    out = []
    fam = "bwd"
    tab = np.asarray(tab)
    kbs, nbs = tuple(int(k) for k in kbs), tuple(int(n) for n in nbs)
    if tab.ndim != 2 or tab.shape[0] != BW_ROWS:
        out.append(("schema", f"{fam}: expected ({BW_ROWS}, T) table, "
                              f"got shape {tab.shape}"))
        return out
    T = sum(m_blocks * k * n + k * n * m_blocks
            for k, n in zip(kbs, nbs))
    if tab.shape[1] != T:
        out.append(("schema", f"{fam}: expected {T} steps, "
                              f"got {tab.shape[1]}"))
        return out
    dyb = np.concatenate([[0], np.cumsum([m_blocks * n for n in nbs])])
    wtb = np.concatenate([[0], np.cumsum([n * k for k, n in zip(kbs, nbs)])])
    dxb = np.concatenate([[0], np.cumsum([m_blocks * k for k in kbs])])
    total_wt, total_dx = int(wtb[-1]), int(dxb[-1])
    xb = dxb + total_wt          # X tiles follow all W^T tiles
    dwb = wtb + total_dx         # dW tiles follow all dX tiles
    cb = np.concatenate([[0], np.cumsum(nbs)])
    for row, nm in ((BW_FIRST, "first"), (BW_LAST, "last"),
                    (BW_DW, "phase"), (BW_DODB, "dodb")):
        if not np.isin(tab[row], (0, 1)).all():
            out.append(("schema", f"{fam}: {nm}-flag row is not 0/1"))
            return out
    if (np.diff(tab[BW_DW].astype(np.int64)) < 0).any():
        out.append(("schema", f"{fam}: dW phase precedes a dX step"))
    runs = _runs(tab[BW_FIRST], tab[BW_LAST], out, fam)
    if runs is None:
        return out
    seen_dx, seen_dw = set(), set()
    for lo, hi in runs:
        phase = int(tab[BW_DW, lo])
        ot = int(tab[BW_OT, lo])
        if phase == 0:
            g = _group_of(dxb, ot)
            if g < 0:
                out.append(("bounds", f"{fam}: dX tile {ot} at step {lo} "
                                      f"outside [0, {total_dx})"))
                continue
            nkb, npb = kbs[g], nbs[g]
            rel = ot - int(dxb[g])
            i, kk = rel // nkb, rel % nkb
            if hi - lo + 1 != npb:
                out.append(("schema", f"{fam}: dX run at step {lo} has "
                                      f"{hi - lo + 1} N-steps, want "
                                      f"{npb}"))
                continue
            for j, t in enumerate(range(lo, hi + 1)):
                _check_exp(out, fam, tab, t, {
                    BW_DYT: int(dyb[g]) + i * npb + j,
                    BW_ABT: int(wtb[g]) + j * nkb + kk,
                    BW_FIRST: int(j == 0),
                    BW_LAST: int(j == npb - 1),
                    BW_OT: ot, BW_DODB: 0, BW_DW: 0, BW_BJ: 0,
                })
            key = (g, i, kk)
            if key in seen_dx:
                out.append(("schema", f"{fam}: dX tile {key} produced "
                                      "by two runs"))
            seen_dx.add(key)
        else:
            g = _group_of(dwb, ot)
            if g < 0 or ot < total_dx:
                out.append(("bounds", f"{fam}: dW tile {ot} at step {lo} "
                                      f"outside [{total_dx}, "
                                      f"{total_dx + total_wt})"))
                continue
            nkb, npb = kbs[g], nbs[g]
            rel = ot - int(dwb[g])
            ki, j = rel // npb, rel % npb
            if ki >= nkb:
                out.append(("bounds", f"{fam}: dW tile {ot} at step {lo} "
                                      f"inconsistent with group {g}"))
                continue
            if hi - lo + 1 != m_blocks:
                out.append(("schema", f"{fam}: dW run at step {lo} has "
                                      f"{hi - lo + 1} M-steps, want "
                                      f"{m_blocks}"))
                continue
            for mi, t in enumerate(range(lo, hi + 1)):
                _check_exp(out, fam, tab, t, {
                    BW_DYT: int(dyb[g]) + mi * npb + j,
                    BW_ABT: int(xb[g]) + mi * nkb + ki,
                    BW_FIRST: int(mi == 0),
                    BW_LAST: int(mi == m_blocks - 1),
                    BW_OT: ot, BW_DODB: int(ki == 0), BW_DW: 1,
                    BW_BJ: int(cb[g]) + j,
                })
            key = (g, ki, j)
            if key in seen_dw:
                out.append(("schema", f"{fam}: dW tile {key} produced "
                                      "by two runs"))
            seen_dw.add(key)
    want_dx = m_blocks * sum(kbs)
    want_dw = sum(k * n for k, n in zip(kbs, nbs))
    if len(seen_dx) != want_dx:
        out.append(("schema", f"{fam}: {len(seen_dx)} distinct dX tiles "
                              f"produced, want {want_dx}"))
    if len(seen_dw) != want_dw:
        out.append(("schema", f"{fam}: {len(seen_dw)} distinct dW tiles "
                              f"produced, want {want_dw}"))
    return out


# ---------------------------------------------------------------------------
# replay-compare helper (chained + experts families)
#
# The remaining three families carry phase interleavings (the lag-1 wave
# walk, the A/B/D expert phases with no first/last flags) that a
# run-structural check cannot pin down column-by-column, so their
# checkers REPLAY the emission independently from the declarative spec
# and diff the whole table — any mutated cell, flag, or reordering shows
# up as a mismatch.

def _compare(out, fam, tab, exp, limit=8):
    tab = np.asarray(tab)
    if tab.shape != exp.shape:
        out.append(("schema", f"{fam}: expected table shape {exp.shape}, "
                              f"got {tab.shape}"))
        return
    diff = np.argwhere(tab != exp)
    for r, t in diff[:limit]:
        out.append(("schema", f"{fam}: row {int(r)} at step {int(t)} is "
                              f"{int(tab[r, t])}, want {int(exp[r, t])}"))
    if len(diff) > limit:
        out.append(("schema",
                    f"{fam}: ... and {len(diff) - limit} more mismatches"))


# ---------------------------------------------------------------------------
# chained (lag-1 wave schedule)

def _chain_steps(tag, src):
    """The ordered k-steps of one chained branch — mirrors the kernel's
    ``_chain_ksteps`` (which imports its row constants from here)."""
    if tag == "x":
        return [("x", kk) for kk in range(src)]
    if tag == "panel":
        return [("panel", pc) for pc in src]
    taps, rcs = src
    return [("ring", (d, dh, dw, rc)) for (d, dh, dw) in taps
            for rc in rcs]


def expected_chained(m_blocks, spec):
    """Independent replay of ``_plan_tiles_chained`` from the planner
    spec (per phase a tuple of ``(tag, src, nbb, rwcs)`` branch specs):
    the expected (CH_ROWS + 2*P + 1, T) table including the wave walk,
    the per-phase output-stability backfill and the trailing
    ``ch_mrow_row`` liveness-slot row."""
    nph = len(spec)
    nrows = CH_ROWS + 2 * nph + 1
    pstride = chained_panel_stride(spec)
    info, xbase, wbase, bbase = [], 0, 0, 0
    for phase in spec:
        pinfo, ob = [], 0
        for (tag, src, nbb, rwcs) in phase:
            steps = _chain_steps(tag, src)
            pinfo.append((tag, src, nbb, rwcs, steps, xbase, wbase,
                          bbase, ob))
            if tag == "x":
                xbase += m_blocks * src
            wbase += len(steps) * nbb
            bbase += nbb
            ob += nbb
        info.append(pinfo)
    cols = []
    for wave in range(m_blocks + nph - 1):
        for p in range(nph):
            i = wave - p
            if not 0 <= i < m_blocks:
                continue
            for (tag, src, nbb, rwcs, steps, xb, wb, bb, ob) in info[p]:
                ns = len(steps)
                for j in range(nbb):
                    for s, (kt, kd) in enumerate(steps):
                        c = [0] * nrows
                        c[CH_I], c[CH_PH] = i, p
                        c[ch_mrow_row(nph)] = p * m_blocks + i
                        c[CH_WT] = wb + s * nbb + j
                        c[CH_BJ] = bb + j
                        c[CH_FIRST] = int(s == 0)
                        c[CH_LAST] = int(s == ns - 1)
                        c[CH_RWC] = -1
                        if kt == "x":
                            c[CH_SRC] = 0
                            c[CH_XT] = xb + i * src + kd
                        elif kt == "panel":
                            pidx, pcb = kd
                            c[CH_SRC] = 3 + pidx
                            c[CH_PCA if pidx == 0 else CH_PCB] = \
                                i * pstride + pcb
                        else:
                            d, dh, dw, rc = kd
                            c[CH_SRC] = 2
                            c[CH_RC], c[CH_DELTA] = rc, d
                            c[CH_DH], c[CH_DW] = dh, dw
                        if c[CH_LAST]:
                            c[ch_out_i_row(p)] = i
                            c[ch_out_j_row(p)] = ob + j
                            if rwcs:
                                c[CH_RWC] = rwcs[j]
                        cols.append(c)
    ncbs = [sum(br[2] for br in pinfo) for pinfo in info]
    for p in range(nph):
        nr, nc = ch_out_i_row(p), ch_out_j_row(p)
        nxt = (m_blocks - 1, ncbs[p] - 1)
        for c in reversed(cols):
            if c[CH_PH] == p and c[CH_LAST] == 1:
                nxt = (c[nr], c[nc])
            c[nr], c[nc] = nxt
    # operand stability: steps that read no x (panel A, panel B) tile
    # carry the next reader's tile, the last reader's past it
    for row, want in ((CH_XT, 0), (CH_PCA, 3), (CH_PCB, 4)):
        readers = [c[row] for c in cols if c[CH_SRC] == want]
        nxt = readers[-1] if readers else 0
        for c in reversed(cols):
            if c[CH_SRC] == want:
                nxt = c[row]
            else:
                c[row] = nxt
    return np.array(cols, np.int32).T


def check_chained(tab, m_blocks, spec):
    """Validate a ``_plan_tiles_chained`` table against the planner spec
    by full replay-compare, plus explicit bounds on the wave anchors."""
    out = []
    fam = "chained"
    exp = expected_chained(m_blocks, spec)
    tab = np.asarray(tab)
    _compare(out, fam, tab, exp)
    if tab.shape == exp.shape and tab.shape[1]:
        nph = len(spec)
        if not ((tab[CH_I] >= 0) & (tab[CH_I] < m_blocks)).all():
            out.append(("bounds", f"{fam}: M-block row outside "
                                  f"[0, {m_blocks})"))
        if not ((tab[CH_PH] >= 0) & (tab[CH_PH] < nph)).all():
            out.append(("bounds", f"{fam}: phase row outside [0, {nph})"))
        wave = tab[CH_I].astype(np.int64) + tab[CH_PH].astype(np.int64)
        if (np.diff(wave) < 0).any():
            out.append(("schema", f"{fam}: wave order regresses — a step "
                                  "runs before its producers' wave"))
        mr = tab[ch_mrow_row(nph)].astype(np.int64)
        if not ((mr >= 0) & (mr < nph * m_blocks)).all():
            out.append(("bounds", f"{fam}: mrow slot row outside "
                                  f"[0, {nph * m_blocks})"))
        if (mr != tab[CH_PH].astype(np.int64) * m_blocks
                + tab[CH_I].astype(np.int64)).any():
            out.append(("schema", f"{fam}: mrow slot row disagrees with "
                                  "phase*m_blocks + block — a ragged "
                                  "launch would read the wrong liveness"))
        out += _check_idle_operands(tab, fam)
    return out


def _check_idle_operands(tab, fam):
    """A step that reads no x (panel A, panel B) tile must hold the tile
    the next step that reads one does, else the pipeline fetches a tile
    no step reads.  Past the last reader it holds the last reader's."""
    out = []
    for row, want, what in ((CH_XT, 0, "x"), (CH_PCA, 3, "panel-A"),
                            (CH_PCB, 4, "panel-B")):
        readers = np.nonzero(tab[CH_SRC] == want)[0]
        if not readers.size:
            continue
        nxt = np.searchsorted(readers, np.arange(tab.shape[1]))
        carry = tab[row, readers[np.minimum(nxt, readers.size - 1)]]
        bad = np.nonzero((tab[CH_SRC] != want) & (tab[row] != carry))[0]
        if bad.size:
            t = int(bad[0])
            out.append(("schema", f"{fam}: step {t} reads no {what} tile "
                                  f"but holds {int(tab[row, t])}, not the "
                                  f"neighbouring reader's {int(carry[t])}"
                                  " — the pipeline would fetch a tile no "
                                  "step reads"))
    return out


# ---------------------------------------------------------------------------
# MoE experts (forward + combined backward)

def expected_experts(mbs, db, fb, gated):
    """Independent replay of ``_plan_tiles_experts``: per M-block the H
    phases (one per W_in channel, accumulating over D-blocks into the
    post-activation scratch) then the Y phase (accumulating H over
    F-blocks against W_out columns)."""
    nw = 1 + int(gated)
    cols = []
    for i in range(mbs):
        for j in range(fb):
            for wch in range(nw):
                for k in range(db):
                    cols.append([i, i * db + k, wch * db * fb + k * fb + j,
                                 0, wch, int(k == 0), int(k == db - 1),
                                 j, i * db, i * fb + j])
        for c in range(db):
            for j in range(fb):
                cols.append([i, i * db + db - 1, 0, j * db + c, 2,
                             int(j == 0), int(j == fb - 1), j,
                             i * db + c,
                             (i + 1) * fb if i + 1 < mbs
                             else i * fb + fb - 1])
    return np.array(cols, np.int32).T


def check_experts(tab, mbs, db, fb, gated):
    """Validate a ``_plan_tiles_experts`` table by replay-compare plus
    bounds on the block-index anchor row."""
    out = []
    fam = "experts"
    exp = expected_experts(mbs, db, fb, gated)
    tab = np.asarray(tab)
    _compare(out, fam, tab, exp)
    if tab.shape == exp.shape and tab.shape[1]:
        if not ((tab[EX_BI] >= 0) & (tab[EX_BI] < mbs)).all():
            out.append(("bounds", f"{fam}: expert block row outside "
                                  f"[0, {mbs})"))
        if (np.diff(tab[EX_BI].astype(np.int64)) < 0).any():
            out.append(("schema", f"{fam}: expert blocks out of order"))
    return out


def expected_experts_bwd(mbs, db, fb, gated):
    """Independent replay of ``_plan_tiles_experts_bwd``: per M-block
    the A (dH_post), B (dW_out accumulate), C (dX) and D (dW_h
    accumulate) phases."""
    nw = 1 + int(gated)
    hold = db * fb - 1
    cols = []
    for i in range(mbs):
        for j in range(fb):
            for c in range(db):
                cols.append([i, i * db + c, i * db, 0, c * fb + j,
                             i * fb + j, 0, int(c == 0),
                             int(c == db - 1), j, i * db, 0, 0])
        for j in range(fb):
            for c in range(db):
                cols.append([i, i * db + c, i * db, 0, hold, i * fb + j,
                             1, 0, 0, j, i * db, 0, j * db + c])
        for c in range(db):
            for wch in range(nw):
                for j in range(fb):
                    cols.append([i, i * db + db - 1, i * db,
                                 wch * fb * db + j * db + c, hold,
                                 i * fb + fb - 1, 2,
                                 int(wch == 0 and j == 0),
                                 int(wch == nw - 1 and j == fb - 1),
                                 wch * fb + j, i * db + c, 0, hold])
        for wch in range(nw):
            for c in range(db):
                for j in range(fb):
                    cols.append([i, i * db + db - 1, i * db + c,
                                 wch * fb * db, hold, i * fb + fb - 1,
                                 3, 0, 0, wch * fb + j, i * db + db - 1,
                                 wch * db * fb + c * fb + j, hold])
    return np.array(cols, np.int32).T


def check_experts_bwd(tab, mbs, db, fb, gated):
    """Validate a ``_plan_tiles_experts_bwd`` table by replay-compare
    plus bounds and phase-order checks."""
    out = []
    fam = "experts-bwd"
    exp = expected_experts_bwd(mbs, db, fb, gated)
    tab = np.asarray(tab)
    _compare(out, fam, tab, exp)
    if tab.shape == exp.shape and tab.shape[1]:
        if not ((tab[EB_BI] >= 0) & (tab[EB_BI] < mbs)).all():
            out.append(("bounds", f"{fam}: expert block row outside "
                                  f"[0, {mbs})"))
        if not ((tab[EB_PH] >= 0) & (tab[EB_PH] <= 3)).all():
            out.append(("bounds", f"{fam}: phase row outside [0, 3]"))
    return out
