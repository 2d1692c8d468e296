"""planlint hazard analysis — happens-before on the chained wave
schedule and write-write checks on fused-concat column layouts.

The chained kernel (``grouped_matmul_chained``) runs its phases in a
lag-1 wave: wave ``w`` executes phase ``p``'s M-block ``i = w - p``, so
when a phase-``p+1`` consumer runs block ``i`` the producer phase has
already stored blocks ``0..i+1`` — block ``i+1`` lands EARLIER in the
same wave (phases ascend within a wave).  The kernel banks on that: the
first ring read of a (phase, block) for a ring column copies producer
blocks ``i-1 / i / i+1`` from a 3-slot VMEM ring (slot = block mod 3)
into that column's window, and every tap of the (phase, block) slices
the halo-shifted rows out of the window.  Nothing at runtime checks the
bank holds — these checkers prove it statically from the offset table
alone:

  ``check_chained_schedule``  walks the table in execution order,
      tracking which M-block each (slot, ring column) pair last
      received and which blocks each column's window took at its
      build; every ring read must find in the window exactly the block
      the slice touches (mid always; lo when the halo shifts backward;
      hi when it shifts forward), every tap must satisfy
      ``delta == dh*W + dw``
      and ``|delta| <= bm`` (rows the shift pushes past a resident
      block are exactly the rows the border mask zeroes — the algebra
      is in the function docstring), and every ring column index must
      sit inside the declared ring.

  ``check_chained_masked``  the ragged-M extension: a serving launch
      skips M-blocks entirely past ``m_valid`` (the per-phase mrow slot
      row, ``tables.ch_mrow_row``), so a consumer wave must never need
      a producer wave the mask could have skipped.  The checker proves
      the liveness lookup is sound for EVERY image-aligned cutoff.

  ``check_concat_segments``  the write-write hazard check for fused
      concat layouts: branch panel segments and passthrough
      dynamic-update-slice column ranges must tile the join's [M, N]
      output without overlap.

Pure numpy — callable on a mutated table in fault-injection tests
without touching a kernel.
"""
from __future__ import annotations

import numpy as np

from repro.analysis.tables import (CH_DELTA, CH_DH, CH_DW, CH_I, CH_LAST,
                                   CH_PH, CH_RC, CH_RWC, CH_SRC,
                                   ch_mrow_row)


def check_chained_schedule(tab, m_blocks, nph, *, h, w, bm, nring):
    """Happens-before + geometry check on a chained offset table.

    Ring-read soundness: a read of producer block ``b`` is safe when the
    slot ``b % 3`` had last received exactly block ``b`` when the ring
    column's window was built — the first ring read of the step's
    (phase, block) for that column, ``tables.chained_step_counts``.
    The border mask covers the rest: a window row ``r`` (global output
    row) reads producer row ``r + delta`` with ``delta = dh*W + dw``;
    when the tap is unmasked (``0 <= r//W%H + dh < H`` and
    ``0 <= r%W + dw < W``) then ``r + delta`` provably stays inside the
    same image — ``rem = r % (H*W)`` satisfies ``rem + dh*W + dw in
    [0, H*W)`` — so unmasked rows never cross into a block outside the
    resident ``i-1..i+1`` window as long as ``|delta| <= bm``.

    Findings are ``(kind, message)`` with kind ``"hazard"`` (order or
    slot violations) or ``"bounds"`` (ring geometry).
    """
    out = []
    fam = "chained-schedule"
    tab = np.asarray(tab)
    if tab.ndim != 2 or tab.shape[0] <= ch_mrow_row(nph):
        out.append(("hazard", f"{fam}: table has {tab.shape[0] if tab.ndim == 2 else 0} "
                              f"rows, want > {ch_mrow_row(nph)}"))
        return out
    ring: dict[tuple[int, int], int] = {}   # (slot, ring col) -> block
    # ring col -> ((phase, block) slot it was built for, slot -> block)
    wins: dict[int, tuple[int, dict]] = {}
    for t in range(tab.shape[1]):
        i = int(tab[CH_I, t])
        if not 0 <= i < m_blocks:
            out.append(("bounds", f"{fam}: step {t} runs M-block {i} "
                                  f"outside [0, {m_blocks})"))
            continue
        src = int(tab[CH_SRC, t])
        if src == 2:
            rc = int(tab[CH_RC, t])
            d = int(tab[CH_DELTA, t])
            dh, dw = int(tab[CH_DH, t]), int(tab[CH_DW, t])
            if not 0 <= rc < nring:
                out.append(("bounds", f"{fam}: ring read at step {t} "
                                      f"addresses column {rc} outside "
                                      f"[0, {nring})"))
                continue
            if d != dh * w + dw:
                out.append(("bounds", f"{fam}: tap at step {t} has "
                                      f"delta {d} != dh*W+dw = "
                                      f"{dh * w + dw} (W={w})"))
            if abs(d) > bm:
                out.append(("bounds", f"{fam}: halo {d} at step {t} "
                                      f"exceeds bm={bm} — the shift "
                                      "window cannot cover it"))
                continue
            # which of the three ring slots does the shifted slice touch?
            needs = []
            if d < 0:
                needs.append(i - 1)        # lo slot
            if -bm < d < bm:
                needs.append(i)            # mid slot
            if d > 0:
                needs.append(i + 1)        # hi slot
            key = int(tab[ch_mrow_row(nph), t])
            if wins.get(rc, (None,))[0] != key:
                wins[rc] = (key, {sl: ring.get((sl, rc)) for sl in range(3)})
            held = wins[rc][1]
            for b in needs:
                if not 0 <= b < m_blocks:
                    continue               # border-masked edge rows
                got = held[b % 3]
                if got != b:
                    out.append((
                        "hazard",
                        f"{fam}: step {t} (block {i}) reads producer "
                        f"block {b} from ring column {rc}, but its window "
                        f"took {'nothing' if got is None else f'block {got}'}"
                        f" from slot {b % 3} — the wave schedule broke "
                        "happens-before"))
        if int(tab[CH_LAST, t]) == 1:
            rwc = int(tab[CH_RWC, t])
            if rwc >= 0:
                if rwc >= nring:
                    out.append(("bounds", f"{fam}: ring write at step "
                                          f"{t} addresses column {rwc} "
                                          f"outside [0, {nring})"))
                else:
                    ring[(i % 3, rwc)] = i
    return out


def check_chained_masked(tab, m_blocks, nph, *, h, w):
    """Prove a ragged-M chained launch cannot race for ANY image-aligned
    cutoff ``m_valid = valid_images * h * w``.

    The kernel guards every step with ``mrow[tab[ch_mrow_row, t]] > 0``
    and the per-phase mrow vector holds ``clip(m_valid - i*bm, 0, bm)``
    at slot ``p*m_blocks + i`` — liveness depends only on the block
    index, identically for every phase.  Two obligations make the skip
    safe:

      1. the liveness lookup addresses THIS step's (phase, block): the
         mrow slot row must equal ``phase * m_blocks + block``
         everywhere.  A wrong slot could report a consumer live while
         its producer wave was skipped (or mask a live block's store).
      2. a live consumer row never taps a skipped producer block: an
         unmasked ring tap of output row ``r`` reads ``r + delta`` with
         ``delta == dh*W + dw`` inside the SAME image (the border-mask
         algebra in ``check_chained_schedule``), and ``m_valid`` is
         image-aligned — so ``r < m_valid`` implies
         ``r + delta < m_valid``, i.e. the tapped block satisfies
         ``b*bm <= r + delta < m_valid`` and is live.  Statically that
         reduces to every ring tap satisfying the in-image identity,
         re-checked here so the masked proof stands alone.

    Dead blocks' epilogue stores are skipped too, but their panel slots
    are only ever addressed by equally-dead consumer blocks (same block
    index next launch), and live tail blocks store exact zeros past
    ``m_valid`` — the kernel's epilogue row mask, not a table property.
    """
    out = []
    fam = "chained-masked"
    tab = np.asarray(tab)
    mrr = ch_mrow_row(nph)
    if tab.ndim != 2 or tab.shape[0] <= mrr:
        out.append(("hazard", f"{fam}: table has no mrow slot row "
                              f"(want > {mrr} rows, got "
                              f"{tab.shape[0] if tab.ndim == 2 else 0})"))
        return out
    mr = tab[mrr].astype(np.int64)
    bad = np.nonzero((mr < 0) | (mr >= nph * m_blocks))[0]
    if bad.size:
        out.append(("bounds", f"{fam}: mrow slot {int(mr[bad[0]])} at "
                              f"step {int(bad[0])} outside "
                              f"[0, {nph * m_blocks})"))
    want = tab[CH_PH].astype(np.int64) * m_blocks + tab[CH_I].astype(
        np.int64)
    diff = np.nonzero(mr != want)[0]
    if diff.size:
        t = int(diff[0])
        out.append(("hazard", f"{fam}: step {t} reads liveness slot "
                              f"{int(mr[t])}, want {int(want[t])} "
                              f"(phase*m_blocks + block) — the no-op "
                              "guard would skip/run the wrong wave"))
    ring_steps = np.nonzero(tab[CH_SRC] == 2)[0]
    for t in ring_steps:
        d = int(tab[CH_DELTA, t])
        dh, dw = int(tab[CH_DH, t]), int(tab[CH_DW, t])
        if d != dh * w + dw:
            out.append(("bounds", f"{fam}: tap at step {int(t)} has "
                                  f"delta {d} != dh*W+dw = {dh * w + dw}"
                                  " — an unmasked row could tap across "
                                  "the image (and the m_valid) boundary"
                                  " into a skipped block"))
    return out


def check_concat_segments(segments, total):
    """Write-write hazard check on a fused-concat column layout.

    ``segments`` is a list of ``(offset, width, who)`` column ranges —
    branch panel segments plus passthrough DUS ranges — and ``total``
    the join's N.  Findings (kind ``"hazard"``) when any two ranges
    overlap or a range escapes ``[0, total)``; a gap is reported as a
    schema finding (a join column nobody writes would serve garbage).
    """
    out = []
    fam = "concat-segments"
    segs = sorted((int(o), int(n), str(who)) for o, n, who in segments)
    covered = 0
    prev = None
    for o, n, who in segs:
        if n <= 0 or o < 0 or o + n > total:
            out.append(("hazard", f"{fam}: segment {who} [{o}, {o + n}) "
                                  f"escapes the join's [0, {total})"))
            continue
        if prev is not None and o < prev[0] + prev[1]:
            out.append(("hazard", f"{fam}: segments {prev[2]} "
                                  f"[{prev[0]}, {prev[0] + prev[1]}) and "
                                  f"{who} [{o}, {o + n}) overlap — "
                                  "write-write hazard on the join"))
        prev = (o, n, who)
        covered += n
    if not out and covered != total:
        out.append(("schema", f"{fam}: segments cover {covered} of "
                              f"{total} join columns"))
    return out
