"""planlint — zero findings on real lowerings, and fault injection
proving every checker class fires.

The static verifier is only trustworthy if (a) every table the real
``_plan_tiles*`` builders emit comes back clean and (b) corrupting ANY
row of those tables produces a finding.  The mutation tests walk every
row of every family's table, corrupt one cell, and require the family
checker to object — a checker that ignores a row would pass a broken
schedule silently, which is exactly the failure mode planlint exists to
rule out.  Hazard, budget and fallback-provenance classes get targeted
mutants of their own.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import Finding, PlanVerificationError, verify_plan
from repro.analysis import fallbacks, hazards, tables
from repro.configs import get_config, get_reduced
from repro.core import launch_count as lc
from repro.models import cnn

# the package re-exports a function named ``grouped_matmul`` that shadows
# the submodule attribute — importlib reaches the module itself; the
# package-level experts entry point is the differentiable custom-vjp one
gmm = importlib.import_module("repro.kernels.grouped_matmul")
from repro import kernels as K


def _mutants_fire(tab, check, rows):
    """Corrupting each listed row (at some step) must produce >= 1
    finding; returns the number of fired mutants."""
    fired = 0
    for row in rows:
        hit = False
        for t in range(tab.shape[1]):
            bad = np.array(tab, copy=True)
            bad[row, t] += 3
            if check(bad):
                hit = True
                fired += 1
                break
        assert hit, f"no mutant on row {row} produced a finding"
    return fired


# ---------------------------------------------------------------------------
# table schemas: builder output is clean, every row is load-bearing
# ---------------------------------------------------------------------------

def test_plain_table_clean_and_mutants():
    tab = gmm._plan_tiles(2, (2, 1), (1, 2))
    check = lambda tb: tables.check_plain(tb, 2, (2, 1), (1, 2))
    assert check(tab) == []
    assert _mutants_fire(tab, check, range(tables.GM_ROWS)) == 7


def test_pooled_table_clean_and_mutants():
    # group 0 pooled (3x3 = 9 taps), group 1 plain
    tab = gmm._plan_tiles_pooled(2, (1, 1), (1, 1), (9, 1), False)
    check = lambda tb: tables.check_pooled(tb, 2, (1, 1), (1, 1),
                                           (9, 1), False)
    assert check(tab) == []
    assert _mutants_fire(tab, check, range(tables.GP_ROWS)) == 11


def test_dw_table_clean_and_mutants():
    tab = gmm._plan_tiles_dw(2, (2, 1), (1, 2))
    check = lambda tb: tables.check_dw(tb, 2, (2, 1), (1, 2))
    assert check(tab) == []
    assert _mutants_fire(tab, check, range(tables.DW_ROWS)) == 7


def test_bwd_table_clean_and_mutants():
    tab = gmm._plan_tiles_bwd(2, (2, 1), (1, 2))
    check = lambda tb: tables.check_bwd(tb, 2, (2, 1), (1, 2))
    assert check(tab) == []
    assert _mutants_fire(tab, check, range(tables.BW_ROWS)) == 8


def _chained_spec():
    """2-phase chain on a 4x4 image: phase 0 a packed-x producer that
    ring-writes column 0, phase 1 a 3x3 in-launch conv consuming it."""
    taps = tuple((dh * 4 + dw, dh, dw)
                 for dh in (-1, 0, 1) for dw in (-1, 0, 1))
    return ((("x", 2, 1, (0,)),),
            (("ring", (taps, (0,)), 1, ()),))


def test_chained_table_clean_and_mutants():
    spec = _chained_spec()
    tab = gmm._plan_tiles_chained(2, spec)
    check = lambda tb: tables.check_chained(tb, 2, spec)
    assert check(tab) == []
    # ... + 1: the trailing per-phase mrow slot row ragged launches
    # read their liveness from (``tables.ch_mrow_row``)
    nrows = tables.CH_ROWS + 2 * len(spec) + 1
    assert _mutants_fire(tab, check, range(nrows)) == nrows


def _mixed_spec():
    """Phase 0: an x branch (ring columns 0, 1) and a panel branch over
    panel A's column blocks 0 and 2; phase 1: a 3x3 ring conv over both
    ring columns and a panel branch over panel B's column block 1."""
    taps = tuple((dh * 4 + dw, dh, dw)
                 for dh in (-1, 0, 1) for dw in (-1, 0, 1))
    return ((("x", 1, 2, (0, 1)), ("panel", ((0, 0), (0, 2)), 1, ())),
            (("ring", (taps, (0, 1)), 1, ()), ("panel", ((1, 1),), 1, ())))


@pytest.mark.parametrize("row,src", [
    (tables.CH_XT, 0), (tables.CH_PCA, 3), (tables.CH_PCB, 4)])
def test_chained_idle_steps_hold_the_neighbouring_readers_tile(row, src):
    """A step that reads no x (panel A, panel B) tile holds the tile of
    the next step that reads one, the last reader's past it — so the
    pipeline fetches no tile that no step reads — and the verifier names
    a step that does not."""
    spec = _mixed_spec()
    tab = np.asarray(gmm._plan_tiles_chained(3, spec))
    assert tables.check_chained(tab, 3, spec) == []
    readers = np.nonzero(tab[tables.CH_SRC] == src)[0]
    assert readers.size
    for t in range(tab.shape[1]):
        later = readers[readers >= t]
        r = later[0] if later.size else readers[-1]
        assert tab[row, t] == tab[row, r], (t, r)
    # a panel tile names its block: stride = widest column block + 1
    if src == 3:
        assert (tab[row, readers] // tables.chained_panel_stride(spec)
                == tab[tables.CH_I, readers]).all()
    idle = np.nonzero(tab[tables.CH_SRC] != src)[0]
    bad = tab.copy()
    bad[row, idle[0]] += 1
    assert any("reads no" in msg
               for _, msg in tables.check_chained(bad, 3, spec))


def test_experts_tables_clean_and_mutants():
    tab = gmm._plan_tiles_experts(2, 1, 1, 1)
    check = lambda tb: tables.check_experts(tb, 2, 1, 1, 1)
    assert check(tab) == []
    assert _mutants_fire(tab, check, range(tables.EX_ROWS)) == 10

    tabb = gmm._plan_tiles_experts_bwd(2, 1, 1, 1)
    checkb = lambda tb: tables.check_experts_bwd(tb, 2, 1, 1, 1)
    assert checkb(tabb) == []
    assert _mutants_fire(tabb, checkb, range(tables.EB_ROWS)) == 13


# ---------------------------------------------------------------------------
# hazards: wave happens-before and concat write-write
# ---------------------------------------------------------------------------

def _schedule(tab):
    return hazards.check_chained_schedule(np.asarray(tab), 2, 2,
                                          h=4, w=4, bm=128, nring=1)


def test_chained_schedule_clean():
    assert _schedule(gmm._plan_tiles_chained(2, _chained_spec())) == []


def test_chained_schedule_order_violation():
    # reversed execution order: every ring read now precedes its
    # producer's ring write
    tab = np.array(gmm._plan_tiles_chained(2, _chained_spec()))[:, ::-1]
    out = _schedule(tab)
    assert any(kind == "hazard" for kind, _ in out)


def test_chained_schedule_stale_window():
    """The window of a (phase, block) is built at its first ring read and
    reused: a producer write that lands after that build, though before
    the taps that need it, is a hazard.  Block 1's producer steps moved
    to just after block 0's first consumer tap (which needs blocks -1
    and 0 only) leave every later tap reading a window without block 1."""
    tab = np.array(gmm._plan_tiles_chained(2, _chained_spec()))
    assert tab[tables.CH_SRC, 4] == 2 and tab[tables.CH_DELTA, 4] < 0
    order = [0, 1, 4, 2, 3] + list(range(5, tab.shape[1]))
    out = _schedule(tab[:, order])
    assert any(kind == "hazard" and "window" in msg for kind, msg in out)


def test_chained_schedule_bounds_mutants():
    base = np.array(gmm._plan_tiles_chained(2, _chained_spec()))
    ring_steps = np.nonzero(base[tables.CH_SRC] == 2)[0]
    t = int(ring_steps[0])

    bad = base.copy()
    bad[tables.CH_RC, t] = 5                       # outside nring=1
    assert any(k == "bounds" for k, _ in _schedule(bad))

    bad = base.copy()
    bad[tables.CH_DELTA, t] = 200                  # halo beyond bm=128
    assert any(k == "bounds" for k, _ in _schedule(bad))

    bad = base.copy()
    bad[tables.CH_DH, t] += 1                      # delta != dh*W + dw
    assert any(k == "bounds" for k, _ in _schedule(bad))


def _masked(tab):
    return hazards.check_chained_masked(np.asarray(tab), 2, 2, h=4, w=4)


def test_chained_masked_clean():
    assert _masked(gmm._plan_tiles_chained(2, _chained_spec())) == []


def test_chained_masked_mutants():
    """Fault injection for every obligation of the ragged no-op guard:
    a wrong liveness slot, an out-of-range slot, a tap whose delta
    breaks the in-image identity (the masked proof's boundary premise),
    and a table with no mrow row at all."""
    base = np.array(gmm._plan_tiles_chained(2, _chained_spec()))
    mrr = tables.ch_mrow_row(2)

    bad = base.copy()
    bad[mrr, 1] += 1                               # wrong (phase, block)
    assert any(k == "hazard" for k, _ in _masked(bad))

    bad = base.copy()
    bad[mrr, 0] = 99                               # outside [0, nph*mb)
    assert any(k == "bounds" for k, _ in _masked(bad))

    t = int(np.nonzero(base[tables.CH_SRC] == 2)[0][0])
    bad = base.copy()
    bad[tables.CH_DW, t] += 1                      # delta != dh*W + dw
    assert any(k == "bounds" for k, _ in _masked(bad))

    assert any(k == "hazard" for k, _ in _masked(base[:mrr]))


def test_concat_segments():
    ok = [(0, 4, "a"), (4, 6, "b")]
    assert hazards.check_concat_segments(ok, 10) == []
    overlap = [(0, 5, "a"), (4, 6, "b")]
    assert any(k == "hazard"
               for k, _ in hazards.check_concat_segments(overlap, 10))
    gap = [(0, 4, "a"), (6, 4, "b")]
    assert any(k == "schema"
               for k, _ in hazards.check_concat_segments(gap, 10))
    escape = [(0, 12, "a")]
    assert any(k == "hazard"
               for k, _ in hazards.check_concat_segments(escape, 10))


# ---------------------------------------------------------------------------
# plan level: zero findings, default-on stamping, budget fault injection
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fused_plan():
    plan, _ = cnn.plan_cnn(get_reduced("googlenet"), 2)
    return plan


def test_verify_plan_zero_findings(fused_plan):
    assert verify_plan(fused_plan) == []
    assert verify_plan(fused_plan.context["backward"]) == []


def test_lower_stamps_verified_under_pytest(fused_plan):
    # PYTEST_CURRENT_TEST is set, so lower()/backward_plan() auto-verify
    # and stamp the context for the plan cache's ``verified`` flag
    assert fused_plan.context.get("verified") is True
    assert fused_plan.context["backward"].context.get("verified") is True


def test_budget_fault_injection(fused_plan):
    from repro.core import plan as planlib
    plan, _ = cnn.plan_cnn(get_reduced("googlenet"), 2)
    plan.context["budgets"] = {"hbm": 64.0, "vmem": 64.0}
    out = verify_plan(plan)
    assert out and all(f.checker == "budget" for f in out)
    with pytest.raises(PlanVerificationError):
        planlib._maybe_verify(plan, None, True)


def test_smem_budget_fault_injection():
    # the bucket-8 serving plan runs its stem chain as SMEM chunks: undo
    # the chunking and planlint must see the 1.5 MiB offset table the
    # chip's compiler refuses
    import dataclasses
    plan, _ = cnn.plan_cnn(get_config("googlenet"), 8, chain_modules=True)
    assert verify_plan(plan) == []
    i = next(i for i, g in enumerate(plan.groups) if g.ops[0] == "stem0")
    stem = plan.groups[i]
    assert stem.chunks > 1
    plan.groups[i] = dataclasses.replace(stem, chunk_rows=0, chunks=1)
    out = verify_plan(plan)
    assert [f.checker for f in out] == ["budget"] and "SMEM" in out[0].detail
    # a chunk count its chunk_rows does not give is a bounds finding
    plan.groups[i] = dataclasses.replace(stem, chunks=stem.chunks + 1)
    assert [f.checker for f in verify_plan(plan)] == ["bounds"]


# ---------------------------------------------------------------------------
# fallback provenance lint
# ---------------------------------------------------------------------------

def test_fallback_leak_in_clean_scope_fires():
    # the chained pack path is dynamic-update-slice only by contract —
    # a concatenate in its scope is a finding (grouped/pooled/stacked
    # get a packing-copy allowance; chained does not)
    def leaky(a, b):
        with jax.named_scope("plan[grouped_chained:inc3a.b3x3]"):
            return jnp.concatenate([a, b], axis=0)
    out = fallbacks.lint_fallbacks(leaky, jnp.ones((2, 2)),
                                   jnp.ones((2, 2)))
    assert len(out) == 1 and out[0][0] == "fallback"
    assert "concatenate" in out[0][1] and "grouped_chained" in out[0][1]


def test_fallback_gather_attribution():
    def leaky(a):
        with jax.named_scope("plan[grouped_chained:stem]"):
            return jnp.take(a, jnp.array([1, 0]), axis=0)
    out = fallbacks.lint_fallbacks(leaky, jnp.ones((2, 2)))
    assert out and "gather" in out[0][1]


def test_fallback_serial_scope_exempt():
    def serial(a, b):
        with jax.named_scope("plan[serial:pool3]"):
            return jnp.concatenate([a, b], axis=0)
    assert fallbacks.lint_fallbacks(serial, jnp.ones((2, 2)),
                                    jnp.ones((2, 2))) == []


def test_fallback_concat_mode_allows_assembly():
    def assembly(a, b):
        with jax.named_scope("plan[grouped_concat:inc3a.join]"):
            return jnp.concatenate([a, b], axis=1)
    assert fallbacks.lint_fallbacks(assembly, jnp.ones((2, 2)),
                                    jnp.ones((2, 2))) == []


# ---------------------------------------------------------------------------
# launch_count: MoE grouped path, scan and checkpoint bodies
# ---------------------------------------------------------------------------

def _moe_case(counts=(16, 0, 9, 3), d=128, f=64, bm=8):
    offs = np.asarray(gmm.expert_row_offsets(counts, bm))
    e = len(counts)
    n_rows = int(np.maximum(-(-np.asarray(counts) // bm), 1).sum()) * bm
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    xp = jnp.zeros((n_rows, d), jnp.float32)
    swp = jnp.zeros((n_rows,), jnp.float32)
    for g, c in enumerate(counts):
        if c:
            xp = xp.at[offs[g]:offs[g] + c].set(
                jax.random.normal(jax.random.fold_in(ks[0], g),
                                  (c, d)) * 0.3)
            swp = swp.at[offs[g]:offs[g] + c].set(1.0)
    w_in = jax.random.normal(ks[1], (e, d, f)) * 0.3
    w_out = jax.random.normal(ks[2], (e, f, d)) * 0.3
    w_gate = jax.random.normal(ks[3], (e, d, f)) * 0.3
    return xp, swp, w_in, w_out, w_gate, jnp.asarray(counts, jnp.int32)


def test_launch_count_moe_grouped():
    xp, swp, w_in, w_out, w_gate, cnt = _moe_case()
    fwd = lc.count_launches(
        lambda x: gmm.grouped_matmul_experts(x, swp, w_in, w_out, w_gate,
                                             cnt, bm=8), xp)
    assert fwd["pallas_call"] == 1

    both = lc.count_grad_launches(
        lambda x: jnp.sum(K.grouped_matmul_experts(
            x, swp, w_in, w_out, w_gate, cnt, bm=8)), xp)
    # residual forward + the ONE combined experts backward
    assert both["pallas_call"] == 2


def test_launch_count_inside_scan_and_checkpoint():
    xp, swp, w_in, w_out, w_gate, cnt = _moe_case()
    f = lambda x: K.grouped_matmul_experts(x, swp, w_in, w_out, w_gate,
                                           cnt, bm=8)
    # the scan body's sub-jaxpr is walked: its single kernel is counted
    scanned = lc.count_launches(
        lambda x: jax.lax.scan(lambda c, _: (f(c), None), x, None,
                               length=3)[0], xp)
    assert scanned["pallas_call"] == 1

    # checkpoint (remat) bodies are walked too — the grad trace sees the
    # rematerialized forward kernel plus the backward kernel
    both = lc.count_grad_launches(
        lambda x: jnp.sum(jax.checkpoint(f)(x)), xp)
    assert both["pallas_call"] >= 2
