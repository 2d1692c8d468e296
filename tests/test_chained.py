"""Cross-module streaming (ISSUE 6): the chained grouped launch, the
chain-lowering pass, launch-count pins on googlenet, the partial shared-X
dedup, and the layout-pass hygiene (zero gather/concat in the counted
trace) the single-digit-launch claim rests on."""
import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import launch_bm, mixed_chain, tol_for
from repro.analysis import tables
from repro.configs.googlenet import CONFIG as GOOGLENET, reduced
from repro.core import launch_count as lc
from repro.core import plan as planlib
from repro.core.plan import OpImpl
from repro.kernels import ops as kops
from repro.models import cnn as CNN
from repro.models.cnn import CNNConfig, InceptionSpec

gmm = importlib.import_module("repro.kernels.grouped_matmul")

# The ceilings scripts/ci.sh gates on (keep in sync with ci.sh): the
# chained googlenet forward must stay single-digit-launch territory
# counting EVERY surviving launch-like primitive, the default plan's
# pallas count is its 21-kernel structure plus one slack.
LAUNCH_CEILING_CHAINED_FWD = 12
LAUNCH_CEILING_UNCHAINED_PALLAS = 22


# ---------------------------------------------------------------------------
# kernel-level: one hand-built 2-phase chain vs the tap-shift reference
# ---------------------------------------------------------------------------

def _tap_rows(wmat, kh, kw, dh, dw):
    return jax.lax.slice(wmat, (dh * kw + dw, 0), wmat.shape, (kh * kw, 1))


def _chain_reference(x0, w0, b0, wmat, b1, m, h, w):
    """Phase 0 dense GEMM -> phase 1 in-launch 3x3 ring conv, as plain
    differentiable jnp (shift-tap semantics == SAME conv, zero borders)."""
    y0 = jnp.maximum(x0 @ w0 + b0, 0.0)
    acc = b1.astype(jnp.float32)
    for dh in range(3):
        for dw in range(3):
            sh = gmm._shift_spatial(y0, m, h, w, dh - 1, dw - 1)
            acc = acc + sh @ _tap_rows(wmat, 3, 3, dh, dw)
    return y0, jnp.maximum(acc, 0.0)


def _chain_phases(x0, w0, b0, wmat, b1):
    return [
        [{"n": w0.shape[1], "w": planlib._pad_w_dense(w0, 128), "b": b0,
          "src": ("x", [x0]), "ring_write": (0,)}],
        [{"n": wmat.shape[1],
          "w": planlib._pack_w_ring(wmat, 3, 3, w0.shape[1], 1, 128),
          "b": b1, "src": ("ring", 3, 3, (0,)), "ring_write": None}],
    ]


def _chain_fixture(dtype=jnp.float32):
    b, h, w = 2, 8, 8
    m = b * h * w
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x0 = jax.random.normal(ks[0], (m, 64), dtype) * 0.3
    w0 = jax.random.normal(ks[1], (64, 48), dtype) * 0.3
    b0 = jax.random.normal(ks[2], (48,), dtype)
    wmat = jax.random.normal(ks[3], (48 * 9, 40), dtype) * 0.1
    b1 = jax.random.normal(ks[4], (40,), dtype)
    return (x0, w0, b0, wmat, b1), m, h, w


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chained_kernel_matches_reference(dtype):
    args, m, h, w = _chain_fixture(dtype)
    x0, w0, b0, wmat, b1 = args
    outs = kops.grouped_matmul_chained(_chain_phases(*args), m=m, h=h, w=w,
                                       interpret=True)
    refs = kops.grouped_matmul_chained_ref(_chain_phases(*args), m=m, h=h,
                                           w=w)
    y0, y1 = _chain_reference(*(a.astype(jnp.float32) for a in args), m, h, w)
    tol = tol_for(dtype)
    for got in (outs, refs):
        np.testing.assert_allclose(np.asarray(got[0][:m, :48], np.float32),
                                   np.asarray(y0, np.float32), **tol)
        np.testing.assert_allclose(np.asarray(got[1][:m, :40], np.float32),
                                   np.asarray(y1, np.float32), **tol)
        # padding columns are part of the panel contract: exactly zero
        assert not np.asarray(got[0][:m, 48:]).any()
        assert not np.asarray(got[1][:m, 40:]).any()


def test_chained_kernel_gradients_match_reference():
    args, m, h, w = _chain_fixture()

    def f_kernel(*a):
        outs = kops.grouped_matmul_chained(_chain_phases(*a), m=m, h=h, w=w,
                                           interpret=True)
        wt0 = jnp.arange(1, m * 48 + 1, dtype=jnp.float32).reshape(m, 48)
        wt1 = jnp.arange(1, m * 40 + 1, dtype=jnp.float32).reshape(m, 40)
        return (outs[0][:m, :48] * wt0).sum() + (outs[1][:m, :40] * wt1).sum()

    def f_ref(*a):
        y0, y1 = _chain_reference(*a, m, h, w)
        wt0 = jnp.arange(1, m * 48 + 1, dtype=jnp.float32).reshape(m, 48)
        wt1 = jnp.arange(1, m * 40 + 1, dtype=jnp.float32).reshape(m, 40)
        return (y0 * wt0).sum() + (y1 * wt1).sum()

    gk = jax.grad(f_kernel, argnums=(0, 1, 2, 3, 4))(*args)
    gr = jax.grad(f_ref, argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# kernel-level: one launch over every lhs source, and what each step does
# ---------------------------------------------------------------------------

def _mixed_outputs(phases, x, panel, m, m_valid=None, bm=None):
    return gmm.grouped_matmul_chained(phases(x), m=m, h=14, w=14,
                                      panels=[panel], bm=bm, m_valid=m_valid,
                                      debug_steps=True, interpret=True)


@pytest.mark.parametrize("dtype,valid_images", [
    ("float32", None), ("bfloat16", None), ("float32", 1),
])
def test_chained_kernel_mixed_sources_match_reference(dtype, valid_images):
    """x, panel and ring steps in one launch (a 3x3 and a 5x5 over two
    ring columns, two n-blocks), dense and ragged with dead tail blocks:
    each step takes its own source's lhs, and the live rows match the
    reference; a ragged launch zeroes its live tail block past m_valid."""
    phases, x, panel, m = mixed_chain(dtype)
    mv = None if valid_images is None else valid_images * 14 * 14
    outs, _ = _mixed_outputs(phases, x, panel, m, mv)
    refs = gmm.grouped_matmul_chained_ref(phases(x), m=m, h=14, w=14,
                                          panels=[panel])
    rows = m if mv is None else mv
    bm = launch_bm(phases(x), m, 14, 14)
    tol = tol_for(dtype)
    for got, want in zip(outs, refs):
        got = np.asarray(got, np.float32)
        np.testing.assert_allclose(got[:rows],
                                   np.asarray(want, np.float32)[:rows],
                                   **tol)
        if mv is not None:
            assert not got[mv:-(-mv // bm) * bm].any()


@functools.lru_cache(maxsize=None)
def _mixed_at(images, valid_images, bm):
    phases, x, panel, m = mixed_chain(b=images)
    mv = None if valid_images is None else valid_images * 14 * 14
    outs, cnt = _mixed_outputs(phases, x, panel, m, mv, bm)
    return [np.asarray(o) for o in outs], np.asarray(cnt)


@pytest.mark.parametrize("bm", [128, 256, 512, 1024])
@pytest.mark.parametrize("images,valid_images", [
    (2, None), (6, None), (6, 5),
])
def test_chained_kernel_at_each_block_m(bm, images, valid_images):
    """The mixed-source launch at M-block ``bm``: 196-row images, so a
    512-row block spans 2.6 of them and a 1024-row block 5.2, and a row's
    h wraps past two image heights; dense, and ragged with the live tail
    ending inside a block.
    The live rows bit-match the 128-row launch (each row sums the same
    products in the same k order) and match the reference; a ragged
    launch zeroes its live tail block past m_valid; the kernel's own step
    and window counts are the offset table's at ``bm``."""
    phases, x, panel, m = mixed_chain(b=images)
    mv = None if valid_images is None else valid_images * 14 * 14
    outs, cnt = _mixed_at(images, valid_images, bm)
    base, _ = _mixed_at(images, valid_images, 128)
    refs = gmm.grouped_matmul_chained_ref(phases(x), m=m, h=14, w=14,
                                          panels=[panel])
    rows = m if mv is None else mv
    mb = -(-m // bm)
    for got, b128, want in zip(outs, base, refs):
        assert got.shape[0] == mb * bm
        np.testing.assert_array_equal(got[:rows], b128[:rows])
        np.testing.assert_allclose(got[:rows], np.asarray(want)[:rows],
                                   **tol_for("float32"))
        if mv is not None:
            assert not got[mv:-(-mv // bm) * bm].any()
    spec = gmm._chain_static(phases(x), 128, 14)
    tab = np.asarray(gmm._plan_tiles_chained(mb, spec))
    counts = tables.chained_step_counts(tab, len(spec))
    live = mb if mv is None else -(-mv // bm)
    assert int(cnt[0, 0]) == int((tab[tables.CH_I] < live).sum())
    assert int(cnt[0, 1]) == counts["window_builds"] * live // mb
    if mv is None:
        assert int(cnt[0, 0]) == counts["x"] + counts["ring"] \
            + counts["panel"]


@pytest.mark.parametrize("valid_images", [None, 1])
def test_chained_window_builds_once_per_block_and_ring_column(
        valid_images):
    """The kernel builds a ring column's window once per (phase, block)
    and every tap and n-block reuses it: the launch's M-blocks x 2 ring
    columns, all read by phase 1; a ragged launch with one live image
    builds only the live blocks'.  The table's static count at the
    launch's M-block and the kernel's own counter agree."""
    phases, x, panel, m = mixed_chain()
    bm = launch_bm(phases(x), m, 14, 14)
    mb = -(-m // bm)
    mv = None if valid_images is None else valid_images * 14 * 14
    _, cnt = _mixed_outputs(phases, x, panel, m, mv)
    spec = gmm._chain_static(phases(x), 128, 14)
    counts = tables.chained_step_counts(gmm._plan_tiles_chained(mb, spec),
                                        len(spec))
    live = mb if mv is None else -(-mv // bm)
    assert counts["window_builds"] == mb * 2
    assert int(np.asarray(cnt)[0, 1]) == live * 2
    per_block = {"x": 2, "panel": 2 + 2, "ring": (9 * 2) * 2 + 25 * 2}
    assert {k: counts[k] for k in per_block} == \
        {k: v * mb for k, v in per_block.items()}
    if mv is None:
        assert int(np.asarray(cnt)[0, 0]) == sum(per_block.values()) * mb


def test_chained_launch_without_ring_builds_no_window():
    """A launch with no ring branch has no ring, window or coordinate
    operand, and counts no window build."""
    phases, x, panel, m = mixed_chain()
    p0 = [dict(br, ring_write=None) for br in phases(x)[0]]
    outs, cnt = gmm.grouped_matmul_chained(
        [p0], m=m, h=14, w=14, panels=[panel], debug_steps=True,
        interpret=True)
    ref = gmm.grouped_matmul_chained_ref([p0], m=m, h=14, w=14,
                                         panels=[panel])
    np.testing.assert_allclose(np.asarray(outs[0])[:m],
                               np.asarray(ref[0])[:m], **tol_for("float32"))
    spec = gmm._chain_static([p0], 128, 14)
    counts = tables.chained_step_counts(
        gmm._plan_tiles_chained(-(-m // launch_bm([p0], m, 14, 14)), spec),
        1)
    assert counts["ring"] == counts["window_builds"] == 0
    assert int(np.asarray(cnt)[0, 1]) == 0
    assert int(np.asarray(cnt)[0, 0]) == counts["x"] + counts["panel"]


# ---------------------------------------------------------------------------
# plan-level: chained vs unchained equivalence (value AND gradient)
# ---------------------------------------------------------------------------

def _batch(cfg, n, dtype=jnp.float32, seed=1):
    r = np.random.RandomState(seed)
    return {"images": jnp.asarray(r.randn(n, *cfg.img), dtype),
            "labels": jnp.asarray(r.randint(0, cfg.num_classes, n))}


STRIDED = dataclasses.replace(
    GOOGLENET, name="tiny-strided", img=(16, 16, 3),
    stem=((3, 16, 2), (1, 16, 1)),
    modules=(InceptionSpec(8, 12, 16, 4, 8, 8),),
    pool_between=(), num_classes=5)


@pytest.mark.parametrize("cfg,dtype", [
    (reduced(), jnp.float32),
    (reduced(), jnp.bfloat16),
    (STRIDED, jnp.float32),
])
def test_chained_plan_forward_matches_unchained(cfg, dtype):
    params = CNN.init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)
    batch = _batch(cfg, 2, dtype)
    plan_c, _ = CNN.plan_cnn(cfg, batch=2, chain_modules=True)
    plan_u, _ = CNN.plan_cnn(cfg, batch=2)
    assert any(g.mode == "grouped_chained" for g in plan_c.groups), \
        [g.mode for g in plan_c.groups]
    yc = CNN.forward_plan(params, cfg, batch["images"], plan_c)
    yu = CNN.forward_plan(params, cfg, batch["images"], plan_u)
    np.testing.assert_allclose(np.asarray(yc, np.float32),
                               np.asarray(yu, np.float32), **tol_for(dtype))


@pytest.mark.parametrize("cfg", [reduced(), STRIDED])
def test_chained_plan_gradcheck(cfg):
    params = CNN.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, 2)
    plan_c, _ = CNN.plan_cnn(cfg, batch=2, chain_modules=True, train=True)
    plan_u, _ = CNN.plan_cnn(cfg, batch=2, train=True)
    vc, gc = jax.value_and_grad(
        lambda p: CNN.loss_fn(p, cfg, batch, plan=plan_c)[0])(params)
    vu, gu = jax.value_and_grad(
        lambda p: CNN.loss_fn(p, cfg, batch, plan=plan_u)[0])(params)
    assert abs(float(vc) - float(vu)) < 1e-5
    errs = jax.tree.map(
        lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32)).max()), gc, gu)
    assert max(jax.tree.leaves(errs)) < 1e-4, errs


# ---------------------------------------------------------------------------
# googlenet: launch-count pins + modeled-makespan ordering
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def googlenet_plans():
    plan_c, _ = CNN.plan_cnn(GOOGLENET, batch=2, chain_modules=True,
                             train=True)
    plan_u, _ = CNN.plan_cnn(GOOGLENET, batch=2, train=True)
    return plan_c, plan_u


def test_googlenet_launch_pins(googlenet_plans):
    """Per-direction traced-jaxpr launch counts: the chained plan's
    forward is 10 launches TOTAL (1 stem chain + 9 module chains, zero
    surviving concat/conv/reduce_window), under the CI ceiling; the
    backward adds one combined launch per chain phase; and the chained
    trace is strictly cheaper than the default plan in both directions."""
    plan_c, plan_u = googlenet_plans
    params = CNN.init_params(GOOGLENET, jax.random.PRNGKey(0))
    batch = _batch(GOOGLENET, 2)

    def loss(plan):
        return lambda p, b: CNN.loss_fn(p, GOOGLENET, b, plan=plan)[0]

    fwd_c = lc.count_launches(loss(plan_c), params, batch)
    assert fwd_c["total"] == fwd_c["pallas_call"] == 10, fwd_c
    assert fwd_c["total"] <= LAUNCH_CEILING_CHAINED_FWD
    fwd_u = lc.count_launches(loss(plan_u), params, batch)
    assert fwd_u["pallas_call"] <= LAUNCH_CEILING_UNCHAINED_PALLAS, fwd_u

    both_c = lc.count_grad_launches(loss(plan_c), params, batch)
    both_u = lc.count_grad_launches(loss(plan_u), params, batch)
    # 10 forward + ONE combined bwd launch per chain phase (3 stem + 9x2)
    assert both_c["pallas_call"] == 31, both_c
    assert both_c["total"] < both_u["total"], (both_c, both_u)
    assert fwd_c["total"] < fwd_u["total"], (fwd_c, fwd_u)


def test_googlenet_chained_modeled_makespan_beats_unchained(googlenet_plans):
    plan_c, plan_u = googlenet_plans
    assert plan_c.makespan < plan_u.makespan, \
        (plan_c.makespan, plan_u.makespan)
    bwd_c = plan_c.context["backward"]
    bwd_u = plan_u.context["backward"]
    assert bwd_c.makespan < bwd_u.makespan, (bwd_c.makespan, bwd_u.makespan)


def test_googlenet_chained_plan_shape(googlenet_plans):
    """1 three-phase stem chain + 9 two-phase module chains; the grad plan
    mirrors every chain with reversed phases."""
    plan_c, _ = googlenet_plans
    chains = [g for g in plan_c.groups if g.mode == "grouped_chained"]
    assert len(chains) == 10
    phase_shapes = sorted(tuple(len(p) for p in g.chain) for g in chains)
    assert phase_shapes.count((1, 1, 1)) == 1     # the absorbed stem
    assert phase_shapes.count((4, 2)) == 9        # the inception modules
    bwd = plan_c.context["backward"]
    gchains = [g for g in bwd.groups if g.mode == "grouped_chained"]
    assert len(gchains) == 10
    for g in gchains:
        assert all(n.startswith("grad:") for ph in g.chain for n in ph)


# ---------------------------------------------------------------------------
# googlenet serving: planner, hazard replay and kernel agree on the M-block
# ---------------------------------------------------------------------------

_CHAINED_MODULES = [("grouped_chained", f"inc{i}/r5" if i < 2 else
                     f"inc{i}/r3") for i in range(9)]
#: the serving plans' groups, (mode, first op) per bucket
SERVE_GROUPS = {
    1: [("serial", "input"), ("grouped_chained", "stem0")]
    + _CHAINED_MODULES[:7]
    + [("grouped_pooled", "inc7/r3"), ("grouped_concat", "inc7/3x3"),
       ("grouped_pooled", "inc8/r3"), ("serial", "inc8/3x3"),
       ("serial", "inc8/5x5"), ("serial", "inc8/join")],
}
for _b in (2, 4, 8):
    SERVE_GROUPS[_b] = [("serial", "input"), ("grouped_chained", "stem0")] \
        + _CHAINED_MODULES


@pytest.mark.parametrize("bucket", [1, 2, 4, 8])
def test_serving_planner_replay_and_kernel_agree_on_block_m(bucket):
    """GoogLeNet at each serving bucket: every chained launch's M-block,
    M-block count and chunk rows are the same in the planner
    (``budgets.chained_spec``, ``budgets.chunk_rows``) and in the launches
    the kernel wrapper makes as the serve step traces; the hazard replay
    passes at that M-block; the bucket ladder and the plan's groups and
    their modes are the serving path's."""
    from repro.analysis import budgets, hazards
    from repro.core import cost_model as cm
    from repro.launch.steps import make_cnn_serve_step
    assert cm.serve_buckets(8, 224 * 224) == [1, 2, 4, 8]
    plan, _ = CNN.plan_cnn(GOOGLENET, bucket, chain_modules=True)
    assert [(g.mode, g.ops[0]) for g in plan.groups] == SERVE_GROUPS[bucket]
    graph = plan.context["graph"]
    want = []
    for g in plan.groups:
        if g.mode != "grouped_chained":
            continue
        bm, mb, spec, oh, ow, nring, problems = budgets.chained_spec(
            graph, g.chain, g.pools)
        assert problems == []
        rows, m = budgets.chunk_rows(graph, g)
        assert rows == (g.chunk_rows or m) and mb == -(-m // bm)
        for _s, r in gmm._row_spans(m, rows):
            mbr = -(-r // bm)
            tab = gmm._plan_tiles_chained(mbr, spec)
            assert hazards.check_chained_schedule(
                tab, mbr, len(spec), h=oh, w=ow, bm=bm, nring=nring) == []
            want.append((bm, mbr))
    params = jax.eval_shape(
        lambda: CNN.init_params(GOOGLENET, jax.random.PRNGKey(0)))
    with gmm.chained_steps_recording() as rec:
        jax.eval_shape(make_cnn_serve_step(GOOGLENET, plan, interpret=True),
                       params,
                       jax.ShapeDtypeStruct((bucket,) + GOOGLENET.img,
                                            jnp.float32),
                       jax.ShapeDtypeStruct((), jnp.int32))
    assert rec["blocks"] == want


# ---------------------------------------------------------------------------
# layout-pass hygiene: the counted-primitive-free decompositions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chain", [((3, 1),), ((3, 2),), ((3, 2), (3, 1))])
def test_pool_tap_views_trace_is_clean(chain):
    """Strided tap views must lower to pad+slice — jnp's strided getitem
    gathers (with a concatenate-built index grid) and one counted
    primitive per tap would void the chained launch ceiling."""
    x = jnp.ones((2, 14, 14, 4))
    counts = lc.count_launches(
        lambda a: gmm.pool_from_taps(gmm.pool_tap_views(a, chain)), x)
    assert counts["total"] == 0, counts


@pytest.mark.parametrize("dh,dw", [(0, 0), (1, -1), (-1, 1), (1, 1)])
def test_shift_spatial_matches_roll_reference(dh, dw):
    b, h, w, c = 2, 5, 4, 3
    m = b * h * w
    x = jnp.asarray(np.random.RandomState(0).randn(m, c), jnp.float32)
    got = np.asarray(gmm._shift_spatial(x, m, h, w, dh, dw))
    img = np.asarray(x).reshape(b, h, w, c)
    want = np.zeros_like(img)
    for i in range(h):
        for j in range(w):
            if 0 <= i + dh < h and 0 <= j + dw < w:
                want[:, i, j] = img[:, i + dh, j + dw]
    np.testing.assert_array_equal(got, want.reshape(m, c))
    counts = lc.count_launches(
        lambda a: gmm._shift_spatial(a, m, h, w, dh, dw), x)
    assert counts["total"] == 0, counts


# ---------------------------------------------------------------------------
# partial shared-X dedup (satellite): bucketing + numerics
# ---------------------------------------------------------------------------

def _impl(deps, key, k):
    return OpImpl(deps=deps, fn=lambda *a: None, gemm_x=lambda *a: a,
                  gemm_x_key=key, gemm_w=np.zeros((k, 4), np.float32))


def test_dedup_buckets_partial():
    """The inception shape: three branches share (deps, x-key, K) and
    bucket into one wide sub-GEMM; the pooled branch (different absorbed
    pool) and the different-K branch stay ragged singletons."""
    impls = {"a": _impl(("x",), "relu:x", 8),
             "b": _impl(("x",), "relu:x", 8),
             "c": _impl(("x",), "relu:x", 8),
             "p": _impl(("x",), "relu:x", 8),
             "q": _impl(("x",), "relu:x", 16)}
    buckets = planlib._dedup_buckets(
        impls, ["a", "b", "p", "c", "q"], {"p": ((3, 1),)})
    assert buckets == [["a", "b", "c"], ["p"], ["q"]]


def test_dedup_buckets_none_key_never_buckets():
    impls = {"a": _impl(("x",), None, 8), "b": _impl(("x",), None, 8)}
    assert planlib._dedup_buckets(impls, ["a", "b"], {}) == [["a"], ["b"]]


def test_grouped_forward_matches_eager_with_dedup():
    """The always-on partial dedup inside _run_grouped must not change the
    unchained plan's numerics (reduced googlenet, plan vs eager)."""
    cfg = reduced()
    params = CNN.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, 2)
    plan_u, _ = CNN.plan_cnn(cfg, batch=2)
    yp = CNN.forward_plan(params, cfg, batch["images"], plan_u)
    ye = CNN.forward(params, cfg, batch["images"])
    np.testing.assert_allclose(np.asarray(yp), np.asarray(ye),
                               rtol=2e-4, atol=2e-4)
