"""Ragged-M grouped launches: the continuous-batching serving path.

Kernel level — every grouped-family wrapper with ``m_valid`` set must
BIT-match its per-branch XLA oracle (requests pack contiguously, so the
raggedness is a tail mask; K <= 128 keeps kernel and oracle on the same
single-k-block f32 accumulation, making exact equality the honest bar)
and store exact zeros past the true row count.  Model level — a padded
batch served with ``valid_images`` must reproduce the dense run's logits
for the valid images bit-for-bit, through ONE grouped-family launch per
co-executed group (the eager launch counters), and must be invariant to
whatever garbage sits in the padding images.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import given, launch_bm, mixed_chain, settings, st

from repro import kernels as K
from repro.configs import get_reduced
from repro.core import plan as planlib
from repro.kernels import ops as kops
from repro.models import cnn as CNN

# the package re-exports a function named ``grouped_matmul`` that
# shadows the submodule attribute — importlib reaches the module
gmm = importlib.import_module("repro.kernels.grouped_matmul")

# K <= 128 (one k-block): kernel accumulation == oracle's single f32 dot
RAGGED_SETS = [
    [(128, 128), (64, 60)],
    [(100, 60), (64, 129), (128, 16)],
    [(96, 250)],
]


def _branches(m, shapes, dtype, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3 * len(shapes))
    xs = [jax.random.normal(ks[3 * i], (m, kg), dtype) * 0.3
          for i, (kg, _) in enumerate(shapes)]
    ws = [jax.random.normal(ks[3 * i + 1], (kg, ng), dtype) * 0.3
          for i, (kg, ng) in enumerate(shapes)]
    bs = [jax.random.normal(ks[3 * i + 2], (ng,), dtype)
          for i, (_, ng) in enumerate(shapes)]
    return xs, ws, bs


def _assert_ragged_bitmatch(got, want, m_valid):
    for y, yw in zip(got, want):
        y, yw = np.asarray(y), np.asarray(yw)
        assert np.array_equal(y, yw), (
            f"ragged output != oracle (max |d| "
            f"{np.abs(y.astype(np.float32) - yw.astype(np.float32)).max()})")
        assert not y[m_valid:].any(), "tail rows past m_valid not zeroed"


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 200), st.integers(0, 2),
       st.sampled_from(["float32", "bfloat16"]))
def test_ragged_grouped_bitmatches_oracle(m_valid, set_idx, dtype):
    """Mixed request sizes x dtypes: the ragged grouped launch equals the
    per-request XLA oracle bit-for-bit, zeros past the true M."""
    shapes = RAGGED_SETS[set_idx]
    m = 200   # fixed padded M (the bucket); m_valid is the true row count
    xs, ws, bs = _branches(m, shapes, jnp.dtype(dtype))
    got = K.grouped_matmul(xs, ws, bs, relu=True, m_valid=m_valid)
    want = K.grouped_matmul_ref(xs, ws, bs, relu=True, m_valid=m_valid)
    _assert_ragged_bitmatch(got, want, m_valid)


@settings(max_examples=6, deadline=None)
@given(st.integers(1, 150), st.sampled_from(["float32", "bfloat16"]))
def test_ragged_concat_bitmatches_oracle(m_valid, dtype):
    """Ragged fused-concat: branch outputs land in the join buffer with
    the same tail mask.  compact=True — compact=False returns the padded
    panel layout for the executor to assemble, not the (M, total) join
    the oracle produces."""
    shapes = RAGGED_SETS[1]
    xs, ws, bs = _branches(150, shapes, jnp.dtype(dtype))
    offs = [0, 60, 189]
    total = 205
    got = K.grouped_matmul_concat(xs, ws, bs, offsets=offs, total=total,
                                  relu=True, compact=True,
                                  m_valid=m_valid)
    want = K.grouped_matmul_concat_ref(xs, ws, bs, offsets=offs,
                                       total=total, relu=True,
                                       m_valid=m_valid)
    _assert_ragged_bitmatch([got], [want], m_valid)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("set_idx", range(len(RAGGED_SETS)))
def test_ragged_seeded_sweep(set_idx, dtype):
    """Seeded fallback for the property tests above (runs without
    hypothesis, mirroring test_properties.py): a spread of valid counts
    incl. both block-aligned and mid-block tails."""
    shapes = RAGGED_SETS[set_idx]
    xs, ws, bs = _branches(200, shapes, jnp.dtype(dtype), key=set_idx)
    for m_valid in (1, 77, 128, 200):
        got = K.grouped_matmul(xs, ws, bs, relu=True, m_valid=m_valid)
        want = K.grouped_matmul_ref(xs, ws, bs, relu=True, m_valid=m_valid)
        _assert_ragged_bitmatch(got, want, m_valid)


def test_ragged_concat_seeded_sweep():
    shapes = RAGGED_SETS[1]
    offs, total = [0, 60, 189], 205
    for dtype in ("float32", "bfloat16"):
        xs, ws, bs = _branches(150, shapes, jnp.dtype(dtype))
        for m_valid in (1, 64, 150):
            got = K.grouped_matmul_concat(xs, ws, bs, offsets=offs,
                                          total=total, relu=True,
                                          compact=True, m_valid=m_valid)
            want = K.grouped_matmul_concat_ref(xs, ws, bs, offsets=offs,
                                               total=total, relu=True,
                                               m_valid=m_valid)
            _assert_ragged_bitmatch([got], [want], m_valid)


def test_ragged_pooled_bitmatches_oracle():
    """Ragged pooled launch: in-kernel maxpool + GEMM with the tail mask
    on the pooled output's row space."""
    b, h, w, c = 4, 8, 8, 5
    x4 = jnp.maximum(
        jax.random.normal(jax.random.PRNGKey(0), (b, h, w, c)), 0)
    taps = tuple(t.reshape(-1, c) for t in K.pool_tap_views(x4, ((3, 1),)))
    m = b * h * w
    xs = [taps,
          jax.random.normal(jax.random.PRNGKey(1), (m, 64)) * 0.3]
    ws = [jax.random.normal(jax.random.PRNGKey(2), (c, 60)) * 0.3,
          jax.random.normal(jax.random.PRNGKey(3), (64, 16)) * 0.3]
    for m_valid in (1, h * w, 3 * h * w):   # 1 row .. whole-image counts
        got = kops.grouped_matmul_pooled(xs, ws, relu=True, m_valid=m_valid)
        want = K.grouped_matmul_pooled_ref(xs, ws, relu=True,
                                           m_valid=m_valid)
        _assert_ragged_bitmatch(got, want, m_valid)


def test_ragged_traced_m_valid_shares_one_executable():
    """A TRACED i32 ``m_valid`` jits once and serves every valid count —
    the property that lets one bucket executable cover all request
    mixes."""
    xs, ws, bs = _branches(128, RAGGED_SETS[0], jnp.float32)
    traces = []

    @jax.jit
    def run(mv):
        traces.append(1)
        return K.grouped_matmul(xs, ws, bs, m_valid=mv)

    for mv in (1, 37, 128):
        got = run(jnp.int32(mv))
        want = K.grouped_matmul_ref(xs, ws, bs, m_valid=mv)
        _assert_ragged_bitmatch(got, want, mv)
    assert len(traces) == 1, "m_valid retraced per value"


# ---------------------------------------------------------------------------
# chained launch: ragged-M inside grouped_matmul_chained
# ---------------------------------------------------------------------------

def _chain_case(b, h, w, dtype=jnp.float32, key=0):
    """2-phase chain (dense producer -> in-launch 3x3 ring conv) plus a
    phase-dict builder, so the same weights spec both the padded-bucket
    launch and its sliced-input oracle."""
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    m = b * h * w
    x0 = jax.random.normal(ks[0], (m, 64), dtype) * 0.3
    w0 = jax.random.normal(ks[1], (64, 48), dtype) * 0.3
    b0 = jax.random.normal(ks[2], (48,), dtype)
    wmat = jax.random.normal(ks[3], (48 * 9, 40), dtype) * 0.3
    b1 = jax.random.normal(ks[4], (40,), dtype)

    def phases(x):
        p0 = [{"n": 48, "w": planlib._pad_w_dense(w0, 128), "b": b0,
               "src": ("x", [x]), "ring_write": (0,)}]
        p1 = [{"n": 40, "w": planlib._pack_w_ring(wmat, 3, 3, 48, 1, 128),
               "b": b1, "src": ("ring", 3, 3, (0,)), "ring_write": None}]
        return [p0, p1]

    return x0, phases


def _assert_chained_ragged(got, oracle, m_valid, bm=128):
    """Live rows bit-match; the LIVE TAIL BLOCK stores exact zeros past
    ``m_valid``.  Dead blocks past the live tail are skipped outright —
    their contents are unspecified garbage no live consumer reads, so
    they are deliberately NOT asserted on."""
    tail_end = min(-(-m_valid // bm) * bm, got[0].shape[0])
    for y, yw in zip(got, oracle):
        y = np.asarray(y)
        np.testing.assert_array_equal(y[:m_valid],
                                      np.asarray(yw)[:m_valid])
        assert not y[m_valid:tail_end].any(), \
            "live tail block rows past m_valid not zeroed"


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("bucket", [2, 4])
def test_ragged_chained_bitmatches_per_request_oracle(bucket, dtype):
    """Every ladder bucket x dtype: the masked chained launch bit-matches
    the dense chained kernel run on just the request's images (requests
    pack contiguously, so the per-request oracle IS the sliced input;
    accumulation is row-local, so padding cannot perturb live rows)."""
    h, w = 8, 8
    x0, phases = _chain_case(bucket, h, w, jnp.dtype(dtype), key=bucket)
    for vi in range(1, bucket + 1):
        mv = vi * h * w
        got = kops.grouped_matmul_chained(phases(x0), m=x0.shape[0],
                                          h=h, w=w, m_valid=mv)
        oracle = kops.grouped_matmul_chained(phases(x0[:mv]), m=mv,
                                             h=h, w=w)
        _assert_chained_ragged(got, oracle, mv,
                               launch_bm(phases(x0), x0.shape[0], h, w))


def test_ragged_chained_traced_m_valid_shares_one_executable():
    x0, phases = _chain_case(2, 8, 8)
    traces = []

    @jax.jit
    def run(mv):
        traces.append(1)
        return kops.grouped_matmul_chained(phases(x0), m=x0.shape[0],
                                           h=8, w=8, m_valid=mv)

    for vi in (1, 2):
        mv = vi * 64
        got = run(jnp.int32(mv))
        oracle = kops.grouped_matmul_chained(phases(x0[:mv]), m=mv,
                                             h=8, w=8)
        _assert_chained_ragged(got, oracle, mv,
                               launch_bm(phases(x0), x0.shape[0], 8, 8))
    assert len(traces) == 1, "chained m_valid retraced per value"


def test_ragged_chained_dead_blocks_execute_zero_steps():
    """The no-op guard SKIPS dead M-blocks — it does not merely zero
    them.  rows/image == bm (h*w = 128, a 128-row M-block) makes image
    count == block count, so the grid-step counter must read exactly the
    live blocks' share of the table and the skip ratio is exactly
    1 - n/bucket."""
    b, h, w = 4, 16, 8          # 128 rows/image == bm: 4 images, 4 blocks
    x0, phases = _chain_case(b, h, w)
    m = b * h * w
    spec = gmm._chain_static(phases(x0), 128, w)
    tab = np.asarray(gmm._plan_tiles_chained(m // 128, spec))
    total = tab.shape[1]
    from repro.analysis import tables
    for vi in (1, 2, 3, 4):
        _, steps = gmm.grouped_matmul_chained(
            phases(x0), m=m, h=h, w=w, bm=128, m_valid=vi * h * w,
            debug_steps=True, interpret=True)
        executed = int(np.asarray(steps)[0, 0])
        expected = int((tab[tables.CH_I] < vi).sum())
        assert executed == expected, (vi, executed, expected)
        assert total - executed == total * (1 - vi / b), \
            "skip ratio != 1 - n/bucket"


@pytest.mark.parametrize("valid_images", [1, 2])
def test_ragged_chained_mixed_sources_skip_dead_steps_and_windows(
        valid_images):
    """A launch over x, panel and ring sources (3x3 and 5x5 over two ring
    columns, two n-blocks; 196-row images in 128-row blocks): the live
    rows bit-match the dense launch on just the request's images, the
    live tail block stores zeros past m_valid, and the kernel runs only
    the live blocks' steps and builds only their ring windows."""
    phases, x, panel, m = mixed_chain()
    mv = valid_images * 14 * 14
    bm = launch_bm(phases(x), m, 14, 14)
    live = -(-mv // bm)
    got, cnt = gmm.grouped_matmul_chained(
        phases(x), m=m, h=14, w=14, panels=[panel], m_valid=mv,
        debug_steps=True, interpret=True)
    oracle = gmm.grouped_matmul_chained(
        phases(x[:mv]), m=mv, h=14, w=14, panels=[panel[:mv]],
        interpret=True)
    _assert_chained_ragged(got, oracle, mv, bm)
    spec = gmm._chain_static(phases(x), 128, 14)
    tab = np.asarray(gmm._plan_tiles_chained(-(-m // bm), spec))
    from repro.analysis import tables
    cnt = np.asarray(cnt)
    assert int(cnt[0, 0]) == int((tab[tables.CH_I] < live).sum())
    assert int(cnt[0, 1]) == live * 2


# ---------------------------------------------------------------------------
# model level: the served planned forward
# ---------------------------------------------------------------------------

def test_planned_ragged_forward_bitmatches_dense_one_launch_per_group():
    """Batch-4 plan served with valid_images=2: (a) the first two logits
    rows bit-match the dense (unragged) run of the same padded batch,
    (b) zeroing the padding images changes nothing (per-image isolation
    of the padded rows), (c) the mixed batch runs ONE grouped-family
    launch per co-executed group."""
    cfg = get_reduced("googlenet")
    plan, _ = CNN.plan_cnn(cfg, batch=4)
    params = CNN.init_params(cfg, jax.random.PRNGKey(0))
    imgs = jax.random.normal(jax.random.PRNGKey(1), (4,) + cfg.img)

    dense = CNN.forward_plan(params, cfg, imgs, plan)
    kops.reset_launch_counts()
    ragged = CNN.forward_plan(params, cfg, imgs, plan, valid_images=2)
    launches = dict(kops.KERNEL_LAUNCHES)
    grouped_family = {g.mode for g in plan.groups
                      if g.mode.startswith("grouped")}
    n_grouped_groups = sum(1 for g in plan.groups
                           if g.mode.startswith("grouped"))
    assert grouped_family, "reduced googlenet plan lost its grouped groups"
    assert sum(launches.get(k, 0) for k in
               ("grouped_matmul", "grouped_matmul_pooled",
                "grouped_matmul_concat",
                "grouped_matmul_pooled_concat")) == n_grouped_groups, \
        (launches, plan.mode_counts())

    np.testing.assert_array_equal(np.asarray(ragged)[:2],
                                  np.asarray(dense)[:2])

    junk = imgs.at[2:].set(jax.random.normal(jax.random.PRNGKey(9),
                                             (2,) + cfg.img) * 50.0)
    ragged_junk = CNN.forward_plan(params, cfg, junk, plan, valid_images=2)
    np.testing.assert_array_equal(np.asarray(ragged_junk)[:2],
                                  np.asarray(ragged)[:2])


def test_run_plan_valid_images_requires_batch_context():
    """valid_images without plan.context['batch'] must fail loudly, not
    silently mis-scale the per-group row counts."""
    cfg = get_reduced("googlenet")
    plan, _ = CNN.plan_cnn(cfg, batch=2)
    plan.context.pop("batch", None)
    params = CNN.init_params(cfg, jax.random.PRNGKey(0))
    imgs = jnp.zeros((2,) + cfg.img)
    with pytest.raises(AssertionError):
        CNN.forward_plan(params, cfg, imgs, plan, valid_images=1)


def test_valid_rows_rejects_inconsistent_geometry():
    """_valid_rows must not trust xs[0]: mixed per-branch M is a loud
    error, and M not divisible by the batch (fractional rows/image)
    cannot produce an image-aligned cutoff."""
    a, b = jnp.zeros((128, 4)), jnp.zeros((64, 4))
    with pytest.raises(ValueError, match="mixes lhs row counts"):
        planlib._valid_rows([a, b], 1, 2)
    with pytest.raises(ValueError, match="not a multiple"):
        planlib._valid_rows([jnp.zeros((129, 4))], 1, 2)
    assert planlib._valid_rows([a, a], 1, 2) == 64
    assert planlib._valid_rows([a], None, 2) is None


def test_planned_ragged_chained_forward_bitmatches_dense():
    """The chained (cross-module) plan served with valid_images: valid
    logits bit-match the dense run and are invariant to garbage in the
    padding images — the masked chained launch, not a caller-side slice,
    provides the isolation."""
    cfg = get_reduced("googlenet")
    plan, _ = CNN.plan_cnn(cfg, batch=4, chain_modules=True)
    assert any(g.mode == "grouped_chained" for g in plan.groups), \
        "chain_modules plan lost its chained groups"
    params = CNN.init_params(cfg, jax.random.PRNGKey(0))
    imgs = jax.random.normal(jax.random.PRNGKey(1), (4,) + cfg.img)

    dense = CNN.forward_plan(params, cfg, imgs, plan)
    for vi in (1, 3):
        ragged = CNN.forward_plan(params, cfg, imgs, plan, valid_images=vi)
        np.testing.assert_array_equal(np.asarray(ragged)[:vi],
                                      np.asarray(dense)[:vi])
    junk = imgs.at[2:].set(jax.random.normal(jax.random.PRNGKey(9),
                                             (2,) + cfg.img) * 50.0)
    ragged2 = CNN.forward_plan(params, cfg, junk, plan, valid_images=2)
    np.testing.assert_array_equal(np.asarray(ragged2)[:2],
                                  np.asarray(dense)[:2])


# ---------------------------------------------------------------------------
# serving: admission, oversized splits, request-level latency
# ---------------------------------------------------------------------------

def test_serve_split_request_conserves_images():
    from repro.launch import serve

    imgs = np.arange(5 * 2 * 2 * 1, dtype=np.float32).reshape(5, 2, 2, 1)
    chunks = serve._split_request(7, imgs, 0.1, max_images=2)
    assert [c["imgs"].shape[0] for c in chunks] == [2, 2, 1]
    assert all(c["rid"] == 7 for c in chunks)
    np.testing.assert_array_equal(
        np.concatenate([c["imgs"] for c in chunks]), imgs)


def test_serve_admit_edf_anchor_and_waste_packing():
    from repro.core.cost_model import padded_m_factor
    from repro.launch import serve

    def chunk(rid, n, dl):
        return {"rid": rid, "imgs": np.zeros((n, 2, 2, 1), np.float32),
                "deadline": dl}

    # rows_per_image = 128 = bm, so factor(n images) =
    # bucket_for(n)/n and the packing choice is visible.  EDF: the
    # earliest deadline (r2) anchors even from the back of the queue.
    # Fill: r1 (earlier deadline) would leave 3 images in the 4-bucket
    # (factor 4/3); r0 fills it exactly (factor 1.0) — waste, not queue
    # order, picks the rider.
    pending = [chunk(0, 2, 0.9), chunk(1, 1, 0.5), chunk(2, 2, 0.1)]
    batch, total = serve._admit(pending, 4, [1, 2, 4], 128,
                                padded_m_factor)
    assert batch[0]["rid"] == 2 and total == 4
    assert {c["rid"] for c in batch} == {0, 2}

    # conservation: repeated admission drains every chunk exactly once
    pending = [chunk(i, 1 + i % 3, 0.1 * i) for i in range(7)]
    want = sum(c["imgs"].shape[0] for c in pending)
    got = 0
    while pending:
        batch, total = serve._admit(pending, 4, [1, 2, 4], 128,
                                    padded_m_factor)
        got += total
    assert got == want, "admission dropped or duplicated a chunk"


def test_serving_loop_serves_every_submitted_image():
    """End-to-end regression for the oversized-truncation bug: the
    stream contains requests larger than max_images (sizes reach
    max_images + 1), and every submitted image must reach a launch.
    Also pins the request-level latency contract: one sample per
    request, not per dispatch."""
    from repro.launch.serve import serve_cnn_metrics

    m = serve_cnn_metrics(get_reduced("googlenet"), max_images=2,
                          num_requests=5, seed=3)
    assert m["images"] == m["images_submitted"] > 0
    assert m["latency_samples"] == m["requests"] == 5
    assert m["p99_ms"] >= m["p50_ms"] > 0
    assert m["dispatch_p99_ms"] >= m["dispatch_p50_ms"] > 0
    assert m["plan_cache"]["hit_rate"] == 1.0
