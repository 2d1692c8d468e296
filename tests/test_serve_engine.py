"""The CNN stream-serving engine (``launch.serve.CNNServer``) on a tiny
inception network, kernels interpreted: packing and dispatch give what the
inline pack-and-call gives, and the counters add up."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import budgets
from repro.core import plan_cache
from repro.launch import serve
from repro.models.cnn import CNNConfig, InceptionSpec, init_params

TINY = CNNConfig(name="tiny-engine", img=(16, 16, 3),
                 stem=((3, 16, 2),),
                 modules=(InceptionSpec(8, 8, 16, 4, 8, 8),),
                 pool_between=(), num_classes=10)


@pytest.fixture(scope="module")
def engine():
    eng = serve.CNNServer(TINY, init_params(TINY, jax.random.PRNGKey(0)),
                          max_images=4)
    eng.warm()
    return eng


def _images(sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((n,) + TINY.img).astype(np.float32)
            for n in sizes]


def _inline(engine, arrs):
    """The pack and call as written inline before the engine: the smallest
    bucket that holds the images, zero-filled, the bucket's executable."""
    n = sum(r.shape[0] for r in arrs)
    bucket = next(b for b in engine.ladder if n <= b)
    imgs = np.zeros((bucket,) + TINY.img, np.float32)
    imgs[:n] = np.concatenate(arrs)
    out = engine.entries[bucket].executable(engine.params, jnp.asarray(imgs),
                                            jnp.int32(n))
    return np.asarray(out), bucket, n


@pytest.mark.parametrize("sizes", [[1], [2, 1], [1, 1, 1], [3, 1]])
def test_pack_and_run_match_the_inline_dispatch(engine, sizes):
    arrs = _images(sizes)
    imgs, bucket, n = engine.pack(arrs)
    want, want_bucket, want_n = _inline(engine, arrs)
    assert (bucket, n) == (want_bucket, want_n)
    assert imgs.shape == (bucket,) + TINY.img and imgs.dtype == np.float32
    np.testing.assert_array_equal(imgs[:n], np.concatenate(arrs))
    assert not imgs[n:].any()
    np.testing.assert_array_equal(np.asarray(engine.run(imgs, bucket, n)),
                                  want)


def test_counters_add_up_and_reset(engine):
    engine.reset_counters()
    pending = []
    for rid, r in enumerate(_images([3, 1, 5, 2, 1], seed=1)):
        pending.extend(engine.split(rid, r, 0.1 * rid))
    chunks = len(pending)
    assert chunks == 6                     # the 5-image request splits
    longest, served = chunks, []
    while pending:
        batch, total = engine.admit(pending)
        imgs, bucket, n = engine.pack([c["imgs"] for c in batch])
        assert n == total
        engine.run(imgs, bucket, n)
        served.append((bucket, n))
    c = engine.counters
    assert c["chunks_admitted"] == chunks
    assert c["pending_max"] == longest
    assert c["dispatches"] == {b: sum(1 for bb, _ in served if bb == b)
                               for b in engine.ladder}
    assert c["valid_images"] == 12
    assert c["valid_images"] + c["padded_images"] == sum(
        b for b, _ in served)
    assert all(c[k] > 0 for k in ("h2d_s", "launch_s", "wait_s"))
    engine.reset_counters()
    assert engine.counters == {"dispatches": dict.fromkeys(engine.ladder, 0),
                               "valid_images": 0, "padded_images": 0,
                               "chunks_admitted": 0, "pending_max": 0,
                               "h2d_s": 0.0, "launch_s": 0.0, "wait_s": 0.0}


def test_setup_record_per_bucket(engine):
    s = engine.setup
    for key in ("lower_s", "warm_s"):
        assert set(s[key]) == set(engine.ladder)
        assert all(v >= 0 for v in s[key].values())
    for b in engine.ladder:
        assert s["input_host_bytes"][b] == b * 16 * 16 * 3 * 4
        assert s["input_device_bytes"][b] >= s["input_host_bytes"][b]


def test_engine_holds_the_plan_caches_entries(engine):
    for b, entry in engine.entries.items():
        assert plan_cache.cached_cnn_plan(TINY, b, chain_modules=True) \
            is entry
        assert entry.executable is not None


def test_setup_counts_the_chained_launches_steps():
    """``setup`` counts per bucket what the chained launches do, read off
    their offset tables as the warm dispatch traces the step; an engine
    whose executables were traced before it reads the same counts from an
    abstract trace.  The window builds sit far below the ring steps: one
    per (phase, block, ring column), not one per tap.  ``chained_bm``
    holds each chained launch's (M-block, M-block count), the planner's
    for each of the bucket's chained groups."""
    from repro.configs import get_reduced
    cfg = get_reduced("googlenet")
    params = init_params(cfg, jax.random.PRNGKey(0))
    first = serve.CNNServer(cfg, params, max_images=2)
    first.warm()
    again = serve.CNNServer(cfg, params, max_images=2)
    again.warm()
    for eng in (first, again):
        assert set(eng.setup["chained_steps"]) == set(eng.ladder)
    s = first.setup
    assert again.setup["chained_steps"] == s["chained_steps"]
    assert again.setup["ring_window_builds"] == s["ring_window_builds"]
    assert again.setup["chained_bm"] == s["chained_bm"]
    for b in first.ladder:
        steps, builds = s["chained_steps"][b], s["ring_window_builds"][b]
        assert steps["x"] > 0 and steps["ring"] > 0
        assert 0 < builds * 9 <= steps["ring"]
        plan = first.entries[b].plan
        graph = plan.context["graph"]
        assert s["chained_bm"][b] == [
            budgets.chained_spec(graph, g.chain, g.pools)[:2]
            for g in plan.groups if g.mode == "grouped_chained"]
