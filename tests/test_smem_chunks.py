"""SMEM chunking: every grouped-family launch prefetches its offset table
into the chip's 1 MiB of SMEM, the table grows with M, and a launch whose
table would not fit runs as image-aligned M-chunks.  Checks that the
planner sizes the chunks so every table fits (at full googlenet width),
that the per-block table shape the sizing extrapolates from is exact,
and that chunked launches compute what one launch computes."""
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import budgets, tables
from repro.configs import get_config, get_reduced
from repro.core import cost_model as cm
from repro.models import cnn as CNN

gmm = importlib.import_module("repro.kernels.grouped_matmul")

TABLE_MODES = ("grouped", "grouped_pooled", "grouped_concat",
               "grouped_chained")


@pytest.mark.parametrize("batch,kw,chunked_groups", [
    # the serving ladder's top bucket: at 1024-row M-blocks every chained
    # launch's table fits one launch
    pytest.param(8, dict(chain_modules=True), 0, id="8-kw0"),
    # chained at 64 images: the stem and 3b split in two
    pytest.param(64, dict(chain_modules=True), 2, id="64-chained"),
    # the README's training batch
    pytest.param(32, dict(train=True), 2, id="32-kw1"),
])
def test_planner_chunks_fit_smem(batch, kw, chunked_groups):
    cfg = get_config("googlenet")
    plan, _ = CNN.plan_cnn(cfg, batch, **kw)
    graph = plan.context["graph"]
    dirs = ("fwd", "bwd") if kw.get("train") else ("fwd",)
    chunked = 0
    for g in plan.groups:
        if g.mode not in TABLE_MODES:
            continue
        m = budgets.group_launches(graph, g, dirs)[0][0]
        rows = g.chunk_rows or m
        assert g.chunks == -(-m // rows)
        assert budgets.group_smem_bytes(graph, g, rows, dirs) \
            <= cm.SMEM_PREFETCH_BYTES
        if g.chunks > 1:
            chunked += 1
            # image-aligned, and one launch over all M would not fit
            assert rows % (m // batch) == 0
            assert budgets.group_smem_bytes(graph, g, m, dirs) \
                > cm.SMEM_PREFETCH_BYTES
            assert f"SMEM: {g.chunks} launches" in g.reason
    assert chunked == chunked_groups
    bwd = plan.context["backward"]
    assert [g.chunks for g in reversed(bwd.groups)] == \
        [g.chunks if g.mode != "serial" else 1 for g in plan.groups]


@pytest.mark.parametrize("family", ["plain", "concat", "pooled", "bwd",
                                    "chained"])
def test_table_columns_linear_in_m_blocks(family):
    # chunk sizing prices a launch from the ONE-block table shape: exact
    # only if every family's step count is linear in the M-block count
    kbs, nbs = (2, 1, 3), (1, 2, 1)
    build = {
        "plain": lambda mb: gmm._plan_tiles(mb, kbs, nbs),
        "concat": lambda mb: gmm._plan_tiles_concat(mb, kbs, nbs),
        "pooled": lambda mb: gmm._plan_tiles_pooled(mb, kbs, nbs,
                                                    (9, 1, 1), True),
        "bwd": lambda mb: gmm._plan_tiles_bwd(mb, kbs, nbs),
        "chained": lambda mb: gmm._plan_tiles_chained(mb, (
            (("x", 2, 1, (0,)), ("x", 1, 1, ())),
            (("ring", (((-9, -1, -1), (0, 0, 0), (9, 1, 1)), (0,)), 2,
              ()),))),
    }[family]
    r, s = build(1).shape
    for mb in (2, 5, 13):
        tab = build(mb)
        assert tab.shape == (r, mb * s)
        assert gmm.launch_smem_bytes((r, s), mb, 0) \
            == tables.smem_bytes(tab.shape)


def test_smem_bytes_padding():
    # the v5e compiler's reported allocation sizes for prefetched tables
    assert tables.smem_bytes((8, 39200)) == 1257472
    assert tables.smem_bytes((3, 100000)) == 1601536
    assert tables.smem_bytes((17, 16000)) == 1536000
    assert tables.smem_bytes((270000,)) == 1081344


def test_chunk_rows_rejects_what_cannot_fit(monkeypatch):
    monkeypatch.setattr(cm, "SMEM_PREFETCH_BYTES", 1024)
    with pytest.raises(ValueError, match="SMEM"):
        gmm.smem_chunk_rows(4096, 128, (7, 4), unit=1024)


def test_group_whose_image_cannot_fit_runs_serial(monkeypatch):
    # like a VMEM overflow: no chunking helps when one image's table alone
    # busts SMEM, so the group is budget-infeasible — and says why
    monkeypatch.setattr(cm, "SMEM_PREFETCH_BYTES", 4 * 1024)
    plan, _ = CNN.plan_cnn(get_reduced("googlenet"), 2, chain_modules=True)
    assert not [g for g in plan.groups if g.mode in TABLE_MODES]
    assert any("SMEM" in g.reason for g in plan.groups)


# ---------------------------------------------------------------------------
# chunked == unchunked on the reduced config (interpret mode)
# ---------------------------------------------------------------------------

def _one_image_chunks(plan, batch):
    """The plan with every grouped-family group forced into one launch
    per image."""
    graph = plan.context["graph"]
    groups = []
    for g in plan.groups:
        if g.mode in TABLE_MODES:
            m = budgets.group_launches(graph, g)[0][0]
            g = dataclasses.replace(g, chunk_rows=m // batch, chunks=batch)
        groups.append(g)
    return dataclasses.replace(plan, groups=groups)


@pytest.mark.parametrize("path", ["forward", "ragged", "grad"])
def test_chunked_launches_match_one_launch(path):
    cfg = get_reduced("googlenet")
    batch = 2
    plan, _ = CNN.plan_cnn(cfg, batch, chain_modules=path != "grad",
                           train=path == "grad")
    chunked = _one_image_chunks(plan, batch)
    assert any(g.chunks == batch for g in chunked.groups)
    params = CNN.init_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (batch,) + cfg.img)
    if path == "grad":
        labels = jnp.arange(batch, dtype=jnp.int32)

        def run(p):
            return jax.jit(jax.grad(lambda pp: CNN.loss_fn(
                pp, cfg, {"images": x, "labels": labels}, plan=p,
                interpret=True)[0]))(params)
        one, many = run(plan), run(chunked)
        # dW/db sum per-chunk partials: f32 reassociation only
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(many)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        return
    valid = 1 if path == "ragged" else None

    def fwd(p):
        return lambda pp, xx: CNN.forward_plan(pp, cfg, xx, p,
                                               interpret=True,
                                               valid_images=valid)

    def run(p):
        return jax.jit(fwd(p))(params, x)
    # the executor launches what the plan records: one per chunk
    from repro.core.launch_count import count_launches

    def launches(p):
        return count_launches(fwd(p), params, x)["pallas_call"]
    assert launches(chunked) - launches(plan) == sum(
        g.chunks - 1 for g in chunked.groups)
    # rows are image-local: chunks reproduce one launch's rows exactly.
    # Ragged, only the valid images' rows have a value: a block past
    # m_valid is skipped and holds what it held, and where an M-block
    # spans both images one launch zeroes the padding image's rows that
    # a skipped chunk leaves as they were.
    rows = batch if valid is None else valid
    np.testing.assert_array_equal(np.asarray(run(plan))[:rows],
                                  np.asarray(run(chunked))[:rows])
