"""Compile the main path's grouped-family kernels for a v5e chip, at full
googlenet width, without the chip: the TPU compiler is installed, and it
compiles for a described ``v5e:2x2`` topology.  Nothing runs — these are
what the chip's compiler would refuse (SMEM, VMEM, tiling), caught here.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import cost_model as cm
from repro.core import plan as planlib
from repro.kernels import ops
from repro.models import cnn as CNN

CFG = get_config("googlenet")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to an enabled persistent
    # cache but cannot be read back without the chip: keep it off here
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _compile(fn, *args):
    """AOT-compile ``fn`` for the described chip; returns the number of
    Pallas (Mosaic) kernels in the compiled program."""
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _params(sharding):
    shapes = jax.eval_shape(lambda: CNN.init_params(CFG,
                                                    jax.random.PRNGKey(0)))
    return jax.tree.map(lambda s: _sds(s.shape, sharding, s.dtype), shapes)


def _run_group(plan, group, seed_env):
    """fn(params, seeds, valid_images) running ``group`` of ``plan``
    through the plan executor with compiled (not interpreted) kernels."""
    part = planlib.Plan([group], dict(plan.context))

    def fn(params, seeds, valid):
        impls, _ = CNN._plan_impls(params, CFG, interpret=False)
        env = dict(zip(seed_env, seeds))
        planlib.run_plan(impls, env, part, interpret=False,
                         valid_images=valid)
        out = env[group.join or group.chain[-1][-1]]
        return out.panels
    return fn


def _group(plan, first_op):
    return next(g for g in plan.groups if g.ops[0] == first_op)


def test_stem_chain_serving_bucket8(one_chip):
    # refused at bucket 8 before SMEM chunking: the stem chain's offset
    # table alone is 1.5 MiB
    plan, _ = CNN.plan_cnn(CFG, 8, chain_modules=True)
    stem = _group(plan, "stem0")
    assert stem.mode == "grouped_chained" and stem.chunks > 1
    n = _compile(_run_group(plan, stem, ["input"]), _params(one_chip),
                 [_sds((8,) + CFG.img, one_chip)], _sds((), one_chip,
                                                        jnp.int32))
    assert n == stem.chunks


def test_ragged_chained_module_bucket8(one_chip):
    # inc1 (3b) at bucket 8: a two-phase chained launch with a traced
    # m_valid, its pool-proj pool folded in, split into SMEM chunks
    plan, _ = CNN.plan_cnn(CFG, 8, chain_modules=True)
    inc1 = _group(plan, "inc1/r5")
    assert inc1.mode == "grouped_chained"
    n = _compile(_run_group(plan, inc1, ["inc0/join"]), _params(one_chip),
                 [_sds((8, 56, 56, 256), one_chip)],
                 _sds((), one_chip, jnp.int32))
    assert n == inc1.chunks


def test_inc1_combined_backward_batch32(one_chip):
    # the 3b 3x3/5x5 pair's ONE combined dx + dW/db launch at the
    # training batch 32: refused before SMEM chunking (1.2 MiB table)
    plan, _ = CNN.plan_cnn(CFG, 32, train=True)
    g = _group(plan, "inc1/5x5")
    assert g.mode == "grouped_concat" and g.chunks > 1
    graph = plan.context["graph"]
    shapes = [cm.gemm_shape(graph.ops[n]) for n in g.ops if n != g.join]
    xs = [_sds((m, k), one_chip) for m, k, _ in shapes]
    ws = [_sds((k, n), one_chip) for _, k, n in shapes]
    dys = [_sds((m, n), one_chip) for m, _, n in shapes]
    n = _compile(lambda xs, ws, dys, ys: ops.grouped_matmul_bwd(
        xs, ws, dys, ys, interpret=False, chunk_rows=g.chunk_rows),
        xs, ws, dys, dys)
    assert n == g.chunks


def test_pooled_quad_batch16(one_chip):
    # 4a's quad at the training batch 16: the 1x1/r3/r5 trio (one wide
    # GEMM) pools its 3x3/s2 input in-kernel from 9 tap views; the
    # pool-proj's 81-tap chain folds at pack time
    m, c, n3, npp = 16 * 14 * 14, 480, 192 + 96 + 16, 64
    taps = [_sds((m, c), one_chip) for _ in range(9)]
    n = _compile(lambda taps, xp, w, wp, b, bp: ops.grouped_matmul_pooled(
        [tuple(taps), xp], [w, wp], [b, bp], relu=True, interpret=False),
        taps, _sds((m, c), one_chip), _sds((c, n3), one_chip),
        _sds((c, npp), one_chip), _sds((n3,), one_chip),
        _sds((npp,), one_chip))
    assert n == 1
