"""``work.py``'s counts against XLA's cost analysis of the plain reference."""
import chiptiny
import jax
import jax.numpy as jnp
import pytest
from chipbench import work, peaks

REDUCED = {"img": [32, 32, 3], "num_classes": 10, "dtype": "float32",
           "stem": [[7, 16, 2], [1, 16, 1], [3, 24, 1]],
           "modules": [[8, 8, 16, 4, 8, 8], [16, 8, 24, 4, 8, 8]],
           "pool_between": [1]}


def _xla_flops(fn, *shapes):
    c = jax.jit(fn).lower(*shapes).compile().cost_analysis()
    return (c[0] if isinstance(c, list) else c)["flops"]


@pytest.mark.parametrize("k,cin,cout,stride,h", [
    (1, 8, 16, 1, 8), (3, 4, 8, 1, 8), (5, 4, 8, 1, 9), (7, 3, 16, 2, 16),
    (3, 5, 6, 2, 7)])
def test_conv_flops_equal_xla(k, cin, cout, stride, h):
    def conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    xla = _xla_flops(conv, jax.ShapeDtypeStruct((2, h, h, cin), jnp.float32),
                     jax.ShapeDtypeStruct((k, k, cin, cout), jnp.float32))
    sizes = {"img": [h, h, cin], "num_classes": 1, "dtype": "float32",
             "stem": [[k, cout, stride]], "modules": [], "pool_between": []}
    assert work.forward_ops(sizes, 2)[0].flops == xla


def test_forward_flops_match_xla_cost_analysis_of_the_reference():
    ref = chiptiny.reference_module()
    p = jax.eval_shape(lambda: ref.init_params(REDUCED,
                                               jnp.zeros(2, jnp.uint32)))
    x = jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32)
    xla = _xla_flops(lambda p, x: ref.forward(p, REDUCED, x, "highest"),
                     p, x)
    ours = work.forward_flops(REDUCED, 2)
    # XLA adds the elementwise ops (bias, ReLU, pools, mean) we count as
    # bytes only: a few percent at this size, 0.5% at googlenet's
    assert ours <= xla <= 1.05 * ours


def test_ideal_time_and_plan_ops():
    fwd = work.forward_flops(REDUCED, 4)
    pk = peaks.peaks_for("TPU v5 lite")
    ops = work.forward_ops(REDUCED, 4)
    assert work.ideal_s(ops, pk) >= fwd / pk["flops_bf16"]
    planned = work.plan_ops(ops)
    assert {op.name for op in ops} - {op.name for op in planned} == {
        "gap", "head", "inc0/pppool", "inc1/pool", "inc1/pppool"}


def test_googlenet_counts():
    g = chiptiny.googlenet_sizes()
    assert work.forward_flops(g, 1) / 1e9 == pytest.approx(
        g["gflop_per_image_forward"])
    with pytest.raises(KeyError, match="peaks table"):
        peaks.peaks_for("TPU v9")
