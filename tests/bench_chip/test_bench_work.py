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

# GoogLeNet's forward as the harness counted it before a configuration's
# module listed its own ops: FLOPs and bytes summed over every op and over
# the plan's kernels' ops, per batch.  serve_mfu and
# plan_kernels_roofline.serve read these, so they may not move.
GOOGLENET = {
    1: (11634060800.0, 196221440, 11632012800.0, 138671808),
    2: (23268121600.0, 364448672, 23264025600.0, 253449408),
    4: (46536243200.0, 700903136, 46528051200.0, 483004608),
    8: (93072486400.0, 1373812064, 93056102400.0, 942115008),
}
GOOGLENET_PLANNED = {"stem0", "stem1", "stem2"} | {
    f"inc{i}/{b}" for i in range(9)
    for b in ("1x1", "r3", "3x3", "r5", "5x5", "pp")}
GOOGLENET_NOT_PLANNED = {"gap", "head"} | {
    f"inc{i}/pool" for i in (0, 2, 7)} | {
    f"inc{i}/pppool" for i in range(9)}


def _xla(fn, *shapes):
    c = jax.jit(fn).lower(*shapes).compile().cost_analysis()
    return c[0] if isinstance(c, list) else c


@pytest.mark.parametrize("kh,kw,cin,cout,stride,h,w,padding", [
    (1, 1, 8, 16, 1, 8, 8, "SAME"), (3, 3, 4, 8, 1, 8, 8, "SAME"),
    (5, 5, 4, 8, 1, 9, 9, "SAME"), (7, 7, 3, 16, 2, 16, 16, "SAME"),
    (3, 3, 5, 6, 2, 7, 7, "SAME"),
    # the rectangular and VALID shapes of Inception-v3's modules and stem
    (1, 7, 8, 12, 1, 17, 17, "SAME"), (7, 1, 8, 12, 1, 17, 17, "SAME"),
    (1, 3, 8, 12, 1, 17, 17, "SAME"), (3, 1, 8, 12, 1, 17, 17, "SAME"),
    (1, 7, 8, 12, 1, 8, 8, "SAME"), (7, 1, 8, 12, 1, 8, 8, "SAME"),
    (1, 3, 8, 12, 1, 8, 8, "SAME"), (3, 1, 8, 12, 1, 8, 8, "SAME"),
    (3, 3, 8, 12, 2, 35, 35, "VALID"), (3, 3, 8, 12, 2, 17, 17, "VALID"),
    (3, 3, 4, 8, 1, 37, 37, "VALID"),
    # a rectangular kernel on a rectangular image: kh goes with the height
    (1, 7, 4, 8, 1, 17, 9, "SAME"), (7, 1, 4, 8, 2, 9, 17, "VALID")])
def test_conv_flops_equal_xla(kh, kw, cin, cout, stride, h, w, padding):
    def conv(x, wt):
        return jax.lax.conv_general_dilated(
            x, wt, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    x = jax.ShapeDtypeStruct((2, h, w, cin), jnp.float32)
    wt = jax.ShapeDtypeStruct((kh, kw, cin, cout), jnp.float32)
    op, y = work.conv("c", x.shape, cout, kh, kw, stride, padding,
                      dtype="float32", kernel=True)
    assert op.flops == _xla(conv, x, wt)["flops"]
    assert y == jax.eval_shape(conv, x, wt).shape


def test_avg_pool_bytes_are_one_read_and_one_write():
    # 3x3/1 SAME: the window sum (the average's one pass over the input;
    # its divide fuses into it) reads the input and writes the output once,
    # besides its 4-byte initial value
    x = jax.ShapeDtypeStruct((2, 17, 17, 8), jnp.float32)
    xla = _xla(lambda x: jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, 3, 3, 1), (1, 1, 1, 1), "SAME"), x)
    op, y = work.pool("p", x.shape, 3, 1, "SAME", "avg", dtype="float32")
    assert y == x.shape and op.flops == 0.0
    assert op.bytes == xla["bytes accessed"] - 4 == 2 * 4 * 2 * 17 * 17 * 8
    assert op == work.pool("p", x.shape, 3, 1, "SAME", "max",
                           dtype="float32")[0]


def test_forward_flops_match_xla_cost_analysis_of_the_reference():
    ref = chiptiny.reference_module()
    p = jax.eval_shape(lambda: ref.init_params(REDUCED,
                                               jnp.zeros(2, jnp.uint32)))
    x = jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32)
    xla = _xla(lambda p, x: ref.forward(p, REDUCED, x, "highest"),
               p, x)["flops"]
    ours = work.forward_flops(ref.forward_ops(REDUCED, 2))
    # XLA adds the elementwise ops (bias, ReLU, pools, mean) we count as
    # bytes only: a few percent at this size, 0.5% at googlenet's
    assert ours <= xla <= 1.05 * ours


def test_ideal_time_and_plan_ops():
    ops = chiptiny.reference_module().forward_ops(REDUCED, 4)
    fwd = work.forward_flops(ops)
    pk = peaks.peaks_for("TPU v5 lite")
    assert work.ideal_s(ops, pk) >= fwd / pk["flops_bf16"]
    planned = work.plan_ops(ops)
    assert all(op.kernel for op in planned)
    assert {op.name for op in ops} - {op.name for op in planned} == {
        "gap", "head", "inc0/pppool", "inc1/pool", "inc1/pppool"}


def test_googlenet_counts():
    g = chiptiny.googlenet_sizes()
    ref = chiptiny.reference_module()
    assert work.forward_flops(ref.forward_ops(g, 1)) == 11.6340608e9
    assert g["gflop_per_image_forward"] == 11.6340608
    for batch, (flops, nbytes, plan_flops, plan_bytes) in GOOGLENET.items():
        ops = ref.forward_ops(g, batch)
        planned = work.plan_ops(ops)
        assert len(ops) == 71
        assert [op.name for op in planned] == [
            op.name for op in ops if op.name in GOOGLENET_PLANNED]
        assert {op.name for op in planned} == GOOGLENET_PLANNED
        assert sum(op.flops for op in ops) == flops
        assert sum(op.bytes for op in ops) == nbytes
        assert sum(op.flops for op in planned) == plan_flops
        assert sum(op.bytes for op in planned) == plan_bytes
        assert {op.name for op in ops} - {op.name for op in planned} == \
            GOOGLENET_NOT_PLANNED
    with pytest.raises(KeyError, match="peaks table"):
        peaks.peaks_for("TPU v9")


@pytest.mark.parametrize("bad", [
    lambda: work.out_size(8, 3, 1, "FULL"),
    lambda: work.pool("p", (1, 8, 8, 4), 3, 1, "SAME", "min",
                      dtype="float32")])
def test_unknown_padding_or_pool_is_refused(bad):
    with pytest.raises(ValueError):
        bad()
