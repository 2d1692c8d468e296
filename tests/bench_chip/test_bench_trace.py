"""The trace reduction, on a synthesized trace shaped as a TPU's: busy and
idle union, kernel time per ``plan[mode:op]`` scope, kernel launches, and
idle gaps named by the harness span the host was in."""
import chiptiny  # noqa: F401  (puts the harness on the path)
import pytest
from chipbench import trace as T

KERNEL = ', custom_call_target="tpu_custom_call"'


def ev(name, start_ms, dur_ms):
    return T.Event(name, start_ms * 1e6, dur_ms * 1e6)


def kernel(scope, start_ms, dur_ms, n=1):
    return ev(f"%{scope}.{n} = f32[8,128]{{1,0}} custom-call(f32[8,128] "
              f"%x){KERNEL}", start_ms, dur_ms)


def test_union_merges_overlaps_and_clips_to_window():
    evs = [ev("a", 0, 10), ev("b", 5, 10), ev("c", 30, 10), ev("d", 95, 10)]
    assert T.merged(evs, 0, 100e6) == [(0, 15e6), (30e6, 40e6),
                                       (95e6, 100e6)]
    assert T.busy_ns(evs, 0, 100e6) == pytest.approx(30e6)
    assert T.gaps(evs, 0, 100e6) == [(15e6, 30e6), (40e6, 95e6)]
    assert T.busy_ns(evs, 50e6, 60e6) == 0
    assert T.gaps(evs, 50e6, 60e6) == [(50e6, 60e6)]


@pytest.mark.parametrize("name,scope", [
    ("%plan_grouped_chained_inc1.r5_.2 = f32[1]", "grouped_chained:inc1/r5"),
    ("%jvp_plan_grouped_concat_inc1.5x5__.1 = f32[1]",
     "grouped_concat:inc1/5x5"),
    ("%transpose_jvp_plan_serial_stem2___.3 = f32[1]", "serial:stem2 bwd"),
    ("%fusion.1278 = f32[1] fusion(...)", None),
    ("jit(step)/plan[grouped:inc0/r3]/mul", "grouped:inc0/r3"),
])
def test_scope_from_the_instruction_name(name, scope):
    assert T.scope_of(ev(name, 0, 1)) == scope


def test_kernel_launch_is_a_tpu_custom_call():
    assert T.is_kernel_launch(kernel("plan_grouped_chained_stem0_", 0, 1))
    assert T.is_kernel_launch(kernel("pallas_call", 0, 1))
    assert not T.is_kernel_launch(ev(
        '%custom-call.4 = f32[4] custom-call(f32[2] %a, f32[2] %b), '
        'custom_call_target="ConcatBitcast"', 0, 1))
    assert not T.is_kernel_launch(ev("%fusion.1 = f32[1] fusion()", 0, 1))
    assert T.op_key(ev("%copy-start.208 = (f32[1]) copy-start()", 0, 1)) \
        == "xla:copy-start"
    assert T.op_key(kernel("transpose_jvp_plan_grouped_concat_inc1.5x5__",
                           0, 1)) == "plan[grouped_concat:inc1/5x5 bwd]"


def _trace():
    ops = [kernel("jvp_plan_grouped_chained_stem0_", 10, 20, 1),
           kernel("transpose_jvp_plan_grouped_inc0.1x1__", 30, 10, 2),
           ev("%fusion.9 = f32[1] fusion(f32[1] %a)", 40, 5),
           kernel("jvp_plan_grouped_chained_stem0_", 70, 20, 1)]
    host = [ev("bench.window", 0, 100), ev("bench.step", 5, 40),
            ev("bench.next_batch", 46, 23), ev("bench.step", 69, 31)]
    return T.Trace({"/device:TPU:0": ops}, host)


def test_reduce_window_busy_kernels_launches_and_named_gaps():
    r = T.reduce(_trace())
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.055)
    assert r["plan_kernels_s"] == pytest.approx(0.050)
    assert r["launches"] == 3
    ops = dict(r["device_ops"])
    assert ops["plan[grouped_chained:stem0]"] == pytest.approx(0.040)
    assert ops["plan[grouped:inc0/1x1 bwd]"] == pytest.approx(0.010)
    assert ops["xla:fusion"] == pytest.approx(0.005)
    # gaps: 0-10 (step from 5), 45-70 (next_batch 46-69), 90-100 (step)
    idle = dict(r["idle_by_span"])
    assert idle["next_batch"] == pytest.approx(0.025)
    assert idle["step"] == pytest.approx(0.020)
    assert r["idle_gaps"][0] == ["next_batch", pytest.approx(0.025)]
    assert r["spans"]["bench.step"] == 2


def test_short_gaps_lie_between_ops():
    ops = [kernel("plan_grouped_inc0.r3_", 0, 1),
           kernel("plan_grouped_inc0.r3_", 1.005, 98.995)]
    r = T.reduce(T.Trace({"/device:TPU:0": ops},
                         [ev("bench.window", 0, 100),
                          ev("bench.dispatch", 0, 100)]))
    assert dict(r["idle_by_span"]) == {T.SHORT_GAP: pytest.approx(5e-6)}


def test_reduce_averages_chips_and_needs_a_window():
    t = _trace()
    t.device_ops["/device:TPU:1"] = [kernel("plan_grouped_inc0.r3_", 0, 100)]
    r = T.reduce(t)
    assert r["busy_s"] == pytest.approx((0.055 + 0.100) / 2)
    assert r["launches"] == pytest.approx(2.0)
    with pytest.raises(RuntimeError, match="bench.window"):
        T.reduce(T.Trace(t.device_ops, []))
    with pytest.raises(RuntimeError, match="no device operations"):
        T.reduce(T.Trace({}, t.host_spans))
