"""The trace reduction, on a synthesized trace shaped as a TPU's: busy and
idle union, kernel time per ``plan[mode:op]`` scope read by the
configuration's kernel ops, kernel launches, and idle gaps named by the
harness span the host was in."""
import functools

import chiptiny
import pytest
from chipbench import trace as T

KERNEL = ', custom_call_target="tpu_custom_call"'
#: kernel ops of another inception network, named as Inception-v3's are
OTHER = ("mixed6b/b7x7dbl_2", "red0/b3x3", "inc3/b7x7_1", "inc3/b7x7")


@functools.cache
def googlenet_ops() -> tuple[str, ...]:
    """GoogLeNet's kernel ops, as its ``forward_ops`` marks them."""
    ops = chiptiny.reference_module().forward_ops(
        chiptiny.googlenet_sizes(), 1)
    return tuple(op.name for op in ops if op.kernel)


def pattern(ops=None):
    return T.scope_pattern(googlenet_ops() if ops is None else ops)


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


def case(name, scope, ops=None):
    """A scope case; ``ops`` None reads it by GoogLeNet's kernel ops."""
    return pytest.param(name, scope, ops, id=f"{name}-{scope}")


@pytest.mark.parametrize("name,scope,ops", [
    case("%plan_grouped_chained_inc1.r5_.2 = f32[1]",
         "grouped_chained:inc1/r5"),
    case("%jvp_plan_grouped_concat_inc1.5x5__.1 = f32[1]",
         "grouped_concat:inc1/5x5"),
    case("%transpose_jvp_plan_serial_stem2___.3 = f32[1]",
         "serial:stem2 bwd"),
    case("%fusion.1278 = f32[1] fusion(...)", None),
    case("jit(step)/plan[grouped:inc0/r3]/mul", "grouped:inc0/r3"),
    case("%plan_grouped_chained_mixed6b.b7x7dbl_2_.2 = f32[1]",
         "grouped_chained:mixed6b/b7x7dbl_2", OTHER),
    case("%plan_grouped_pooled_mixed6b.b7x7dbl_2_.1 = f32[1]",
         "grouped_pooled:mixed6b/b7x7dbl_2", OTHER),
    case("%plan_grouped_chained_red0.b3x3_.1 = f32[1]",
         "grouped_chained:red0/b3x3", OTHER),
    case("%plan_grouped_pooled_red0.b3x3_.4 = f32[1]",
         "grouped_pooled:red0/b3x3", OTHER),
    case("%plan_grouped_chained_inc3.b7x7_1_.2 = f32[1]",
         "grouped_chained:inc3/b7x7_1", OTHER),
    case("%plan_grouped_chained_inc3.b7x7_.2 = f32[1]",
         "grouped_chained:inc3/b7x7", OTHER),
    case("%transpose_jvp_plan_grouped_chained_mixed6b.b7x7dbl_2__.3 = f32[1]",
         "grouped_chained:mixed6b/b7x7dbl_2 bwd", OTHER),
    # another network's op is no scope of GoogLeNet's, and the reverse
    case("%plan_grouped_chained_mixed6b.b7x7dbl_2_.2 = f32[1]", None),
    case("%plan_grouped_chained_inc1.r5_.2 = f32[1]", None, OTHER),
])
def test_scope_from_the_instruction_name(name, scope, ops):
    assert T.scope_of(ev(name, 0, 1), pattern(ops)) == scope


def test_kernel_launch_is_a_tpu_custom_call():
    assert T.is_kernel_launch(kernel("plan_grouped_chained_stem0_", 0, 1))
    assert T.is_kernel_launch(kernel("pallas_call", 0, 1))
    assert not T.is_kernel_launch(ev(
        '%custom-call.4 = f32[4] custom-call(f32[2] %a, f32[2] %b), '
        'custom_call_target="ConcatBitcast"', 0, 1))
    assert not T.is_kernel_launch(ev("%fusion.1 = f32[1] fusion()", 0, 1))
    assert T.op_key(ev("%copy-start.208 = (f32[1]) copy-start()", 0, 1),
                    pattern()) == "xla:copy-start"
    assert T.op_key(kernel("transpose_jvp_plan_grouped_concat_inc1.5x5__",
                           0, 1), pattern()) \
        == "plan[grouped_concat:inc1/5x5 bwd]"


def _trace():
    ops = [kernel("jvp_plan_grouped_chained_stem0_", 10, 20, 1),
           kernel("transpose_jvp_plan_grouped_inc0.1x1__", 30, 10, 2),
           ev("%fusion.9 = f32[1] fusion(f32[1] %a)", 40, 5),
           kernel("jvp_plan_grouped_chained_stem0_", 70, 20, 1)]
    host = [ev("bench.window", 0, 100), ev("bench.step", 5, 40),
            ev("bench.next_batch", 46, 23), ev("bench.step", 69, 31)]
    return T.Trace({"/device:TPU:0": ops}, host)


def test_reduce_window_busy_kernels_launches_and_named_gaps():
    r = T.reduce(_trace(), googlenet_ops())
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
                          ev("bench.dispatch", 0, 100)]), googlenet_ops())
    assert dict(r["idle_by_span"]) == {T.SHORT_GAP: pytest.approx(5e-6)}


def test_reduce_averages_chips_and_needs_a_window():
    t = _trace()
    t.device_ops["/device:TPU:1"] = [kernel("plan_grouped_inc0.r3_", 0, 100)]
    r = T.reduce(t, googlenet_ops())
    assert r["busy_s"] == pytest.approx((0.055 + 0.100) / 2)
    assert r["launches"] == pytest.approx(2.0)
    with pytest.raises(RuntimeError, match="bench.window"):
        T.reduce(T.Trace(t.device_ops, []), googlenet_ops())
    with pytest.raises(RuntimeError, match="no device operations"):
        T.reduce(T.Trace({}, t.host_spans), googlenet_ops())


def _other_trace():
    ops = [kernel("plan_grouped_chained_inc3.b7x7_1_", 10, 1, 2),
           kernel("plan_grouped_chained_mixed6b.b7x7dbl_2_", 20, 3, 2),
           ev("%fusion.3 = f32[1] fusion(f32[1] %a)", 30, 2)]
    return T.Trace({"/device:TPU:0": ops}, [ev("bench.window", 0, 100)])


def test_another_networks_kernels_are_read_by_its_own_ops():
    r = T.reduce(_other_trace(), OTHER)
    assert r["plan_kernels_s"] == pytest.approx(0.004)
    assert r["launches"] == 2
    assert dict(r["device_ops"]) == pytest.approx({
        "plan[grouped_chained:mixed6b/b7x7dbl_2]": 0.003,
        "plan[grouped_chained:inc3/b7x7_1]": 0.001, "xla:fusion": 0.002})


def test_a_plan_kernel_of_no_kernel_op_is_refused():
    with pytest.raises(RuntimeError,
                       match="plan_grouped_chained_mixed6b.b7x7dbl_2_ is"):
        T.reduce(_other_trace(), googlenet_ops() + ("inc3/b7x7_1",))
    # an op of XLA's own named so is no kernel of ours, and is not refused
    t = T.Trace({"/device:TPU:0": [ev(
        "%plan_grouped_red9.mul_.1 = f32[1] multiply(f32[1] %a)", 0, 1)]},
        [ev("bench.window", 0, 10)])
    assert T.reduce(t, OTHER)["device_ops"] == [
        ["xla:plan_grouped_red9.mul_", pytest.approx(0.001)]]


def googlenet_trace() -> T.Trace:
    """A window of one kernel for each of GoogLeNet's kernel ops, each under
    a mode of the plan, some as a backward's, with XLA's own ops between
    them, on two chips, under the harness's spans."""
    modes = ("grouped_chained", "grouped_pooled", "grouped_concat", "serial")
    xla = ("%fusion.{} = f32[1] fusion(f32[1] %a)",
           "%copy-start.{} = (f32[1]) copy-start(f32[1] %a)",
           "%slice.{} = f32[1] slice(f32[2] %a)",
           '%custom-call.{} = f32[4] custom-call(f32[2] %a), '
           'custom_call_target="ConcatBitcast"')
    chip0, chip1, t = [], [], 2.0
    for i, op in enumerate(googlenet_ops()):
        bwd = "transpose_jvp_" if i % 7 == 3 else ""
        name = f"{bwd}plan_{modes[i % 4]}_{op.replace('/', '.')}_"
        dur = 0.25 + 0.0625 * (i % 13) + 0.001 * i
        chip0.append(kernel(name, t, dur, i % 3 + 1))
        chip1.append(kernel(name, t + 0.5, dur * 0.75, i % 3 + 1))
        t += dur
        if i % 3 == 0:
            chip0.append(ev(xla[i % 4].format(i), t + 0.125, 0.0625 * i))
            t += 0.125 + 0.0625 * i
        t += 0.03125 * (i % 5)
    # a kernel cut by the window's end
    chip0.append(kernel(f"plan_grouped_chained_{googlenet_ops()[0]}_", t,
                        4.0, 3))
    host = [ev("bench.window", 0, t + 2.0),
            ev("bench.wait_for_arrival", 0, 3.0),
            ev("bench.dispatch", 3.0, t / 2),
            ev("bench.admit", 3.0 + t / 2, 1.0),
            ev("bench.dispatch", 4.0 + t / 2, t / 2)]
    return T.Trace({"/device:TPU:0": chip0, "/device:TPU:1": chip1}, host)


#: ``googlenet_trace`` as the reduction read it when it knew GoogLeNet's op
#: names alone, by a pattern of its own (``top`` 100, so every key shows)
PINNED = {
    "plan_kernels_s": 0.032474625, "launches": 57.5,
    "busy_s": 0.04850587499999999}
PINNED_KEYS = [
    "xla:slice", "xla:custom-call", "xla:fusion", "xla:copy-start",
    "plan[grouped_chained:stem0]", "plan[serial:inc8/1x1]",
    "plan[grouped_concat:inc5/pp bwd]", "plan[grouped_pooled:inc3/5x5]",
    "plan[grouped_chained:inc1/r5]", "plan[grouped_concat:inc7/pp]",
    "plan[grouped_pooled:inc5/5x5]", "plan[grouped_chained:inc3/r5 bwd]",
    "plan[serial:inc1/3x3]", "plan[grouped_pooled:inc7/5x5]",
    "plan[grouped_chained:inc5/r5]", "plan[serial:inc3/3x3]",
    "plan[grouped_concat:inc1/r3 bwd]", "plan[grouped_chained:inc7/r5]",
    "plan[serial:inc5/3x3]", "plan[grouped_concat:inc3/r3]",
    "plan[grouped_pooled:inc1/1x1]", "plan[serial:inc7/3x3]",
    "plan[grouped_concat:inc5/r3]", "plan[grouped_pooled:inc3/1x1]",
    "plan[grouped_chained:inc0/pp]", "plan[grouped_concat:inc7/r3]",
    "plan[grouped_pooled:inc5/1x1]", "plan[grouped_chained:inc2/pp]",
    "plan[serial:inc0/5x5]", "plan[grouped_pooled:inc7/1x1 bwd]",
    "plan[grouped_chained:inc4/pp]", "plan[serial:inc2/5x5]",
    "plan[grouped_concat:inc0/r5]", "plan[grouped_chained:inc6/pp]",
    "plan[serial:inc4/5x5 bwd]", "plan[grouped_concat:inc2/r5]",
    "plan[grouped_pooled:inc0/3x3]", "plan[grouped_chained:inc8/pp]",
    "plan[serial:inc6/5x5]", "plan[grouped_concat:inc4/r5]",
    "plan[grouped_pooled:inc2/3x3 bwd]", "plan[grouped_chained:inc0/r3]",
    "plan[serial:inc8/5x5]", "plan[grouped_concat:inc6/r5]",
    "plan[grouped_pooled:inc4/3x3]", "plan[grouped_chained:inc2/r3]",
    "plan[serial:inc0/1x1 bwd]", "plan[grouped_concat:inc8/r5]",
    "plan[grouped_pooled:inc6/3x3]", "plan[grouped_chained:inc4/r3]",
    "plan[serial:inc2/1x1]", "plan[grouped_concat:stem2]",
    "plan[grouped_pooled:inc8/3x3]", "plan[grouped_chained:inc6/r3]",
    "plan[serial:inc4/1x1]", "plan[grouped_concat:inc1/pp]",
    "plan[grouped_pooled:stem1]", "plan[grouped_chained:inc8/r3 bwd]",
    "plan[serial:inc6/1x1]", "plan[grouped_concat:inc3/pp]",
    "plan[grouped_pooled:inc1/5x5]"]


def test_googlenet_reads_as_when_its_op_names_were_built_in():
    assert len(googlenet_ops()) == 57
    r = T.reduce(googlenet_trace(), googlenet_ops(), top=100)
    assert {k: r[k] for k in PINNED} == PINNED
    assert [k for k, _v in r["device_ops"]] == PINNED_KEYS
