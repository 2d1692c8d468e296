"""The split of a serving dispatch's device idle time (``dispatch_phases.py``):
lead, inner and tail per ``serve.dispatch`` span on a synthesized trace,
gaps named by the innermost span, and a real profiler session on the CPU in
which the program's spans nest inside the harness's on one clock."""
import shutil

import chiptiny
import pytest

import dispatch_phases as P
from chipbench import spec
from chipbench import trace as T


def ev(name, start_ms, dur_ms, **stats):
    return T.Event(name, start_ms * 1e6, dur_ms * 1e6,
                   tuple((k, str(v)) for k, v in stats.items()))


def op(start_ms, dur_ms):
    return ev("%fusion.1 = f32[1] fusion()", start_ms, dur_ms)


def dispatch(start, h2d, launch, end, seq, bucket):
    """bench.dispatch over serve.dispatch over its three phases, from
    ``start`` to ``end`` ms: transfer for ``h2d``, launch for ``launch``,
    then the wait."""
    s = start + 1
    return [ev("bench.dispatch", start, end - start),
            ev("serve.dispatch", s, end - 1 - s, seq=seq, bucket=bucket,
               valid=1),
            ev("serve.h2d", s, h2d), ev("serve.launch", s + h2d, launch),
            ev("serve.wait", s + h2d + launch, end - 1 - s - h2d - launch)]


def _trace():
    # dispatch 0: spans 11-39, ops 18-25 and 27-35: lead 7, inner 2, tail 4
    # dispatch 1: spans 61-89, ops 72-80 and 80.005-85: lead 11, inner
    # 0.005 (a gap between ops of one program), tail 4.  An op of an
    # earlier dispatch runs 0-9.
    ops = [op(0, 9), op(18, 7), op(27, 8), op(72, 8), op(80.005, 4.995)]
    host = [ev("bench.window", 0, 100),
            *dispatch(10, 4, 1, 40, 0, 8),
            ev("bench.wait_for_arrival", 41, 18),
            *dispatch(60, 9, 1, 90, 1, 4)]
    return T.Trace({"/device:TPU:0": ops}, host)


def test_lead_inner_tail_per_dispatch():
    r = P.dispatch_phases(_trace())
    assert r["dispatches"] == 2
    assert r["lead_s"] == pytest.approx((7 + 11) / 1e3)
    assert r["inner_s"] == pytest.approx((2 + 0.005) / 1e3)
    assert r["tail_s"] == pytest.approx((4 + 4) / 1e3)
    # idle outside both dispatch spans: 9-11, 39-61, 89-100
    assert r["outside_s"] == pytest.approx(35 / 1e3)
    assert r["h2d_ms_p50"] == pytest.approx(6.5)
    assert r["launch_ms_p50"] == pytest.approx(1.0)
    assert r["wait_ms_p50"] == pytest.approx((23 + 18) / 2)
    assert r["by_bucket"]["8"]["lead_ms_p50"] == pytest.approx(7.0)
    assert r["by_bucket"]["4"]["tail_ms_p50"] == pytest.approx(4.0)
    slow = r["slowest"][0]
    assert slow["seq"] == "0" and slow["inner_ms"] == pytest.approx(2.0)


def test_shares_and_outside_add_up_to_the_idle_share():
    t = _trace()
    t.device_ops["/device:TPU:1"] = [op(15, 30), op(65, 22)]
    r = P.dispatch_phases(t)
    busy = T.reduce(t, ())["busy_s"]
    parts = (r["idle_lead_share"] + r["idle_inner_share"]
             + r["idle_tail_share"] + r["idle_outside_share"])
    assert parts == pytest.approx(100.0 * (1 - busy / 0.1))
    assert r["idle_share"] == pytest.approx(parts)
    assert r["identity_gap_pp"] == pytest.approx(0.0, abs=1e-9)


@pytest.mark.parametrize("lo,hi,name", [
    (25, 27, "serve.wait"),           # wholly inside the wait
    (11, 14, "serve.h2d"),            # inside the transfer
    (11, 18, "serve.h2d"),            # the transfer covers 4 of 7 ms
    (9, 18, "serve.dispatch"),        # the transfer covers 4 of 9 ms
    (35, 72, "bench.wait_for_arrival"),   # none covers half: the most
    (25, 25.01, T.SHORT_GAP),
    (95, 99, "outside any span"),
])
def test_gap_named_by_the_innermost_span_over_most_of_it(lo, hi, name):
    assert P.Namer(_trace().host_spans).name(lo * 1e6, hi * 1e6) == name


def test_idle_by_span_names_every_gap():
    # gaps 9-18, 25-27, 35-72, 80-80.005, 85-100
    idle = dict(P.idle_by_span(_trace()))
    assert idle == pytest.approx({
        "serve.dispatch": 9e-3, "serve.wait": 2e-3,
        "bench.wait_for_arrival": 37e-3, T.SHORT_GAP: 5e-6,
        "bench.dispatch": 15e-3})
    assert sum(idle.values()) == pytest.approx(
        P.dispatch_phases(_trace())["idle_s"])


def _inside(inner, outer):
    return outer.start_ns <= inner.start_ns and inner.end_ns <= outer.end_ns


def test_program_spans_nest_in_the_harness_spans_on_one_clock(tmp_path):
    root = chiptiny.make_root(tmp_path / "co")
    cell = spec.load_cell(root, "tiny-serve")
    seed = 2**31 + 5
    run = P.windows(cell, seed, 1.0, log=lambda _m: None)
    try:
        tr, _other = P.load(run["tracer"].dir)
    finally:
        shutil.rmtree(run["tracer"].dir, ignore_errors=True)
    spans = {}
    for s in tr.host_spans:
        spans.setdefault(s.name, []).append(s)
    traced = run["summaries"]["engine_traced"]
    for w in run["summaries"].values():
        assert w["answered"] == w["requests"] == 8
    n = traced["dispatches"]
    counted = run["counters"]["engine_traced"]
    assert sum(counted["dispatches"].values()) == n
    assert counted["valid_images"] == sum(
        int(s) for s in run["window"]["sizes"])
    assert sum(run["counters"]["engine"]["dispatches"].values()) == \
        run["summaries"]["engine"]["dispatches"]
    assert len(spans["bench.dispatch"]) == len(spans["serve.dispatch"]) == n
    for name in P.PHASES + ("serve.dispatch",):
        assert len(spans[name]) == n
    for d in spans["serve.dispatch"]:
        assert any(_inside(d, b) for b in spans["bench.dispatch"])
        assert dict(d.stats)["bucket"] in {str(b) for b in
                                           run["setup"]["warm_s"]}
    for name in P.PHASES:
        for s in spans[name]:
            assert any(_inside(s, d) for d in spans["serve.dispatch"]), name
    for s in spans["serve.admit"]:
        assert any(_inside(s, b) for b in spans["bench.admit"])
    got = P.check(cell, seed, run["serving"], run["window"])
    assert got["logits_err"] <= chiptiny.LIMITS["tiny-serve"]["logits_err"]


def test_device_lines_count_only_the_time_inside_a_transfer():
    t = _trace()
    other = {"/device:TPU:0": {
        "XLA Modules": [ev("jit_serve_step(1)", 13, 25),    # 2 ms of 11-15
                        ev("jit_serve_step(1)", 72, 13)],   # none
        "Steps": []}}
    lines = P.h2d_lines(t, other)
    mods = lines["/device:TPU:0 XLA Modules"]
    assert mods["events"] == 2
    assert mods["in_h2d"] == [["jit_serve_step(1)", 1, pytest.approx(2.0)]]
    assert lines["/device:TPU:0 Steps"] == {"events": 0, "in_h2d": []}
