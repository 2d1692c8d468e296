"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time, device time inside each ``plan[mode:op]``
scope, kernel launches, and the idle gaps named by what the host was doing.

A kernel's scope is read from its instruction's name, by the program's plan
modes (``repro.core.plan.MODES``) and the configuration's kernel ops: the
names of the ops its ``forward_ops`` marks ``kernel``.  So the reduction
knows no network, and a kernel named for an op the configuration does not
list is an error, not a launch outside the roofline.

Reading the file (``load``) is kept apart from the arithmetic, which works
on plain ``Event`` lists so that a test can hand it a synthesized trace.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
import shutil
import tempfile
import time

#: the device line whose events are the operations XLA ran
OPS_LINE = "XLA Ops"
#: host spans the harness writes around each phase of a window
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
#: a ``plan[mode:op]`` scope as a name stack keeps it
_SCOPE = re.compile(r"plan\[([^\]]+)\]")
#: the start of a scope as XLA's instruction names keep it, behind "jvp_"
#: in a forward that is differentiated and "transpose_jvp_" in its backward
_HLO_PREFIX = r"^%?((?:transpose_)?(?:jvp_)?)plan_"
_HLO_NAME = re.compile(r"^%?([A-Za-z0-9_.\-]+?)(?:\.\d+)? = ")
KERNEL = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: tuple = ()            # ((key, value-as-str), ...)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    device_ops: dict          # device plane name -> [Event] on OPS_LINE
    host_spans: list          # [Event]: the harness's bench.* spans


def _event(e) -> Event:
    # the name carries all the reduction needs: XLA's instruction text on
    # the device, the span's name on the host
    return Event(e.name, float(e.start_ns), float(e.duration_ns))


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {files}")
    pd = ProfileData.from_file(files[0])
    device_ops, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [_event(e) for line in plane.lines
                   if line.name == OPS_LINE for e in line.events]
            if ops:
                device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            host.extend(_event(e) for line in plane.lines
                        for e in line.events
                        if e.name.startswith(SPAN_PREFIX))
    return Trace(device_ops, host)


# ---------------------------------------------------------------------------
# arithmetic on events
# ---------------------------------------------------------------------------

def merged(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of the events' intervals clipped to [lo, hi], as sorted
    disjoint (start, end) pairs."""
    iv = sorted((max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
                if e.end_ns > lo and e.start_ns < hi)
    out: list[list[float]] = []
    for s, t in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_ns(events, lo: float, hi: float) -> float:
    return sum(t - s for s, t in merged(events, lo, hi))


def gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of [lo, hi]: where no event runs."""
    out, cur = [], lo
    for s, t in merged(events, lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if cur < hi:
        out.append((cur, hi))
    return out


def _alternatives(names) -> str:
    """A regex alternation of ``names``, longest first, so that a name that
    extends another ("inc3.b7x7_1", "inc3.b7x7") is matched as itself."""
    return "|".join(re.escape(n) for n in sorted(set(names), key=len,
                                                 reverse=True))


def scope_pattern(kernel_ops) -> re.Pattern:
    """The instruction-name pattern of a plan scope for a configuration
    whose kernel ops are ``kernel_ops``: "plan_", a mode of the program's
    plan, "_", then one of those ops as XLA spells it ("/" becomes ".") and
    "_".  The op's group is None where the mode matches and no op does."""
    from repro.core.plan import MODES
    ops = _alternatives(op.replace("/", ".") for op in kernel_ops)
    return re.compile(
        f"{_HLO_PREFIX}({_alternatives(MODES)})_(?:({ops})_)?")


def scope_of(e: Event, pattern: re.Pattern) -> str | None:
    """The ``plan[mode:op]`` scope an op ran in, as "mode:op" with " bwd"
    for the backward's ops: from the XLA instruction's name by ``pattern``
    (``scope_pattern``), which a Pallas kernel takes from the scope, or from
    a name stack in any stat (the innermost where scopes nest)."""
    m = pattern.match(e.name)
    if m and m.group(3):
        bwd = " bwd" if m.group(1).startswith("transpose") else ""
        return f"{m.group(2)}:{m.group(3).replace('.', '/')}{bwd}"
    found = None
    for text in (e.name,) + tuple(v for _k, v in e.stats):
        for m in _SCOPE.finditer(text):
            found = m.group(1)
    return found


def is_kernel_launch(e: Event) -> bool:
    """A Pallas kernel: a custom call to the TPU's kernel target (XLA's own
    custom calls, such as ConcatBitcast, are not launches of ours)."""
    return KERNEL in e.name or any(KERNEL in v for _k, v in e.stats)


def _instruction(e: Event) -> str:
    m = _HLO_NAME.match(e.name)
    return m.group(1) if m else e.name[:40]


def op_key(e: Event, pattern: re.Pattern) -> str:
    """How the breakdown names an op: its plan scope where it has one, else
    "xla:" and the instruction's name without its number."""
    sc = scope_of(e, pattern)
    return f"plan[{sc}]" if sc else "xla:" + _instruction(e)


#: idle gaps shorter than this lie between two ops of one program
SHORT_GAP_NS = 20_000.0
SHORT_GAP = "between ops of a program"


class _Spans:
    """The harness spans sorted by start, to name a gap by the span that
    covers most of it."""

    def __init__(self, spans):
        self.spans = sorted((s.start_ns, s.end_ns, s.name[len(SPAN_PREFIX):])
                            for s in spans if s.name != WINDOW_SPAN)
        self.starts = [s[0] for s in self.spans]

    def name(self, lo: float, hi: float) -> str:
        if hi - lo < SHORT_GAP_NS:
            return SHORT_GAP
        best, best_ov = "outside any harness span", 0.0
        i = bisect.bisect_right(self.starts, hi)
        for s, t, nm in self.spans[max(0, i - 4):i]:
            ov = min(hi, t) - max(lo, s)
            if ov > best_ov:
                best, best_ov = nm, ov
        return best


def window_of(trace: Trace) -> tuple[float, float]:
    wins = [s for s in trace.host_spans if s.name == WINDOW_SPAN]
    if not wins:
        raise RuntimeError("the trace holds no bench.window span")
    return min(s.start_ns for s in wins), max(s.end_ns for s in wins)


def reduce(trace: Trace, kernel_ops, top: int = 10) -> dict:
    """Everything the per-layer metrics read from a trace, over the
    ``bench.window`` span, averaged over the chips that ran ops.
    ``kernel_ops`` are the configuration's kernel ops (``Cell.kernel_ops``);
    a kernel named for a plan mode and none of them is refused, since its
    time would fall out of the plan's kernels."""
    pattern = scope_pattern(kernel_ops)
    lo, hi = window_of(trace)
    planes = list(trace.device_ops.values())
    if not planes:
        raise RuntimeError("the trace holds no device operations")
    n = len(planes)
    busy = sum(busy_ns(ops, lo, hi) for ops in planes) / n
    by_key: dict[str, float] = {}
    launches, kernels = 0, 0.0
    seen: dict[str, tuple] = {}          # name -> (key, kernel of a plan)
    for ops in planes:
        for e in ops:
            if e.end_ns <= lo or e.start_ns >= hi:
                continue
            dur = min(e.end_ns, hi) - max(e.start_ns, lo)
            if e.name not in seen:
                launch = is_kernel_launch(e)
                m = pattern.match(e.name)
                if launch and m and not m.group(3):
                    raise RuntimeError(
                        f"the kernel {_instruction(e)} is named for a plan "
                        f"scope of none of the configuration's kernel ops "
                        f"(the ops its forward_ops marks kernel)")
                seen[e.name] = (op_key(e, pattern), launch,
                                launch and scope_of(e, pattern) is not None)
            key, launch, planned = seen[e.name]
            by_key[key] = by_key.get(key, 0.0) + dur / n
            launches += launch
            kernels += dur / n if planned else 0.0
    spans = _Spans(trace.host_spans)
    named: dict[str, float] = {}
    all_gaps = [g for ops in planes for g in gaps(ops, lo, hi)]
    for s, t in all_gaps:
        nm = spans.name(s, t)
        named[nm] = named.get(nm, 0.0) + (t - s) / n
    longest = sorted(((t - s, s, t) for s, t in all_gaps),
                     reverse=True)[:top]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / 1e9,
        "plan_kernels_s": kernels / 1e9,
        "launches": launches / n,
        "spans": {nm: sum(1 for s in trace.host_spans if s.name == nm)
                  for nm in {s.name for s in trace.host_spans}},
        "device_ops": [[k, v / 1e9] for k, v in sorted(
            by_key.items(), key=lambda kv: -kv[1])[:top]],
        "idle_by_span": [[k, v / 1e9] for k, v in sorted(
            named.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[spans.name(s, t), d / 1e9] for d, s, t in longest],
    }


# ---------------------------------------------------------------------------
# taking a trace
# ---------------------------------------------------------------------------

class Tracer:
    """Starts and stops the profiler around a window, and writes the
    harness's host spans; with ``enabled`` False it does nothing at all."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
            if enabled else None
        self.wall_s = None

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        if not self.enabled:
            yield
            return
        import jax
        # the harness's spans and the device; no Python call tracing, no
        # runtime internals (a host transpose of each input is 10^5 events)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            self.wall_s = time.perf_counter() - t0
            jax.profiler.stop_trace()

    def reduce(self, kernel_ops) -> dict:
        try:
            return reduce(load(self.dir), kernel_ops)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
