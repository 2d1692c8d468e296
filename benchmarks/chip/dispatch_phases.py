"""Where a serving dispatch's time goes, from the program's own spans: one
window of a serving cell run through ``launch.serve.CNNServer``, traced,
and the device's idle time split by the phase of the dispatch it fell in.
The benchmark's own runs never run this.

    python3 benchmarks/chip/dispatch_phases.py --workload <name> \\
        --seed <n> [--seconds 51] [--out <dir>]

After one set-up it serves the cell's window three times with the same
requests: through the harness's ``program.Server`` (its own inline pack and
call), through the engine untraced, and through the engine under the
profiler.  The first two differ by the engine's spans and its wait for the
transfer; the last two by tracing.  From the trace it splits the idle time
of each ``serve.dispatch`` span into:

  lead   from the span's start to the first device op that starts in it
         (transfer, enqueue and the program's start)
  inner  idle between that dispatch's first and last op (inside the program)
  tail   from the last op's end to the span's end (completion)

and the idle time outside any ``serve.dispatch`` (arrivals, admission,
packing).  The four add up to the device's idle time; ``identity_gap_pp``
says by how much they miss, in points of the window.  Gaps are also named
by the innermost span that covers most of each.  The last line of standard
output is the result as JSON; ``--out`` also gets it, with the trace's
``.xplane.pb`` where that is small enough to bring back.
"""
from __future__ import annotations

import argparse
import bisect
import contextlib
import glob
import json
import os
import pathlib
import shutil
import statistics
import sys
import time

from chipbench import trace as T
from chipbench.kinds import serve_open_loop as S

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]

#: host spans kept from the trace: the harness's and the program's
PREFIXES = (T.SPAN_PREFIX, "serve.")
DISPATCH = "serve.dispatch"
PHASES = ("serve.h2d", "serve.launch", "serve.wait")
KEEP_TRACE_BYTES = 40 << 20


# ---------------------------------------------------------------------------
# the program's engine behind the harness's serving window
# ---------------------------------------------------------------------------

class EngineServer:
    """``program.Server``'s interface over the program's ``CNNServer``:
    the engine packs and runs, inside the harness's ``bench.pack`` and
    ``bench.dispatch`` spans, and the host clock times the same work."""

    def __init__(self, cfg, params, max_images: int, *,
                 chain_modules: bool = True, interpret=None):
        from repro.core import plan_cache
        from repro.launch import serve
        self._plan_cache = plan_cache
        self.engine = serve.CNNServer(cfg, params, max_images,
                                      chain_modules=chain_modules,
                                      interpret=interpret)
        self.max_images = max_images

    def warm(self) -> None:
        self.engine.warm()
        self._plan_cache.reset()          # counters only
        self.engine.reset_counters()

    def split(self, rid: int, imgs, due: float):
        return self.engine.split(rid, imgs, due)

    def admit(self, pending):
        return self.engine.admit(pending)

    def dispatch(self, arrs, span=contextlib.nullcontext):
        with span("bench.pack"):
            imgs, bucket, n = self.engine.pack(arrs)
        t0 = time.perf_counter()
        with span("bench.dispatch"):
            logits = self.engine.run(imgs, bucket, n)
        return logits, time.perf_counter() - t0, bucket, n

    def cache_stats(self) -> dict:
        return self._plan_cache.stats()


class EngineServing(S.Serving):
    """The serving kind's window, run on ``EngineServer``."""

    def __init__(self, cell, seed: int, *, interpret=None):
        self.cell = cell
        self.cfg = cell.ref.program_config(cell.sizes)
        self.server = EngineServer(
            self.cfg, cell.ref.make_params(cell.sizes, seed),
            cell.traffic["max_images"],
            chain_modules=cell.traffic["chain_modules"], interpret=interpret)
        self.server.warm()


# ---------------------------------------------------------------------------
# reading the trace
# ---------------------------------------------------------------------------

def load(log_dir: str) -> tuple[T.Trace, dict]:
    """The trace's device ops and its ``bench.*``/``serve.*`` host spans
    (with their metadata as stats), and the events of every other device
    line, by plane and line."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {files}")
    pd = ProfileData.from_file(files[0])
    ops, host, other = {}, [], {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                evs = [T.Event(e.name, float(e.start_ns),
                               float(e.duration_ns)) for e in line.events]
                if line.name == T.OPS_LINE:
                    ops[plane.name] = evs
                else:
                    other.setdefault(plane.name, {})[line.name] = evs
        elif plane.name.startswith("/host:"):
            host.extend(T.Event(e.name, float(e.start_ns),
                                float(e.duration_ns),
                                tuple((k, str(v)) for k, v in e.stats))
                        for line in plane.lines for e in line.events
                        if e.name.startswith(PREFIXES))
    return T.Trace({k: v for k, v in ops.items() if v}, host), other


class Busy:
    """The union of one plane's ops over the window, for the busy time of
    any interval of it in logarithmic time."""

    def __init__(self, ops, lo: float, hi: float):
        self.iv = T.merged(ops, lo, hi)
        self.starts = [s for s, _t in self.iv]
        self.cum = [0.0]
        for s, t in self.iv:
            self.cum.append(self.cum[-1] + t - s)

    def _upto(self, x: float) -> float:
        i = bisect.bisect_right(self.starts, x)
        if i == 0:
            return 0.0
        s, t = self.iv[i - 1]
        return self.cum[i - 1] + min(t, x) - s

    def idle(self, a: float, b: float) -> float:
        return 0.0 if b <= a else (b - a) - (self._upto(b) - self._upto(a))


class Namer:
    """Names an idle gap by the innermost host span that covers most of it:
    the shortest of the spans that cover at least half of it, else the one
    that covers the most.  ``bench.window`` names nothing."""

    def __init__(self, spans):
        self.spans = sorted((s.start_ns, s.end_ns, s.name) for s in spans
                            if s.name != T.WINDOW_SPAN)
        self.starts = [s[0] for s in self.spans]
        self.longest = max((t - s for s, t, _n in self.spans), default=0.0)

    def name(self, lo: float, hi: float) -> str:
        if hi - lo < T.SHORT_GAP_NS:
            return T.SHORT_GAP
        best, best_key = "outside any span", None
        i = bisect.bisect_left(self.starts, hi) - 1
        while i >= 0 and self.spans[i][0] >= lo - self.longest:
            s, t, nm = self.spans[i]
            ov = min(hi, t) - max(lo, s)
            if ov > 0:
                # most-covering first; among spans covering half, shortest
                key = (True, -(t - s)) if 2 * ov >= hi - lo else (False, ov)
                if best_key is None or key > best_key:
                    best, best_key = nm, key
            i -= 1
        return best


def _ms_p50(xs) -> float | None:
    return statistics.median(xs) / 1e6 if xs else None


def dispatch_phases(trace: T.Trace, top: int = 3) -> dict:
    """Each ``serve.dispatch`` span's idle time in lead, inner and tail, and
    its ``serve.h2d``/``.launch``/``.wait`` durations, over the
    ``bench.window`` span, averaged over the chips that ran ops.  An op
    belongs to the dispatch span it starts in: dispatches block before the
    next is packed, so they do not overlap."""
    lo, hi = T.window_of(trace)
    planes = list(trace.device_ops.values())
    if not planes:
        raise RuntimeError("the trace holds no device operations")
    n = len(planes)
    disp = sorted((d for d in trace.host_spans if d.name == DISPATCH
                   and d.end_ns > lo and d.start_ns < hi),
                  key=lambda d: d.start_ns)
    d_starts = [d.start_ns for d in disp]
    phase_ns = {p: [0.0] * len(disp) for p in PHASES}
    for s in trace.host_spans:
        if s.name in phase_ns:
            k = bisect.bisect_right(d_starts, s.start_ns) - 1
            if k >= 0 and s.start_ns < disp[k].end_ns:
                phase_ns[s.name][k] += s.dur_ns
    parts = [[0.0, 0.0, 0.0] for _ in disp]
    idle = outside = 0.0
    for ops in planes:
        busy = Busy(ops, lo, hi)
        by_start = sorted(ops, key=lambda e: e.start_ns)
        starts = [e.start_ns for e in by_start]
        ends = [e.end_ns for e in by_start]
        idle += busy.idle(lo, hi)
        cur = lo
        for k, d in enumerate(disp):
            a, b = max(d.start_ns, lo), min(d.end_ns, hi)
            outside += busy.idle(cur, a)
            cur = max(cur, b)
            i, j = (bisect.bisect_left(starts, d.start_ns),
                    bisect.bisect_left(starts, d.end_ns))
            if i < j:
                first = min(max(starts[i], a), b)
                last = min(max(max(ends[i:j]), first), b)
            else:
                first = last = b
            for m, (x, y) in enumerate(((a, first), (first, last),
                                        (last, b))):
                parts[k][m] += busy.idle(x, y) / n
        outside += busy.idle(cur, hi)
    idle, outside = idle / n, outside / n
    window = hi - lo
    lead, inner, tail = (sum(p[m] for p in parts) for m in range(3))
    share = 100.0 / window

    def one(k: int) -> dict:
        d = disp[k]
        st = dict(d.stats)
        return {"seq": st.get("seq"), "bucket": st.get("bucket"),
                "valid": st.get("valid"), "wall_ms": d.dur_ns / 1e6,
                **{f"{m}_ms": parts[k][i] / 1e6
                   for i, m in enumerate(("lead", "inner", "tail"))},
                **{f"{p[6:]}_ms": phase_ns[p][k] / 1e6 for p in PHASES}}

    per_bucket: dict[str, dict] = {}
    for k, d in enumerate(disp):
        b = dict(d.stats).get("bucket", "?")
        per_bucket.setdefault(b, []).append(k)
    by_bucket = {b: {"dispatches": len(ks),
                     "wall_ms_p50": _ms_p50([disp[k].dur_ns for k in ks]),
                     **{f"{m}_ms_p50": _ms_p50([parts[k][i] for k in ks])
                        for i, m in enumerate(("lead", "inner", "tail"))},
                     **{f"{p[6:]}_ms_p50": _ms_p50([phase_ns[p][k]
                                                    for k in ks])
                        for p in PHASES}}
                 for b, ks in sorted(per_bucket.items())}
    slowest = sorted(range(len(disp)), key=lambda k: -disp[k].dur_ns)[:top]
    return {
        "window_s": window / 1e9, "idle_s": idle / 1e9,
        "dispatches": len(disp),
        "lead_s": lead / 1e9, "inner_s": inner / 1e9, "tail_s": tail / 1e9,
        "outside_s": outside / 1e9,
        "idle_share": idle * share,
        "idle_lead_share": lead * share, "idle_inner_share": inner * share,
        "idle_tail_share": tail * share, "idle_outside_share":
            outside * share,
        "identity_gap_pp": (lead + inner + tail + outside - idle) * share,
        **{f"{p[6:]}_ms_p50": _ms_p50(phase_ns[p]) for p in PHASES},
        "by_bucket": by_bucket,
        "slowest": [one(k) for k in slowest],
    }


def idle_by_span(trace: T.Trace, top: int = 12) -> list:
    """Idle seconds of the window by the innermost span over each gap."""
    lo, hi = T.window_of(trace)
    namer = Namer(trace.host_spans)
    planes = list(trace.device_ops.values())
    named: dict[str, float] = {}
    for ops in planes:
        for s, t in T.gaps(ops, lo, hi):
            nm = namer.name(s, t)
            named[nm] = named.get(nm, 0.0) + (t - s) / len(planes)
    return [[k, v / 1e9] for k, v in sorted(named.items(),
                                            key=lambda kv: -kv[1])[:top]]


def h2d_lines(trace: T.Trace, other: dict, top: int = 8) -> dict:
    """Every device line but the ops', with its event count, and the events
    that overlap a ``serve.h2d`` span, with the milliseconds they spend
    inside it: where the transfer shows on the device, if anywhere."""
    h2d = T.merged([s for s in trace.host_spans if s.name == PHASES[0]],
                   float("-inf"), float("inf"))
    starts = [s for s, _t in h2d]
    out = {}
    for plane, lines in other.items():
        for line, evs in lines.items():
            hit: dict[str, list] = {}
            for e in evs:
                i = bisect.bisect_right(starts, e.end_ns) - 1
                inside = T.busy_ns([e], h2d[i][0], h2d[i][1]) if i >= 0 \
                    else 0.0
                if inside > 0:
                    c = hit.setdefault(e.name[:80], [0, 0.0])
                    c[0] += 1
                    c[1] += inside / 1e6
            out[f"{plane} {line}"] = {
                "events": len(evs),
                "in_h2d": sorted(([k, c, ms] for k, (c, ms) in hit.items()),
                                 key=lambda r: -r[2])[:top]}
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def windows(cell, seed: int, seconds: float, *, interpret=None,
            log=print) -> dict:
    """One set-up, then the window three times over the same requests:
    the harness's inline server, the engine, the engine traced.  Returns
    each window's summary, the engine's counters of its two windows, its
    set-up record, the traced window itself and its tracer."""
    t0 = time.perf_counter()
    eng = EngineServing(cell, seed, interpret=interpret)
    reqs = eng.requests(cell.traffic, seed, seconds)
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.3f} s; engine set-up {eng.server.engine.setup}")
    out = {"setup_s": setup_s, "summaries": {}, "counters": {}}
    engine = eng.server.engine
    inline = S.Serving(cell, seed, interpret=interpret)
    for name, srv, tracer in (("inline", inline, T.Tracer(False)),
                              ("engine", eng, T.Tracer(False)),
                              ("engine_traced", eng, T.Tracer(True))):
        engine.reset_counters()
        w = srv.window(reqs, seconds, tracer)
        out["summaries"][name] = S.summary(w, seconds)
        if srv is eng:
            out["counters"][name] = engine.counters
        log(f"{name}: {json.dumps(out['summaries'][name])}")
    out.update(setup=engine.setup, window=w, tracer=tracer, serving=eng)
    return out


def check(cell, seed: int, serving, w) -> dict:
    """The traced window's answers against the plain reference."""
    import numpy as np
    rids = S.check_sample(cell.traffic, w, seed)
    got = serving.served_logits(w, rids)
    imgs = np.concatenate([w["images"][r] for r in rids])
    want = S.reference_logits(cell, seed, imgs)
    return {"logits_err": S.logits_err(got, want),
            "images_compared": int(len(got))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", default=None,
                    help="a directory for the result and the trace")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import device, spec

    def log(msg):
        print(f"[phases] {msg}", flush=True)

    cell = spec.load_cell(ROOT, args.workload)
    try:
        info = device.describe(cell.chips)
    except (device.NoChip, ImportError) as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    log(f"device: {info}")
    device.use_compile_cache(ROOT)
    run = windows(cell, args.seed, args.seconds, log=log)
    tracer = run["tracer"]
    t0 = time.perf_counter()
    tr, other = load(tracer.dir)
    bench_only = T.Trace(tr.device_ops, [s for s in tr.host_spans
                                         if s.name.startswith(T.SPAN_PREFIX)])
    red = T.reduce(bench_only, cell.kernel_ops)
    res = {"device": info, "seed": args.seed, "seconds": args.seconds,
           "setup_s": run["setup_s"], "setup": run["setup"],
           "counters": run["counters"], "summaries": run["summaries"],
           "busy_s": red["busy_s"], "window_s": red["window_s"],
           "device_idle_share": 100.0 * (1 - red["busy_s"]
                                         / red["window_s"]),
           "idle_by_bench_span": red["idle_by_span"],
           "phases": dispatch_phases(tr),
           "idle_by_span": idle_by_span(tr),
           "device_lines": h2d_lines(tr, other),
           "spans": {nm: sum(1 for s in tr.host_spans if s.name == nm)
                     for nm in sorted({s.name for s in tr.host_spans})},
           "reduce_s": time.perf_counter() - t0}
    res["check"] = check(cell, args.seed, run["serving"], run["window"])
    if args.out:
        out = pathlib.Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        (out / f"phases_{args.seed}.json").write_text(
            json.dumps(res, indent=1))
        for f in glob.glob(os.path.join(tracer.dir, "**", "*.xplane.pb"),
                           recursive=True):
            if os.path.getsize(f) <= KEEP_TRACE_BYTES:
                shutil.copy(f, out / f"trace_{args.seed}.xplane.pb")
    shutil.rmtree(tracer.dir, ignore_errors=True)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
