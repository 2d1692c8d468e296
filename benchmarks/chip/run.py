"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

From the root of a checkout, on a machine that holds the chips the cell asks
for.  In order: name the device and refuse anything but a TPU in the peaks
table; place JAX's persistent compilation cache; build the cell's program
and weights from the seed; warm every shape the cell uses; measure for
``--seconds``; compare what the timed path produced with the plain
reference; print the result as the last line of standard output, and the
numbers compared, each beside its limit, as the last lines of standard
error.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.

``--sweep r1,r2,...`` (serving cells) instead runs one window per offered
rate after one set-up, to find where the server stops keeping up.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def _say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated offered rates (serving cells)")
    return ap.parse_args(argv)


def result_line(cell, res: dict, info: dict, limits: dict, *, traced,
                trace_red=None, peaks=None) -> tuple[dict, dict]:
    """The contract's last line, and the table of numbers compared."""
    from chipbench import compare, spec
    nums = {k: v for k, v in res["numbers"].items()
            if not k.startswith("_")}
    ok, table = compare.judge(nums, limits)
    dev = dict(info)
    dev["memory_peak_bytes"] = res["memory_peak_bytes"]
    if traced:
        dev["busy_s"] = trace_red["busy_s"]
        dev["window_s"] = trace_red["window_s"]
        ctx = {**res["ctx"], "trace": trace_red, "peaks": peaks}
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(cell.root, m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": res["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    line = {"correct": bool(ok and res["failed"] == 0),
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": metrics, "device": dev}
    if traced:
        line["breakdown"] = {"device_ops": trace_red["device_ops"],
                             "idle_gaps": trace_red["idle_gaps"]}
    line["checks"] = table
    return line, table


def run_cell(root, workload: str, seed: int, seconds: float, trace_on: bool,
             *, describe=None, wrap=None, compile_cache=True,
             log=_say) -> dict | None:
    """One run of one cell; returns its result line, or None where the
    device is refused.  ``describe``, ``wrap`` and ``compile_cache`` are
    for tests: the look for a chip, a fault put under the timed path, and
    the persistent compilation cache left alone."""
    from chipbench import compare, device, peaks, spec, trace

    cell = spec.load_cell(root, workload)
    limits = compare.load_limits(spec.limits_path(cell))
    try:
        info = (describe or device.describe)(cell.chips)
        pk = peaks.peaks_for(info["kind"])
    except (device.NoChip, KeyError, ImportError) as e:
        print(f"[bench] refused: {e}", file=sys.stderr, flush=True)
        return None
    log(f"device: {info}")
    if compile_cache:
        log(f"compile cache: {device.use_compile_cache(root)}")
    kind = spec.kind_module(cell.kind)
    tracer = trace.Tracer(trace_on)
    with device.CompileCounter() as counter:
        res = kind.run(cell, seed, seconds, tracer, counter, T_START,
                       peaks=pk, wrap=wrap, log=log)
    red = None
    if trace_on:
        t0 = time.perf_counter()
        red = tracer.reduce(cell.kernel_ops)
        log(f"trace reduced in {time.perf_counter() - t0:.3f} s: window "
            f"{red['window_s']:.3f} s, busy {red['busy_s']:.3f} s, "
            f"launches {red['launches']}, harness spans {red['spans']}, "
            f"idle by span {red['idle_by_span']}")
    for k, v in res["info"].items():
        log(f"{k}: {v}")
    log("numbers read but held to no limit: " + json.dumps(
        {k: v for k, v in res["numbers"].items() if k not in limits}))
    line, table = result_line(cell, res, info, limits, traced=trace_on,
                              trace_red=red, peaks=pk)
    for name, row in table.items():
        print(f"[check] {name} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr, flush=True)
    return line


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    if args.sweep:
        from chipbench import device, spec
        cell = spec.load_cell(ROOT, args.workload)
        try:
            _say(f"device: {device.describe(cell.chips)}")
        except (device.NoChip, ImportError) as e:
            print(f"[bench] refused: {e}", file=sys.stderr, flush=True)
            return 2
        device.use_compile_cache(ROOT)
        spec.kind_module(cell.kind).sweep(
            cell, args.seed, args.seconds,
            [float(r) for r in args.sweep.split(",")], log=_say)
        return 0
    line = run_cell(ROOT, args.workload, args.seed, args.seconds,
                    bool(args.trace))
    if line is None:
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
