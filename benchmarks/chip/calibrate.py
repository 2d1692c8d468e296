"""Readings that set a cell's limits: what sound runs of the program give,
and what the control gives, over many seeds in one process.  The benchmark's own runs never run this.

    python3 benchmarks/chip/calibrate.py --workload <name> \\
        --seeds 1,2,3 [--seconds 8]

For each seed it serves one window of the cell's traffic and prints one
JSON line: ``program``, the numbers of the program's timed path against
the reference at the configuration's precision; ``control``, the same
numbers for the reference computed in the nearest precision below
("high": three bf16 passes) put in the program's place.  The last line
gives, per number, the largest program reading and the smallest control
reading.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CONTROL = "high"


def _numbers_only(d: dict) -> dict:
    return {k: v for k, v in d.items() if not k.startswith("_")}


def calibrate_serve(cell, seeds, seconds, log):
    from chipbench import trace
    from chipbench.kinds import serve_open_loop as S
    serving = S.Serving(cell, seeds[0])
    off = trace.Tracer(False)
    for seed in seeds:
        t0 = time.perf_counter()
        serving.use_seed(seed)
        w = serving.window(serving.requests(cell.traffic, seed, seconds),
                           seconds, off)
        rids = S.check_sample(cell.traffic, w, seed)
        got = serving.served_logits(w, rids)
        imgs = np.concatenate([w["images"][r]
                              for r in rids])
        want = S.reference_logits(cell, seed, imgs)
        ctl = S.reference_logits(cell, seed, imgs, CONTROL)
        row = {"seed": seed,
               "program": {"logits_err": S.logits_err(got, want),
                           "_images_compared": int(len(got)),
                           "_answered": len(w["done_at"]),
                           "_requests": len(w["due"])},
               "control": {"logits_err": S.logits_err(ctl, want)},
               "seconds": time.perf_counter() - t0}
        log(json.dumps(row))
        yield row


def summarize(rows: list[dict]) -> dict:
    out = {}
    for r in rows:
        for who, nums in r.items():
            if not isinstance(nums, dict):
                continue
            for k, v in _numbers_only(nums).items():
                agg = max if who == "program" else min
                key = f"{who}.{k}"
                out[key] = v if key not in out else agg(out[key], v)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import device, spec

    def log(msg):
        print(msg, flush=True)

    cell = spec.load_cell(ROOT, args.workload)
    try:
        log(f"device: {device.describe(cell.chips)}")
    except (device.NoChip, ImportError) as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    device.use_compile_cache(ROOT)
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = list(calibrate_serve(cell, seeds, args.seconds, log))
    log(json.dumps({"summary": summarize(rows), "seeds": seeds}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
