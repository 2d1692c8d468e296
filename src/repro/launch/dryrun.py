import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS",
                                         "--xla_force_host_platform_device_count=512")
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware:
  * single-pod (data=16, model=16) = 256 chips,
  * multi-pod (pod=2, data=16, model=16) = 512 chips,
for every assigned architecture x its shape set.  Emits per-cell JSON with
memory_analysis, cost_analysis and the HLO collective inventory that
§Roofline consumes.

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
import argparse
import json
import re
import time
import traceback

import jax

from repro.configs import ARCHS, SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import input_specs
from repro.sharding import specs as SH

LM_ARCHS = tuple(a for a in ARCHS if a != "googlenet")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"^\s*(?:%|ROOT\s+%?)?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.M)
_GROUP_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUP_RE2 = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(type_str: str) -> int:
    """Sum bytes over one HLO type (possibly a tuple)."""
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_inventory(hlo_text: str) -> dict:
    """Per-kind result-bytes + ring-model wire bytes per chip.

    Ring model (documented in EXPERIMENTS.md §Roofline):
      all-gather:        wire = (g-1)/g * result_bytes
      reduce-scatter:    wire = (g-1)   * result_bytes   (operand = g*result)
      all-reduce:        wire = 2(g-1)/g * result_bytes
      all-to-all:        wire = (g-1)/g * result_bytes
      collective-permute: wire = result_bytes
    g = replica group size parsed per op (fallback: 2).
    """
    inv = {}
    wire_total = 0.0
    for m in _COLL_RE.finditer(hlo_text):
        type_str, kind = m.group(1), m.group(2)
        rb = _shape_bytes(type_str)
        line_end = hlo_text.find("\n", m.end())
        line = hlo_text[m.start():line_end if line_end > 0 else None]
        g = 2
        gm = _GROUP_RE.search(line)
        if gm:
            g = max(len(gm.group(1).split(",")), 1)
        else:
            gm2 = _GROUP_RE2.search(line)
            if gm2:
                g = max(int(gm2.group(2)), 1)
        if kind == "all-gather":
            wire = (g - 1) / g * rb
        elif kind == "reduce-scatter":
            wire = (g - 1) * rb
        elif kind == "all-reduce":
            wire = 2 * (g - 1) / g * rb
        elif kind == "all-to-all":
            wire = (g - 1) / g * rb
        else:
            wire = rb
        d = inv.setdefault(kind, {"count": 0, "result_bytes": 0.0,
                                  "wire_bytes": 0.0})
        d["count"] += 1
        d["result_bytes"] += rb
        d["wire_bytes"] += wire
        wire_total += wire
    inv["total_wire_bytes"] = wire_total
    return inv


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save_hlo: str | None = None, perf: dict | None = None) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "multi" if multi_pod else "single",
           "chips": mesh.size,
           "perf": sorted((perf or {}).keys()),
           "params": cfg.param_count(),
           "active_params": cfg.active_param_count()}
    t0 = time.time()
    with SH.activations_on(mesh, **(perf or {})):
        fn, args, in_sh, out_sh, donate = input_specs(cfg, shape, mesh)
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
    mem = compiled.memory_analysis()
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                rec[k] = int(v)
    cost = compiled.cost_analysis()
    if cost:
        rec["cost_flops"] = float(cost.get("flops", -1))
        rec["cost_bytes"] = float(cost.get("bytes accessed", -1))
        rec["cost_transcendentals"] = float(cost.get("transcendentals", -1))
    hlo = compiled.as_text()
    rec["hlo_chars"] = len(hlo)
    rec["collectives"] = collective_inventory(hlo)
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    del hlo, compiled, lowered
    return rec


def cells_for(arch: str) -> list[str]:
    cfg = get_config(arch)
    out = []
    for name in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
        if name == "long_500k" and not cfg.sub_quadratic:
            continue   # skipped per assignment: pure full-attention archs
        out.append(name)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--perf", default="",
                    help="comma-separated perf options: seq_shard,"
                         "dp_over_model,causal_skip,dots_remat")
    args = ap.parse_args()
    perf = {k: True for k in args.perf.split(",") if k}
    perf_tag = ("__" + "_".join(sorted(perf))) if perf else ""

    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    if args.all:
        todo = [(a, s) for a in LM_ARCHS for s in cells_for(a)]
    else:
        assert args.arch, "--arch or --all"
        shapes = [args.shape] if args.shape else cells_for(args.arch)
        todo = [(args.arch, s) for s in shapes]

    n_fail = 0
    for arch, shape in todo:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'multi' if mp else 'single'}{perf_tag}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip] {tag} (cached)", flush=True)
                continue
            print(f"[cell] {tag} ...", flush=True)
            try:
                hlo_path = (os.path.join(args.out, tag + ".hlo.txt")
                            if args.save_hlo else None)
                rec = run_cell(arch, shape, mp, save_hlo=hlo_path, perf=perf)
                rec["ok"] = True
            except Exception as e:
                rec = {"arch": arch, "shape": shape,
                       "mesh": "multi" if mp else "single", "ok": False,
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
                n_fail += 1
                print(f"[FAIL] {tag}: {rec['error']}", flush=True)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            if rec.get("ok"):
                coll = rec["collectives"].get("total_wire_bytes", 0)
                print(f"[ok]   {tag} lower={rec['lower_s']}s "
                      f"compile={rec['compile_s']}s "
                      f"flops/dev={rec.get('cost_flops', -1):.3g} "
                      f"wire/dev={coll:.3g}B", flush=True)
    print(f"done. failures={n_fail}", flush=True)
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
