"""A benchmark checkout in miniature for the harness's CPU tests: the real
harness, readers and reference, and a tiny inception configuration whose
serving cell runs in seconds with the Pallas kernels interpreted."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

TINY = {
    "name": "tiny", "img": [16, 16, 3], "num_classes": 10,
    "stem": [[3, 16, 2]], "modules": [[8, 8, 16, 4, 8, 8]],
    "pool_between": [], "dtype": "float32", "matmul_precision": "highest",
}
SERVE = {"kind": "serve_open_loop", "rate_per_s": 8.0, "images_min": 1,
         "images_max": 3, "max_images": 2, "chain_modules": True,
         "check_requests": 4}
# The tiny cell's limit, set as the real one is, from readings on the CPU
# over twelve seeds (1-8, 2**31 + 5, 6, 77, 99): the program's largest
# logits gap 3.4e-7, the control's (three bf16 passes) smallest 5.2e-6.
LIMITS = {"tiny-serve": {"logits_err": 1.5e-6}}


def make_root(tmp: pathlib.Path, *, extra_traffic=None, extra_metric=None,
              serve=None) -> pathlib.Path:
    """A checkout holding BENCHMARK.json and the benchmark's files, with the
    tiny configuration and its serving cell beside the real ones."""
    root = pathlib.Path(tmp)
    bench = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__"))
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY))
    shutil.copy(bench / "configs" / "googlenet.py",
                bench / "configs" / "tiny.py")
    (bench / "traffic" / "tiny-serve.json").write_text(
        json.dumps({**SERVE, **(serve or {})}))
    for wl, lim in LIMITS.items():
        (bench / "checks" / f"{wl}.json").write_text(json.dumps(
            {"numbers": {k: {"limit": v} for k, v in lim.items()}}))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "a test",
                            "file": "benchmarks/chip/configs/tiny.json",
                            "reduced": [], "why": "CPU tests"})
    spec["workloads"].append({"name": "tiny-serve", "config": "tiny",
                              "traffic": "tiny-serve", "chips": 1,
                              "why": "CPU tests"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "googlenet-serve-mixed" in m.get("workloads", ()):
            m["workloads"].append("tiny-serve")
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return root


def fake_tpu(chips: int) -> dict:
    """The look for a chip, skipped: what a v5e reports."""
    return {"platform": "tpu", "kind": "TPU v5 lite", "count": chips}


def reference_module():
    from chipbench import spec
    return spec.load_module(BENCH / "configs" / "googlenet.py",
                            "chipbench_config_googlenet")


def googlenet_sizes() -> dict:
    return json.loads((BENCH / "configs" / "googlenet.json").read_text())
