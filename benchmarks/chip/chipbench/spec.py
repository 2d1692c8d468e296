"""``BENCHMARK.json`` and the files it names, resolved for one cell.

A configuration's module, ``configs/<config>.py`` beside its sizes, is the
one place that knows its network.  It provides:

  make_params(sizes, seed)    the weights, made on the device from the seed
  logits(params, sizes, images, precision)
                              the plain reference's logits
  program_config(sizes)       the program's config object, built through
                              the program's public API; it has ``img``,
                              and ``plan_cnn`` and ``make_cnn_serve_step``
                              take it
  forward_ops(sizes, batch)   the forward's work op by op, a list of
                              ``work.OpWork`` written with ``work.conv``
                              and ``work.pool``; ``kernel`` marks the ops
                              the plan's kernels run

``load_cell`` refuses a module that lacks one of them, before any device
work.

Beside the module, ``configs/<config>.json`` holds the sizes as they are
run.  It carries ``name``, the configs entry's name, and
``matmul_precision``, the precision the configuration states, at which the
harness runs the reference.  The rest of its keys are the module's.

The profiler's trace names each Pallas kernel by the ``plan[mode:op]``
scope it ran in, and the harness recognises a kernel by that mode, one of
the program's plan modes, and that op, one that ``forward_ops`` lists and
marks ``kernel`` (``Cell.kernel_ops``).  So a configuration's op names in
``forward_ops`` are the names its plan gives the same ops.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import pathlib
import sys

from chipbench import work

CONFIG_FUNCTIONS = ("make_params", "logits", "program_config",
                    "forward_ops")


@dataclasses.dataclass
class Cell:
    root: pathlib.Path          # the checkout: BENCHMARK.json lives here
    workload: dict              # the BENCHMARK.json entry
    config: dict                # the configs entry
    sizes: dict                 # configs/<config>.json
    traffic: dict               # traffic/<traffic>.json
    end_to_end: list            # metric entries this cell reports
    per_layer: list
    ref: object = None          # configs/<config>.py, its network

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    @property
    def kernel_ops(self) -> list[str]:
        """The names of the ops the plan's kernels run, by ``forward_ops``:
        the trace reads a kernel's scope by them."""
        return [op.name for op in
                work.plan_ops(self.ref.forward_ops(self.sizes, 1))]


def bench_dir(root: pathlib.Path) -> pathlib.Path:
    return pathlib.Path(root) / "benchmarks" / "chip"


def load_module(path: pathlib.Path, name: str):
    """Import one file by path, under a name of its own."""
    path = pathlib.Path(path)
    if not path.is_file():
        raise FileNotFoundError(path)
    name = re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _reported_in(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root, workload: str) -> Cell:
    root = pathlib.Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in bench["workloads"]}
    if workload not in wl:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(wl)}")
    w = wl[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    sizes = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads(
        (bench_dir(root) / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _reported_in(m, workload)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in reported and _reported_in(m, workload)]
    cell = Cell(root, w, cfg, sizes, traffic, e2e, per_layer)
    path = (root / cfg["file"]).with_suffix(".py")
    cell.ref = load_module(path, f"chipbench_config_{cfg['name']}")
    for fn in CONFIG_FUNCTIONS:
        if not callable(getattr(cell.ref, fn, None)):
            raise AttributeError(f"{path} has no {fn}(): a configuration's "
                                 f"module provides {', '.join(CONFIG_FUNCTIONS)}")
    return cell


def limits_path(cell: Cell) -> pathlib.Path:
    """``checks/<workload>.json``: the limit of each number compared."""
    return bench_dir(cell.root) / "checks" / f"{cell.name}.json"


def kind_module(kind: str):
    path = pathlib.Path(__file__).parent / "kinds" / f"{kind}.py"
    return load_module(path, f"chipbench_kind_{kind}")


def metric_reader(root, name: str):
    path = bench_dir(root) / "metrics" / f"{name}.py"
    return load_module(path, f"chipbench_metric_{name}")
