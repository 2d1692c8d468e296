"""``BENCHMARK.json`` and the files it names, resolved for one cell."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import pathlib
import sys


@dataclasses.dataclass
class Cell:
    root: pathlib.Path          # the checkout: BENCHMARK.json lives here
    workload: dict              # the BENCHMARK.json entry
    config: dict                # the configs entry
    sizes: dict                 # configs/<config>.json
    traffic: dict               # traffic/<traffic>.json
    end_to_end: list            # metric entries this cell reports
    per_layer: list
    ref: object = None          # configs/<config>.py, the plain reference

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


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
    cell.ref = load_module((root / cfg["file"]).with_suffix(".py"),
                           f"chipbench_config_{cfg['name']}")
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
