"""The harness finds every piece of a cell by its name in BENCHMARK.json: a
new cell with a new traffic mix and a new per-layer metric needs new
entries and new files only."""
import hashlib
import json

import chiptiny
import pytest
from chipbench import spec


def _digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


def test_real_cells_resolve():
    bench = json.loads((chiptiny.REPO / "BENCHMARK.json").read_text())
    assert bench["workloads"]
    for wl in (w["name"] for w in bench["workloads"]):
        cell = spec.load_cell(chiptiny.REPO, wl)
        assert cell.ref.SIZES["name"] == "googlenet"
        assert spec.limits_path(cell).is_file()
        spec.kind_module(cell.kind)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
            assert callable(spec.metric_reader(cell.root, m["name"]).read)


def test_new_cell_traffic_and_metric_need_no_edit(tmp_path):
    root = chiptiny.make_root(tmp_path / "co")
    before = _digest(root)
    bench = root / "benchmarks" / "chip"
    (bench / "traffic" / "serve-single.json").write_text(json.dumps(
        {**chiptiny.SERVE, "rate_per_s": 3.0, "images_max": 1}))
    (bench / "metrics" / "dispatches_per_s.serve.py").write_text(
        "def read(ctx):\n"
        "    if ctx['kind'] != 'serve':\n"
        "        return None\n"
        "    return ctx['units'] / sum(ctx['dispatch_walls_s'])\n")
    (bench / "checks" / "tiny-single.json").write_text(json.dumps(
        {"numbers": {"logits_err": {"limit": 1e-5}}}))
    bj = json.loads((root / "BENCHMARK.json").read_text())
    bj["workloads"].append({"name": "tiny-single", "config": "tiny",
                            "traffic": "serve-single", "chips": 1,
                            "why": "a test"})
    for m in bj["end_to_end"]:
        if "tiny-serve" in m.get("workloads", ()):
            m["workloads"].append("tiny-single")
    bj["per_layer"].append({"name": "dispatches_per_s.serve", "unit": "1/s",
                            "better": "higher", "source": "host_clock",
                            "layer": "launch loops", "moves": "serve_p95_ms",
                            "workloads": ["tiny-single"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bj))
    after = _digest(root)
    changed = {k for k in before if before[k] != after.get(k)}
    assert changed == {"BENCHMARK.json"}

    cell = spec.load_cell(root, "tiny-single")
    assert cell.traffic["images_max"] == 1
    assert cell.kind == "serve_open_loop"
    layer = {m["name"] for m in cell.per_layer}
    assert "dispatches_per_s.serve" in layer
    assert "launches_per_dispatch.serve" not in layer   # listed elsewhere
    reader = spec.metric_reader(root, "dispatches_per_s.serve")
    assert reader.read({"kind": "serve", "units": 4,
                        "dispatch_walls_s": [1.0, 1.0]}) == 2.0
    assert reader.read({"kind": "other"}) is None


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        spec.load_cell(chiptiny.REPO, "nope")


def test_every_cell_of_a_kind_finds_its_readers(tmp_path):
    root = chiptiny.make_root(tmp_path / "co")
    cell = spec.load_cell(root, "tiny-serve")
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.metric_reader(root, m["name"]).read)


@pytest.mark.parametrize("missing", ["program_config", "forward_ops"])
def test_config_module_without_a_contract_function_is_refused(tmp_path,
                                                              missing):
    root = chiptiny.make_root(tmp_path / "co")
    mod = root / "benchmarks" / "chip" / "configs" / "tiny.py"
    mod.write_text(mod.read_text().replace(f"def {missing}(",
                                           f"def _{missing}("))
    with pytest.raises(AttributeError, match=f"tiny.py has no {missing}"):
        spec.load_cell(root, "tiny-serve")
