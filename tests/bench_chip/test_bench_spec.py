"""The harness finds every piece of a cell by its name in BENCHMARK.json: a
new cell with a new traffic mix and a new per-layer metric, or a new
configuration with its own network and op names, needs new entries and new
files only."""
import hashlib
import json
import pathlib

import chiptiny
import pytest
from chipbench import spec, trace


def _digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


def assert_cells_resolve(root):
    """Every cell of ``root`` loads with its own configuration's module and
    sizes, its limits, its kind and a reader for each per-layer metric."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    assert bench["workloads"]
    for wl in bench["workloads"]:
        cell = spec.load_cell(root, wl["name"])
        assert cell.config["name"] == wl["config"]
        assert pathlib.Path(cell.ref.__file__) == \
            spec.bench_dir(root) / "configs" / f"{wl['config']}.py"
        assert cell.sizes["name"] == cell.config["name"]
        assert "matmul_precision" in cell.sizes
        assert spec.limits_path(cell).is_file()
        spec.kind_module(cell.kind)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
            assert callable(spec.metric_reader(cell.root, m["name"]).read)


def test_real_cells_resolve():
    assert_cells_resolve(chiptiny.REPO)


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


#: a configuration's module whose network is not GoogLeNet's: its own
#: sizes, and kernel ops named as Inception-v3's are
OTHER_MODULE = '''"""A second network, as far as the harness sees it."""
import json
import pathlib

from chipbench import work

SIZES = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def make_params(sizes, seed):
    return {"seed": seed}


def logits(params, sizes, images, precision):
    raise NotImplementedError


def program_config(sizes):
    return None


def forward_ops(sizes, batch):
    x, ops = (batch, *sizes["img"]), []
    for name, cout in (("red0/b3x3", 16), ("mixed6b/b7x7dbl_2", 32)):
        op, x = work.conv(name, x, cout, 3, 3, dtype=sizes["dtype"],
                          kernel=True)
        ops.append(op)
    op, _ = work.pool("pool", x, 3, 2, "VALID", "avg", dtype=sizes["dtype"])
    return ops + [op]
'''


def test_new_configuration_needs_no_edit(tmp_path):
    root = chiptiny.make_root(tmp_path / "co")
    before = _digest(root)
    bench = root / "benchmarks" / "chip"
    (bench / "configs" / "other.json").write_text(json.dumps(
        {"name": "other", "img": [17, 17, 8], "dtype": "float32",
         "matmul_precision": "highest"}))
    (bench / "configs" / "other.py").write_text(OTHER_MODULE)
    (bench / "traffic" / "other-serve.json").write_text(json.dumps(
        chiptiny.SERVE))
    (bench / "checks" / "other-serve.json").write_text(json.dumps(
        {"numbers": {"logits_err": {"limit": 1e-5}}}))
    bj = json.loads((root / "BENCHMARK.json").read_text())
    bj["configs"].append({"name": "other", "source": "a test",
                          "file": "benchmarks/chip/configs/other.json",
                          "reduced": [], "why": "a test"})
    bj["workloads"].append({"name": "other-serve", "config": "other",
                            "traffic": "other-serve", "chips": 1,
                            "why": "a test"})
    for m in bj["end_to_end"] + bj["per_layer"]:
        if "tiny-serve" in m.get("workloads", ()):
            m["workloads"].append("other-serve")
    (root / "BENCHMARK.json").write_text(json.dumps(bj))
    after = _digest(root)
    changed = {k for k in before if before[k] != after.get(k)}
    assert changed == {"BENCHMARK.json"}

    cell = spec.load_cell(root, "other-serve")
    assert cell.sizes["img"] == [17, 17, 8]
    assert cell.kernel_ops == ["red0/b3x3", "mixed6b/b7x7dbl_2"]
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in spec.load_cell(root, "tiny-serve").per_layer}
    assert_cells_resolve(root)
    # its kernels are read under their own scopes
    kernel = ', custom_call_target="tpu_custom_call"'
    ops = [trace.Event(f"%plan_{mode}_{op}_.1 = f32[1] custom-call(){kernel}",
                       start, 1e6)
           for start, (mode, op) in enumerate([
               ("grouped_pooled", "red0.b3x3"),
               ("grouped_chained", "mixed6b.b7x7dbl_2")], 1)]
    red = trace.reduce(trace.Trace({"/device:TPU:0": ops}, [trace.Event(
        "bench.window", 0, 1e7)]), cell.kernel_ops)
    assert red["launches"] == 2
    assert dict(red["device_ops"]) == {
        "plan[grouped_pooled:red0/b3x3]": 1e-3,
        "plan[grouped_chained:mixed6b/b7x7dbl_2]": 1e-3}
    assert red["plan_kernels_s"] == 2e-3


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
