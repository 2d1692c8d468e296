"""A whole run of the tiny serving cell on the CPU, past the harness's look
for a chip: sound, it comes out correct; with an answer altered where it is
produced, half a dispatch left out, or the control (the reference in three
bf16 passes) in the program's place, it does not."""
import chiptiny
import pytest

import calibrate
import run
from chipbench import spec


def _run(tmp_path, wrap=None, describe=chiptiny.fake_tpu, sub="co"):
    root = chiptiny.make_root(tmp_path / sub)
    return run.run_cell(root, "tiny-serve", 2**31 + 77, 1.0, False,
                        describe=describe, wrap=wrap, compile_cache=False,
                        log=lambda _m: None)


def altered(serve):
    """Every answer nudged by a thousandth where it is produced."""
    def broken(params, images, valid):
        return serve(params, images, valid) * 1.001
    return broken


def half_left_out(serve):
    """Half of each dispatch's images left out."""
    def broken(params, images, valid):
        return serve(params, images, (valid + 1) // 2)
    return broken


def test_sound_run_is_correct(tmp_path):
    line = _run(tmp_path)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] == 8 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_p50_ms", "serve_p95_ms",
                                    "serve_images_per_s", "setup_s"}
    assert list(line)[-1] == "checks"


def control(_serve):
    """The reference at the precision below the configuration's, "high"
    (three bf16 passes), put in the program's place."""
    ref = chiptiny.reference_module()

    def lower(params, images, _valid):
        return ref.forward(params, chiptiny.TINY, images, "high")
    return lower


@pytest.mark.parametrize("fault", [altered, half_left_out, control])
def test_broken_serving_is_not_correct(tmp_path, fault):
    line = _run(tmp_path, fault)
    assert line["correct"] is False
    check = line["checks"]["logits_err"]
    assert not check["value"] <= check["limit"]


def test_no_chip_or_unknown_chip_is_refused(tmp_path):
    def cpu(_chips):
        from chipbench import device
        return device.describe(1)          # JAX here runs on the CPU
    assert _run(tmp_path, describe=cpu) is None
    assert _run(tmp_path, describe=lambda c: {
        "platform": "tpu", "kind": "TPU v9", "count": c}, sub="v9") is None


def test_control_fails_the_limit(tmp_path):
    root = chiptiny.make_root(tmp_path / "co")
    cell = spec.load_cell(root, "tiny-serve")
    lim = chiptiny.LIMITS["tiny-serve"]["logits_err"]
    for row in calibrate.calibrate_serve(cell, [4, 2**31 + 6], 1.0,
                                         lambda _m: None):
        assert row["program"]["logits_err"] <= lim
        assert row["control"]["logits_err"] > lim
