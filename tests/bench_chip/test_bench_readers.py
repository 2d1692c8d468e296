"""The per-layer readers on a hand-made context: each reads its own kind's
numbers and nothing from another kind's."""
import chiptiny
import pytest
from chipbench import spec

PEAKS = {"flops_bf16": 200e12}
TRACE = {"launches": 88.0, "plan_kernels_s": 0.2, "busy_s": 0.8,
         "window_s": 1.0}
OTHER = {"kind": "other", "units": 2, "window_s": 1.0, "model_flops": 2e12,
         "plan_ideal_s": 0.02, "trace": TRACE, "peaks": PEAKS}
SERVE = {"kind": "serve", "units": 4, "dispatch_walls_s": [0.1, 0.3, 0.2,
                                                          0.4],
         "valid_images": [1, 8, 4, 8], "model_flops": 1e12,
         "plan_ideal_s": 0.01, "trace": TRACE, "peaks": PEAKS}


@pytest.mark.parametrize("name,ctx,want", [
    ("serve_mfu", SERVE, 0.5),
    ("dispatch_wall_ms_p50.serve", SERVE, 250.0),
    ("launches_per_dispatch.serve", SERVE, 22.0),
    ("plan_kernels_roofline.serve", SERVE, 5.0),
    ("device_idle_share.serve", SERVE, 20.0),
])
def test_reader(name, ctx, want):
    read = spec.metric_reader(chiptiny.REPO, name).read
    assert read(ctx) == pytest.approx(want)
    assert read(OTHER) is None


def test_roofline_silent_without_kernels():
    ctx = {**SERVE, "trace": {**TRACE, "plan_kernels_s": 0.0}}
    assert spec.metric_reader(chiptiny.REPO, "plan_kernels_roofline.serve"
                              ).read(ctx) is None
