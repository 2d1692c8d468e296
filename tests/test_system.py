"""End-to-end behaviour tests for the paper's system."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=900):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    res = subprocess.run([sys.executable, "-m"] + args, capture_output=True,
                         text=True, env=env, timeout=timeout)
    assert res.returncode == 0, f"\nSTDOUT:{res.stdout}\nSTDERR:{res.stderr}"
    return res.stdout


def test_train_driver_loss_decreases(tmp_path):
    out = _run(["repro.launch.train", "--arch", "llama3-8b", "--reduced",
                "--steps", "40", "--batch", "8", "--seq", "64",
                "--lr", "3e-3", "--ckpt-dir", str(tmp_path),
                "--ckpt-every", "20", "--log-every", "20"])
    assert "improved" in out and "NOT improved" not in out


def test_train_driver_resume(tmp_path):
    _run(["repro.launch.train", "--arch", "mamba2-370m", "--reduced",
          "--steps", "10", "--batch", "4", "--seq", "32",
          "--ckpt-dir", str(tmp_path), "--ckpt-every", "5"])
    out = _run(["repro.launch.train", "--arch", "mamba2-370m", "--reduced",
                "--steps", "15", "--batch", "4", "--seq", "32",
                "--ckpt-dir", str(tmp_path), "--resume"])
    assert "resumed from step 10" in out


def test_serve_driver():
    out = _run(["repro.launch.serve", "--arch", "granite-moe-1b-a400m",
                "--reduced", "--batch", "2", "--prompt-len", "16",
                "--gen", "8"])
    assert "ms/tok" in out


def test_elastic_checkpoint_remesh(tmp_path):
    """A checkpoint saved unsharded restores onto a different topology."""
    from repro.checkpoint import CheckpointManager
    from repro.launch.elastic import RemeshPlan
    mgr = CheckpointManager(str(tmp_path))
    state = {"params": {"w": jnp.arange(32.0).reshape(4, 8)}}
    mgr.save(3, state)
    restored, _ = mgr.restore(state)      # same-host restore
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(state["params"]["w"]))
    plan = RemeshPlan.plan(False, True)   # 256 -> 512 chips
    assert plan.batch_ratio == 2.0


def test_straggler_detection():
    from repro.launch.elastic import StepTimer
    t = StepTimer(window=20, ratio=2.0)
    t.times = [0.1] * 18 + [0.5, 0.6]
    assert t.straggling
    t.times = [0.1] * 20
    assert not t.straggling


def test_googlenet_scheduler_beats_serial():
    """The paper's headline behaviour on its own network."""
    from repro.configs import get_config
    from repro.core import compare_policies
    from repro.models.cnn import build_graph
    g = build_graph(get_config("googlenet"), batch=32)
    res = compare_policies(g)
    assert res["speedup"] > 1.05
    co = [grp for grp in res["concurrent"].groups if len(grp.ops) > 1]
    assert len(co) >= 9   # at least one co-exec group per inception module


def test_dryrun_artifacts_complete():
    """Every (arch x shape x mesh) cell the assignment requires has a
    passing dry-run record (produced by launch/dryrun.py)."""
    d = os.path.join(REPO, "results", "dryrun")
    if not os.path.isdir(d):
        pytest.skip("dry-run results not generated in this environment")
    from repro.configs import ARCHS, get_config
    missing, failed = [], []
    for arch in (a for a in ARCHS if a != "googlenet"):
        cfg = get_config(arch)
        shapes = ["train_4k", "prefill_32k", "decode_32k"] + \
            (["long_500k"] if cfg.sub_quadratic else [])
        for shape in shapes:
            for mesh in ("single", "multi"):
                p = os.path.join(d, f"{arch}__{shape}__{mesh}.json")
                if not os.path.exists(p):
                    missing.append((arch, shape, mesh))
                    continue
                rec = json.load(open(p))
                if not rec.get("ok"):
                    failed.append((arch, shape, mesh))
    assert not missing, f"missing cells: {missing}"
    assert not failed, f"failed cells: {failed}"


def test_hlo_analyzer_against_xla_on_unrolled():
    """The while-corrected analyzer agrees with XLA cost_analysis when
    there are no loops (exactness check)."""
    from repro.roofline import analyze_hlo

    def unrolled(w, x):
        for i in range(4):
            x = jnp.tanh(x @ w[i])
        return x

    w = jax.ShapeDtypeStruct((4, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    c = jax.jit(unrolled).lower(w, x).compile()
    mine = analyze_hlo(c.as_text()).flops
    xla = c.cost_analysis()["flops"]
    assert abs(mine - xla) / xla < 0.05


def test_hlo_analyzer_corrects_scan_undercount():
    from repro.roofline import analyze_hlo

    def scanned(w, x):
        def body(x, wi):
            return jnp.tanh(x @ wi), None
        return jax.lax.scan(body, x, w)[0]

    w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    c = jax.jit(scanned).lower(w, x).compile()
    mine = analyze_hlo(c.as_text()).flops
    xla = c.cost_analysis()["flops"]
    assert mine > 7 * xla / 8 * 7      # ~8x the single-body count
    assert abs(mine - 8 * 2 * 64 * 128 * 128) / mine < 0.1
