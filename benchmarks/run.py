"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  t1        — Table-1 analogue: per-algorithm resource profiles of two
              independent inception convolutions.
  t2        — Table-2 analogue: workspace memory vs runtime per conv
              algorithm (C4 non-correlation).
  gemm      — the GEMM algorithm zoo (LM-scale analogue).
  makespan  — serial vs concurrency-aware scheduling on GoogleNet (the
              paper's proposal, modeled TPU makespan) + the 27-cases count.
  stacked   — intra-chip stacked branch GEMM vs per-branch GEMMs.
  branch_gemm_modes — pooled vs fused_concat vs grouped vs stacked vs
              serial execution of one ragged Inception module's CoGroups,
              forward AND backward (the eager VJP pullback per forced
              mode — the grad CoGroups of core/plan.py backward_plan;
              fused_concat absorbs the join into the grouped launch,
              pooled additionally streams the pool-proj maxpool through
              the quad's launch, and both run ONE combined backward
              launch per grad CoGroup).
  plan_makespan — modeled vs executed makespan per execution mode for the
              lowered plan (core/plan.py), serial vs planned — the
              cost-model validation table.
  roofline  — summary of the dry-run roofline table (if generated).

Wall times are XLA-CPU (this host); modeled columns are TPU-v5e analytic.

Besides the CSV, writes ``BENCH_plan.json`` (machine-readable perf
baseline: branch-GEMM mode wall/modeled times forward+backward, googlenet
forward/backward mode counts and modeled train-step makespan, the
cross-module-streaming column — chained-plan mode counts, modeled
makespans and traced-jaxpr ``googlenet_launches`` per direction for the
default AND ``chain_modules=True`` plans — the continuous-batching
serving column (QPS + request-level p50/p99 latency through the cached
ragged plans of ``launch/serve.py``, plan-cache hit stats, padded-M
waste, the served chained forward's traced launch count, the
masked-chained bit-match verdict and the dead-block skip ratio) — the MoE
expert-dispatch column (grouped ragged engine vs capacity-padded einsum:
wall + modeled per engine, one-launch-per-direction counts, bit-match
and zero-token-expert verdicts, padded_slot_fraction) — and the
plan_makespan rows).  ``--smoke`` runs a seconds-scale subset (fewer
reps, no plan_makespan; same batch=2 module — batch 1 is unrepresentative
of the grouped-vs-stacked backward) and writes ``BENCH_plan.smoke.json``
instead
so a quick CI pass never clobbers the committed baseline; ``scripts/ci.sh``
asserts the smoke guardrails (backward wall ordering grouped <= stacked
<= serial, fused_concat no slower than grouped, one combined backward
launch per grad CoGroup, zero standalone googlenet joins).
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def _emit(rows):
    for r in rows:
        name = r.pop("table")
        us = r.pop("us_per_call", "")
        derived = ";".join(f"{k}={v}" for k, v in r.items())
        print(f"{name},{us},{derived}", flush=True)


def _dead_block_skip():
    """Executed-vs-skipped grid steps of a masked chained launch on a
    rows/image == bm fixture (4 images, 4 M-blocks) at one live image:
    the grid-step counter must show the dead blocks ran ZERO steps, so
    the skip ratio is exactly 1 - n/bucket = 0.75."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis import tables
    from repro.core import plan as planlib
    gmm = importlib.import_module("repro.kernels.grouped_matmul")

    b, h, w = 4, 16, 8                      # h*w = 128 rows/image = bm
    m = b * h * w
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x0 = jax.random.normal(ks[0], (m, 64)) * 0.3
    w0 = jax.random.normal(ks[1], (64, 48)) * 0.3
    wmat = jax.random.normal(ks[3], (48 * 9, 40)) * 0.3
    phases = [
        [{"n": 48, "w": planlib._pad_w_dense(w0, 128),
          "b": jax.random.normal(ks[2], (48,)),
          "src": ("x", [x0]), "ring_write": (0,)}],
        [{"n": 40, "w": planlib._pack_w_ring(wmat, 3, 3, 48, 1, 128),
          "b": jax.random.normal(ks[4], (40,)),
          "src": ("ring", 3, 3, (0,)), "ring_write": None}],
    ]
    _, steps = gmm.grouped_matmul_chained(
        phases, m=m, h=h, w=w, bm=128, m_valid=h * w, debug_steps=True,
        interpret=True)
    tab = np.asarray(gmm._plan_tiles_chained(
        m // 128, gmm._chain_static(phases, 128, w)))
    total = tab.shape[1]
    executed = int(jnp.asarray(steps)[0, 0])
    return {
        "bucket_images": b,
        "live_images": 1,
        "grid_steps": total,
        "executed_steps": executed,
        "skip_ratio": (total - executed) / total,
        "expected_skip_ratio": 1 - 1 / b,
    }


def main(smoke: bool = False) -> None:
    from benchmarks.paper_tables import (matmul_algorithm_table,
                                         table1_resource_profiles,
                                         table2_workspace_vs_time)
    from benchmarks.branch_parallel_bench import (
        branch_mode_bench, fused_complementary_bench, makespan_table,
        modeled_vs_executed_table, stacked_branch_gemm_bench)
    from benchmarks.tolerances import FUSED_WALL_TOL, POOLED_WALL_TOL
    from repro.configs import get_config, get_reduced
    from repro.models import cnn as CNN

    bench_json: dict = {"host": "xla-cpu (Pallas interpret)",
                        "modeled": "TPU-v5e analytic cost model"}

    print("name,us_per_call,derived")
    if not smoke:
        _emit(table1_resource_profiles())
        _emit(table2_workspace_vs_time())
        _emit(matmul_algorithm_table())
    _emit(makespan_table())

    # batch 2 even in smoke: at batch 1 (M=256 rows) the grouped kernels'
    # fixed packing overhead dominates the interpret-mode wall and the
    # grouped-vs-stacked backward ordering is not representative
    mode_rows, modes = branch_mode_bench(batch=2, reps=3 if smoke else 5)
    _emit([dict(r) for r in mode_rows])
    wall = {m: v["wall_us"] for m, v in modes.items()}
    bwd_wall = {m: v["bwd_wall_us"] for m, v in modes.items()}
    modeled = {m: v["modeled_us"] for m, v in modes.items()}
    bwd_modeled = {m: v["bwd_modeled_us"] for m, v in modes.items()}
    bench_json["branch_gemm"] = {
        "module": mode_rows[0]["module"] if mode_rows else "",
        "wall_us": wall,
        "modeled_us": modeled,
        "wall_ordering_ok": wall["grouped"] <= wall["stacked"]
        <= wall["serial"],
        # *_wall_ok booleans apply the SAME named tolerances ci.sh gates
        # with (benchmarks/tolerances.py) — previously they recorded the
        # raw strict comparison, so a run inside tolerance could write
        # "fused_wall_ok": false into the baseline while CI passed
        "fused_wall_ok":
            wall["fused_concat"] <= FUSED_WALL_TOL * wall["grouped"],
        "fused_modeled_ok": modeled["fused_concat"] <= modeled["grouped"]
        and bwd_modeled["fused_concat"] <= bwd_modeled["grouped"],
        # pooled = fused_concat + the pool-proj maxpool absorbed into the
        # quad launch: modeled drops the standalone reduce_window term
        # (strict win); wall trades a compiled reduce_window for in-kernel
        # pool steps the interpret emulation charges per grid step, so the
        # wall gate lives in ci.sh behind a named tolerance
        "pooled_wall_ok":
            wall["pooled"] <= POOLED_WALL_TOL * wall["fused_concat"],
        "pooled_modeled_ok":
            modeled["pooled"] < modeled["fused_concat"]
            and bwd_modeled["pooled"] <= bwd_modeled["fused_concat"],
        "bwd_wall_us": bwd_wall,
        "bwd_modeled_us": bwd_modeled,
        "bwd_wall_ordering_ok": bwd_wall["grouped"] <= bwd_wall["stacked"]
        <= bwd_wall["serial"],
        "bwd_grouped_beats_serial": bwd_wall["grouped"] <= bwd_wall["serial"],
        "bwd_launches_per_group":
            modes["fused_concat"]["bwd_launches_per_group"],
        "pooled_fwd_launches_per_group":
            modes["pooled"]["fwd_launches_per_group"],
        "pooled_bwd_launches_per_group":
            modes["pooled"]["bwd_launches_per_group"],
        "pooled_standalone_pool_groups":
            modes["pooled"]["standalone_pool_groups"],
    }
    # train=True: the same packing + per-direction budget checks the train
    # driver lowers with — the recorded backward metrics describe the plan
    # the training step actually executes, not an inference-packed one
    plan, _ = CNN.plan_cnn(get_config("googlenet"), batch=32, train=True)
    bwd_plan = plan.context["backward"]
    bench_json["googlenet_mode_counts"] = plan.mode_counts()
    bench_json["googlenet_xla_fallback_groups"] = len(
        plan.groups_of_mode("xla"))
    # zero standalone inception joins on the fused path: every join rides
    # a grouped_concat launch
    bench_json["googlenet_standalone_join_groups"] = sum(
        1 for g in plan.groups
        if g.mode != "grouped_concat" and any("join" in n for n in g.ops))
    # zero standalone maxpool (reduce_window) groups: every pooling
    # primitive streams through a grouped launch (_absorb_pools) — count
    # by op KIND from the graph, not by name, so a rename can't make the
    # ci.sh gate vacuous
    g32 = CNN.build_graph(get_config("googlenet"), 32)
    bench_json["googlenet_standalone_pool_groups"] = sum(
        1 for g in plan.groups
        if any(n in g32.ops and g32.ops[n].kind == "maxpool"
               for n in g.ops))
    bench_json["googlenet_bwd_mode_counts"] = bwd_plan.mode_counts()
    bench_json["googlenet_bwd_xla_fallback_groups"] = len(
        bwd_plan.groups_of_mode("xla"))
    # forward+backward modeled makespans (TPU-v5e analytic, seconds): the
    # training step's two halves under the lowered plans
    bench_json["googlenet_makespan_modeled_s"] = {
        "forward": plan.makespan,
        "backward": bwd_plan.makespan,
        "train_step": plan.makespan + bwd_plan.makespan,
    }

    # cross-module streaming: the chained plan's column next to the
    # default — modeled makespans (both directions) and the traced-jaxpr
    # launch counts the ci.sh launch-ceiling gate pins.  Counts are
    # batch-invariant (plan structure, not data), so the trace runs at
    # batch 2 to keep the smoke pass seconds-scale.
    import jax
    import jax.numpy as jnp
    from repro.core import launch_count as launch_lc
    gcfg = get_config("googlenet")
    plan_c, _ = CNN.plan_cnn(gcfg, batch=32, train=True, chain_modules=True)
    bwd_c = plan_c.context["backward"]
    bench_json["googlenet_chained_mode_counts"] = plan_c.mode_counts()
    bench_json["googlenet_chained_makespan_modeled_s"] = {
        "forward": plan_c.makespan,
        "backward": bwd_c.makespan,
        "train_step": plan_c.makespan + bwd_c.makespan,
    }
    bench_json["googlenet_chained_modeled_ok"] = (
        plan_c.makespan < plan.makespan and bwd_c.makespan < bwd_plan.makespan)

    cparams = CNN.init_params(gcfg, jax.random.PRNGKey(0))
    cbatch = {"images": jnp.zeros((2,) + gcfg.img, jnp.float32),
              "labels": jnp.zeros((2,), jnp.int32)}
    pc2, _ = CNN.plan_cnn(gcfg, batch=2, train=True, chain_modules=True)
    pu2, _ = CNN.plan_cnn(gcfg, batch=2, train=True)
    launches = {}
    for lname, lplan in (("default", pu2), ("chained", pc2)):
        def _loss(p, b, _pl=lplan):
            return CNN.loss_fn(p, gcfg, b, plan=_pl)[0]
        fwd = launch_lc.count_launches(_loss, cparams, cbatch)
        both = launch_lc.count_grad_launches(_loss, cparams, cbatch)
        launches[lname] = {
            "per_forward": fwd["total"],
            "per_backward": max(both["total"] - fwd["total"], 0),
            "pallas_per_forward": fwd.get("pallas_call", 0),
            "grad_trace_total": both["total"],
        }
    bench_json["googlenet_launches"] = launches

    # continuous-batching serving column (runs in smoke too — ci.sh gates
    # it): the ragged-M + plan-cache path of launch/serve.py on
    # googlenet-reduced.  Executed QPS and p50/p99 dispatch latency
    # through ONE cached chained plan + offset tables + jitted executable
    # per M-bucket; the driver itself asserts the post-warmup stream runs
    # at plan-cache hit rate 1.0.  Interpret-mode wall times — the
    # recorded value is the cache/raggedness behavior, not TPU latency.
    from repro.core import plan_cache
    from repro.launch.serve import serve_cnn_metrics
    from repro.launch.steps import make_cnn_serve_step
    plan_cache.reset(clear_entries=True)
    bench_json["serving"] = serve_cnn_metrics(
        get_reduced("googlenet"), max_images=4,
        num_requests=10 if smoke else 24, seed=0)
    # trace-only ceiling for FULL googlenet: the served (ragged, chained)
    # forward must stay under the same launch ceiling as the training
    # trace above — raggedness must not add launches
    sentry = plan_cache.cached_cnn_plan(gcfg, 2, chain_modules=True)
    sfwd = launch_lc.count_launches(
        make_cnn_serve_step(gcfg, sentry.plan), cparams,
        jnp.zeros((2,) + gcfg.img, jnp.float32), jnp.int32(1))
    bench_json["serving"]["served_chained_launches_per_forward"] = \
        sfwd["total"]

    # masked-chained correctness + dead-block skip, gated by ci.sh:
    # (a) a CHAINED reduced-googlenet plan served ragged must bit-match
    # the dense forward on the valid images; (b) at the kernel layer a
    # rows/image == bm fixture must skip exactly 1 - n/bucket of the
    # chained grid's steps (the no-op guard executes nothing for dead
    # M-blocks — the serving win raggedness buys the chained launch)
    rcfg = get_reduced("googlenet")
    rplan, _ = CNN.plan_cnn(rcfg, batch=4, chain_modules=True)
    rparams = CNN.init_params(rcfg, jax.random.PRNGKey(0))
    rimgs = jax.random.normal(jax.random.PRNGKey(2), (4,) + rcfg.img)
    rdense = CNN.forward_plan(rparams, rcfg, rimgs, rplan)
    rragged = CNN.forward_plan(rparams, rcfg, rimgs, rplan,
                               valid_images=2)
    bench_json["serving"]["chained_masked_ok"] = bool(
        any(g.mode == "grouped_chained" for g in rplan.groups)
        and jnp.array_equal(rragged[:2], rdense[:2]))
    bench_json["serving"]["dead_block_skip"] = _dead_block_skip()

    # MoE expert-dispatch column (runs in smoke too — ci.sh gates it):
    # grouped ragged engine vs capacity-padded einsum on a
    # serving-representative layer; modeled times come back through the
    # CACHED lower_moe plan so cached_moe_plan is exercised end-to-end
    from benchmarks.moe_bench import moe_dispatch_bench
    moe_rows, moe_col = moe_dispatch_bench(reps=3 if smoke else 5)
    _emit([dict(r) for r in moe_rows])
    bench_json["moe"] = moe_col

    if not smoke:
        _emit(stacked_branch_gemm_bench())
        _emit(fused_complementary_bench())
        pm_rows = modeled_vs_executed_table()
        _emit([dict(r) for r in pm_rows])
        bench_json["plan_makespan"] = pm_rows

    out = os.path.join(REPO, "BENCH_plan.smoke.json" if smoke
                       else "BENCH_plan.json")
    with open(out, "w") as f:
        json.dump(bench_json, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {os.path.relpath(out, REPO)}", flush=True)

    # roofline summary (from results/roofline.json if the dry-run ran)
    rl = os.path.join(os.path.dirname(__file__), "..", "results",
                      "roofline.json")
    if os.path.exists(rl):
        rows = json.load(open(rl))
        for r in rows:
            t = r.get("roofline")
            if not t:
                continue
            print(f"roofline,,arch={r['arch']};shape={r['shape']};"
                  f"dominant={t['dominant']};compute_s={t['compute_s']:.4f};"
                  f"memory_s={t['memory_s']:.4f};"
                  f"coll_s={t['collective_s']:.4f};"
                  f"useful={t['usefulness']:.3f};"
                  f"roofline_frac={t['roofline_fraction']:.3f}", flush=True)


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv[1:])
