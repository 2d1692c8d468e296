"""Open-loop serving: requests arrive on a schedule drawn from the seed,
whether or not the server keeps up, and go through the program's own
serving path (split into chunks, admitted by ``launch.serve._admit``, one
cached executable per bucket).

Each request is timed from when it was due until its last chunk's logits
are ready, so a stall charges every request queued behind it.  The window
closes when every request due in it has been answered, or a minute after
its end; one never answered counts as failed, at that minute's latency.
"""
from __future__ import annotations

import gc
import json
import time

import numpy as np

from chipbench import device, program, traffic, work

DRAIN_S = 60.0


def request_images(seed: int, sizes, img) -> list[np.ndarray]:
    rng = np.random.default_rng(np.random.SeedSequence([seed, 4]))
    return [rng.standard_normal((int(n),) + tuple(img), dtype=np.float32)
            for n in sizes]


class Serving:
    """The program's server over its whole bucket ladder, warmed once per
    process; ``use_seed`` swaps in the weights of another seed."""

    def __init__(self, cell, seed: int, *, wrap=None, interpret=None):
        self.cell = cell
        tr = cell.traffic
        self.cfg = cell.ref.program_config(cell.sizes)
        self.server = program.Server(
            self.cfg, cell.ref.make_params(cell.sizes, seed),
            tr["max_images"], chain_modules=tr["chain_modules"], wrap=wrap,
            interpret=interpret)
        self.server.warm()

    def use_seed(self, seed: int) -> None:
        self.server.params = self.cell.ref.make_params(self.cell.sizes,
                                                       seed)

    def requests(self, tr: dict, seed: int, seconds: float):
        """The window's arrivals, sizes and images, made before it opens."""
        due, sizes = traffic.schedule(tr, seconds)
        return due, sizes, request_images(seed, sizes, self.cfg.img)

    def window(self, requests, seconds: float, tracer) -> dict:
        """Serve every request due in ``seconds``; returns what happened to
        each request and each dispatch."""
        server = self.server
        due, sizes, images = requests
        n_req = len(due)
        pending, i = [], 0
        done_at, chunks_left, placed = {}, {}, {}
        logits, walls, valid, buckets, admit_lag = [], [], [], [], []
        host, pauses, ends = [], [], []
        stats0 = server.cache_stats()
        gc_hook = _gc_timer(pauses)
        gc.callbacks.append(gc_hook)
        with tracer.window():
            t0 = time.perf_counter()
            while len(done_at) < n_req:
                now = time.perf_counter() - t0
                if now > seconds + DRAIN_S:
                    break
                i0 = i
                while i < n_req and due[i] <= now:
                    chunks = server.split(i, images[i], float(due[i]))
                    for j, c in enumerate(chunks):
                        c["off"] = j * server.max_images
                    chunks_left[i] = len(chunks)
                    pending.extend(chunks)
                    admit_lag.append(now - due[i])
                    i += 1
                if i > i0:
                    host.append(time.perf_counter() - t0 - now)
                if not pending:
                    with tracer.span("bench.wait_for_arrival"):
                        time.sleep(max(0.0, due[i] - (time.perf_counter()
                                                      - t0)))
                    continue
                t_iter = time.perf_counter() - t0
                with tracer.span("bench.admit"):
                    batch, _total = server.admit(pending)
                out, wall, bucket, n = server.dispatch(
                    [c["imgs"] for c in batch], tracer.span)
                t_end = time.perf_counter() - t0
                host.append(t_end - t_iter - wall)
                row = 0
                for c in batch:
                    placed[(c["rid"], c["off"])] = (len(logits), row)
                    row += c["imgs"].shape[0]
                    chunks_left[c["rid"]] -= 1
                    if chunks_left[c["rid"]] == 0:
                        done_at[c["rid"]] = t_end
                logits.append(out)
                ends.append(t_end)
                walls.append(wall)
                valid.append(n)
                buckets.append(bucket)
            t_last = time.perf_counter() - t0
        gc.callbacks.remove(gc_hook)
        stats1 = server.cache_stats()
        lat = np.asarray([done_at.get(r, seconds + DRAIN_S) - due[r]
                          for r in range(n_req)])
        served = sum(int(sizes[r]) for r in done_at)
        return {"due": due, "sizes": sizes, "images": images,
                "done_at": done_at, "placed": placed, "logits": logits,
                "latency_s": lat, "walls": walls, "valid": valid,
                "buckets": buckets, "admit_lag": np.asarray(admit_lag),
                "host_s": host, "gc_pauses": pauses, "ends": ends,
                "t_last": t_last, "served_images": served,
                "plan_cache_misses": stats1["misses"] - stats0["misses"]}

    def served_logits(self, w: dict, rids) -> np.ndarray:
        """The logits the window produced for the images of ``rids``, in
        request order."""
        mx = self.server.max_images
        rows = []
        for r in rids:
            n = int(w["sizes"][r])
            for off in range(0, n, mx):
                d, row = w["placed"][(r, off)]
                k = min(mx, n - off)
                rows.append(np.asarray(w["logits"][d][row:row + k]))
        return np.concatenate(rows)


def _gc_timer(pauses: list):
    """A ``gc.callbacks`` hook that appends (generation, seconds) of each
    collection to ``pauses``."""
    t = [0.0]

    def hook(phase, info):
        if phase == "start":
            t[0] = time.perf_counter()
        else:
            pauses.append((info["generation"], time.perf_counter() - t[0]))
    return hook


def _slowest(w: dict) -> dict | None:
    """Where in the window the longest dispatch came, and what it held."""
    if not w["walls"]:
        return None
    k = int(np.argmax(w["walls"]))
    return {"index": k, "of": len(w["walls"]),
            "ended_s": round(w["ends"][k], 3), "bucket": int(w["buckets"][k]),
            "images": int(w["valid"][k])}


def check_sample(tr: dict, w: dict, seed: int) -> list[int]:
    """The answered requests compared with the reference: a sample drawn
    from the seed, with a largest request among them."""
    answered = sorted(w["done_at"])
    if not answered:
        return []
    biggest = max(answered, key=lambda r: (w["sizes"][r], -r))
    pick = traffic.sample(len(answered), tr["check_requests"], seed,
                          must=[answered.index(biggest)])
    return [answered[k] for k in pick]


def logits_err(got: np.ndarray, want: np.ndarray) -> float:
    """The worst image's largest logit gap, against that image's largest
    reference logit."""
    gap = np.abs(got.astype(np.float64) - want).max(axis=1)
    return float((gap / np.abs(want).max(axis=1)).max())


def reference_logits(cell, seed: int, images: np.ndarray,
                     precision: str | None = None) -> np.ndarray:
    p = cell.ref.make_params(cell.sizes, seed)
    return cell.ref.logits(p, cell.sizes, images,
                           precision or cell.sizes["matmul_precision"])


def summary(w: dict, seconds: float) -> dict:
    lat_ms = w["latency_s"] * 1e3
    return {"requests": int(len(lat_ms)), "answered": len(w["done_at"]),
            "p50_ms": float(np.percentile(lat_ms, 50)),
            "p95_ms": float(np.percentile(lat_ms, 95)),
            "images_per_s": w["served_images"] / w["t_last"],
            "dispatches": len(w["walls"]),
            "dispatch_p50_ms": float(np.median(w["walls"]) * 1e3)
            if w["walls"] else None,
            "dispatch_max_ms": max(w["walls"], default=0.0) * 1e3,
            "slowest_dispatch": _slowest(w),
            "host_per_dispatch_max_ms": max(w["host_s"], default=0.0) * 1e3,
            "gc_collections": len(w["gc_pauses"]),
            "gc_max_ms": max((s for _g, s in w["gc_pauses"]),
                             default=0.0) * 1e3,
            "admit_lag_p50_ms": float(np.median(w["admit_lag"]) * 1e3),
            "admit_lag_max_ms": float(w["admit_lag"].max() * 1e3),
            "drain_s": w["t_last"] - seconds,
            "buckets": {int(b): int(np.sum(np.asarray(w["buckets"]) == b))
                        for b in sorted(set(w["buckets"]))}}


def run(cell, seed: int, seconds: float, tracer, counter, t_start: float,
        *, peaks: dict, wrap=None, interpret=None, log=print) -> dict:
    tr = cell.traffic
    serving = Serving(cell, seed, wrap=wrap, interpret=interpret)
    reqs = serving.requests(tr, seed, seconds)
    setup_s = time.perf_counter() - t_start
    before = counter.snapshot()
    w = serving.window(reqs, seconds, tracer)
    del reqs
    after = counter.snapshot()
    mem = device.peak_memory_bytes()
    s = summary(w, seconds)
    log(f"{s['requests']} requests due in {seconds} s, "
        f"{s['answered']} answered; latency samples {len(w['latency_s'])}"
        f" (p95 has {int(len(w['latency_s']) * 0.05)} beyond it); "
        f"{s['dispatches']} dispatches by bucket {s['buckets']}; "
        f"admission lag p50 {s['admit_lag_p50_ms']:.3f} ms, max "
        f"{s['admit_lag_max_ms']:.3f} ms; drained {s['drain_s']:.3f} s "
        f"past the window")

    t_ref = time.perf_counter()
    rids = check_sample(tr, w, seed)
    got = serving.served_logits(w, rids)
    imgs = np.concatenate([w["images"][r] for r in rids]) if rids \
        else np.zeros((0,) + tuple(serving.cfg.img), np.float32)
    ideal = sum(work.ideal_s(work.plan_ops(
        cell.ref.forward_ops(cell.sizes, int(n))), peaks)
        for n in w["valid"])
    walls, valid = list(w["walls"]), list(w["valid"])
    fwd_flops = work.forward_flops(cell.ref.forward_ops(cell.sizes, 1))
    failed = s["requests"] - s["answered"]
    misses = w["plan_cache_misses"]
    del w, serving
    want = reference_logits(cell, seed, imgs)
    err = logits_err(got, want) if len(got) else float("inf")
    return {
        "attempted": s["requests"], "failed": failed,
        "e2e": {"serve_p50_ms": s["p50_ms"], "serve_p95_ms": s["p95_ms"],
                "serve_images_per_s": s["images_per_s"],
                "setup_s": setup_s},
        "ctx": {"kind": "serve", "dispatch_walls_s": walls,
                "valid_images": valid, "units": len(walls),
                "model_flops": sum(valid) * fwd_flops,
                "plan_ideal_s": ideal},
        "numbers": {"logits_err": err, "_requests_compared": len(rids),
                    "_images_compared": int(len(got))},
        "memory_peak_bytes": mem,
        "info": {"compiles_in_setup": before,
                 "compiles_in_window": after["backend"] - before["backend"],
                 "plan_cache_misses_in_window": misses,
                 "reference_s": time.perf_counter() - t_ref, **s},
    }


def sweep(cell, seed: int, seconds: float, rates, *, log=print) -> list:
    """One set-up, then a window at each offered rate: where the server
    stops keeping up (the knee) shows as a queue that grows through the
    window, a drain past its end, and images/s below the offered."""
    from chipbench import trace as trace_lib
    serving = Serving(cell, seed)
    off = trace_lib.Tracer(False)
    rows = []
    for rate in rates:
        tr = {**cell.traffic, "rate_per_s": float(rate)}
        s = summary(serving.window(serving.requests(tr, seed, seconds),
                                   seconds, off), seconds)
        s.update({"rate_per_s": float(rate),
                  "offered_images_per_s": traffic.offered_images_per_s(tr)})
        log(json.dumps(s))
        rows.append(s)
    return rows
