"""Analytic TPU roofline cost model per (op, algorithm).

The paper profiles kernels with nvprof to get per-algorithm resource
profiles (Table 1) and workspace/time (Table 2).  This container has no TPU,
so the equivalent instrument is an analytic model over the target hardware
constants (TPU v5e-class, per assignment):

    peak bf16 FLOP/s : 197e12 per chip
    HBM bandwidth    : 819e9  B/s per chip
    ICI link bw      : 50e9   B/s per link
    VMEM             : 128 MiB per core (static-resource budget,
                       the SM register/smem analogue)
    SMEM             : 1 MiB per core (holds every launch's
                       scalar-prefetched offset tables)

Per algorithm we model: FLOPs, HBM traffic (algorithm-dependent — direct
conv re-reads the input per tap, im2col writes+reads the patch matrix,
materialized attention writes+reads the score matrix), HBM *workspace*
(Table-2 quantity), and VMEM claim (Table-1 static-resource quantity).
``op_time`` is the roofline max(compute, memory); ``co_execution_time``
models a fused/batched co-execution group where one op's DMA traffic
overlaps another's MXU work — the paper's complementarity argument.
"""
from __future__ import annotations

import dataclasses

from repro.core.graph import Op

DEVICE_KIND = "TPU v5 lite"  # jax's device_kind for the v5e these describe
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # B/s per chip
ICI_BW = 50e9                # B/s per link
VMEM_BYTES = 128 * 1024 * 1024
# scalar memory: the capacity the v5e compiler reports when a launch's
# scalar-prefetch operands (offset tables) overflow it
SMEM_BYTES = 1024 * 1024
# what one launch's prefetched operands may claim — the rest stays free
# for the scalars the compiler itself places in SMEM
SMEM_PREFETCH_BYTES = SMEM_BYTES - 64 * 1024
HBM_BYTES = 16 * 1024**3     # v5e-class per-chip HBM
# per-launch fixed cost (dispatch + SMEM table fill) charged to every extra
# launch an SMEM-chunked group makes — uncalibrated, like PIPELINE_LOSS
LAUNCH_OVERHEAD = 5e-6

# A single kernel cannot perfectly overlap its own DMA with its own MXU work:
# intra-op dependencies (next block's compute needs this block's data) leave
# pipeline bubbles — the TPU analogue of the paper's "memory stalls" column in
# Table 1.  We model a lone op as max(c, m) + LAMBDA * min(c, m); a
# co-execution group has independent work available to fill those bubbles, so
# the loss term amortizes by the group size (see co_execution_time).
PIPELINE_LOSS = 0.2


@dataclasses.dataclass(frozen=True)
class OpProfile:
    """The per-(op, algorithm) profile — Table-1/Table-2 analogue row."""
    op: str
    algorithm: str
    flops: float
    hbm_bytes: float          # total HBM traffic
    workspace_bytes: float    # HBM workspace (Table 2)
    vmem_bytes: float         # static VMEM claim (Table 1)

    @property
    def compute_time(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def memory_time(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def time(self) -> float:
        c, m = self.compute_time, self.memory_time
        return max(c, m) + PIPELINE_LOSS * min(c, m)

    @property
    def intensity(self) -> float:
        return self.flops / max(self.hbm_bytes, 1.0)

    @property
    def bound(self) -> str:
        return "compute" if self.compute_time >= self.memory_time else "memory"


def _mxu_efficiency(*dims: int) -> float:
    """Alignment-derate: each matmul dim not a multiple of 128 wastes the
    padded fraction of the systolic array."""
    eff = 1.0
    for d in dims:
        pad = -(-d // 128) * 128
        eff *= d / pad
    return max(eff, 0.05)


ALGORITHMS_BY_KIND = {
    "matmul": ("mxu128", "large_tile", "ksplit"),
    "conv2d": ("im2col_gemm", "direct", "winograd3x3"),
    "attention": ("flash", "materialized"),
    "ssd": ("chunked", "quadratic"),
    "pointwise": ("vpu",),
    "maxpool": ("reduce_window",),
}


def profile(op: Op, algorithm: str) -> OpProfile:
    p, eb = op.p, op.dtype_bytes
    if op.kind == "matmul":
        m, k, n = p["m"], p["k"], p["n"]
        flops = 2.0 * m * k * n / _mxu_efficiency(m, k, n)
        io = (m * k + k * n + m * n) * eb
        ws = 0.0
        vmem = 0.0
        if algorithm == "mxu128":
            vmem = (128 * 128 * 2) * eb + 128 * 128 * 4
        elif algorithm == "large_tile":
            flops = 2.0 * m * k * n / _mxu_efficiency(m, n)  # K always aligned
            vmem = (256 * 128 + 128 * 256) * eb + 256 * 256 * 4
            # 256-tiles halve the number of lhs/rhs reloads across the grid:
            io = (m * k + k * n) * eb * 0.75 + m * n * eb
        elif algorithm == "ksplit":
            splits = 4
            ws = splits * m * n * 4
            io = (m * k + k * n + m * n) * eb + 2 * ws  # write + reduce read
            vmem = (128 * 128 * 2) * eb + 128 * 128 * 4
        return OpProfile(op.name, algorithm, flops, io, ws, vmem)

    if op.kind == "conv2d":
        n_, h, w, c = p["n"], p["h"], p["w"], p["c"]
        kh, kw, k, s = p["kh"], p["kw"], p["k"], p.get("stride", 1)
        oh, ow = -(-h // s), -(-w // s)
        mac = n_ * oh * ow * kh * kw * c * k
        xin = n_ * h * w * c * eb
        xout = n_ * oh * ow * k * eb
        wts = kh * kw * c * k * eb
        if algorithm == "im2col_gemm":
            ws = n_ * oh * ow * kh * kw * c * eb
            flops = 2.0 * mac / _mxu_efficiency(n_ * oh * ow, kh * kw * c, k)
            io = xin + xout + wts + 2 * ws
            vmem = (128 * 128 * 2) * eb + 128 * 128 * 4
        elif algorithm == "direct":
            ws = 0.0
            flops = 2.0 * mac / _mxu_efficiency(c, k)
            # overlapping window re-reads; a 1x1 tap still reads X once in
            # full (the kh*kw*0.5 re-read factor bottoms out at 1)
            io = xin * max(kh * kw * 0.5, 1.0) + xout + wts
            vmem = (h + kh) * (w + kw) * c * eb  # whole row-window resident
        elif algorithm == "winograd3x3":
            t = n_ * -(-oh // 2) * -(-ow // 2)
            flops = 2.0 * 16 * t * c * k / _mxu_efficiency(t, c, k) \
                + 2.0 * (16 + 16) * 4 * t * c  # transforms (VPU)
            ws = 16 * (t * c + c * k + t * k) * eb
            io = xin + xout + wts + 2 * ws
            vmem = (128 * 128 * 2) * eb + 128 * 128 * 4
        else:
            raise ValueError(algorithm)
        return OpProfile(op.name, algorithm, flops, io, ws, vmem)

    if op.kind == "attention":
        b, sq, skv = p["b"], p["sq"], p["skv"]
        hq, hkv, d = p["hq"], p["hkv"], p["d"]
        flops = 2.0 * b * hq * sq * skv * d * 2  # qk + pv
        qio = b * sq * hq * d * eb
        kvio = 2 * b * skv * hkv * d * eb
        oio = b * sq * hq * d * eb
        if algorithm == "flash":
            ws = 0.0
            io = qio + kvio + oio
            vmem = (128 * d * 3) * eb + 128 * 128 * 4 + 128 * d * 4
        elif algorithm == "materialized":
            ws = b * hq * sq * skv * 4.0
            io = qio + kvio + oio + 3 * ws     # write scores, read, write probs
            vmem = (128 * 128 * 2) * eb + 128 * 128 * 4
        else:
            raise ValueError(algorithm)
        return OpProfile(op.name, algorithm, flops, io, ws, vmem)

    if op.kind == "ssd":
        b, s, h = p["b"], p["s"], p["h"]
        pp, g, n = p["p"], p["g"], p["n"]
        l = p.get("chunk", 128)
        nc = -(-s // l)
        xio = b * s * h * pp * eb
        bcio = 2 * b * s * g * n * eb
        if algorithm == "chunked":
            # intra-chunk quadratic + state build + off-diagonal apply
            flops = 2.0 * b * nc * (l * l * g * n + l * l * h * pp
                                    + 2 * l * h * n * pp)
            ws = b * nc * h * n * pp * 4.0
            io = 2 * xio + bcio + 2 * ws
            vmem = (l * l * h + l * h * pp + h * n * pp) * 4
        elif algorithm == "quadratic":
            flops = 2.0 * b * (s * s * g * n + s * s * h * pp)
            ws = b * s * s * h * 4.0
            io = xio * 2 + bcio + 3 * ws
            vmem = (128 * 128 * 2) * eb + 128 * 128 * 4
        else:
            raise ValueError(algorithm)
        return OpProfile(op.name, algorithm, flops, io, ws, vmem)

    if op.kind == "pointwise":
        e = p["elements"]
        return OpProfile(op.name, "vpu", 1.0 * e, 2.0 * e * eb, 0.0,
                         128 * 1024)

    if op.kind == "maxpool":
        # the standalone pooling primitive (cuDNN pooling / XLA
        # reduce_window): each chain stage reads its input and writes the
        # pooled output — pure VPU compares, pure HBM traffic.  A chained
        # pool (pool-proj of a pooled inception module) materializes the
        # intermediate stages as workspace.  This is the launch (and the
        # pre-GEMM round-trip) the pooled grouped kernel absorbs; see
        # ``pool_profile``.
        n_, h, w, c = p["n"], p["h"], p["w"], p["c"]
        flops = io = ws = 0.0
        e_in = n_ * h * w * c
        for i, (window, stride) in enumerate(p["chain"]):
            h, w = -(-h // stride), -(-w // stride)
            e_out = n_ * h * w * c
            flops += float(window * window) * e_out
            io += (e_in + e_out) * eb
            if i < len(p["chain"]) - 1:
                ws += e_out * eb
            e_in = e_out
        return OpProfile(op.name, "reduce_window", flops, io, ws, 128 * 1024)

    raise ValueError(f"unknown op kind {op.kind}")


def op_time(op: Op, algorithm: str) -> float:
    return profile(op, algorithm).time


def best_algorithm(op: Op) -> tuple[str, float]:
    """Per-op fastest (the TF-r1.10 policy the paper critiques)."""
    algs = ALGORITHMS_BY_KIND[op.kind]
    times = {a: op_time(op, a) for a in algs if _supported(op, a)}
    a = min(times, key=times.get)
    return a, times[a]


def _supported(op: Op, algorithm: str) -> bool:
    if op.kind == "conv2d" and algorithm == "winograd3x3":
        p = op.p
        return (p["kh"], p["kw"]) == (3, 3) and p.get("stride", 1) == 1
    return True


def supported_algorithms(op: Op) -> tuple[str, ...]:
    return tuple(a for a in ALGORITHMS_BY_KIND[op.kind] if _supported(op, a))


def gemm_shape(op: Op) -> tuple[int, int, int] | None:
    """(M, K, N) if the op is expressible as ONE GEMM, else None.

    matmul ops are themselves; a conv2d is its im2col view
    (M = N*OH*OW, K = C*KH*KW, N = K_out) — the cuDNN GEMM lowering the
    paper profiles, which is what lets K×K branches join a grouped
    branch-GEMM co-execution group instead of falling back to XLA.
    """
    p = op.p
    if op.kind == "matmul":
        return p["m"], p["k"], p["n"]
    if op.kind == "conv2d":
        s = p.get("stride", 1)
        oh, ow = -(-p["h"] // s), -(-p["w"] // s)
        return p["n"] * oh * ow, p["c"] * p["kh"] * p["kw"], p["k"]
    return None


def gemm_shape_bwd(op: Op) -> tuple[tuple[int, int, int],
                                    tuple[int, int, int]] | None:
    """The op's two backward GEMMs as (M, K, N) shapes, or None.

    For a forward GEMM view (M, K, N) — convs via im2col like
    ``gemm_shape`` — the VJP computes

        dx = dY (M, N) @ W^T (N, K)      ->  (M, N, K)   shared-M ragged
        dw = X^T (K, M) @ dY (M, N)      ->  (K, M, N)   shared-M contraction

    which is why a forward co-execution group mirrors into a backward
    one: the dx GEMMs of G branches again share M, and the dw GEMMs
    share the M *contraction* with ragged (K_g, N_g) outputs — the two
    phases of the combined backward kernel (``grouped_matmul_bwd``,
    ReLU cotangent mask folded into the dY packing, db reduced on the
    first k-row).
    """
    s = gemm_shape(op)
    if s is None:
        return None
    m, k, n = s
    return (m, n, k), (k, m, n)


def backward_profiles(op: Op, algorithm: str) -> list[OpProfile]:
    """Profiles of the op's VJP computation (the Table-1 rows of the
    backward pass).

    GEMM-view ops price as their two backward GEMMs (``gemm_shape_bwd``),
    each an aligned MXU matmul — the lowering the combined backward
    kernel's two phases execute.  pointwise grads are the same traffic shape (a concat
    backward is a split), so the forward profile stands; a maxpool
    backward is likewise ONE scatter pass of forward-equal traffic (dy
    read, dx written through the argmax mask), not the doubled fallback.
    Remaining kinds (attention/ssd) use the forward profile doubled —
    their backward does roughly twice the forward work.
    """
    sb = gemm_shape_bwd(op)
    if sb is None:
        p = profile(op, algorithm)
        return [p] if op.kind in ("pointwise", "maxpool") else [p, p]
    profs = [profile(Op.make(f"{op.name}:{tag}", "matmul",
                             dtype_bytes=op.dtype_bytes, m=m, k=k, n=n),
                     "mxu128")
             for tag, (m, k, n) in zip(("dx", "dw"), sb)]
    kh, kw = op.p.get("kh", 1), op.p.get("kw", 1)
    stride = op.p.get("stride", 1)
    if op.kind == "conv2d" and ((kh, kw) != (1, 1) or stride != 1):
        # the GEMM view of a KxK / strided conv backward materializes the
        # im2col patch buffer both ways (dw reads the patches, dx scatters
        # the patch cotangent) — the same M*(C*KH*KW) workspace the
        # forward im2col_gemm profile charges.  A 1x1 stride-1 conv's
        # backward is pure reshapes (no patch buffer, see _conv_gemm_bwd's
        # fast path) and charges nothing.  The aligned-matmul time proxy
        # stands (ROADMAP calibration caveat), but the C2 budget checks
        # must see the real HBM footprint or they are vacuous for convs.
        m, k, _ = gemm_shape(op)
        ws = m * k * op.dtype_bytes
        profs = [dataclasses.replace(p, workspace_bytes=p.workspace_bytes + ws)
                 for p in profs]
    return profs


def concat_profile(join_op: Op, elements: float | None = None) -> OpProfile:
    """The fork/join concat as an explicit profile row: reading the branch
    outputs back and writing the joint buffer — 2 * elements * eb bytes of
    pure HBM traffic, zero MXU work.  ``elements`` defaults to the join
    op's full element count (the standalone-concat cost every unfused mode
    pays); the fused epilogue-concat passes only the passthrough columns
    (branch slices produced by an earlier launch), because its in-launch
    branches leave the kernel already inside the join buffer."""
    e = join_op.p["elements"] if elements is None else elements
    return OpProfile(f"{join_op.name}:concat", "concat", 0.0,
                     2.0 * e * join_op.dtype_bytes, 0.0, 0.0)


def pool_profile(op: Op) -> OpProfile:
    """The branch maxpool as an explicit profile row — the term the cost
    model used to leave invisible (the pre-GEMM ``reduce_window`` launch
    ran outside every priced group).  Standalone (unfused) plans pay this
    row as the pool op's own singleton group; when the pool is ABSORBED
    into a pooled grouped launch the rider is ZERO — the tap reads stream
    through the launch's existing lhs DMA and the pooled activation never
    touches HBM, so the whole row disappears with the launch (same shape
    as ``concat_profile``, whose fused rider keeps only the passthrough
    columns).  Calibrating the zero-rider claim on real hardware rides
    the ROADMAP's cost-model validation item."""
    assert op.kind == "maxpool", op
    return profile(op, "reduce_window")


def gemm_profiles(ops: list[Op]) -> list[OpProfile]:
    """Per-branch profiles of the GEMM lowering the grouped/stacked
    kernels actually execute: each op priced as its aligned
    ``gemm_shape`` matmul, with a K×K/strided conv additionally charged
    the im2col patch workspace its view materializes (write + read) —
    mirroring ``backward_profiles``'s treatment of the same lowering.

    This replaces the old proxy (the scheduler-chosen per-op algorithm
    profiles), which priced grouped groups at whatever algorithm the
    SERIAL path would have picked — a direct-conv or winograd profile for
    a kernel that always executes the GEMM lowering (the docstring-
    acknowledged drift).  The patch buffer charges the C2 *budget* only,
    not the time: packing/unpacking layout passes around the kernel are
    fused by XLA and modeled as riding the launch's DMA throughout this
    file — exactly how ``backward_profiles`` prices the same lowering."""
    profs = []
    for op in ops:
        s = gemm_shape(op)
        assert s is not None, op
        m, k, n = s
        pr = profile(Op.make(f"{op.name}:gemm", "matmul",
                             dtype_bytes=op.dtype_bytes, m=m, k=k, n=n),
                     "mxu128")
        kh, kw = op.p.get("kh", 1), op.p.get("kw", 1)
        stride = op.p.get("stride", 1)
        if op.kind == "conv2d" and ((kh, kw) != (1, 1) or stride != 1):
            ws = m * k * op.dtype_bytes
            pr = dataclasses.replace(pr, workspace_bytes=pr.workspace_bytes + ws)
        profs.append(pr)
    return profs


def _passthrough_elements(shapes, join_op: Op) -> float:
    """Join elements NOT produced by the group's own branch GEMMs — the
    columns a fused epilogue-concat still has to copy in."""
    own = sum(m * n for m, _, n in shapes)
    return max(join_op.p["elements"] - own, 0.0)


def group_execution_time_bwd(ops: list[Op], algorithms: dict | None = None,
                             mode: str | None = None,
                             join: Op | None = None) -> tuple[str, float]:
    """(realizable mode, modeled makespan) for the GRAD group mirroring a
    forward co-execution group — the backward analogue of
    ``group_execution_time``, and what the custom VJPs actually launch.

    Branches with shared-M GEMM views backward-co-execute in ONE combined
    grouped launch (masked dx + dw/db over a concatenated offset table —
    the single kernel ``kernels.ops``' VJPs emit) or, for uniform shapes,
    two stacked ones (``branch_matmul``'s VJP).  Anything else only has
    the per-op XLA pullback, priced with the interleave loss.  ``mode``
    forces the pricing to a known forward mode (``plan.backward_plan``
    passes the lowered mode; the scheduler omits it to judge candidates).
    ``join`` + mode="grouped_concat" prices the grad of a fused
    epilogue-concat group: the joint cotangent is sliced straight into
    the combined launch's packing, so only the passthrough columns pay
    the split's read+write (the standalone join backward disappears).
    """
    algs = algorithms or {}

    def bprofs(op):
        return backward_profiles(
            op, algs.get(op.name) or best_algorithm(op)[0])

    if len(ops) == 1:
        return "serial", sum(p.time for p in bprofs(ops[0]))
    shapes = [gemm_shape(op) for op in ops]
    grouped_ok = (all(s is not None for s in shapes)
                  and len({s[0] for s in shapes}) == 1)
    if grouped_ok and mode in ("grouped", "grouped_pooled",
                               "grouped_concat", "stacked", None):
        per_op = [bprofs(op) for op in ops]
        dxp = [p[0] for p in per_op]
        dwp = [p[1] for p in per_op]
        if mode == "grouped_concat":
            assert join is not None, "grouped_concat backward needs the join"
            rider = concat_profile(join, _passthrough_elements(shapes, join))
            return "grouped_concat", co_execution_time(dxp + dwp + [rider])
        # ONE combined launch: dx and dw/db share the grid, so compute of
        # one phase overlaps memory of the other across the whole union
        t_grouped = co_execution_time(dxp + dwp)
        uniform = len({s[:2] for s in shapes}) == 1
        # a FORCED stacked mode prices pad-to-max even on ragged branches
        # (the stacked kernel pads K and N to the widest, so it executes
        # — and pays — exactly that); the auto choice (mode=None) only
        # prefers stacked on uniform shapes, like the forward judgement
        if mode == "stacked" or (uniform and mode is None):
            dx_shapes = [(m, n, k) for m, k, n in shapes]
            dw_shapes = [(k, m, n) for m, k, n in shapes]
            t_stacked = (stacked_time(dxp, dx_shapes)
                         + stacked_time(dwp, dw_shapes))
            if mode == "stacked" or t_stacked <= t_grouped:
                return "stacked", t_stacked
        # a pooled forward mirrors to the SAME combined launch (the
        # pooling cotangent mask rides its unpacking — zero rider, like
        # the forward's pool_profile when fused)
        return ("grouped_pooled" if mode == "grouped_pooled"
                else "grouped"), t_grouped
    flat = [p for op in ops for p in bprofs(op)]
    return "xla", xla_interleave_time(flat)


def co_execution_time(profiles: list[OpProfile]) -> float:
    """Modeled makespan of a co-execution group on ONE chip.

    Fused/batched ops share the chip: MXU work serializes across the group,
    HBM traffic serializes across the group, but compute of one op overlaps
    memory traffic of another (DMA/MXU pipelining) — so the group finishes at
    max(sum_compute, sum_memory) instead of sum(max(c_i, m_i)).
    Complementary groups (compute-bound + memory-bound) win; same-bound
    groups don't — exactly the paper's Table-1 observation.  The lone-kernel
    pipeline-loss term amortizes by the group size: other branches' blocks
    fill the bubbles one op's intra-dependencies leave.
    """
    c = sum(pr.compute_time for pr in profiles)
    m = sum(pr.memory_time for pr in profiles)
    return max(c, m) + PIPELINE_LOSS * min(c, m) / len(profiles)


def serial_time(profiles: list[OpProfile]) -> float:
    return sum(pr.time for pr in profiles)


def grouped_time(ops: list[Op]) -> float:
    """Makespan of a grouped ragged branch GEMM (kernels/grouped_matmul):
    every branch runs only its own alignment-padded tiles, so there is no
    padding-waste term — the group is pure co-execution, priced directly
    off the ``gemm_shape`` lowering the kernel executes
    (``gemm_profiles``; was the scheduler-chosen per-op algorithm
    profiles — a proxy whose drift the docstring used to acknowledge).
    Calibrating against hardware stays a ROADMAP open item."""
    return co_execution_time(gemm_profiles(ops))


def stacked_time(profiles: list[OpProfile],
                 shapes: list[tuple[int, int, int]]) -> float:
    """Makespan of the pad-to-max stacked kernel (kernels/branch_matmul):
    every branch's MXU grid is inflated to the widest branch's aligned
    (K, N), so branch g pays round128(Kmax)*round128(Nmax) /
    (round128(K_g)*round128(N_g)) of its own compute.  (Memory traffic is
    dominated by the shared-M inputs; padded tiles are modeled as noise.)
    ``profiles`` should be the ``gemm_profiles`` of the branches — the
    stacked kernel executes the same GEMM lowering the grouped one does,
    just padded (``group_execution_time`` prices both arms off it)."""
    def al(d):
        return -(-d // 128) * 128
    kmax = max(al(k) for _, k, _ in shapes)
    nmax = max(al(n) for _, _, n in shapes)
    c = sum(pr.compute_time * (kmax * nmax) / (al(k) * al(n))
            for pr, (_, k, n) in zip(profiles, shapes))
    m = sum(pr.memory_time for pr in profiles)
    return max(c, m) + PIPELINE_LOSS * min(c, m) / len(profiles)


def padded_m_factor(m_true: int, m_bucket: int, *, bm: int = 128) -> float:
    """Padded-M waste of serving a ragged request mix through an M-bucket:
    the grouped grid runs ``ceil(M_bucket/bm)`` row-blocks regardless of
    how many rows are real, so a mix with ``m_true`` true rows pays
    ``al(M_bucket)/al(m_true)`` of its useful compute (the same
    aligned-tile inflation idiom ``stacked_time`` prices pad-to-max
    branches with — M is just the dimension being padded here).  1.0 means
    the bucket is free for this mix."""
    def al(d):
        return max(-(-d // bm) * bm, bm)
    return al(m_bucket) / al(m_true)


def serve_buckets(max_images: int, rows_per_image: int, *,
                  bm: int = 128) -> list[int]:
    """The serving driver's M-bucket ladder, a MODELED decision: start
    from powers-of-two image counts up to ``max_images`` and merge any
    bucket whose worst-case padded-M factor over the next bucket is 1.0 —
    when ``rows_per_image`` image-rows already tile the bm-aligned grid
    identically for both bucket sizes (every googlenet group has
    rows_per_image a multiple of bm once H*W*B aligns), the smaller bucket
    buys no fewer row-blocks and only fragments the plan/executable cache.
    The surviving ladder is exactly the set of bucket sizes whose grids
    actually differ."""
    assert max_images >= 1 and rows_per_image >= 1
    ladder = []
    b = 1
    while b < max_images:
        ladder.append(b)
        b *= 2
    ladder.append(max_images)
    kept = []
    for lo, hi in zip(ladder, ladder[1:]):
        # worst case inside bucket `hi` but servable by `lo`: m_true =
        # lo * rows_per_image.  If hi's grid is no bigger, lo is redundant.
        if padded_m_factor(lo * rows_per_image, hi * rows_per_image,
                           bm=bm) > 1.0:
            kept.append(lo)
    kept.append(ladder[-1])
    return kept


# XLA interleaving recovers only part of the co-execution overlap: the
# framework baseline the paper critiques emits ops together and hopes, so we
# model it halfway between perfect overlap and serial launch.  Giving the
# scheduler this (worse) number for groups no kernel can realize stops it
# over-grouping heterogeneous ops whose only execution path is XLA.
XLA_INTERLEAVE_LOSS = 0.5


def xla_interleave_time(profiles: list[OpProfile]) -> float:
    co = co_execution_time(profiles)
    return co + XLA_INTERLEAVE_LOSS * (serial_time(profiles) - co)


def group_execution_time(ops: list[Op], profiles: list[OpProfile],
                         join: Op | None = None) -> tuple[str, float]:
    """(realizable single-chip mode, modeled makespan) for a co-execution
    group — the shared judgement ``scheduler`` packs with and
    ``plan.lower`` turns into an ExecGroup.

    Branches expressible as shared-M GEMMs co-execute as one grouped
    (ragged) or stacked (uniform-shape) kernel; a compute+memory
    complementary (GEMM, pointwise) pair fuses; anything else only has the
    XLA-interleave path, modeled with its overlap loss.  ``spatial`` needs
    a mesh and is decided by ``plan.lower`` on top of this.

    ``join``: the fork/join concat this group's outputs feed, when the
    caller wants the concat traffic priced WITH the group (the absorption
    judgement in ``plan.lower``).  A grouped group then becomes
    ``grouped_concat`` — the fused epilogue-concat writes branch slices
    in place, so only the passthrough columns keep their copy cost
    (``concat_profile``) — while any other mode pays the standalone
    concat's full read+write on top (the term the join's own singleton
    group prices when it is NOT absorbed; never count both).
    """
    if len(ops) == 1:
        return "serial", profiles[0].time
    shapes = [gemm_shape(op) for op in ops]
    if all(s is not None for s in shapes) \
            and len({s[0] for s in shapes}) == 1:
        # grouped/stacked price off the GEMM lowering the kernels execute
        # (gemm_profiles), not the serial path's chosen algorithms
        gprofs = gemm_profiles(ops)
        if join is not None:
            rider = concat_profile(join, _passthrough_elements(shapes, join))
            return "grouped_concat", co_execution_time(gprofs + [rider])
        t_grouped = co_execution_time(gprofs)
        if len({s[:2] for s in shapes}) == 1:   # uniform (M, K): stackable
            t_stacked = stacked_time(gprofs, shapes)
            if t_stacked <= t_grouped:
                return "stacked", t_stacked
        return "grouped", t_grouped
    if join is not None:
        mode, t = group_execution_time(ops, profiles)
        return mode, t + concat_profile(join).time
    gemm = [i for i, s in enumerate(shapes) if s is not None]
    stream = [i for i, op in enumerate(ops) if op.kind == "pointwise"]
    if (len(ops) == 2 and len(gemm) == 1 and len(stream) == 1
            and gemm[0] != stream[0]
            and profiles[gemm[0]].bound == "compute"
            and profiles[stream[0]].bound == "memory"):
        return "fused", co_execution_time(profiles)
    return "xla", xla_interleave_time(profiles)


def spatial_time(profiles: list[OpProfile], chips: int,
                 split: list[int] | None = None) -> float:
    """Makespan when branches run on disjoint chip groups (inter-chip
    spatial partitioning).  ``split`` = chips per branch; defaults to equal.
    Assumes per-branch work is chip-divisible (true for our batched GEMMs)."""
    k = len(profiles)
    split = split or [max(chips // k, 1)] * k
    return max(
        max(pr.compute_time / c, pr.memory_time / c)
        for pr, c in zip(profiles, split)
    )


# ---------------------------------------------------------------------------
# chained launches (cross-module streaming)
# ---------------------------------------------------------------------------

def chained_profiles(ops: list[Op], ring=frozenset()) -> list[OpProfile]:
    """``gemm_profiles`` with ring-consumer branches repriced for the
    chained launch: a branch whose lhs streams from the in-kernel VMEM
    ring (its producer runs one wave ahead in the SAME launch) never
    reads its input activation from HBM and never materializes an im2col
    patch buffer — drop the M*K lhs read from traffic and the patch
    workspace from the C2 budget.  Every other term (weights, bias,
    output write) stands: chained outputs still land in HBM as the next
    launch's panel operands."""
    ring = frozenset(ring)
    profs = []
    for op, pr in zip(ops, gemm_profiles(ops)):
        if op.name in ring:
            s = gemm_shape(op)
            assert s is not None, op
            m, k, _ = s
            lhs = m * k * op.dtype_bytes
            pr = dataclasses.replace(
                pr,
                hbm_bytes=max(pr.hbm_bytes - lhs, 0.0),
                workspace_bytes=max(pr.workspace_bytes - lhs, 0.0))
        profs.append(pr)
    return profs


def chained_time(phase_ops: list[list[Op]], ring=frozenset(),
                 m_valid: int | None = None) -> float:
    """Modeled makespan of ONE chained launch over ``phase_ops`` (one op
    list per phase, Shi-et-al.-style honest pricing rather than
    assertion): the union co-executes like one big grouped launch —
    MXU work and HBM traffic serialize across ALL branches of ALL
    phases, compute overlapping memory — with ring consumers' lhs
    traffic dropped (``chained_profiles``) and NO concat rider (the next
    launch consumes the padded panels in place via its lhs-source
    descriptors).  On top rides the pipeline-FILL term the wave schedule
    costs: a P-phase chain runs mb + P - 1 waves for mb row blocks, so
    the steady-state makespan stretches by (P-1)/(mb+P-1).

    ``m_valid`` prices the ragged serving launch: dead M-blocks past the
    cutoff are skipped as no-op waves, so the steady-state work scales
    by the live-block fraction and the fill term runs over live blocks
    only (the no-op waves cost grid steps, not GEMMs — negligible next
    to a block's tap-GEMM ladder, so the model drops them)."""
    ops = [op for ph in phase_ops for op in ph]
    t = co_execution_time(chained_profiles(ops, ring))
    m = max(gemm_shape(op)[0] for op in ops)
    mb = max(-(-m // 128), 1)
    if m_valid is not None:
        mbl = min(max(-(-m_valid // 128), 1), mb)
        t *= mbl / mb
        mb = mbl
    nph = len(phase_ops)
    return t * (1.0 + (nph - 1) / (mb + nph - 1))


def chunked_time(t: float, chunks: int) -> float:
    """Makespan of a group whose launch SMEM splits into ``chunks``
    image-aligned M-chunks: the same work plus one ``LAUNCH_OVERHEAD``
    per extra launch."""
    return t + (chunks - 1) * LAUNCH_OVERHEAD


def chained_time_bwd(phase_ops: list[list[Op]],
                     algorithms: dict | None = None) -> float:
    """Backward makespan of a chained launch: the VJP mirrors the chain
    in REVERSE phase order with one combined grouped launch (masked dx +
    dw/db) per phase — phases cannot backward-co-execute with each other
    because a ring consumer's lhs cotangent feeds the producer phase's
    dy.  Ring consumers' lhs is recomputed from the residual panels
    (HBM reads the forward skipped), so no traffic is dropped here —
    the backward win is launch count and the vanished join split, not
    bytes."""
    algs = algorithms or {}
    total = 0.0
    for ops in phase_ops:
        per = [backward_profiles(op, algs.get(op.name)
                                 or best_algorithm(op)[0])
               for op in ops]
        total += co_execution_time([p[0] for p in per]
                                   + [p[1] for p in per])
    return total


# ---------------------------------------------------------------------------
# MoE expert dispatch: ragged-per-expert grouped vs capacity-padded einsum
# ---------------------------------------------------------------------------

def _al128(d: int) -> int:
    return -(-d // 128) * 128


def moe_grouped_profile(n_slots: int, e: int, d: int, f: int, *,
                        gated: bool, bm: int, dtype_bytes: int = 4,
                        train: bool = False) -> OpProfile:
    """Forward profile of ``grouped_matmul_experts``: the static grid is
    ``n_slots // bm + e`` M-blocks (every routed token once, plus at most
    one partial block per expert), each running (1+gated) in-GEMMs and
    one out-GEMM on 128-aligned tiles — FLOPs scale with routed tokens,
    never with E*capacity.  ``bm`` is a parameter so this module stays
    free of the kernels dependency (plan passes ``kernels.moe_block_m``).

    Traffic by index-change counting on the offset table: with one
    k-block the X tile is fetched once per M-block (held through every
    H and Y step); expert weights are fetched per block they serve."""
    mbs = n_slots // bm + e
    dp, fp = _al128(d), _al128(f)
    db, fb = dp // 128, fp // 128
    nw = 1 + int(gated)
    ngemm = nw + 1
    flops = 2.0 * mbs * bm * dp * fp * ngemm
    x_fetch = 1 if db == 1 else db * nw * fb
    bytes_ = (mbs * bm * dp * dtype_bytes * x_fetch          # X
              + mbs * nw * db * fb * 128 * 128 * dtype_bytes  # W_in/W_gate
              + mbs * fb * db * 128 * 128 * dtype_bytes       # W_out
              + mbs * bm * 4                                  # sw
              + mbs * bm * dp * dtype_bytes)                  # Y
    if train:
        bytes_ += mbs * bm * fp * dtype_bytes * nw            # preacts
    vmem = (bm * 128 + 2 * fb * bm * 128) * 4
    return OpProfile("moe_experts", "grouped_ragged", flops, bytes_,
                     0.0, vmem)


def moe_einsum_profile(b: int, cap: int, e: int, d: int, f: int, *,
                       gated: bool, dtype_bytes: int = 4) -> OpProfile:
    """The capacity-padded E-leading stacked einsum (``_moe_apply_core``):
    every one of the B*E*cap capacity slots pays the full expert chain
    whether a token was routed to it or not, and the per-expert M is
    ``cap`` — both the padding waste and the alignment derate are priced.
    Dispatch gather/scatter traffic is skipped on BOTH engines (identical
    routing work), so the comparison isolates the expert compute."""
    rows = b * e * cap
    nw = 1 + int(gated)
    eff = _mxu_efficiency(cap, d, f)
    flops = 2.0 * rows * d * f * (nw + 1) / eff
    bytes_ = (rows * d * dtype_bytes * nw                     # xe reads
              + 2 * rows * f * dtype_bytes                    # h write+read
              + rows * d * dtype_bytes                        # ye
              + e * (nw * d * f + f * d) * dtype_bytes)       # weights
    return OpProfile("moe_experts", "einsum_padded", flops, bytes_,
                     rows * f * dtype_bytes, 0.0)


def moe_stacked_profile(b: int, cap: int, e: int, d: int, f: int, *,
                        gated: bool, bm: int,
                        dtype_bytes: int = 4) -> OpProfile:
    """Pad-to-max stacked branch kernel baseline (``branch_matmul``
    generalized to the expert chain): E branches each inflated to the
    shared capacity M = B*cap, tiles 128-aligned — what PR 2's stacked
    mode would charge if pointed at the expert fork."""
    mbs = e * (-(-(b * cap) // bm))
    dp, fp = _al128(d), _al128(f)
    nw = 1 + int(gated)
    flops = 2.0 * mbs * bm * dp * fp * (nw + 1)
    bytes_ = (mbs * bm * dp * dtype_bytes
              + e * (nw * dp * fp + fp * dp) * dtype_bytes
              + mbs * bm * dp * dtype_bytes)
    return OpProfile("moe_experts", "stacked_padded", flops, bytes_,
                     0.0, 0.0)


def moe_dispatch_times(n_slots: int, b: int, cap: int, e: int, d: int,
                       f: int, *, gated: bool, bm: int,
                       dtype_bytes: int = 4) -> dict:
    """Modeled forward wall per expert engine — the pricing ``lower_moe``
    picks from and the bench/CI gate compares."""
    return {
        "grouped": moe_grouped_profile(n_slots, e, d, f, gated=gated,
                                       bm=bm, dtype_bytes=dtype_bytes).time,
        "einsum": moe_einsum_profile(b, cap, e, d, f, gated=gated,
                                     dtype_bytes=dtype_bytes).time,
        "stacked": moe_stacked_profile(b, cap, e, d, f, gated=gated,
                                       bm=bm, dtype_bytes=dtype_bytes).time,
    }
