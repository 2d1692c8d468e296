"""Inception-style CNN — the paper's native subject (GoogleNet, Fig. 1).

Every conv routes through the kernel algorithm zoo (``kernels.conv2d``),
with per-op algorithms chosen by the core scheduler/selector; Inception
modules are ``core.Branches`` fork/joins, executable in any branch-parallel
mode (xla / spatial).  ``build_graph`` exports the op-level DAG the paper
reasons about — the benchmark harness runs the Table-1/Table-2 analogues
and the 27-case complementary-pair sweep on it.

Execution is plan-driven: ``plan_cnn`` lowers the scheduler's CoGroups to a
``core.plan.Plan`` (grouped / stacked / fused / spatial / serial / xla per
group) and ``forward_plan`` executes it.  Every branch conv carries its
GEMM view (1x1 = channel matmul; K×K = im2col patches), so a whole
Inception module co-executes: the ragged 1x1 projections AND the 3x3/5x5
critical-path convs each run as ONE grouped Pallas kernel with bias+ReLU
fused in-kernel, instead of six serial convs.  The algorithms-dict path
(``forward(algorithms=...)``) remains as the serial fallback.

The backward pass co-executes the mirrored fork/join: grouped (and
join-absorbing ``grouped_concat``) groups differentiate through ONE
combined dx/dw/db launch per grad CoGroup (``grouped_matmul_bwd``, their
custom VJP), serial convs through the stride-aware im2col GEMM-view
backward (``_conv_gemm_bwd`` — no XLA conv-transpose anywhere on the zoo
path), and ``plan_cnn`` attaches the lowered grad CoGroups as
``plan.context["backward"]`` (``core.plan.backward_plan``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import Op, OpGraph
# import from the conv2d module file directly (the package re-exports the
# ops.conv2d *function* under the same name, shadowing the submodule)
from repro.kernels.conv2d import CONV2D_ALGORITHMS as _CONV_ALGS
from repro.kernels.matmul import mxu_precision
from repro.kernels.ops import default_interpret
from repro.kernels import ref as k_ref
from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class InceptionSpec:
    n1: int      # 1x1 branch
    r3: int      # 3x3 reduce
    n3: int      # 3x3 branch
    r5: int      # 5x5 reduce
    n5: int      # 5x5 branch
    pp: int      # pool-proj branch

    @property
    def out(self) -> int:
        return self.n1 + self.n3 + self.n5 + self.pp


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    name: str
    img: tuple[int, int, int]            # (H, W, C)
    stem: tuple[tuple[int, int, int], ...]  # (k, out_ch, stride) convs
    modules: tuple[InceptionSpec, ...]
    pool_between: tuple[int, ...]        # module idxs preceded by 2x2 maxpool
    num_classes: int = 1000
    family: str = "cnn"

    def param_count(self) -> int:
        n, c = 0, self.img[2]
        for (k, out, _s) in self.stem:
            n += k * k * c * out + out
            c = out
        for m in self.modules:
            n += c * m.n1 + m.n1
            n += c * m.r3 + m.r3 + 9 * m.r3 * m.n3 + m.n3
            n += c * m.r5 + m.r5 + 25 * m.r5 * m.n5 + m.n5
            n += c * m.pp + m.pp
            c = m.out
        return n + c * self.num_classes + self.num_classes


def conv(x, w, b, *, stride=1, algorithm="xla", interpret=None):
    if algorithm == "xla":
        y = k_ref.conv2d_ref(x, w, stride=stride, padding="SAME")
    else:
        y = _conv_alg(x, w, stride, algorithm,
                      default_interpret() if interpret is None else interpret)
    return jax.nn.relu(y + b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _conv_alg(x, w, stride, algorithm, interpret):
    """Algorithm-zoo conv with a GEMM-view VJP: the paper's algorithm knob
    concerns the FORWARD kernel; the gradient of the mathematical op is
    algorithm-independent and routes through the stride-aware im2col GEMM
    lowering (``_conv_gemm_bwd``) — the same cuDNN-style view the grouped
    dw/dx kernels co-execute for branch groups, here launched per-op
    through the matmul zoo (the serial regime's one-kernel-per-op
    backward)."""
    return _CONV_ALGS[algorithm](x, w, stride=stride, padding="SAME",
                                 interpret=interpret)


def _conv_alg_fwd(x, w, stride, algorithm, interpret):
    return _conv_alg(x, w, stride, algorithm, interpret), (x, w)


def _conv_alg_bwd(stride, algorithm, interpret, res, g):
    x, w = res
    return _conv_gemm_bwd(x, w, g.astype(x.dtype), stride,
                          interpret=interpret)


_conv_alg.defvjp(_conv_alg_fwd, _conv_alg_bwd)


def _im2col(x, kh, kw, stride):
    """SAME-padded im2col patches, feature order (C, KH, KW) — the GEMM
    lhs every conv's forward AND backward lowering shares.

    Built from pad + strided slices + dynamic_update_slice (NOT
    ``conv_general_dilated_patches``): the patch gather must not lower to
    an XLA convolution primitive, or the traced-jaxpr launch counter
    (``core.launch_count``) would charge every im2col view as a surviving
    conv launch.  Matches the patches primitive bit-for-bit, tap order
    included."""
    b, h, w, c = x.shape
    oh, ow = -(-h // stride), -(-w // stride)
    pad_h = max((oh - 1) * stride + kh - h, 0)
    pad_w = max((ow - 1) * stride + kw - w, 0)
    plo_h, plo_w = pad_h // 2, pad_w // 2
    xp = jnp.pad(x, ((0, 0), (plo_h, pad_h - plo_h),
                     (plo_w, pad_w - plo_w), (0, 0)))
    buf = jnp.zeros((b, oh, ow, c, kh * kw), x.dtype)
    for ki in range(kh):
        for kj in range(kw):
            tap = jax.lax.slice(
                xp, (0, ki, kj, 0),
                (b, ki + (oh - 1) * stride + 1, kj + (ow - 1) * stride + 1,
                 c), (1, stride, stride, 1))
            buf = jax.lax.dynamic_update_slice(
                buf, tap[..., None], (0, 0, 0, 0, ki * kw + kj))
    # (..., C, KH*KW) -> flat (C, KH, KW)-major feature axis
    return buf.reshape(b, oh, ow, c * kh * kw)


def _conv_gemm_bwd(x, w, dy, stride, interpret=None):
    """Conv backward through the stride-aware GEMM view (no XLA
    conv-transpose): dw is the transposed GEMM patches^T @ dY2d — exactly
    the contraction the grouped dw kernel co-executes for branch groups —
    and dx pulls the patch cotangent back through the im2col gather.
    The two GEMMs launch per-op through the Pallas matmul zoo, so the
    serial baseline's backward is kernel-for-kernel comparable with the
    grouped backward (one launch per op vs one per group)."""
    from repro.kernels.ops import matmul as k_matmul
    kh, kw, cin, cout = w.shape
    dy2 = dy.reshape(-1, cout)
    if (kh, kw) == (1, 1) and stride == 1:
        x2 = x.reshape(-1, cin)
        dx = k_matmul(dy2, w.reshape(cin, cout).T,
                      interpret=interpret).reshape(x.shape)
        dw2 = k_matmul(x2.T, dy2, interpret=interpret)
        return dx, dw2.reshape(1, 1, cin, cout)
    patches, pat_vjp = jax.vjp(lambda xx: _im2col(xx, kh, kw, stride), x)
    p2 = patches.reshape(-1, cin * kh * kw)
    wmat = w.transpose(2, 0, 1, 3).reshape(cin * kh * kw, cout)
    dpat = k_matmul(dy2, wmat.T, interpret=interpret)
    dx = pat_vjp(dpat.reshape(patches.shape))[0]
    dw2 = k_matmul(p2.T, dy2, interpret=interpret)
    return dx, dw2.reshape(cin, kh, kw, cout).transpose(1, 2, 0, 3)


def maxpool(x, k=3, stride=2):
    # numpy (not jnp) init: dtype-preserving for bf16, and still a
    # concrete monoid identity — a traced jnp array defeats
    # reduce_window's max-monoid detection, lowering to the generic
    # reduce_window_p which has no transpose rule (jit+grad asserts)
    return jax.lax.reduce_window(
        x, np.array(-np.inf, x.dtype), jax.lax.max, (1, k, k, 1),
        (1, stride, stride, 1), "SAME")


def maxpool_chain(x, chain):
    """A ``((window, stride), ...)`` maxpool chain via ``reduce_window`` —
    the standalone pooling primitive the serial/unfused paths launch (and
    the baseline the pooled grouped launch absorbs)."""
    for k, s in chain:
        x = maxpool(x, k, s)
    return x


def _conv_init(key, kh, cin, cout, dtype):
    w = L.normal_init(key, (kh, kh, cin, cout), (kh * kh * cin) ** -0.5,
                      dtype)
    return {"w": w, "b": jnp.zeros((cout,), dtype)}


def init_params(cfg: CNNConfig, key, dtype=jnp.float32):
    ks = iter(jax.random.split(key, 8 + 8 * len(cfg.modules)))
    params: dict = {"stem": []}
    c = cfg.img[2]
    for (k, out, s) in cfg.stem:
        params["stem"].append(_conv_init(next(ks), k, c, out, dtype))
        c = out
    params["modules"] = []
    for m in cfg.modules:
        p = {
            "b1": _conv_init(next(ks), 1, c, m.n1, dtype),
            "r3": _conv_init(next(ks), 1, c, m.r3, dtype),
            "b3": _conv_init(next(ks), 3, m.r3, m.n3, dtype),
            "r5": _conv_init(next(ks), 1, c, m.r5, dtype),
            "b5": _conv_init(next(ks), 5, m.r5, m.n5, dtype),
            "pp": _conv_init(next(ks), 1, c, m.pp, dtype),
        }
        params["modules"].append(p)
        c = m.out
    params["head"] = {
        "w": L.normal_init(next(ks), (c, cfg.num_classes), c ** -0.5, dtype),
        "b": jnp.zeros((cfg.num_classes,), dtype)}
    return params


def inception_module(p, x, spec: InceptionSpec, alg, interpret=None):
    """alg: dict branch-name -> algorithm (from the scheduler) or str."""
    a = (lambda n: alg.get(n, "xla")) if isinstance(alg, dict) else (lambda n: alg)
    b1 = conv(x, p["b1"]["w"], p["b1"]["b"], algorithm=a("1x1"),
              interpret=interpret)
    r3 = conv(x, p["r3"]["w"], p["r3"]["b"], algorithm=a("r3"),
              interpret=interpret)
    b3 = conv(r3, p["b3"]["w"], p["b3"]["b"], algorithm=a("3x3"),
              interpret=interpret)
    r5 = conv(x, p["r5"]["w"], p["r5"]["b"], algorithm=a("r5"),
              interpret=interpret)
    b5 = conv(r5, p["b5"]["w"], p["b5"]["b"], algorithm=a("5x5"),
              interpret=interpret)
    pp = conv(maxpool(x, 3, 1), p["pp"]["w"], p["pp"]["b"],
              algorithm=a("pp"), interpret=interpret)
    return jnp.concatenate([b1, b3, b5, pp], axis=-1)


def forward(params, cfg: CNNConfig, images, *, algorithms=None,
            interpret=None):
    """images (B, H, W, C) -> logits (B, classes).

    algorithms: None (XLA), a str, or {module_idx: {branch: alg}} from the
    scheduler (`schedule_cnn`).
    """
    x = images
    for i, (p, (k, out, s)) in enumerate(zip(params["stem"], cfg.stem)):
        alg = "xla" if algorithms is None else (
            algorithms if isinstance(algorithms, str)
            else algorithms.get(f"stem{i}", "xla"))
        x = conv(x, p["w"], p["b"], stride=s, algorithm=alg,
                 interpret=interpret)
    for i, (p, m) in enumerate(zip(params["modules"], cfg.modules)):
        if i in cfg.pool_between:
            x = maxpool(x, 3, 2)
        alg = "xla" if algorithms is None else (
            algorithms if isinstance(algorithms, str)
            else algorithms.get(i, {}))
        x = inception_module(p, x, m, alg, interpret=interpret)
    x = x.mean(axis=(1, 2))
    return _head(x, params["head"]["w"]) + params["head"]["b"]


def _head(x, w):
    """The classifier GEMM at the precision of its operands: a TPU's
    default takes one bf16 pass even for f32, which alone puts f32 logits
    2.5e-3 off (full googlenet)."""
    return jnp.dot(x, w, precision=mxu_precision(x.dtype))


def loss_fn(params, cfg: CNNConfig, batch, *, plan=None, **kw):
    if plan is not None:
        logits = forward_plan(params, cfg, batch["images"], plan, **kw)
    else:
        logits = forward(params, cfg, batch["images"], **kw)
    return L.cross_entropy(logits, batch["labels"]), {}


# ---------------------------------------------------------------------------
# plan-driven execution (core/plan.py lowering of the schedule)
# ---------------------------------------------------------------------------

def _plan_impls(params, cfg: CNNConfig, interpret=None):
    """``core.plan.OpImpl`` binding for every ``build_graph`` op.

    Mirrors the shape walk of ``build_graph``.  The maxpools are explicit
    graph ops now, so each pool impl carries its ``pool_chain`` (what the
    pooled grouped launch absorbs) and an ``fn`` running the standalone
    ``reduce_window`` chain (the serial/unfused baseline); the pool-proj
    conv reads its pre-pool op's output directly.  Returns (impls, name
    of the final join op).
    """
    from repro.core.plan import OpImpl

    impls: dict = {}
    h, w = cfg.img[:2]
    dep = "input"

    def conv_impl(pb, dep, oh, ow, stride=1):
        """OpImpl with the conv's GEMM views: a 1x1 conv is a channel
        matmul; a K×K conv is its im2col view (M = B*OH*OW, K = C*KH*KW)
        — the cuDNN GEMM lowering, which is what lets the 3x3/5x5
        branches join grouped co-execution groups.  ``oh``/``ow`` must be
        the POST-stride output extent (matching cost_model.gemm_shape).
        The bias+ReLU epilogue is split out (gemm_bias/gemm_relu/
        gemm_reshape) so the grouped kernel can fuse it in-kernel;
        gemm_post keeps the equivalent out-of-kernel epilogue for
        stacked/fused modes.  ``gemm_x`` is a pure function of the dep
        value — for a pool-absorbed branch the executor applies it to
        each raw-input tap view instead of the materialized pooled dep."""
        kh, kw, cin, _ = pb["w"].shape
        # (KH, KW, C, K) -> (C, KH, KW, K) -> (C*KH*KW, K): matches the
        # (C, KH, KW) feature order of conv_general_dilated_patches.
        wmat = pb["w"].transpose(2, 0, 1, 3).reshape(cin * kh * kw, -1)

        def gemm_x(x, kh=kh, kw=kw, cin=cin, s=stride):
            if (kh, kw) == (1, 1) and s == 1:
                return x.reshape(-1, cin)
            return _im2col(x, kh, kw, s).reshape(-1, cin * kh * kw)

        def gemm_reshape(y2d, oh=oh, ow=ow):
            return y2d.reshape(-1, oh, ow, y2d.shape[-1])

        def gemm_post(y2d, pb=pb):
            return jax.nn.relu(gemm_reshape(y2d) + pb["b"])

        return OpImpl(
            deps=(dep,),
            fn=lambda x, algorithm="xla", pb=pb, s=stride: conv(
                x, pb["w"], pb["b"], stride=s, algorithm=algorithm,
                interpret=interpret),
            gemm_x=gemm_x,
            # branches whose dep AND filter geometry coincide produce the
            # identical x2d -> wide-GEMM dedup (deps equality carries the
            # input identity now that pools are explicit ops)
            gemm_x_key=("conv_x", kh, kw, stride, cin),
            gemm_w=wmat,
            gemm_post=gemm_post,
            gemm_bias=pb["b"],
            gemm_relu=True,
            gemm_reshape=gemm_reshape,
            # raw conv geometry for grouped_chained launches: ring tap
            # descriptors, panel-block weight repacking and border masks
            # need what gemm_x's closure hides
            chain_geom=(kh, kw, stride, cin, oh, ow))

    def pool_impl(dep, chain):
        return OpImpl(
            deps=(dep,),
            fn=lambda x, algorithm=None, chain=chain: maxpool_chain(
                x, chain),
            pool_chain=tuple(chain))

    for i, (pb, (k, out, s)) in enumerate(zip(params["stem"], cfg.stem)):
        h, w = -(-h // s), -(-w // s)
        impls[f"stem{i}"] = conv_impl(pb, dep, h, w, stride=s)
        dep = f"stem{i}"

    for i, p in enumerate(params["modules"]):
        pooled = i in cfg.pool_between
        nm = f"inc{i}"
        if pooled:
            impls[f"{nm}/pool"] = pool_impl(dep, ((3, 2),))
            impls[f"{nm}/pppool"] = pool_impl(dep, ((3, 2), (3, 1)))
            bdep = f"{nm}/pool"
            h, w = -(-h // 2), -(-w // 2)
        else:
            impls[f"{nm}/pppool"] = pool_impl(dep, ((3, 1),))
            bdep = dep
        impls[f"{nm}/1x1"] = conv_impl(p["b1"], bdep, h, w)
        impls[f"{nm}/r3"] = conv_impl(p["r3"], bdep, h, w)
        impls[f"{nm}/r5"] = conv_impl(p["r5"], bdep, h, w)
        impls[f"{nm}/pp"] = conv_impl(p["pp"], f"{nm}/pppool", h, w)
        impls[f"{nm}/3x3"] = conv_impl(p["b3"], f"{nm}/r3", h, w)
        impls[f"{nm}/5x5"] = conv_impl(p["b5"], f"{nm}/r5", h, w)
        impls[f"{nm}/join"] = OpImpl(
            deps=(f"{nm}/1x1", f"{nm}/3x3", f"{nm}/5x5", f"{nm}/pp"),
            fn=lambda *ys, algorithm=None: jnp.concatenate(ys, axis=-1),
            # 2D (M, sum N_g) -> NHWC view: what lets a grouped_concat
            # group absorb this join — the grouped kernel's epilogue
            # assembles the concat buffer and only this reshape runs out
            # of kernel (a pure layout view, like gemm_reshape on convs)
            gemm_reshape=lambda y2d, oh=h, ow=w: y2d.reshape(
                -1, oh, ow, y2d.shape[-1]))
        dep = f"{nm}/join"
    return impls, dep


def forward_plan(params, cfg: CNNConfig, images, plan, *, mesh=None,
                 interpret=None, timings=None, valid_images=None):
    """Plan-driven forward: images (B, H, W, C) -> logits (B, classes).

    ``plan`` comes from ``plan_cnn``; stacked groups run in one branch
    kernel, serial groups use the scheduler algorithms, xla groups trust
    XLA — see ``core/plan.py``.  ``valid_images`` makes the grouped
    launches ragged-M for a bucketed serving batch whose first
    ``valid_images`` images are real (see ``core.plan.run_plan``;
    inference-only) — logits rows at/past it are padding.
    """
    from repro.core import plan as planlib
    impls, out_name = _plan_impls(params, cfg, interpret=interpret)
    env = {"input": images}
    planlib.run_plan(impls, env, plan, mesh=mesh, interpret=interpret,
                     timings=timings, valid_images=valid_images)
    out = env[out_name]
    hw = params["head"]["w"]
    if isinstance(out, planlib.ChainPanels):
        # split head: the final chained launch's output never assembles —
        # global-average-pool each panel segment in place and multiply by
        # the matching head-row slab (sum over segments == the composite
        # GAP @ head exactly), so no concatenate survives the forward
        logits = params["head"]["b"]
        coff = 0
        for pidx, cb, n in out.segments:
            seg = out.panels[pidx][:out.m, cb * out.blk: cb * out.blk + n]
            segm = seg.reshape(-1, out.h * out.w, n).mean(axis=1)
            rows = jax.lax.slice(hw, (coff, 0), (coff + n, hw.shape[1]))
            logits = logits + _head(segm, rows.astype(segm.dtype))
            coff += n
        return logits
    x = out.mean(axis=(1, 2))
    return _head(x, hw) + params["head"]["b"]


def plan_cnn(cfg: CNNConfig, batch: int, *, mesh=None, concurrent=True,
             max_group: int = 4, hbm_budget: float | None = None,
             vmem_budget: float | None = None, train: bool = False,
             fuse_concat: bool = True, fuse_pool: bool = True,
             chain_modules: bool = False):
    """graph -> schedule -> executable plan for this CNN.

    Returns (Plan, Schedule).  This supersedes ``schedule_algorithms``: the
    plan carries the same per-op algorithm choices AND the per-group
    execution mode that makes the co-execution decisions real.
    ``fuse_concat`` (default) absorbs each inception module's join into
    the grouped launch feeding it (``grouped_concat`` groups — the
    3x3/5x5 outputs land in the join buffer in-kernel, the 1x1/pool-proj
    outputs copy in as passthrough slices, and no standalone concat op
    remains on the fused path); ``fuse_concat=False`` keeps the
    standalone joins (the unfused baseline the benchmarks compare
    against).  ``fuse_pool`` (default) likewise streams every maxpool op
    through the grouped launch that consumes it (``_absorb_pools`` ->
    ``grouped_pooled`` / pooled ``grouped_concat`` groups — zero
    standalone ``reduce_window`` launches on the fused path);
    ``fuse_pool=False`` keeps the pooling primitives standalone.

    ``chain_modules=True`` additionally chains the absorbed launches
    ACROSS module boundaries (``core.plan._chain_modules``): each
    module's quad + concat-pair merge into ONE two-phase
    ``grouped_chained`` launch (reductions stream to the K*K convs
    through the in-kernel VMEM ring; the join vanishes — the next launch
    consumes the padded output panels in place), and the stem's serial
    convs fold into one multi-phase launch.  On googlenet this takes the
    forward from ~21 kernel launches to one per module plus one for the
    stem.

    The mirrored backward plan (``core.plan.backward_plan``) is attached
    as ``plan.context["backward"]`` — the lowering/pricing of the grad
    CoGroups the training step's VJPs execute.  ``train=True`` packs and
    budget-checks groups at forward+backward cost (a group only forms
    when co-execution wins across the whole step).
    """
    from repro.core import plan as planlib
    from repro.core import scheduler as S
    kw = {}
    if hbm_budget is not None:
        kw["hbm_budget"] = hbm_budget
    if vmem_budget is not None:
        kw["vmem_budget"] = vmem_budget
    g = build_graph(cfg, batch)
    sch = S.schedule(g, concurrent=concurrent, max_group=max_group,
                     train=train, **kw)
    plan = planlib.lower(g, sch, mesh=mesh, train=train,
                         fuse_concat=fuse_concat, fuse_pool=fuse_pool,
                         chain_modules=chain_modules, **kw)
    plan.context.update({"cfg": cfg, "batch": batch})
    plan.context["backward"] = planlib.backward_plan(g, plan, **kw)
    return plan, sch


# ---------------------------------------------------------------------------
# op-graph export (for the scheduler / paper benchmarks)
# ---------------------------------------------------------------------------

def build_graph(cfg: CNNConfig, batch: int) -> OpGraph:
    """Op-level DAG with the pooling primitives EXPLICIT: the inter-module
    maxpool (``inc{i}/pool``) and each pool-proj pre-pool
    (``inc{i}/pppool``) are ``maxpool`` ops — the separate launched
    primitives they are in a cuDNN-style framework, and the ops the plan
    layer's ``_absorb_pools`` streams into the grouped launches.  The
    pool-proj pre-pool reads the RAW module input with its COMPOSED chain
    ((3,2)+(3,1) for pooled modules), so the four branch convs of a
    module still share one ready level (the quad the scheduler packs)."""
    g = OpGraph()
    h, w, c = cfg.img
    g.add(Op.make("input", "pointwise", elements=batch * h * w * c))
    dep = "input"
    for i, (k, out, s) in enumerate(cfg.stem):
        g.add(Op.make(f"stem{i}", "conv2d", n=batch, h=h, w=w, c=c, kh=k,
                      kw=k, k=out, stride=s), [dep])
        dep = f"stem{i}"
        h, w, c = -(-h // s), -(-w // s), out
    for i, m in enumerate(cfg.modules):
        nm = f"inc{i}"
        pooled = i in cfg.pool_between
        if pooled:
            g.add(Op.make(f"{nm}/pool", "maxpool", n=batch, h=h, w=w, c=c,
                          chain=((3, 2),)), [dep])
            pp_chain = ((3, 2), (3, 1))
        else:
            pp_chain = ((3, 1),)
        g.add(Op.make(f"{nm}/pppool", "maxpool", n=batch, h=h, w=w, c=c,
                      chain=pp_chain), [dep])
        branch_dep = f"{nm}/pool" if pooled else dep
        if pooled:
            h, w = -(-h // 2), -(-w // 2)
        g.add(Op.make(f"{nm}/1x1", "conv2d", n=batch, h=h, w=w, c=c, kh=1,
                      kw=1, k=m.n1, stride=1), [branch_dep])
        g.add(Op.make(f"{nm}/r3", "conv2d", n=batch, h=h, w=w, c=c, kh=1,
                      kw=1, k=m.r3, stride=1), [branch_dep])
        g.add(Op.make(f"{nm}/3x3", "conv2d", n=batch, h=h, w=w, c=m.r3,
                      kh=3, kw=3, k=m.n3, stride=1), [f"{nm}/r3"])
        g.add(Op.make(f"{nm}/r5", "conv2d", n=batch, h=h, w=w, c=c, kh=1,
                      kw=1, k=m.r5, stride=1), [branch_dep])
        g.add(Op.make(f"{nm}/5x5", "conv2d", n=batch, h=h, w=w, c=m.r5,
                      kh=5, kw=5, k=m.n5, stride=1), [f"{nm}/r5"])
        g.add(Op.make(f"{nm}/pp", "conv2d", n=batch, h=h, w=w, c=c, kh=1,
                      kw=1, k=m.pp, stride=1), [f"{nm}/pppool"])
        g.add(Op.make(f"{nm}/join", "pointwise",
                      elements=batch * h * w * m.out),
              [f"{nm}/1x1", f"{nm}/3x3", f"{nm}/5x5", f"{nm}/pp"])
        dep = f"{nm}/join"
        c = m.out
    return g


def schedule_algorithms(cfg: CNNConfig, batch: int, concurrent=True):
    """Run the core scheduler on the CNN graph -> per-module algorithm map
    usable by ``forward(algorithms=...)``.

    Superseded by ``plan_cnn`` + ``forward_plan`` (the ``core/plan.py``
    execution-plan IR): this path keeps only the algorithm choices and runs
    every branch serially — the exact framework behaviour the paper
    critiques.  It remains as the plan's ``serial`` fallback."""
    from repro.core import scheduler as S
    g = build_graph(cfg, batch)
    sch = S.schedule(g, concurrent=concurrent)
    algs = sch.algorithms
    out: dict = {}
    for name, alg in algs.items():
        if name.startswith("stem"):
            out[name] = alg
        elif name.startswith("inc"):
            mod, branch = name.split("/")
            out.setdefault(int(mod[3:]), {})[branch] = alg
    return out, sch
