"""Public jit-friendly wrappers over the kernel algorithm zoo.

Every op takes ``algorithm=`` (the paper's central knob) and an
``interpret=`` override; on a CPU-only host the Pallas kernels run in
interpret mode automatically so the whole framework is testable without TPU.
Wrappers pad to hardware-aligned block shapes and slice back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import matmul as _mm
from repro.kernels import conv2d as _conv
from repro.kernels import flash_attention as _attn
from repro.kernels import ssd as _ssd
from repro.kernels import branch_matmul as _bmm
from repro.kernels import fused_branches as _fused
from repro.kernels import grouped_matmul as _gmm


@functools.cache
def default_interpret() -> bool:
    """Pallas interpret mode unless a real TPU backend is present."""
    return jax.default_backend() != "tpu"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------

def matmul(x, y, *, algorithm: str = "mxu128", interpret: bool | None = None):
    """(…, M, K) @ (K, N) with padding to MXU-aligned blocks."""
    interpret = default_interpret() if interpret is None else interpret
    lead = x.shape[:-2] if x.ndim > 2 else ()
    m = int(jnp.prod(jnp.array(x.shape[:-1]))) if x.ndim > 2 else x.shape[0]
    x2 = x.reshape(-1, x.shape[-1])
    m, k = x2.shape
    k2, n = y.shape
    assert k == k2
    bm, bn, bk = _mm.matmul_block_shape(algorithm)
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    xp = jnp.pad(x2, ((0, mp - m), (0, kp - k)))
    yp = jnp.pad(y, ((0, kp - k), (0, np_ - n)))
    out = _mm.MATMUL_ALGORITHMS[algorithm](xp, yp, interpret=interpret)
    out = out[:m, :n]
    return out.reshape(*lead, x.shape[-2] if x.ndim > 2 else m, n) \
        if x.ndim > 2 else out


matmul_workspace_bytes = _mm.matmul_workspace_bytes
matmul_vmem_bytes = _mm.matmul_vmem_bytes
MATMUL_ALGORITHMS = tuple(_mm.MATMUL_ALGORITHMS)


# ---------------------------------------------------------------------------
# conv2d
# ---------------------------------------------------------------------------

def conv2d(x, w, *, stride: int = 1, padding: str = "SAME",
           algorithm: str = "im2col_gemm", interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    fn = _conv.CONV2D_ALGORITHMS[algorithm]
    return fn(x, w, stride=stride, padding=padding, interpret=interpret)


conv2d_workspace_bytes = _conv.conv2d_workspace_bytes
CONV2D_ALGORITHMS = tuple(_conv.CONV2D_ALGORITHMS)


def conv2d_supported(algorithm: str, kh: int, kw: int, stride: int) -> bool:
    """cuDNN-style support matrix ("DIRECT and WINOGRAD are not supported
    for this input" — Table 2 footnote analogue)."""
    if algorithm == "winograd3x3":
        return (kh, kw) == (3, 3) and stride == 1
    return True


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attention(q, k, v, *, causal: bool = True, window: int | None = None,
              softcap: float | None = None, scale: float | None = None,
              algorithm: str = "flash", block_q: int = 128, block_k: int = 128,
              interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    if algorithm == "materialized":
        return _attn.attention_materialized(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale)
    return _attn.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret)


attention_workspace_bytes = _attn.attention_workspace_bytes
ATTENTION_ALGORITHMS = tuple(_attn.ATTENTION_ALGORITHMS)


# ---------------------------------------------------------------------------
# ssd (Mamba-2)
# ---------------------------------------------------------------------------

def ssd(x, a_log, b, c, *, chunk: int = 128, d_skip=None,
        algorithm: str = "chunked", interpret: bool | None = None):
    interpret = default_interpret() if interpret is None else interpret
    if algorithm == "quadratic":
        return _ssd.ssd_quadratic(x, a_log, b, c, d_skip=d_skip)
    return _ssd.ssd_chunked(x, a_log, b, c, chunk=chunk, d_skip=d_skip,
                            interpret=interpret)


ssd_workspace_bytes = _ssd.ssd_workspace_bytes
SSD_ALGORITHMS = tuple(_ssd.SSD_ALGORITHMS)


# ---------------------------------------------------------------------------
# branch matmul (stacked independent GEMMs)
# ---------------------------------------------------------------------------

def branch_matmul(x, y, *, interpret: bool | None = None):
    """(G, M, K) @ (G, K, N) -> (G, M, N), padded per-branch.

    Differentiable: the custom VJP computes dx/dy with the SAME stacked
    kernel (the backward GEMMs of G independent branches are themselves G
    independent same-shape GEMMs)."""
    interpret = default_interpret() if interpret is None else interpret
    return _branch_matmul_vjp(x, y, interpret)


def _branch_matmul_padded(x, y, interpret: bool):
    g, m, k = x.shape
    _, _, n = y.shape
    bm = bn = bk = 128
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    xp = jnp.pad(x, ((0, 0), (0, mp - m), (0, kp - k)))
    yp = jnp.pad(y, ((0, 0), (0, kp - k), (0, np_ - n)))
    out = _bmm.branch_matmul(xp, yp, interpret=interpret)
    return out[:, :m, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _branch_matmul_vjp(x, y, interpret):
    return _branch_matmul_padded(x, y, interpret)


def _branch_matmul_fwd(x, y, interpret):
    return _branch_matmul_padded(x, y, interpret), (x, y)


def _branch_matmul_bwd(interpret, res, g):
    x, y = res
    g = g.astype(x.dtype)
    dx = _branch_matmul_padded(g, y.transpose(0, 2, 1), interpret)
    dy = _branch_matmul_padded(x.transpose(0, 2, 1), g, interpret)
    return dx, dy


_branch_matmul_vjp.defvjp(_branch_matmul_fwd, _branch_matmul_bwd)


# ---------------------------------------------------------------------------
# grouped ragged branch GEMM (per-branch (K_g, N_g), fused epilogue)
# ---------------------------------------------------------------------------

def grouped_matmul(xs, ws, bs=None, *, relu: bool = False, m_valid=None,
                   interpret: bool | None = None,
                   chunk_rows: int | None = None):
    """G ragged branch GEMMs (M, K_g) @ (K_g, N_g) (+bias, +ReLU) in ONE
    kernel — see ``kernels/grouped_matmul.py``.

    Differentiable, and the backward pass co-executes too: the custom VJP
    emits exactly ONE combined grouped launch
    (``kernels/grouped_matmul.py::grouped_matmul_bwd``) — masked dx, dw
    and db over a concatenated two-phase offset table, with the dY/mask
    tile stacks packed once and shared between the phases.  No per-branch
    XLA fallback, and no second launch, remains on the grouped path.

    ``m_valid`` (python int or traced i32 scalar) makes the launch
    ragged-M — the serving path's bucketed multi-request batches, where
    rows at/past ``m_valid`` are padding and the epilogue stores zeros
    there.  The ragged path is INFERENCE-ONLY (a direct kernel call, no
    custom VJP: an integer row count has no meaningful cotangent and the
    serving driver never differentiates).

    ``chunk_rows`` (the plan's ``ExecGroup.chunk_rows``) caps the rows
    per launch so each launch's offset table fits SMEM; both directions
    split at the same rows.  None sizes the chunks per launch."""
    interpret = default_interpret() if interpret is None else interpret
    if m_valid is not None:
        return list(_gmm.grouped_matmul(list(xs), list(ws),
                                        None if bs is None else list(bs),
                                        relu=relu, m_valid=m_valid,
                                        interpret=interpret,
                                        chunk_rows=chunk_rows))
    return _grouped_vjp(tuple(xs), tuple(ws),
                        None if bs is None else tuple(bs), relu, interpret,
                        chunk_rows)


def grouped_matmul_dw(xs, dys, ys=None, *, interpret: bool | None = None):
    """(dws, dbs) of a grouped branch GEMM in ONE kernel: dw_g = x_g^T @
    dy_g (dy masked by y_g > 0 when ``ys`` is given) with db_g reduced in
    the same pass — see ``kernels/grouped_matmul.py``."""
    interpret = default_interpret() if interpret is None else interpret
    return _gmm.grouped_matmul_dw(xs, dys, ys, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _grouped_vjp(xs, ws, bs, relu, interpret, chunk_rows):
    return tuple(_gmm.grouped_matmul(xs, ws, bs, relu=relu,
                                     interpret=interpret,
                                     chunk_rows=chunk_rows))


def _grouped_fwd(xs, ws, bs, relu, interpret, chunk_rows):
    ys = _grouped_vjp(xs, ws, bs, relu, interpret, chunk_rows)
    return ys, (xs, ws, bs, ys if relu else None)


def _grouped_bwd(relu, interpret, chunk_rows, res, gs):
    xs, ws, bs, ys = res
    dys = [g.astype(x.dtype) for g, x in zip(gs, xs)]
    mask = list(ys) if relu else None
    # ONE combined launch: masked dx + dw + db over the concatenated
    # two-phase offset table (was two grouped launches, with the dY and
    # mask stacks packed once per launch instead of once per call)
    dxs, dws, dbs = _gmm.grouped_matmul_bwd(xs, ws, dys, mask,
                                            interpret=interpret,
                                            chunk_rows=chunk_rows)
    dws = tuple(dw.astype(w.dtype) for dw, w in zip(dws, ws))
    dbs = None if bs is None else tuple(
        db.astype(b.dtype) for db, b in zip(dbs, bs))
    return tuple(dxs), dws, dbs


_grouped_vjp.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul_concat(xs, ws, bs=None, *, offsets, total: int,
                          relu: bool = False, compact: bool = True,
                          m_valid=None, interpret: bool | None = None,
                          chunk_rows: int | None = None):
    """Fused epilogue-concat grouped GEMM: G ragged branches whose
    bias+ReLU epilogues write straight into the fork/join's (M, total)
    concat layout at per-branch column ``offsets`` — the join leaves the
    kernel assembled, with no per-branch HBM round-trip and no standalone
    concatenate op (``kernels/grouped_matmul.py::grouped_matmul_concat``).

    Columns not covered by a branch (passthrough slices produced by an
    earlier launch) are placeholders — overwrite them before use.
    ``compact=False`` returns the padded (M, sum Np_g) join buffer
    instead (see the kernel wrapper).  Differentiable: the custom VJP
    slices each branch's cotangent (and its ReLU mask) out of the joint
    buffer and emits ONE combined backward launch (masked dx + dw/db,
    ``grouped_matmul_bwd``).  ``m_valid`` makes the launch ragged-M
    (inference-only direct kernel call — see ``grouped_matmul``), and
    ``chunk_rows`` splits it into SMEM-sized launches (as there)."""
    interpret = default_interpret() if interpret is None else interpret
    if m_valid is not None:
        return _gmm.grouped_matmul_concat(
            list(xs), list(ws), None if bs is None else list(bs),
            offsets=tuple(int(o) for o in offsets), total=int(total),
            relu=relu, compact=compact, m_valid=m_valid,
            interpret=interpret, chunk_rows=chunk_rows)
    return _concat_vjp(tuple(xs), tuple(ws),
                       None if bs is None else tuple(bs),
                       tuple(int(o) for o in offsets), int(total), relu,
                       compact, interpret, chunk_rows)


def grouped_matmul_bwd(xs, ws, dys, ys=None, *,
                       interpret: bool | None = None,
                       chunk_rows: int | None = None):
    """(dxs, dws, dbs) of a grouped branch GEMM in ONE combined launch
    (masked dx + dw/db over a concatenated two-phase offset table; dy is
    masked by y_g > 0 when ``ys`` is given) — see
    ``kernels/grouped_matmul.py``."""
    interpret = default_interpret() if interpret is None else interpret
    return _gmm.grouped_matmul_bwd(xs, ws, dys, ys, interpret=interpret,
                                   chunk_rows=chunk_rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _concat_vjp(xs, ws, bs, offsets, total, relu, compact, interpret,
                chunk_rows):
    return _gmm.grouped_matmul_concat(xs, ws, bs, offsets=offsets,
                                      total=total, relu=relu,
                                      compact=compact, interpret=interpret,
                                      chunk_rows=chunk_rows)


def _concat_fwd(xs, ws, bs, offsets, total, relu, compact, interpret,
                chunk_rows):
    y = _concat_vjp(xs, ws, bs, offsets, total, relu, compact, interpret,
                    chunk_rows)
    return y, (xs, ws, bs, y if relu else None)


def _concat_offsets(xs, ws, offsets, compact):
    """Branch column offsets in the buffer the forward returned: the true
    join offsets when compact, the cumulative padded bases otherwise."""
    if compact:
        return offsets
    blocks = _gmm.grouped_block_shape(
        xs[0].shape[0], [(w.shape[0], w.shape[1]) for w in ws],
        xs[0].dtype)
    offs, base = [], 0
    for w in ws:
        offs.append(base)
        base += _round_up(w.shape[1], blocks.bn)
    return offs


def _concat_bwd(offsets, total, relu, compact, interpret, chunk_rows, res,
                g):
    xs, ws, bs, y = res
    offs = _concat_offsets(xs, ws, offsets, compact)
    dys = [g[:, off:off + w.shape[1]].astype(x.dtype)
           for off, w, x in zip(offs, ws, xs)]
    mask = [y[:, off:off + w.shape[1]]
            for off, w in zip(offs, ws)] if relu else None
    dxs, dws, dbs = _gmm.grouped_matmul_bwd(xs, ws, dys, mask,
                                            interpret=interpret,
                                            chunk_rows=chunk_rows)
    dws = tuple(dw.astype(w.dtype) for dw, w in zip(dws, ws))
    dbs = None if bs is None else tuple(
        db.astype(b.dtype) for db, b in zip(dbs, bs))
    return tuple(dxs), dws, dbs


_concat_vjp.defvjp(_concat_fwd, _concat_bwd)


# ---------------------------------------------------------------------------
# pooled grouped launch (in-kernel maxpool pre-GEMM stage)
# ---------------------------------------------------------------------------

def grouped_matmul_pooled(xs, ws, bs=None, *, relu: bool = False,
                          m_valid=None, interpret: bool | None = None,
                          chunk_rows: int | None = None):
    """Grouped ragged branch GEMMs with each pooled branch's maxpool
    computed IN-KERNEL as a pre-GEMM stage (``xs[g]`` a sequence of
    ``pool_tap_views`` tap arrays) — ONE launch covers pooling, GEMMs and
    the bias+ReLU epilogue; no standalone pooling kernel remains.

    Differentiable: the custom VJP emits exactly ONE combined backward
    launch (``grouped_matmul_bwd`` — masked dx + dw/db), with the pooled
    branches' lhs folded at pack time and the pooling cotangent scattered
    back through the first-argmax window mask in the unpacking pass
    (elementwise, like the ReLU cotangent mask folded into the packing —
    gradients match the XLA ``reduce_window`` oracle bit-for-bit,
    tie-breaking included).  ``m_valid`` makes the launch ragged-M
    (inference-only direct kernel call) and ``chunk_rows`` splits it into
    SMEM-sized launches — both as in ``grouped_matmul``."""
    interpret = default_interpret() if interpret is None else interpret
    if m_valid is not None:
        return list(_gmm.grouped_matmul_pooled(
            list(xs), list(ws), None if bs is None else list(bs),
            relu=relu, m_valid=m_valid, interpret=interpret,
            chunk_rows=chunk_rows))
    xs_t = tuple(tuple(x) if isinstance(x, (list, tuple)) else x
                 for x in xs)
    return _pooled_vjp(xs_t, tuple(ws),
                       None if bs is None else tuple(bs), relu, interpret,
                       chunk_rows)


def grouped_matmul_pooled_concat(xs, ws, bs=None, *, offsets, total: int,
                                 relu: bool = False, compact: bool = True,
                                 m_valid=None, interpret: bool | None = None,
                                 chunk_rows: int | None = None):
    """The fused epilogue-concat grouped GEMM with the in-kernel pool
    stage: pooling + GEMMs + bias/ReLU + the join assembly in ONE launch
    (``kernels/grouped_matmul.py::grouped_matmul_pooled_concat``).  Same
    ``offsets``/``total``/``compact`` semantics as
    ``grouped_matmul_concat``; the custom VJP slices the joint cotangent
    and emits ONE combined backward launch, scattering pooled branches'
    cotangents through their argmax masks in its unpacking.  ``m_valid``
    makes the launch ragged-M (inference-only direct kernel call) and
    ``chunk_rows`` splits it into SMEM-sized launches — both as in
    ``grouped_matmul``."""
    interpret = default_interpret() if interpret is None else interpret
    if m_valid is not None:
        return _gmm.grouped_matmul_pooled_concat(
            list(xs), list(ws), None if bs is None else list(bs),
            offsets=tuple(int(o) for o in offsets), total=int(total),
            relu=relu, compact=compact, m_valid=m_valid,
            interpret=interpret, chunk_rows=chunk_rows)
    xs_t = tuple(tuple(x) if isinstance(x, (list, tuple)) else x
                 for x in xs)
    return _pooled_concat_vjp(xs_t, tuple(ws),
                              None if bs is None else tuple(bs),
                              tuple(int(o) for o in offsets), int(total),
                              relu, compact, interpret, chunk_rows)


def _pooled_flatten(xs):
    """(plain lhs per branch, {branch: folded pooled lhs}) — the pack-time
    fold the forward kernel performs in its pool stage."""
    flat, pooled = [], {}
    for i, x in enumerate(xs):
        if isinstance(x, tuple):
            pooled[i] = _gmm.pool_from_taps(list(x))
            flat.append(pooled[i])
        else:
            flat.append(x)
    return flat, pooled


def _pooled_scatter(xs, pooled, dxs):
    """Route each pooled branch's lhs cotangent back onto its taps."""
    outs = []
    for i, x in enumerate(xs):
        if isinstance(x, tuple):
            outs.append(tuple(_gmm.pool_cotangent_taps(
                list(x), pooled[i], dxs[i])))
        else:
            outs.append(dxs[i])
    return tuple(outs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _pooled_vjp(xs, ws, bs, relu, interpret, chunk_rows):
    return tuple(_gmm.grouped_matmul_pooled(list(xs), ws, bs, relu=relu,
                                            interpret=interpret,
                                            chunk_rows=chunk_rows))


def _pooled_fwd(xs, ws, bs, relu, interpret, chunk_rows):
    ys = _pooled_vjp(xs, ws, bs, relu, interpret, chunk_rows)
    return ys, (xs, ws, bs, ys if relu else None)


def _pooled_bwd(relu, interpret, chunk_rows, res, gs):
    xs, ws, bs, ys = res
    flat, pooled = _pooled_flatten(xs)
    dys = [g.astype(f.dtype) for g, f in zip(gs, flat)]
    mask = list(ys) if relu else None
    dxs, dws, dbs = _gmm.grouped_matmul_bwd(flat, ws, dys, mask,
                                            interpret=interpret,
                                            chunk_rows=chunk_rows)
    dws = tuple(dw.astype(w.dtype) for dw, w in zip(dws, ws))
    dbs = None if bs is None else tuple(
        db.astype(b.dtype) for db, b in zip(dbs, bs))
    return _pooled_scatter(xs, pooled, dxs), dws, dbs


_pooled_vjp.defvjp(_pooled_fwd, _pooled_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _pooled_concat_vjp(xs, ws, bs, offsets, total, relu, compact,
                       interpret, chunk_rows):
    return _gmm.grouped_matmul_pooled_concat(
        list(xs), ws, bs, offsets=offsets, total=total, relu=relu,
        compact=compact, interpret=interpret, chunk_rows=chunk_rows)


def _pooled_concat_fwd(xs, ws, bs, offsets, total, relu, compact,
                       interpret, chunk_rows):
    y = _pooled_concat_vjp(xs, ws, bs, offsets, total, relu, compact,
                           interpret, chunk_rows)
    return y, (xs, ws, bs, y if relu else None)


def _pooled_concat_bwd(offsets, total, relu, compact, interpret, chunk_rows,
                       res, g):
    xs, ws, bs, y = res
    flat, pooled = _pooled_flatten(xs)
    offs = _concat_offsets(flat, ws, offsets, compact)
    dys = [g[:, off:off + w.shape[1]].astype(f.dtype)
           for off, w, f in zip(offs, ws, flat)]
    mask = [y[:, off:off + w.shape[1]]
            for off, w in zip(offs, ws)] if relu else None
    dxs, dws, dbs = _gmm.grouped_matmul_bwd(flat, ws, dys, mask,
                                            interpret=interpret,
                                            chunk_rows=chunk_rows)
    dws = tuple(dw.astype(w.dtype) for dw, w in zip(dws, ws))
    dbs = None if bs is None else tuple(
        db.astype(b.dtype) for db, b in zip(dbs, bs))
    return _pooled_scatter(xs, pooled, dxs), dws, dbs


_pooled_concat_vjp.defvjp(_pooled_concat_fwd, _pooled_concat_bwd)

pool_tap_views = _gmm.pool_tap_views
pool_from_taps = _gmm.pool_from_taps
grouped_matmul_pooled_ref = _gmm.grouped_matmul_pooled_ref
grouped_matmul_pooled_concat_ref = _gmm.grouped_matmul_pooled_concat_ref

grouped_matmul_ref = _gmm.grouped_matmul_ref
grouped_matmul_dw_ref = _gmm.grouped_matmul_dw_ref
grouped_matmul_bwd_ref = _gmm.grouped_matmul_bwd_ref
grouped_matmul_concat_ref = _gmm.grouped_matmul_concat_ref
grouped_matmul_flops = _gmm.grouped_matmul_flops
grouped_block_shape = _gmm.grouped_block_shape
grouped_debug = _gmm.grouped_debug
KERNEL_LAUNCHES = _gmm.KERNEL_LAUNCHES
reset_launch_counts = _gmm.reset_launch_counts


# ---------------------------------------------------------------------------
# fused complementary pair (GEMM + streamed reduction)
# ---------------------------------------------------------------------------

def fused_gemm_reduce(x, y, z, *, bm: int = 128, bn: int = 128,
                      bk: int = 128, interpret: bool | None = None):
    """(M, K) @ (K, N) co-executed with silu(z).sum(0) in one grid.

    Pads x/y to the kernel's block shapes and slices the GEMM result back
    (z row-padding is handled inside the kernel wrapper).  Differentiable:
    like ``_conv_alg`` and ``branch_matmul``, the co-execution knob
    concerns the forward kernel only — the custom VJP computes the GEMM
    cotangents as plain GEMMs and pulls the reduction back through XLA's
    silu, so plans with fused groups stay trainable."""
    interpret = default_interpret() if interpret is None else interpret
    return _fused_vjp(x, y, z, bm, bn, bk, interpret)


def _fused_padded(x, y, z, bm, bn, bk, interpret):
    m, k = x.shape
    _, n = y.shape
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    yp = jnp.pad(y, ((0, kp - k), (0, np_ - n)))
    c, r = _fused.fused_gemm_reduce(xp, yp, z, bm=bm, bn=bn, bk=bk,
                                    interpret=interpret)
    return c[:m, :n], r


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fused_vjp(x, y, z, bm, bn, bk, interpret):
    return _fused_padded(x, y, z, bm, bn, bk, interpret)


def _fused_fwd(x, y, z, bm, bn, bk, interpret):
    return _fused_padded(x, y, z, bm, bn, bk, interpret), (x, y, z)


def _fused_bwd(bm, bn, bk, interpret, res, g):
    x, y, z = res
    dc, dr = g
    dc = dc.astype(x.dtype)
    _, red_vjp = jax.vjp(
        lambda zz: jax.nn.silu(zz.astype(jnp.float32)).sum(0).astype(
            zz.dtype), z)
    return dc @ y.T, x.T @ dc, red_vjp(dr.astype(z.dtype))[0]


_fused_vjp.defvjp(_fused_fwd, _fused_bwd)


# ---------------------------------------------------------------------------
# chained multi-phase launch (cross-module streaming)
# ---------------------------------------------------------------------------

def grouped_matmul_chained(phases, *, m: int, h: int, w: int, panels=(),
                           block: int = 128, m_valid=None,
                           interpret: bool | None = None,
                           chunk_rows: int | None = None):
    """A CHAIN of grouped branch phases in ONE kernel — join-chaining
    (panel-source lhs descriptors), in-launch KxK ring convs and the
    fused bias+ReLU epilogue; see
    ``kernels/grouped_matmul.py::grouped_matmul_chained``.

    Differentiable: the custom VJP mirrors the chain in reverse phase
    order with ONE combined ``grouped_matmul_bwd`` launch per phase.  The
    joint cotangent arrives per-phase on the padded panels; ring
    consumers' lhs is rebuilt as the differentiable tap-shift of the
    producer's residual panel (``jax.vjp`` routes their lhs cotangent
    back onto the producer's slab before its own phase runs), and
    panel-source branches' lhs cotangents accumulate onto the previous
    launch's panel arguments — so gradients flow across the whole chain
    exactly as through the unchained plan.

    ``m_valid`` (python int or traced i32 scalar, image-aligned) makes
    the launch ragged-M and bypasses the VJP entirely — the serving
    path's masked chained launch, where dead M-blocks are skipped as
    no-op waves and live tail blocks store exact zeros.  Inference-only,
    like every other ragged grouped-family wrapper.

    ``chunk_rows`` (a multiple of the h*w image) runs the chain as one
    launch per image-aligned chunk so each offset table fits SMEM; the
    backward's per-phase launches split at the same rows."""
    interpret = default_interpret() if interpret is None else interpret
    if m_valid is not None:
        return list(_gmm.grouped_matmul_chained(
            phases, m=m, h=h, w=w, panels=list(panels), block=block,
            m_valid=m_valid, interpret=interpret, chunk_rows=chunk_rows))
    spec, xs_flat, ws, bss = [], [], [], []
    for phase in phases:
        ps = []
        for br in phase:
            tag = br["src"][0]
            if tag == "x":
                arrs = list(br["src"][1])
                meta = len(arrs)
                xs_flat.extend(arrs)
            elif tag == "panel":
                meta = tuple((int(p), int(c)) for p, c in br["src"][1])
            else:
                meta = (int(br["src"][1]), int(br["src"][2]),
                        tuple(int(c) for c in br["src"][3]))
            ws.append(br["w"])
            bss.append(br.get("b"))
            ps.append((tag, meta, int(br["n"]),
                       tuple(br.get("ring_write") or ())))
        spec.append(tuple(ps))
    return list(_chained_vjp(tuple(xs_flat), tuple(ws), tuple(bss),
                             tuple(panels), tuple(spec), int(m), int(h),
                             int(w), int(block), interpret, chunk_rows))


def _chained_rebuild(xs_flat, ws, bss, spec):
    phases, cur, bi = [], 0, 0
    for pspec in spec:
        phase = []
        for (tag, meta, n, rw) in pspec:
            if tag == "x":
                src = ("x", list(xs_flat[cur:cur + meta]))
                cur += meta
            elif tag == "panel":
                src = ("panel", list(meta))
            else:
                src = ("ring", meta[0], meta[1], meta[2])
            phase.append({"n": n, "w": ws[bi], "b": bss[bi], "src": src,
                          "ring_write": rw or None})
            bi += 1
        phases.append(phase)
    return phases


def _pack_cols(arrs, widths, blk, dtype):
    """dus-pack 2D arrays into an (M, sum ceil(w/blk)*blk) buffer, each at
    its own block-aligned column base — the branch lhs layout the chained
    forward GEMM consumed (padding columns zero)."""
    total = sum(-(-wd // blk) for wd in widths) * blk
    buf = jnp.zeros((arrs[0].shape[0], total), dtype)
    off = 0
    for a, wd in zip(arrs, widths):
        buf = jax.lax.dynamic_update_slice(buf, a.astype(dtype), (0, off))
        off += -(-wd // blk) * blk
    return buf


def _add_block(buf, upd, r0: int, c0: int):
    """buf[r0:r0+R, c0:c0+C] += upd via slice + dynamic_update_slice — a
    scatter-add here would build its index vector with concatenates the
    launch counter counts."""
    cur = jax.lax.slice(buf, (r0, c0),
                        (r0 + upd.shape[0], c0 + upd.shape[1]))
    return jax.lax.dynamic_update_slice(
        buf, cur + upd.astype(buf.dtype), (r0, c0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _chained_vjp(xs_flat, ws, bss, panels, spec, m, h, w, block, interpret,
                 chunk_rows):
    phases = _chained_rebuild(xs_flat, ws, bss, spec)
    return tuple(_gmm.grouped_matmul_chained(
        phases, m=m, h=h, w=w, panels=list(panels), block=block,
        interpret=interpret, chunk_rows=chunk_rows))


def _chained_fwd(xs_flat, ws, bss, panels, spec, m, h, w, block, interpret,
                 chunk_rows):
    outs = _chained_vjp(xs_flat, ws, bss, panels, spec, m, h, w, block,
                        interpret, chunk_rows)
    return outs, (xs_flat, ws, bss, panels, outs)


def _chained_bwd(spec, m, h, w, block, interpret, chunk_rows, res, gs):
    xs_flat, ws, bss, panels, outs = res
    blk = block
    # branch layout + ring col -> (producer phase, producer panel col block)
    flat, ringmap, xoffs = [], {}, []
    cur = 0
    for p, pspec in enumerate(spec):
        cb = 0
        for (tag, meta, n, rw) in pspec:
            nbb = -(-n // blk)
            flat.append((p, cb, nbb, tag, meta, n, rw))
            for j, rc in enumerate(rw):
                ringmap[rc] = (p, cb + j)
            xoffs.append(cur)
            if tag == "x":
                cur += meta
            cb += nbb
    gpanels = [jnp.asarray(g) for g in gs]
    dxs_flat = [None] * len(xs_flat)
    dws = [None] * len(ws)
    dbs = [None] * len(bss)
    dpanels = [jnp.zeros_like(pa) for pa in panels]
    dtype = outs[0].dtype
    for p in reversed(range(len(spec))):
        idxs = [bi for bi, br in enumerate(flat) if br[0] == p]
        lhss, dys, masks, wsl, vjps = [], [], [], [], []
        for bi in idxs:
            _, cb, nbb, tag, meta, n, rw = flat[bi]
            dy = gpanels[p][:m, cb * blk:cb * blk + n].astype(dtype)
            y = outs[p][:m, cb * blk:cb * blk + n]
            if tag == "x":
                arrs = xs_flat[xoffs[bi]:xoffs[bi] + meta]
                lhs = _pack_cols(arrs, [a.shape[1] for a in arrs], blk,
                                 dtype)
                vjps.append(None)
            elif tag == "panel":
                lhs = _pack_cols(
                    [panels[pi][:m, c * blk:(c + 1) * blk]
                     for pi, c in meta],
                    [blk] * len(meta), blk, dtype)
                vjps.append(None)
            else:
                kh, kw, rcs = meta
                blocks = tuple(
                    outs[ringmap[rc][0]][:m, ringmap[rc][1] * blk:
                                         (ringmap[rc][1] + 1) * blk]
                    for rc in rcs)

                def _taps(bl, kh=kh, kw=kw):
                    parts = [_gmm._shift_spatial(seg, m, h, w,
                                                 dh - kh // 2,
                                                 dw_ - kw // 2)
                             for dh in range(kh) for dw_ in range(kw)
                             for seg in bl]
                    return _pack_cols(parts, [blk] * len(parts), blk,
                                      dtype)

                lhs, tapvjp = jax.vjp(_taps, blocks)
                vjps.append((tapvjp, rcs))
            lhss.append(lhs)
            dys.append(dy)
            masks.append(y)
            wsl.append(ws[bi])
        # ONE combined launch for this phase's dx + dw + db
        dxs, dws_p, dbs_p = _gmm.grouped_matmul_bwd(
            lhss, wsl, dys, masks, interpret=interpret,
            chunk_rows=chunk_rows)
        for k, bi in enumerate(idxs):
            _, cb, nbb, tag, meta, n, rw = flat[bi]
            dws[bi] = dws_p[k].astype(ws[bi].dtype)
            dbs[bi] = None if bss[bi] is None else \
                dbs_p[k].astype(bss[bi].dtype)
            dx = dxs[k]
            if tag == "x":
                off = 0
                for a_i, a in enumerate(
                        xs_flat[xoffs[bi]:xoffs[bi] + meta]):
                    da = dx[:, off:off + a.shape[1]].astype(a.dtype)
                    j = xoffs[bi] + a_i
                    dxs_flat[j] = da if dxs_flat[j] is None \
                        else dxs_flat[j] + da
                    off += -(-a.shape[1] // blk) * blk
            elif tag == "panel":
                for s, (pi, c) in enumerate(meta):
                    dpanels[pi] = _add_block(
                        dpanels[pi],
                        dx[:m, s * blk:(s + 1) * blk], 0, c * blk)
            else:
                tapvjp, rcs = vjps[k]
                gblocks = tapvjp(dx)[0]
                for rc, gb in zip(rcs, gblocks):
                    pp, pcb = ringmap[rc]
                    gpanels[pp] = _add_block(
                        gpanels[pp], gb[:m], 0, pcb * blk)
    dxs_flat = tuple(jnp.zeros_like(a) if d is None else d
                     for a, d in zip(xs_flat, dxs_flat))
    return dxs_flat, tuple(dws), tuple(dbs), tuple(dpanels)


_chained_vjp.defvjp(_chained_fwd, _chained_bwd)

grouped_matmul_chained_ref = _gmm.grouped_matmul_chained_ref
chained_layout = _gmm.chained_layout

# ---------------------------------------------------------------------------
# per-expert ragged grouped GEMM: the MoE expert engine
# ---------------------------------------------------------------------------

def grouped_matmul_experts(xp, swp, w_in, w_out, w_gate, counts, *,
                           activation: str = "silu",
                           interpret: bool | None = None, bm: int):
    """Differentiable per-expert ragged expert stack in ONE launch per
    direction: forward fuses in/gate GEMMs, the activation, the out GEMM
    and the router combine-weight row scale; backward is ONE combined
    ``grouped_matmul_experts_bwd`` launch (dx + every dW) plus the dsw
    row reduction computed outside the kernel from the saved output.

    ``counts`` is a TRACED (E,) int32 of routed tokens per expert — it is
    a real custom_vjp operand (cotangent ``float0``) rather than a
    closure capture, so the vjp stays leak-free under ``jax.checkpoint``
    and ``scan``; ``w_gate=None`` flows through the pytree and comes back
    as a ``None`` cotangent, mirroring ``_grouped_bwd``'s optional-bias
    handling."""
    interpret = default_interpret() if interpret is None else interpret

    @jax.custom_vjp
    def run(xp, swp, w_in, w_out, w_gate, counts):
        return _gmm.grouped_matmul_experts(
            xp, swp, w_in, w_out, w_gate, counts,
            activation=activation, bm=bm, interpret=interpret)

    def run_fwd(xp, swp, w_in, w_out, w_gate, counts):
        y, hinp, gatep = _gmm.grouped_matmul_experts(
            xp, swp, w_in, w_out, w_gate, counts, activation=activation,
            train=True, bm=bm, interpret=interpret)
        return y, (xp, swp, w_in, w_out, w_gate, counts, y, hinp, gatep)

    def run_bwd(res, dy):
        xp, swp, w_in, w_out, w_gate, counts, y, hinp, gatep = res
        dy = dy.astype(xp.dtype)
        dyp = dy * swp[:, None].astype(dy.dtype)
        dx, dwin, dwgate, dwout = _gmm.grouped_matmul_experts_bwd(
            xp, dyp, w_in, w_out, w_gate, hinp, gatep, counts,
            activation=activation, bm=bm, interpret=interpret)
        # dsw_r = <dy_r, y_r/sw_r>: recover the unscaled row from the
        # saved output instead of a third kernel pass
        num = jnp.sum(dy.astype(jnp.float32) * y.astype(jnp.float32),
                      axis=-1)
        dsw = jnp.where(swp != 0, num / jnp.where(swp != 0, swp, 1.0),
                        0.0).astype(swp.dtype)
        dwin = dwin.astype(w_in.dtype)
        dwout = dwout.astype(w_out.dtype)
        if w_gate is not None:
            dwgate = dwgate.astype(w_gate.dtype)
        dcounts = np.zeros(counts.shape, jax.dtypes.float0)
        return dx, dsw, dwin, dwout, dwgate, dcounts

    run.defvjp(run_fwd, run_bwd)
    return run(xp, swp, w_in, w_out, w_gate, counts)


grouped_matmul_experts_ref = _gmm.grouped_matmul_experts_ref
moe_block_m = _gmm.moe_block_m
moe_static_blocks = _gmm.moe_static_blocks
expert_row_offsets = _gmm.expert_row_offsets
