"""Conv2D algorithm zoo — the paper's core op, adapted to TPU.

Faithful analogue of the cuDNN algorithm table the paper profiles
(Sec. 2 / Tables 1 & 2).  Each algorithm has a distinct (time, HBM workspace,
arithmetic-intensity) profile, which is what the selector reasons about:

  im2col_gemm — materializes the (N*OH*OW, KH*KW*C) patch matrix in HBM
                (workspace = the full im2col buffer), then a single
                MXU-aligned Pallas GEMM.  Compute-bound, big workspace.
                (cuDNN GEMM / PRECOMP_GEMM analogue.)
  direct      — zero-workspace Pallas kernel: the padded input stays in HBM,
                each grid cell loads an input window into VMEM and iterates
                the KH*KW taps with channel-dim GEMMs.  More HBM traffic per
                FLOP -> memory-bound.  (IMPLICIT_GEMM / DIRECT analogue.)
  winograd3x3 — F(2x2, 3x3): 2.25x fewer MXU FLOPs, moderate workspace for
                the 16 transformed-domain GEMMs, which are *independent
                branches* executed with the stacked ``branch_matmul`` kernel.
                Only for 3x3/stride-1.  (WINOGRAD_NONFUSED analogue; its
                16 pointwise GEMMs are themselves an inter-op parallelism
                instance.)

Layouts: x (N, H, W, C), w (KH, KW, C, K), NHWC out.  Channels last keeps the
GEMM contraction on the TPU lane dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.matmul import matmul_tiled, mxu_precision
from repro.kernels.branch_matmul import branch_matmul


def _out_size(h: int, kh: int, stride: int, padding: str) -> int:
    if padding == "SAME":
        return -(-h // stride)
    return (h - kh) // stride + 1


def _pad_amount(h: int, kh: int, stride: int, padding: str) -> tuple[int, int]:
    if padding == "VALID":
        return (0, 0)
    oh = -(-h // stride)
    total = max((oh - 1) * stride + kh - h, 0)
    return (total // 2, total - total // 2)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# im2col + GEMM
# ---------------------------------------------------------------------------

def conv2d_im2col_gemm(x, w, *, stride: int = 1, padding: str = "SAME",
                       interpret: bool = False):
    n, h, wd, c = x.shape
    kh, kw, c2, k = w.shape
    assert c == c2
    oh = _out_size(h, kh, stride, padding)
    ow = _out_size(wd, kw, stride, padding)
    # HBM workspace: the full patch matrix (the paper's Table-2 quantity).
    patches = jax.lax.conv_general_dilated_patches(
        x, filter_shape=(kh, kw), window_strides=(stride, stride),
        padding=padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )  # (N, OH, OW, C*KH*KW), feature dim ordered (C, KH, KW)
    m = n * oh * ow
    kk = c * kh * kw
    lhs = patches.reshape(m, kk)
    rhs = w.transpose(2, 0, 1, 3).reshape(kk, k)  # (C,KH,KW,K) -> (CKK, K)
    # Pad to MXU-aligned blocks.
    bm, bn, bk = 128, 128, 128
    mp, kp, np_ = _round_up(m, bm), _round_up(kk, bk), _round_up(k, bn)
    lhs = jnp.pad(lhs, ((0, mp - m), (0, kp - kk)))
    rhs = jnp.pad(rhs, ((0, kp - kk), (0, np_ - k)))
    out = matmul_tiled(lhs, rhs, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :k].reshape(n, oh, ow, k)


def conv2d_im2col_workspace_bytes(x_shape, w_shape, stride=1, padding="SAME",
                                  bytes_per_el: int = 2) -> int:
    n, h, wd, c = x_shape
    kh, kw, _, _ = w_shape
    oh = _out_size(h, kh, stride, padding)
    ow = _out_size(wd, kw, stride, padding)
    return n * oh * ow * c * kh * kw * bytes_per_el


# ---------------------------------------------------------------------------
# direct (zero HBM workspace)
# ---------------------------------------------------------------------------

def _direct_kernel(x_ref, w_ref, o_ref, *, kh, kw, stride, oh, ow, bh):
    """One grid cell: one image, ``bh`` output rows, all output channels.

    x_ref: (1, bh*stride + kh - 1, W_pad, C) input window (VMEM)
    w_ref: (KH, KW, C, K)
    o_ref: (1, bh, OW, K)
    """
    x = x_ref[0]
    c = x.shape[-1]
    k = w_ref.shape[-1]
    acc = jnp.zeros((bh * ow, k), jnp.float32)
    for i in range(kh):            # static unroll over filter taps
        for j in range(kw):
            # rows i, i+stride, ...; cols j, j+stride, ...
            window = jax.lax.slice(
                x, (i, j, 0), (i + (bh - 1) * stride + 1,
                               j + (ow - 1) * stride + 1, c),
                (stride, stride, 1))            # (bh, ow, C)
            acc += jnp.dot(window.reshape(bh * ow, c),
                           w_ref[i, j],
                           precision=mxu_precision(window.dtype),
                           preferred_element_type=jnp.float32)
    o_ref[0] = acc.reshape(bh, ow, k).astype(o_ref.dtype)


def conv2d_direct(x, w, *, stride: int = 1, padding: str = "SAME",
                  block_rows: int = 8, interpret: bool = False):
    n, h, wd, c = x.shape
    kh, kw, _, k = w.shape
    oh = _out_size(h, kh, stride, padding)
    ow = _out_size(wd, kw, stride, padding)
    ph, pw = _pad_amount(h, kh, stride, padding), _pad_amount(wd, kw, stride, padding)
    xp = jnp.pad(x, ((0, 0), ph, pw, (0, 0)))
    bh = min(block_rows, oh)
    n_row_blocks = -(-oh // bh)
    # Pad rows so oh divides evenly into blocks of bh.
    oh_pad = n_row_blocks * bh
    extra_in_rows = (oh_pad - 1) * stride + kh - xp.shape[1]
    if extra_in_rows > 0:
        xp = jnp.pad(xp, ((0, 0), (0, extra_in_rows), (0, 0), (0, 0)))
    in_rows_per_block = (bh - 1) * stride + kh
    # Overlapping row blocks -> express via stride-bh index map on a
    # pre-sliced view: materialize overlapping row windows with XLA gather.
    starts = np.arange(n_row_blocks) * bh * stride
    xwin = jnp.stack([
        jax.lax.dynamic_slice_in_dim(xp, int(s), in_rows_per_block, axis=1)
        for s in starts
    ], axis=1)  # (N, n_row_blocks, in_rows_per_block, W_pad, C)

    out = pl.pallas_call(
        functools.partial(_direct_kernel, kh=kh, kw=kw, stride=stride,
                          oh=oh, ow=ow, bh=bh),
        grid=(n, n_row_blocks),
        in_specs=[
            pl.BlockSpec((1, None, in_rows_per_block, xp.shape[2], c),
                         lambda b, r: (b, r, 0, 0, 0)),
            pl.BlockSpec((kh, kw, c, k), lambda b, r: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, None, bh, ow, k), lambda b, r: (b, r, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n_row_blocks, bh, ow, k), x.dtype),
        interpret=interpret,
    )(xwin, w)
    return out.reshape(n, oh_pad, ow, k)[:, :oh]


# ---------------------------------------------------------------------------
# Winograd F(2x2, 3x3)
# ---------------------------------------------------------------------------

_BT = np.array([[1, 0, -1, 0],
                [0, 1, 1, 0],
                [0, -1, 1, 0],
                [0, 1, 0, -1]], np.float32)
_G = np.array([[1, 0, 0],
               [0.5, 0.5, 0.5],
               [0.5, -0.5, 0.5],
               [0, 0, 1]], np.float32)
_AT = np.array([[1, 1, 1, 0],
                [0, 1, -1, -1]], np.float32)


def conv2d_winograd3x3(x, w, *, stride: int = 1, padding: str = "SAME",
                       interpret: bool = False):
    """F(2x2,3x3) Winograd; requires kh=kw=3, stride=1."""
    n, h, wd, c = x.shape
    kh, kw, _, k = w.shape
    assert (kh, kw) == (3, 3) and stride == 1, "winograd3x3 needs 3x3/s1"
    oh = _out_size(h, 3, 1, padding)
    ow = _out_size(wd, 3, 1, padding)
    ph, pw = _pad_amount(h, 3, 1, padding), _pad_amount(wd, 3, 1, padding)
    # Tile grid of 4x4 input tiles with stride 2 producing 2x2 outputs.
    th, tw = -(-oh // 2), -(-ow // 2)
    need_h, need_w = 2 * th + 2, 2 * tw + 2
    xp = jnp.pad(x, ((0, 0),
                     (ph[0], max(need_h - h - ph[0], 0)),
                     (pw[0], max(need_w - wd - pw[0], 0)),
                     (0, 0)))
    # Extract 4x4 tiles: (N, th, tw, 4, 4, C)
    idx_h = (np.arange(th) * 2)[:, None] + np.arange(4)[None, :]
    idx_w = (np.arange(tw) * 2)[:, None] + np.arange(4)[None, :]
    tiles = xp[:, idx_h][:, :, :, idx_w]          # (N, th, 4, tw, 4, C)
    tiles = tiles.transpose(0, 1, 3, 2, 4, 5)     # (N, th, tw, 4, 4, C)
    bt = jnp.asarray(_BT, x.dtype)
    g = jnp.asarray(_G, x.dtype)
    at = jnp.asarray(_AT, x.dtype)
    # Input transform: B^T d B  -> (N, th, tw, 4, 4, C)
    v = jnp.einsum("ij,nxyjkc,kl->nxyilc", bt, tiles, bt.T)
    # Filter transform: G g G^T -> (4, 4, C, K)
    u = jnp.einsum("ij,jkco,kl->ilco", g, w.astype(x.dtype), g.T)
    # 16 independent transformed-domain GEMMs -> stacked branch kernel.
    t = n * th * tw
    v16 = v.transpose(3, 4, 0, 1, 2, 5).reshape(16, t, c)
    u16 = u.reshape(16, c, k)
    bm, bn, bk = 128, 128, 128
    tp, cp, kp = _round_up(t, bm), _round_up(c, bk), _round_up(k, bn)
    v16 = jnp.pad(v16, ((0, 0), (0, tp - t), (0, cp - c)))
    u16 = jnp.pad(u16, ((0, 0), (0, cp - c), (0, kp - k)))
    m16 = branch_matmul(v16, u16, interpret=interpret)[:, :t, :k]
    m = m16.reshape(4, 4, n, th, tw, k)
    # Inverse transform: A^T m A -> (N, th, tw, 2, 2, K)
    y = jnp.einsum("ij,jkntwo,kl->ntwilo", at.astype(m.dtype), m, at.T.astype(m.dtype))
    y = y.transpose(0, 1, 3, 2, 4, 5).reshape(n, 2 * th, 2 * tw, k)
    return y[:, :oh, :ow].astype(x.dtype)


def conv2d_winograd_workspace_bytes(x_shape, w_shape, padding="SAME",
                                    bytes_per_el: int = 2) -> int:
    n, h, wd, c = x_shape
    _, _, _, k = w_shape
    oh = _out_size(h, 3, 1, padding)
    ow = _out_size(wd, 3, 1, padding)
    t = n * -(-oh // 2) * -(-ow // 2)
    return 16 * (t * c + c * k + t * k) * bytes_per_el


CONV2D_ALGORITHMS = {
    "im2col_gemm": conv2d_im2col_gemm,
    "direct": conv2d_direct,
    "winograd3x3": conv2d_winograd3x3,
}


def conv2d_workspace_bytes(algorithm: str, x_shape, w_shape, stride=1,
                           padding="SAME", bytes_per_el: int = 2) -> int:
    if algorithm == "im2col_gemm":
        return conv2d_im2col_workspace_bytes(x_shape, w_shape, stride, padding,
                                             bytes_per_el)
    if algorithm == "winograd3x3":
        return conv2d_winograd_workspace_bytes(x_shape, w_shape, padding,
                                               bytes_per_el)
    return 0  # direct
