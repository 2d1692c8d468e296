"""Tiled matmul Pallas kernels — the "algorithm zoo" for GEMM.

The paper's C3/C4: each op has multiple algorithms with different
time / workspace / resource profiles, and the fastest one is not always the
right one to co-schedule.  We expose three GEMM algorithms:

  mxu128     — 128x128x128 MXU-aligned tiling, fp32 VMEM accumulator,
               zero HBM workspace.  (cuDNN IMPLICIT_GEMM analogue.)
  large_tile — 256x256 output tiles: fewer grid steps / higher VMEM claim,
               zero HBM workspace.  (register-hungry PRECOMP_GEMM analogue:
               "exhausts the static resource".)
  ksplit     — split-K: the K dimension is partitioned across grid cells and
               partial products are written to an HBM workspace of
               ``splits * M * N * 4`` bytes, reduced afterwards.  Trades HBM
               workspace for parallelism on small-M GEMMs.  (FFT/PRECOMP-style
               "big workspace" analogue.)

All kernels require padded inputs (the ``ops.py`` wrappers pad); block sizes
keep the MXU matmul dims multiples of 128 and the accumulator in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def mxu_precision(dtype):
    """Contraction precision of a GEMM on the MXU, in a kernel or in XLA:
    f32 operands take the full-f32 contraction — the TPU default is one
    bf16 pass, which put the f32 googlenet's logits 5e-3 off the f32
    reference on a v5e — and bf16 operands, exact in one pass, keep the
    default."""
    return jax.lax.Precision.HIGHEST \
        if jnp.dtype(dtype) == jnp.float32 else None


def _mm_kernel(x_ref, y_ref, o_ref, acc_ref, *, nk: int):
    """Accumulating tiled matmul body shared by mxu128/large_tile."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], precision=mxu_precision(x_ref.dtype),
        preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_tiled(x, y, *, bm: int, bn: int, bk: int, interpret: bool = False):
    """Generic tiled matmul; x:(M,K) y:(K,N) padded to block multiples."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (x.shape, y.shape, (bm, bn, bk))
    nk = k // bk
    return pl.pallas_call(
        functools.partial(_mm_kernel, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, y)


def _ksplit_kernel(x_ref, y_ref, o_ref, acc_ref, *, nk: int):
    """Split-K partial-product kernel: grid (split, m, n, k_within_split).

    Each ``split`` writes its partial (bm, bn) product into its own slice of
    the (splits, M, N) HBM workspace output.
    """
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], precision=mxu_precision(x_ref.dtype),
        preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...]


def matmul_ksplit(x, y, *, bm: int, bn: int, bk: int, splits: int,
                  interpret: bool = False):
    """Split-K matmul: HBM workspace of (splits, M, N) fp32 partials."""
    m, k = x.shape
    _, n = y.shape
    assert k % (bk * splits) == 0, (k, bk, splits)
    nk = k // (bk * splits)  # k-blocks per split
    partials = pl.pallas_call(
        functools.partial(_ksplit_kernel, nk=nk),
        grid=(splits, m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((None, bm, bk),
                         lambda s, i, j, kk: (s, i, kk)),
            pl.BlockSpec((None, bk, bn),
                         lambda s, i, j, kk: (s, kk, j)),
        ],
        out_specs=pl.BlockSpec((None, bm, bn), lambda s, i, j, kk: (s, i, j)),
        out_shape=jax.ShapeDtypeStruct((splits, m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(
        x.reshape(m, splits, k // splits).transpose(1, 0, 2),
        y.reshape(splits, k // splits, n),
    )
    return partials.sum(axis=0).astype(x.dtype)


# ---------------------------------------------------------------------------
# Algorithm registry — names mirror the cuDNN-zoo framing of the paper.
# ---------------------------------------------------------------------------

def _alg_mxu128(x, y, interpret=False):
    return matmul_tiled(x, y, bm=128, bn=128, bk=128, interpret=interpret)


def _alg_large_tile(x, y, interpret=False):
    return matmul_tiled(x, y, bm=256, bn=256, bk=128, interpret=interpret)


def _alg_ksplit(x, y, interpret=False, splits: int = 4):
    # Largest split count <= requested that divides the K block count.
    nkb = x.shape[1] // 128
    while splits > 1 and nkb % splits:
        splits -= 1
    return matmul_ksplit(x, y, bm=128, bn=128, bk=128, splits=splits,
                         interpret=interpret)


MATMUL_ALGORITHMS = {
    "mxu128": _alg_mxu128,
    "large_tile": _alg_large_tile,
    "ksplit": _alg_ksplit,
}


def matmul_block_shape(algorithm: str) -> tuple[int, int, int]:
    return {"mxu128": (128, 128, 128),
            "large_tile": (256, 256, 128),
            "ksplit": (128, 128, 128)}[algorithm]


def matmul_workspace_bytes(algorithm: str, m: int, n: int, k: int,
                           splits: int = 4) -> int:
    """HBM workspace per algorithm — the paper's Table-2 quantity."""
    if algorithm == "ksplit":
        return splits * m * n * 4
    return 0


def matmul_vmem_bytes(algorithm: str, bytes_per_el: int = 2) -> int:
    """Static VMEM claim per grid cell — the SM-register/smem analogue."""
    bm, bn, bk = matmul_block_shape(algorithm)
    return bm * bk * bytes_per_el + bk * bn * bytes_per_el + bm * bn * 4
