"""The work of a forward pass, counted from its shapes alone: FLOPs and the
least HBM bytes of each op.

A configuration's module lists its ops (``configs/<name>.py:forward_ops``)
with the helpers here; this file knows no network.  The counts describe the
model, not how the program runs it, so a share of a roofline built on them
reads the same work whatever merges, chains or removes launches.  FLOPs
count the multiply-adds of convolutions (a classifier is a 1x1 convolution
over a 1x1 image) as 2 each, and only those whose input lies inside the
image: a tap that falls on the zero padding of a SAME convolution needs no
work (XLA's cost analysis counts the same way).  Pools, bias and ReLU count
bytes only.  Bytes are each op's inputs, weights and output read or written
once at the configuration's dtype: the least any implementation moves.
"""
from __future__ import annotations

import dataclasses

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}
PADDINGS = ("SAME", "VALID")


@dataclasses.dataclass(frozen=True)
class OpWork:
    name: str
    flops: float
    bytes: float
    kernel: bool = False        # the plan's kernels run this op

    def ideal_s(self, peaks: dict) -> float:
        return max(self.flops / peaks["flops_bf16"],
                   self.bytes / peaks["hbm_bytes_per_s"])


def out_size(n: int, k: int, s: int, padding: str) -> int:
    """Outputs along one axis of length ``n`` for a window ``k``, stride
    ``s``."""
    if padding == "SAME":
        return -(-n // s)
    if padding == "VALID":
        return (n - k) // s + 1
    raise ValueError(f"padding {padding!r} is not one of {PADDINGS}")


def valid_taps(n: int, k: int, s: int, padding: str) -> int:
    """(output, tap) pairs along one axis whose input index lies inside
    [0, n).  VALID puts every tap inside; SAME pads the low side by half
    the padding, rounded down, as XLA does."""
    out = out_size(n, k, s, padding)
    if padding == "VALID":
        return out * k
    lo = max((out - 1) * s + k - n, 0) // 2
    return sum(1 for o in range(out) for t in range(k)
               if 0 <= o * s + t - lo < n)


def conv(name: str, x: tuple, cout: int, kh: int, kw: int, stride: int = 1,
         padding: str = "SAME", *, dtype: str,
         kernel: bool) -> tuple[OpWork, tuple]:
    """A ``kh`` x ``kw`` convolution with bias of the NHWC shape ``x`` to
    ``cout`` channels; returns its work and its output's shape."""
    b, h, w, cin = x
    oh, ow = out_size(h, kh, stride, padding), out_size(w, kw, stride,
                                                         padding)
    taps = valid_taps(h, kh, stride, padding) * valid_taps(w, kw, stride,
                                                           padding)
    n_in, n_wt, n_out = b * h * w * cin, kh * kw * cin * cout + cout, \
        b * oh * ow * cout
    op = OpWork(name, 2.0 * b * taps * cin * cout,
                DTYPE_BYTES[dtype] * (n_in + n_wt + n_out), kernel)
    return op, (b, oh, ow, cout)


def pool(name: str, x: tuple, k: int, stride: int, padding: str, kind: str,
         *, dtype: str) -> tuple[OpWork, tuple]:
    """A ``k`` x ``k`` pool of the NHWC shape ``x``, ``kind`` "max" or
    "avg": bytes only, alike for both.  Returns its work and its output's
    shape."""
    if kind not in ("max", "avg"):
        raise ValueError(f"pool kind {kind!r} is not 'max' or 'avg'")
    b, h, w, c = x
    oh, ow = out_size(h, k, stride, padding), out_size(w, k, stride, padding)
    op = OpWork(name, 0.0, DTYPE_BYTES[dtype] * (b * h * w * c
                                                 + b * oh * ow * c))
    return op, (b, oh, ow, c)


def forward_flops(ops) -> float:
    return sum(op.flops for op in ops)


def ideal_s(ops, peaks: dict) -> float:
    """The least time the chip could take for ``ops``, op by op."""
    return sum(op.ideal_s(peaks) for op in ops)


def plan_ops(ops) -> list[OpWork]:
    """The work of the plan's kernels: the ops marked ``kernel``."""
    return [op for op in ops if op.kernel]
