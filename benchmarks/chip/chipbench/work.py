"""The work a configuration needs, counted from its shapes alone: FLOPs and
the least HBM bytes of each op of the forward.

The counts describe the model, not how the program runs it, so a share of
a roofline built on them reads the same work whatever merges, chains or
removes launches.  FLOPs count the multiply-adds of convolutions and the
classifier as 2 each, and only those whose input lies inside the image:
a tap that falls on the zero padding of a SAME convolution needs no work
(XLA's cost analysis counts the same way).  Pools, bias, ReLU and the
average pool count bytes only.  Bytes are each op's inputs, weights and output read or written once
at the configuration's dtype: the least any implementation moves.
"""
from __future__ import annotations

import dataclasses

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


@dataclasses.dataclass(frozen=True)
class OpWork:
    name: str
    flops: float
    bytes: float

    def ideal_s(self, peaks: dict) -> float:
        return max(self.flops / peaks["flops_bf16"],
                   self.bytes / peaks["hbm_bytes_per_s"])


def _same(n: int, s: int) -> int:
    return -(-n // s)


def valid_taps(n: int, k: int, s: int) -> int:
    """(output, tap) pairs along one axis of a SAME convolution whose input
    index lies inside [0, n)."""
    out = _same(n, s)
    lo = max((out - 1) * s + k - n, 0) // 2
    return sum(1 for o in range(out) for t in range(k)
               if 0 <= o * s + t - lo < n)


def forward_ops(sizes: dict, batch: int) -> list[OpWork]:
    """Every op of the inception network ``sizes`` describes, at ``batch``
    images, in execution order."""
    e = DTYPE_BYTES[sizes["dtype"]]
    h, w, c = sizes["img"]
    ops: list[OpWork] = []

    def conv(name, h, w, cin, k, cout, s):
        oh, ow = _same(h, s), _same(w, s)
        x, wt, y = batch * h * w * cin, k * k * cin * cout + cout, \
            batch * oh * ow * cout
        taps = valid_taps(h, k, s) * valid_taps(w, k, s)
        ops.append(OpWork(name, 2.0 * batch * taps * cin * cout,
                          e * (x + wt + y)))
        return oh, ow

    def pool(name, h, w, c, s):
        oh, ow = _same(h, s), _same(w, s)
        x, y = batch * h * w * c, batch * oh * ow * c
        ops.append(OpWork(name, 0.0, e * (x + y)))
        return oh, ow

    for i, (k, cout, s) in enumerate(sizes["stem"]):
        h, w = conv(f"stem{i}", h, w, c, k, cout, s)
        c = cout
    for i, (n1, r3, n3, r5, n5, pp) in enumerate(sizes["modules"]):
        nm = f"inc{i}"
        if i in sizes["pool_between"]:
            h, w = pool(f"{nm}/pool", h, w, c, 2)
        conv(f"{nm}/1x1", h, w, c, 1, n1, 1)
        conv(f"{nm}/r3", h, w, c, 1, r3, 1)
        conv(f"{nm}/3x3", h, w, r3, 3, n3, 1)
        conv(f"{nm}/r5", h, w, c, 1, r5, 1)
        conv(f"{nm}/5x5", h, w, r5, 5, n5, 1)
        pool(f"{nm}/pppool", h, w, c, 1)
        conv(f"{nm}/pp", h, w, c, 1, pp, 1)
        c = n1 + n3 + n5 + pp
    x, y = batch * h * w * c, batch * c
    ops.append(OpWork("gap", 0.0, e * (x + y)))
    k = sizes["num_classes"]
    x, wt, y = batch * c, c * k + k, batch * k
    ops.append(OpWork("head", 2.0 * batch * c * k, e * (x + wt + y)))
    return ops


def forward_flops(sizes: dict, batch: int) -> float:
    return sum(op.flops for op in forward_ops(sizes, batch))


def ideal_s(ops, peaks: dict) -> float:
    """The least time the chip could take for ``ops``, op by op."""
    return sum(op.ideal_s(peaks) for op in ops)


def plan_ops(ops) -> list[OpWork]:
    """The work of the plan's kernels: the convolutions of the stem and the
    modules, not the pools, the average pool or the classifier."""
    return [op for op in ops if op.flops > 0
            and op.name.startswith(("stem", "inc"))]
