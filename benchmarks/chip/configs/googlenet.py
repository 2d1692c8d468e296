"""Plain reference of the inception network that ``googlenet.json`` states:
weights from the seed and the forward, in straightforward ``jax.numpy`` at
float32 with every contraction at the precision the caller names:
"highest", the configuration's own, or "high", the control's: three bf16
passes (what ``Precision.HIGH`` is on a TPU), spelled out here so that it
computes alike on any backend.

The reference (``make_params``, ``logits``) imports nothing of the program
under test.  The weights it makes are the benchmark's, laid out as the
program takes them, and the harness hands the same function's output to
the program.  Beside it stand the network's two other faces that the
harness needs (``chipbench/spec.py``): ``program_config``, the program's
config built through its public API, and ``forward_ops``, the work of the
forward op by op.
"""
from __future__ import annotations

import functools
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
from chipbench import work

SIZES = json.loads(pathlib.Path(__file__).with_suffix(".json").read_text())


def seed_words(seed: int) -> np.ndarray:
    """Two 32-bit words from a seed of any size."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def _conv_param(key, k, cin, cout):
    kw, kb = jax.random.split(key)
    std = (2.0 / (k * k * cin)) ** 0.5          # He: keeps activations O(1)
    return {"w": jax.random.normal(kw, (k, k, cin, cout)) * std,
            "b": jax.random.normal(kb, (cout,)) * 0.01}


def init_params(sizes: dict, words):
    """The network's weights from two traced uint32 words; jit it once and
    every seed reuses the program."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), words[0]),
                             words[1])
    keys = iter(jax.random.split(key, 2 + len(sizes["stem"])
                                 + 6 * len(sizes["modules"])))
    c = sizes["img"][2]
    params = {"stem": [], "modules": []}
    for k, cout, _s in sizes["stem"]:
        params["stem"].append(_conv_param(next(keys), k, c, cout))
        c = cout
    for n1, r3, n3, r5, n5, pp in sizes["modules"]:
        params["modules"].append({
            "b1": _conv_param(next(keys), 1, c, n1),
            "r3": _conv_param(next(keys), 1, c, r3),
            "b3": _conv_param(next(keys), 3, r3, n3),
            "r5": _conv_param(next(keys), 1, c, r5),
            "b5": _conv_param(next(keys), 5, r5, n5),
            "pp": _conv_param(next(keys), 1, c, pp)})
        c = n1 + n3 + n5 + pp
    kw, kb = jax.random.split(next(keys))
    n = sizes["num_classes"]
    params["head"] = {"w": jax.random.normal(kw, (c, n)) * c ** -0.5,
                      "b": jax.random.normal(kb, (n,)) * 0.01}
    return params


@functools.lru_cache(maxsize=None)
def _jitted_init(sizes_json: str):
    sizes = json.loads(sizes_json)
    return jax.jit(lambda words: init_params(sizes, words))


def make_params(sizes: dict, seed: int):
    """The weights for ``seed``, made on the device in one jitted call."""
    return _jitted_init(json.dumps(sizes, sort_keys=True))(
        jnp.asarray(seed_words(seed)))


def _split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _three_pass(f, a, b):
    """A bilinear ``f`` in three bf16 passes: each operand split into a
    bf16 high part and a bf16 remainder, the low-by-low product dropped.
    Each product of bf16 values is exact at ``highest``."""
    ah, al = _split(a)
    bh, bl = _split(b)
    return f(ah, bh) + f(ah, bl) + f(al, bh)


def _contract(f, a, b, precision: str):
    """``f(a, b, precision)``, a convolution or a product, at
    ``precision``."""
    if precision == "high":
        return _three_pass(lambda x, y: f(x, y, "highest"), a, b)
    return f(a, b, precision)


def _conv(x, p, stride, precision):
    def conv(a, w, prec):
        return jax.lax.conv_general_dilated(
            a, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec)
    return jax.nn.relu(_contract(conv, x, p["w"], precision) + p["b"])


def _maxpool(x, stride):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, stride, stride, 1), "SAME")


def forward(params, sizes: dict, images, precision: str):
    """images (B, H, W, C) -> logits (B, classes).  The stem's convs run
    back to back with no pool; a 3x3/2 max-pool precedes each module in
    ``pool_between``; each module is the four branches of Szegedy et al.
    (1x1; 1x1 then 3x3; 1x1 then 5x5; 3x3/1 max-pool then 1x1),
    concatenated; then a global average pool and the classifier."""
    x = images
    for p, (_k, _c, s) in zip(params["stem"], sizes["stem"]):
        x = _conv(x, p, s, precision)
    for i, p in enumerate(params["modules"]):
        if i in sizes["pool_between"]:
            x = _maxpool(x, 2)
        b1 = _conv(x, p["b1"], 1, precision)
        b3 = _conv(_conv(x, p["r3"], 1, precision), p["b3"], 1, precision)
        b5 = _conv(_conv(x, p["r5"], 1, precision), p["b5"], 1, precision)
        pp = _conv(_maxpool(x, 1), p["pp"], 1, precision)
        x = jnp.concatenate([b1, b3, b5, pp], axis=-1)
    x = x.mean(axis=(1, 2))
    return _contract(lambda a, w, prec: jnp.dot(a, w, precision=prec),
                     x, params["head"]["w"], precision) + params["head"]["b"]


@functools.lru_cache(maxsize=None)
def _jitted_forward(sizes_json: str, precision: str):
    sizes = json.loads(sizes_json)
    return jax.jit(lambda p, x: forward(p, sizes, x, precision))


def logits(params, sizes: dict, images: np.ndarray, precision: str,
           block: int = 8) -> np.ndarray:
    """Reference logits of ``images``, ``block`` images at a time (the last
    block padded with zeros), so that it fits beside nothing else."""
    fn = _jitted_forward(json.dumps(sizes, sort_keys=True), precision)
    out = []
    for i in range(0, len(images), block):
        x = images[i:i + block]
        n = len(x)
        if n < block:
            x = np.concatenate([x, np.zeros((block - n,) + x.shape[1:],
                                            x.dtype)])
        out.append(np.asarray(fn(params, jnp.asarray(x)))[:n])
    return np.concatenate(out)


def program_config(sizes: dict):
    """The program's ``CNNConfig`` for an inception configuration file."""
    from repro.models.cnn import CNNConfig, InceptionSpec
    return CNNConfig(
        name=sizes["name"], img=tuple(sizes["img"]),
        stem=tuple(tuple(s) for s in sizes["stem"]),
        modules=tuple(InceptionSpec(*m) for m in sizes["modules"]),
        pool_between=tuple(sizes["pool_between"]),
        num_classes=sizes["num_classes"])


def forward_ops(sizes: dict, batch: int) -> list[work.OpWork]:
    """Every op of ``forward`` at ``batch`` images, in execution order.
    The plan's kernels run the stem's and the modules' convolutions."""
    dtype = sizes["dtype"]
    ops: list[work.OpWork] = []

    def conv(name, x, cout, k, s=1):
        op, y = work.conv(name, x, cout, k, k, s, dtype=dtype, kernel=True)
        ops.append(op)
        return y

    def maxpool(name, x, s):
        op, y = work.pool(name, x, 3, s, "SAME", "max", dtype=dtype)
        ops.append(op)
        return y

    x = (batch, *sizes["img"])
    for i, (k, cout, s) in enumerate(sizes["stem"]):
        x = conv(f"stem{i}", x, cout, k, s)
    for i, (n1, r3, n3, r5, n5, pp) in enumerate(sizes["modules"]):
        nm = f"inc{i}"
        if i in sizes["pool_between"]:
            x = maxpool(f"{nm}/pool", x, 2)
        conv(f"{nm}/1x1", x, n1, 1)
        conv(f"{nm}/3x3", conv(f"{nm}/r3", x, r3, 1), n3, 3)
        conv(f"{nm}/5x5", conv(f"{nm}/r5", x, r5, 1), n5, 5)
        conv(f"{nm}/pp", maxpool(f"{nm}/pppool", x, 1), pp, 1)
        x = x[:3] + (n1 + n3 + n5 + pp,)
    op, x = work.pool("gap", x, x[1], 1, "VALID", "avg", dtype=dtype)
    ops.append(op)
    # the classifier: a 1x1 convolution over the pooled 1x1 image
    ops.append(work.conv("head", x, sizes["num_classes"], 1, 1, dtype=dtype,
                         kernel=False)[0])
    return ops
