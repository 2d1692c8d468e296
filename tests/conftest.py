"""Shared fixtures.  NOTE: XLA_FLAGS / 512-device forcing is deliberately
NOT set here — smoke tests and benches see the real (1-device) host; only
launch/dryrun.py forces placeholder devices (per the assignment).

Also provides a guarded ``hypothesis`` import: test modules do

    from conftest import given, settings, st

and get the real hypothesis API when it is installed, or skip-stubs when it
is not — so every module collects (and its non-property tests run) on hosts
without hypothesis.
"""
import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st  # noqa: F401
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    def given(*_args, **_kwargs):
        """Stub @given: replace the property test with a zero-arg skipper
        (a plain function, so pytest never tries to resolve the strategy
        parameters as fixtures)."""
        def deco(fn):
            def _skipped():
                pytest.skip("hypothesis not installed")
            _skipped.__name__ = fn.__name__
            _skipped.__doc__ = fn.__doc__
            return _skipped
        return deco

    def settings(*_args, **_kwargs):
        def deco(fn):
            return fn
        return deco

    class _AnyStrategy:
        """st.integers(...), st.sampled_from(...), ... — decoration-time
        placeholders; the wrapped test is skipped before they are drawn."""

        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _AnyStrategy()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def tol_for(dtype):
    import jax.numpy as jnp
    return {"float32": dict(rtol=2e-3, atol=2e-3),
            "bfloat16": dict(rtol=5e-2, atol=5e-2)}[jnp.dtype(dtype).name]


def mixed_chain(dtype="float32", b=2, h=14, w=14, key=0):
    """A two-phase chained launch that draws on every lhs source:

      phase 0  an x branch (2 output n-blocks, writing ring columns 0 and
               1) and a panel branch reading both column blocks of a
               previous launch's 256-wide panel;
      phase 1  a 3x3 (2 n-blocks) and a 5x5 ring conv, each over ring
               columns (0, 1), and a panel branch (column blocks 1, 0).

    Returns (phases, x, panel, m): ``phases(x)`` builds the phase dicts
    around an x operand, so a sliced-input run shares the weights; the
    panel branches address ``panel`` by descriptor.
    """
    import jax.numpy as jnp
    from repro.core import plan as planlib

    dt = jnp.dtype(dtype)
    ks = iter(jax.random.split(jax.random.PRNGKey(key), 12))

    def rnd(shape, s):
        return (jax.random.normal(next(ks), shape) * s).astype(dt)

    m = b * h * w
    panel = jnp.maximum(rnd((m, 256), 1.0), 0)
    x = rnd((m, 96), 0.3)
    wx, bx = rnd((96, 200), 0.3), rnd((200,), 1.0)
    wp0, bp0 = rnd((256, 64), 0.3), rnd((64,), 1.0)
    w3, b3 = rnd((200 * 9, 136), 0.05), rnd((136,), 1.0)
    w5, b5 = rnd((200 * 25, 40), 0.03), rnd((40,), 1.0)
    wp1, bp1 = rnd((256, 72), 0.3), rnd((72,), 1.0)

    def phases(x):
        return [
            [{"n": 200, "w": planlib._pad_w_dense(wx, 128), "b": bx,
              "src": ("x", [x]), "ring_write": (0, 1)},
             {"n": 64, "w": planlib._pad_w_dense(wp0, 128), "b": bp0,
              "src": ("panel", [(0, 0), (0, 1)]), "ring_write": None}],
            [{"n": 136, "w": planlib._pack_w_ring(w3, 3, 3, 200, 2, 128),
              "b": b3, "src": ("ring", 3, 3, (0, 1)), "ring_write": None},
             {"n": 40, "w": planlib._pack_w_ring(w5, 5, 5, 200, 2, 128),
              "b": b5, "src": ("ring", 5, 5, (0, 1)), "ring_write": None},
             {"n": 72, "w": planlib._pad_w_dense(wp1, 128), "b": bp1,
              "src": ("panel", [(0, 1), (0, 0)]), "ring_write": None}],
        ]
    return phases, x, panel, m
