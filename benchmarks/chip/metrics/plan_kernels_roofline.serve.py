"""Share of the roofline of the planned ops in serving: the least time the
chip could take for the ops the configuration's ``forward_ops`` marks
``kernel``, on the valid images of the window's dispatches (``work.py``),
over the device time of every kernel (Pallas custom call) the trace names
by its ``plan[mode:op]`` scope."""


def read(ctx):
    if ctx["kind"] != "serve" or not ctx["trace"]["plan_kernels_s"]:
        return None
    return 100.0 * ctx["plan_ideal_s"] / ctx["trace"]["plan_kernels_s"]
