"""Model FLOP utilisation of serving: the forward FLOPs of the valid images
of every dispatch, over the summed dispatch walls and the chip's bf16
peak.  Padding rows are not counted as work."""


def read(ctx):
    if ctx["kind"] != "serve" or not ctx["dispatch_walls_s"]:
        return None
    return 100.0 * ctx["model_flops"] / sum(ctx["dispatch_walls_s"]) \
        / ctx["peaks"]["flops_bf16"]
