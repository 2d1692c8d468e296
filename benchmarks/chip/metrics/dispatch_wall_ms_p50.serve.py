"""Median host-clock time of one dispatch: from the call of the bucket's
cached executable (with the images' transfer) until its logits are
ready."""
import statistics


def read(ctx):
    if ctx["kind"] != "serve" or not ctx["dispatch_walls_s"]:
        return None
    return 1e3 * statistics.median(ctx["dispatch_walls_s"])
