"""Kernel launches (custom calls) per serving dispatch: those the trace
holds in the window, over the dispatches the window ran."""


def read(ctx):
    if ctx["kind"] != "serve" or not ctx["units"]:
        return None
    return ctx["trace"]["launches"] / ctx["units"]
