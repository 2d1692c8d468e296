"""Share of the traced serving window in which no operation ran on the
device: 1 - (union of the device ops' intervals) / window.  Arrival gaps
count as idle: they are what a server at this load sees."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
