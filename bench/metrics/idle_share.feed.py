"""Share of the traced slice in which no operation ran on the device
(feed cells): 100 * (1 - busy / traced wall), busy the union of the
device's op intervals in the profiler trace."""


def read(ctx):
    if ctx.kind != "feed":
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.traced_s)
