"""Host seconds per tick of the stream tick's collect (``stream/service``):
the mean of the program's ``tick.collect`` spans, from the end of the
tick's device work to the end of its bookkeeping (the sketch's land, the
slab copies to the host, the corpus log)."""
import feed_telemetry


def read(ctx):
    if ctx.kind != "feed":
        return None
    return feed_telemetry.seconds_per_tick(ctx, "tick.collect")
