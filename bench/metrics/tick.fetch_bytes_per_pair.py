"""Bytes the stream tick copies device to host per new pair: the
program's ``stream.tick.fetch_bytes`` counter (the padded mask, id and
duration slabs) over ``stream.pairs`` (Σ d·n_old + d(d-1)/2)."""
import feed_telemetry


def read(ctx):
    if ctx.kind != "feed":
        return None
    got = feed_telemetry.counters(ctx, "stream.tick.fetch_bytes",
                                  "stream.pairs")
    if got is None or not got[1]:
        return None
    return got[0] / got[1]
