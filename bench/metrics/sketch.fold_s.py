"""Device seconds per traced tick of the online support sketch
(``stream/counts``): the fold (``sketch_update``: dedup, novelty against
the stored sets, bucket increments, the merge) and the row programs
around it (``sketch_rows`` reads the wave's sets, ``sketch_land`` writes
the merged sets back)."""

PROGRAMS = ("sketch_update", "sketch_rows", "sketch_land")


def read(ctx):
    if ctx.kind != "feed" or not ctx.units:
        return None
    s = ctx.device_seconds(PROGRAMS)
    return None if s is None else s / ctx.units
