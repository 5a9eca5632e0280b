"""Seconds a fit spends compacting pass 2 on the host: the program's
``fit.pass2.compact`` spans (boolean indexing of each fetched chunk to its
survivors) and ``fit.assemble`` (joining the chunks), over the traced
session's fits."""
import fit_telemetry


def read(ctx):
    return fit_telemetry.seconds_per_fit(ctx, "fit.pass2.compact",
                                         "fit.assemble")
