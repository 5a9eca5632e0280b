"""Seconds a fit spends copying pass 2's planes device to host: the sum of
the program's ``fit.pass2.fetch`` spans (keep, id and duration of every
pair slot a chunk computed) over the traced session's fits."""
import fit_telemetry


def read(ctx):
    return fit_telemetry.seconds_per_fit(ctx, "fit.pass2.fetch")
