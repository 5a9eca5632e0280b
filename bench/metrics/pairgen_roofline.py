"""Pass 2's pair generation against the HBM roofline, in percent.

The least traffic any implementation of pass 2 moves: every event read
once (code and date, 8 B) and every survivor written once (id and
duration, 12 B).  Divided by the peak HBM bandwidth, that is the least
time; the share is that over the device seconds of the pairgen kernel
(``kernels/tspm_pairgen``).  Bandwidth-bound: pair generation does no
floating-point work."""

PROGRAMS = ("pairgen",)


def read(ctx):
    if ctx.kind != "fit" or not ctx.units:
        return None
    s = ctx.device_seconds(PROGRAMS)
    if not s:
        return None
    w = ctx.work
    least = ctx.units * (w["events"] * 8 + w["survivors"] * 12)
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / s
