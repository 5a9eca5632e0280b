"""Bytes pass 2 copies device to host per real pair: the program's
``fit.pass2.fetch_bytes`` counter over ``fit.pairs`` (the cohort's
Σ n(n-1)/2).  Compacting on the device brings it toward 12 B per
survivor; padding raises it."""
import fit_telemetry


def read(ctx):
    got = fit_telemetry.counters(ctx, "fit.pass2.fetch_bytes", "fit.pairs")
    if got is None or not got[1]:
        return None
    return got[0] / got[1]
