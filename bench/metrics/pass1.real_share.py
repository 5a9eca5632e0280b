"""Share of the pair slots pass 1 computes that are real pairs, in
percent: the program's ``fit.pairs`` over ``fit.pass1.slots`` (each
patient block's padded [P, E, E] planes).  Padding every history to the
longest lowers it; bucketing histories by length raises it."""
import fit_telemetry


def read(ctx):
    got = fit_telemetry.counters(ctx, "fit.pairs", "fit.pass1.slots")
    if got is None or not got[1]:
        return None
    return 100.0 * got[0] / got[1]
