"""The delta kernel (``kernels/tspm_delta``) against the HBM roofline, in
percent.

The least traffic any delta miner moves: the history it pairs against
read once (code and date, 8 B an event: ``stream.history_events``, Σ
n_old + n_new over the slots), the new events read once (8 B each:
``stream.events``) and every new pair written once (id and duration,
12 B: ``stream.pairs``).  Divided by the peak HBM bandwidth, that is the
least time; the share is that over the device seconds of the kernel's
program (``delta_planes``).  Bandwidth-bound: no floating-point work."""
import feed_telemetry

PROGRAMS = ("delta_planes",)


def least_bytes(history_events: int, new_events: int, new_pairs: int) -> int:
    return 8 * history_events + 8 * new_events + 12 * new_pairs


def read(ctx):
    if ctx.kind != "feed":
        return None
    got = feed_telemetry.counters(ctx, "stream.history_events",
                                  "stream.events", "stream.pairs")
    s = ctx.device_seconds(PROGRAMS)
    if got is None or not s:
        return None
    return 100.0 * least_bytes(*got) / ctx.peaks["hbm_bytes_per_s"] / s
