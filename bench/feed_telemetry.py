"""What the per-layer metrics of a feed cell read from the program's own
telemetry: the spans and counters the traced window's session recorded
(the feed kind resets them at the window's start, so they cover the
window's ticks alone).  Every reader returns None where telemetry was off
or the program records no such name, as a program from before those
names existed does."""
from __future__ import annotations


def _session(ctx):
    s = getattr(ctx.cell, "session", None)
    if s is None or not s.telemetry.enabled:
        return None
    return s


def seconds_per_tick(ctx, name: str) -> float | None:
    """Σ of the durations of the spans ``name`` over their count (one a
    tick); None without such a span."""
    s = _session(ctx)
    if s is None:
        return None
    spans = s.trace().find(name)
    if not spans:
        return None
    return sum(sp.duration_s for sp in spans) / len(spans)


def counters(ctx, *names: str) -> list | None:
    """The session's counters ``names``; None unless all of them exist."""
    s = _session(ctx)
    if s is None:
        return None
    snap = s.metrics()
    if not all(n in snap for n in names):
        return None
    return [snap[n] for n in names]
