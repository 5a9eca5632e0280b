"""What the per-layer metrics of a fit cell read from the program's own
telemetry: the spans and counters that the traced fit's session recorded
(``MiningSession.trace()`` / ``.metrics()``, on in a traced run).  Every
reader returns None where telemetry was off or the program records no
such name, as a program from before those names existed does."""
from __future__ import annotations


def _session(ctx):
    s = getattr(ctx.cell, "session", None)
    if s is None or not s.telemetry.enabled:
        return None
    return s


def seconds_per_fit(ctx, first: str, *more: str) -> float | None:
    """Σ of the durations of the spans named ``first`` and ``more``, over
    the session's fits (``session.fit`` spans); None without a ``first``."""
    s = _session(ctx)
    if s is None:
        return None
    tr = s.trace()
    fits = len(tr.find("session.fit"))
    if not fits or not tr.find(first):
        return None
    return sum(sp.duration_s for name in (first,) + more
               for sp in tr.find(name)) / fits


def counters(ctx, *names: str) -> list | None:
    """The session's counters ``names``; None unless all of them exist."""
    s = _session(ctx)
    if s is None:
        return None
    snap = s.metrics()
    if not all(n in snap for n in names):
        return None
    return [snap[n] for n in names]
