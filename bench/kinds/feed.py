"""A resident corpus kept live under an encounter feed, through
``MiningSession``'s streaming service.

Set-up submits each patient's first ceil(n/2) events as one delta and
drains them (``session.run()``), then runs the feed's first ``warm_ticks``
ticks untimed, so the window's slab shapes are compiled.  The feed is the
rest of every history, cut chronologically into ``deltas_per_patient``
near-equal deltas and submitted in the order of each delta's first date
(ties by patient, then by index).  The loop is closed and saturated:
before every tick the queue is topped up to ``queue_deltas`` deltas, then
the service ticks (``session.service.tick()``, the call
``MiningSession.run`` makes).  The window ends after the first completed
tick at or past its seconds, or when the feed runs dry, and publishes one
frame (``session.frame()``) inside the timed window.

The rate is the pairs the window's ticks made visible, Σ d·n_old +
d(d-1)/2 over its slots, over the time from the window's start to the
end of the published frame.  A patient's visible events are those
submitted less those the service still queues; the checks compare the
frame and the sketch table with the reference over exactly those
prefixes.
"""
from __future__ import annotations

import time

import numpy as np

import harness
import reference as ref


def feed_order(date, first, nevents, per_patient: int):
    """The feed's deltas as (patient, start, stop) arrays in feed order:
    each patient's events ``first[p]:nevents[p]`` cut into
    ``per_patient`` near-equal chronological deltas, ordered by the
    delta's first date, then patient, then index."""
    pat, idx, start, stop = [], [], [], []
    for p, (a, b) in enumerate(zip(first, nevents)):
        cuts = np.linspace(a, b, per_patient + 1).round().astype(np.int64)
        for i in range(per_patient):
            if cuts[i + 1] > cuts[i]:
                pat.append(p)
                idx.append(i)
                start.append(cuts[i])
                stop.append(cuts[i + 1])
    pat, idx = np.asarray(pat), np.asarray(idx)
    start, stop = np.asarray(start), np.asarray(stop)
    order = np.lexsort((idx, pat, date[pat, start]))
    return pat[order], start[order], stop[order]


def visible_pairs(n) -> int:
    n = np.asarray(n, np.int64)
    return int(np.sum(n * (n - 1) // 2))


class Kind(harness.Cell):

    end_to_end = "fit_pairs_per_s"

    def setup(self) -> None:
        from repro.api import MiningSession
        t, dep = self.traffic, self.cfg["deployment"]
        n = np.asarray(self.nevents, np.int64)
        first = -(-n // 2)
        self.feed = feed_order(self.date, first, n,
                               int(t["deltas_per_patient"]))
        self.next = 0
        self.queue_deltas = int(t["queue_deltas"])
        self.submitted = np.zeros(self.n_patients, np.int64)
        self.session = MiningSession(self.config(
            screen=self.cfg["screen"]["mode"],
            tick_patients=int(dep["tick_patients"]),
            max_slot_events=int(dep["max_slot_events"])))
        for p in range(self.n_patients):
            self.submit(p, 0, int(first[p]))
        self.session.run()
        for _ in range(int(t["warm_ticks"])):
            self.tick()
        self.visible = self.visible_events()
        self.work = {"resident_pairs": visible_pairs(self.visible),
                     "feed_deltas": len(self.feed[0]),
                     "feed_pairs": visible_pairs(n)
                     - visible_pairs(self.visible)}

    def submit(self, p: int, a: int, b: int) -> None:
        self.session.submit(p, self.date[p, a:b], self.phenx[p, a:b])
        self.submitted[p] = b

    def tick(self) -> bool:
        """Top the queue up in feed order, then tick; False once the feed
        and the queue are both empty."""
        svc = self.session.service
        pat, start, stop = self.feed
        while len(svc.queue) < self.queue_deltas and self.next < len(pat):
            i = self.next
            self.submit(int(pat[i]), int(start[i]), int(stop[i]))
            self.next += 1
        return svc.tick() is not None

    def visible_events(self) -> np.ndarray:
        """Events per patient that completed ticks ingested: submitted
        less still queued."""
        queued = np.zeros(self.n_patients, np.int64)
        for d in self.session.service.queue:
            queued[d.key] += len(d.dates)
        return self.submitted - queued

    def window(self, seconds: float, units: int | None = None) -> dict:
        # the window's spans and counters only, where telemetry is on
        self.session.telemetry.reset()
        before = visible_pairs(self.visible)
        t0 = time.perf_counter()
        while self.tick():
            self.units += 1
            if units is not None and self.units >= units:
                break
            if units is None and time.perf_counter() - t0 >= seconds:
                break
        self.frame = self.session.frame()
        elapsed = time.perf_counter() - t0
        self.visible = self.visible_events()
        pairs = visible_pairs(self.visible) - before
        self.work.update(pairs=pairs, ticks=self.units,
                         deltas_left=len(self.feed[0]) - self.next,
                         visible_events=int(self.visible.sum()),
                         survivors=len(self.frame))
        return {self.end_to_end: pairs / elapsed}

    def verify(self) -> None:
        want, table = ref.corpus(self.phenx, self.date, self.visible,
                                 self.radix, self.H, self.threshold,
                                 self.codec)
        self.compare(self.frame, want, table, 2 * self.threshold,
                     "survivor_rows_diff", "rescreen_mask_diff")
        got = np.asarray(self.session.service.sketch.counts)
        self.check("sketch_table_diff",
                   int(np.count_nonzero(got != table))
                   if got.shape == table.shape
                   else int(max(got.size, table.size)), 0)
