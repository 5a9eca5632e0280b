"""Whole batch fits back to back, each in a fresh ``MiningSession``, until
the survivors are on the host.  The traffic gives the cohort's patients;
the window's rate is the cohort's pairs times the completed fits over the
time to the end of the last fit."""
from __future__ import annotations

import time

import harness
import reference as ref


class Kind(harness.Cell):

    end_to_end = "fit_pairs_per_s"

    def setup(self) -> None:
        self.db = self.dbmart()
        self.mcfg = self.config(
            screen=self.cfg["screen"]["mode"],
            budget_bytes=harness.budget_bytes(
                self.cfg["deployment"]["budget_share"]))
        self.work = {"pairs": harness.total_pairs(self.nevents),
                     "events": int(self.nevents.sum())}
        self.frame = self.fit()      # warm: compiles every shape

    def fit(self):
        from repro.api import MiningSession
        self.frame = self.session = None   # one fit's state alive at a time
        self.session = MiningSession(self.mcfg)
        return self.session.fit(self.db)

    def window(self, seconds: float, units: int | None = None) -> dict:
        t0 = time.perf_counter()
        while True:
            self.frame = self.fit()
            self.units += 1
            elapsed = time.perf_counter() - t0
            if (units is not None and self.units >= units) or \
                    (units is None and elapsed >= seconds):
                break
        self.work["survivors"] = len(self.frame)
        return {self.end_to_end: self.work["pairs"] * self.units / elapsed}

    def verify(self) -> None:
        want, table = ref.corpus(self.phenx, self.date, self.nevents,
                                 self.radix, self.H, self.threshold,
                                 self.codec)
        # the support table, where it matters: a re-screen at twice the
        # fit threshold keeps exactly the rows whose bucket reaches it
        self.compare(self.frame, want, table, 2 * self.threshold,
                     "survivor_rows_diff", "rescreen_mask_diff")
