"""Cohort generators, kept with the benchmark.

Every cohort is made from ``--seed`` alone.  The events per patient are
fixed by the configuration and the cell's patients: one per stratum of
the count distribution (``lengths``), in one fixed shuffled order.  The
order is the same for every seed because the program chunks patients in order and pads each
chunk to its longest history, so a seed that moved the long histories
would change the padded work (a v5e measured 389k to 497k pairs/s across
seeds of a heavy-tailed cohort that way).  Every seed thus mines the
same pairs at the same shapes; the seed draws the codes and the dates.

A cohort is returned as plain numpy arrays in the padded patient-major
layout the mining program takes: ``phenx[P, E]``, ``date[P, E]`` (each row
sorted by date, padded with its last date) and ``nevents[P]``.
"""
from __future__ import annotations

import numpy as np
from scipy import special, stats

PAD_MULTIPLE = 8
#: the one order of the histories' lengths, whatever the run's seed
LENGTH_ORDER_SEED = 0


def lengths(cfg: dict, n_patients: int) -> np.ndarray:
    """Events per patient, one for each of ``n_patients`` equal-probability
    strata of the configuration's count distribution, in ascending order.

    Poisson counts (a light tail) take each stratum's midpoint quantile
    (i + 0.5)/N.  Lognormal counts take each stratum's conditional mean, so
    the cut keeps E[n]; the spread inside the strata, which a midpoint or a
    mean drops and which lies mostly in the tail, is put back into the two
    longest histories, solved so that the cut keeps E[n^2] (and so the
    pairs per patient) of the whole distribution too."""
    ev = cfg["events"]
    if ev["distribution"] == "poisson":
        q = (np.arange(n_patients) + 0.5) / n_patients
        n = stats.poisson.ppf(q, ev["mean"])
    elif ev["distribution"] == "lognormal":
        n = lognormal_strata(ev["mu"], ev["sigma"], n_patients)
    else:
        raise ValueError(f"unknown event distribution {ev['distribution']!r}")
    return np.maximum(np.round(n), ev.get("min", 2)).astype(np.int64)


def lognormal_strata(mu: float, sigma: float, n: int) -> np.ndarray:
    """``n`` values with the mean and the second moment of
    lognormal(mu, sigma): the conditional means of ``n`` equal-probability
    strata, the two largest replaced by the pair that restores both, in
    ascending order.  Too few strata leave no positive pair that does;
    that is an error."""
    m1 = np.exp(mu + sigma ** 2 / 2)
    m2 = np.exp(2 * mu + 2 * sigma ** 2)
    z = special.ndtri(np.arange(n + 1) / n)
    # E[X; a < Z < b] = m1 (Phi(b - sigma) - Phi(a - sigma))
    cond = m1 * (special.ndtr(z[1:] - sigma) - special.ndtr(z[:-1] - sigma)) * n
    low = cond[:-2]
    s1 = n * m1 - low.sum()                 # the top two: a + b = s1
    s2 = n * m2 - np.sum(low ** 2)          #              a^2 + b^2 = s2
    d = np.sqrt(2 * s2 - s1 ** 2)
    out = np.sort(np.concatenate([low, [(s1 - d) / 2, (s1 + d) / 2]]))
    if n < 2 or not out[0] > 0:
        raise ValueError(f"{n} strata cannot keep both moments of "
                         f"lognormal({mu}, {sigma})")
    return out


def generate(cfg: dict, n_patients: int, seed: int):
    """(phenx, date, nevents) for ``n_patients`` of configuration ``cfg``."""
    n = np.random.default_rng(LENGTH_ORDER_SEED).permutation(
        lengths(cfg, n_patients))
    rng = np.random.default_rng(seed)
    codes, days = cfg["codes"], cfg["days"]
    # Zipf-like code popularity (p ~ 1/rank), as EHR code frequencies are
    ranks = np.arange(1, codes["n"] + 1, dtype=np.float64)
    p = ranks ** -codes["zipf_s"]
    p /= p.sum()
    total = int(n.sum())
    xid = rng.choice(codes["n"], total, p=p).astype(np.int32)
    day = rng.integers(0, days, total, dtype=np.int32)
    pid = np.repeat(np.arange(n_patients), n)
    order = np.lexsort((np.arange(total), day, pid))  # stable by date
    xid, day = xid[order], day[order]
    E = -(-int(n.max()) // PAD_MULTIPLE) * PAD_MULTIPLE
    starts = np.concatenate([[0], np.cumsum(n)[:-1]])
    col = np.arange(total) - np.repeat(starts, n)
    phenx = np.zeros((n_patients, E), np.int32)
    date = np.zeros((n_patients, E), np.int32)
    phenx[pid, col] = xid
    date[pid, col] = day
    last = date[np.arange(n_patients), n - 1]
    pad = np.arange(E)[None, :] >= n[:, None]
    date = np.where(pad, last[:, None], date)
    return phenx, date, n.astype(np.int32)
