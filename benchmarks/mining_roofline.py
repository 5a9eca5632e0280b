"""Mining-kernel roofline: arithmetic intensity + projected TPU throughput.

The pairgen kernel writes 17 bytes/pair (two int32 planes + int32 duration
+ bool mask) and performs ~6 integer VPU ops/pair — arithmetic intensity
~0.35 ops/byte, i.e. the mining pass is PURELY HBM-bandwidth-bound on TPU.
Projection: 819 GB/s / 17 B/pair ≈ 48 G pairs/s/chip — the measured CPU
number here is the correctness-validated baseline, the projection is what
the dry-run-tiled kernel targets.

The cost-model constants live in ``repro.analysis.roofline`` (single
source of truth — the fused-screen tile planner derives its block sizes
from the same numbers); the module-level aliases here are kept for
compat.  Beyond the classic materializing roofline this also prints the
fused memory model: bytes for the full [P, n, n] pair corpus vs the
corpus-free screen pass's peak (one patient block + the bucket table),
and the ``mining_tile_plan`` those constants choose.
"""
from __future__ import annotations

import time

import numpy as np

from repro.analysis import roofline
from repro.analysis.roofline import (
    FUSED_BLOCK_BYTES_PER_PAIR,
    MINING_BYTES_PER_PAIR as BYTES_PER_PAIR,
    MINING_OPS_PER_PAIR as OPS_PER_PAIR,
)
from repro.core import mining
from repro.data import synthea
from repro.data.dbmart import from_rows

HBM_BW = 819e9
PEAK_VPU = 197e12 / 2  # int ops conservatively at half bf16 MXU peak


def main():
    pid, date, xid, _ = synthea.generate_benchmark_rows(512, 96, seed=3)
    db = from_rows(pid.tolist(), date.tolist(),
                   [f"c{v}" for v in xid.tolist()])
    n_pairs = int(mining.count_sequences(db.nevents))

    # measured (CPU, jnp reference path)
    mined = mining.mine(db.phenx, db.date, db.nevents, backend="jnp")
    mined.seq.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(3):
        mined = mining.mine(db.phenx, db.date, db.nevents, backend="jnp")
        mined.seq.block_until_ready()
    dt = (time.perf_counter() - t0) / 3

    intensity = OPS_PER_PAIR / BYTES_PER_PAIR
    tpu_bound = min(HBM_BW / BYTES_PER_PAIR, PEAK_VPU / OPS_PER_PAIR)
    print("name,us_per_call,derived")
    print(f"mining_roofline/cpu_measured,{dt*1e6:.0f},"
          f"pairs_per_s={n_pairs/dt:.2e}")
    print(f"mining_roofline/arithmetic_intensity,,ops_per_byte="
          f"{intensity:.3f}")
    print(f"mining_roofline/tpu_projection,,pairs_per_s={tpu_bound:.2e};"
          f"bound=memory")

    # fused memory model: the corpus the materializing path holds vs the
    # peak of the corpus-free screen pass on the same cohort
    E = int(np.max(db.nevents))
    plan = roofline.mining_tile_plan(E, 20)
    corpus = int(np.sum(np.asarray(db.nevents, np.int64) ** 2)) \
        * FUSED_BLOCK_BYTES_PER_PAIR
    fused_peak = plan.block_patients * E * E * FUSED_BLOCK_BYTES_PER_PAIR \
        + (4 << 20)
    print(f"mining_roofline/fused_memory_model,,corpus={corpus};"
          f"fused_peak={fused_peak};ratio={corpus/max(fused_peak,1):.1f}x")
    print(f"mining_roofline/fused_tile_plan,,pb={plan.pb};ti={plan.ti};"
          f"tj={plan.tj};block={plan.block_patients};"
          f"vmem={plan.vmem_bytes};source={plan.source}")
    return {"pairs_per_s_cpu": n_pairs / dt, "tpu_bound": tpu_bound,
            "corpus_bytes": corpus, "fused_peak_bytes": fused_peak}


if __name__ == "__main__":
    main()
