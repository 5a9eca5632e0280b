"""Three-term roofline from the compiled dry-run artifact.

  compute    = HLO_FLOPs        / (chips * 197e12 FLOP/s bf16)
  memory     = HLO_bytes        / (chips * 819e9  B/s HBM)
  collective = collective_bytes / (chips * 50e9   B/s per ICI link)

cost_analysis() provides FLOPs/bytes; collective bytes come from parsing
the post-SPMD optimized HLO (compiled.as_text()) and summing operand sizes
of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops.  MODEL_FLOPS (6*N*D train, 2*N*D inference; active
params for MoE) over HLO FLOPs measures useful-compute fraction.

This module also owns the *mining* cost model (constants shared with
``benchmarks/mining_roofline.py``) and :func:`mining_tile_plan`, the tile
selection the fused mine+screen kernel (``kernels/tspm_fused``) reads its
defaults from — an 8-patient block by default, the fastest VMEM-fitting
autotune row when ``benchmarks/mining_fused.py`` hands its rows back in.
"""
from __future__ import annotations

import dataclasses
import re

PEAK_FLOPS = 197e12      # TPU v5e bf16 per chip
HBM_BW = 819e9           # bytes/s per chip
ICI_BW = 50e9            # bytes/s per link

# --- mining cost model (tSPM+ pair enumeration) -----------------------------
# materializing pairgen traffic: two int32 phenx planes + int32 duration +
# bool mask + amortized id pack in the XLA consumer
MINING_BYTES_PER_PAIR = 17
MINING_OPS_PER_PAIR = 6      # shift/or pack, sub, 3 compares for the mask
# dense block working set on the corpus-free jnp fallback (mine_dense +
# row-sort dedup): mirrors chunking.BYTES_PER_PAIR — 8B seq + 4B dur +
# 1B mask, x2 sort scratch
FUSED_BLOCK_BYTES_PER_PAIR = 26
VMEM_BYTES = 16 << 20        # TPU v5e per-core VMEM


@dataclasses.dataclass(frozen=True)
class MiningTilePlan:
    """Tile choice for the fused mine+screen kernel (kernels/tspm_fused).

    ``pb x ti x tj`` is the pair-tile grid shared with tspm_pairgen /
    tspm_delta (the kernel keeps the whole [2^H] bucket table in VMEM);
    ``block_patients`` the host-loop patient block bounding the
    corpus-free counting pass's working set."""

    pb: int
    ti: int
    tj: int
    block_patients: int
    vmem_bytes: int          # modeled per-grid-step VMEM working set
    source: str              # 'analytic' | 'measured'


def fused_kernel_vmem(pb: int, ti: int, tj: int, n_buckets_log2: int,
                      max_events: int) -> int:
    """Modeled VMEM bytes of one fused-kernel grid step.

    Rows (full-width phenx for the dedup lookback), the i/j row tiles, the
    [Pb, T, E] dedup compare scratch (32-bit masks), the pair-tile
    hash/flag planes plus their int32 scratch copy, the bf16 one-hot
    planes of one 8-row histogram matmul, and the int32 table with its
    f32 accumulator.
    """
    e = max(ti, -(-max(int(max_events), 1) // ti) * ti)
    rows = pb * e * 4                     # full phenx row block
    tiles = pb * (ti + tj) * 4            # xi / xj row tiles
    dedup = pb * (ti + tj) * e * 4        # eq_i / eq_j masks
    pairs = pb * ti * tj * (4 + 4 + 1 + 4)  # hash, iota masks, flags, scratch
    table_rows = max(8, (1 << n_buckets_log2) // 128)
    hist = (table_rows + 128) * 8 * tj * 2  # one-hot planes (bf16)
    table = table_rows * 128 * (4 + 4)    # int32 table + f32 accumulator
    return int(rows + tiles + dedup + pairs + hist + table)


def mining_tile_plan(max_events: int, n_buckets_log2: int, *,
                     vmem_bytes: int = VMEM_BYTES // 2,
                     block_bytes: int = 64 << 20,
                     rows: list[dict] | None = None) -> MiningTilePlan:
    """Pick (pb, ti, tj, block_patients) for the fused kernel.

    Analytic mode: lane-native ``ti = tj = 128`` (matching the ops-layer
    padding), ``pb = 8`` (one sublane tile of patients), and a
    counting-pass patient block sized so the jnp-fallback dense planes
    stay under ``block_bytes`` at ``FUSED_BLOCK_BYTES_PER_PAIR``.

    Measured mode: ``rows`` are autotune sweep records (dicts with ``pb``
    and ``wall_s``, optionally ``ti``/``tj``, from
    ``benchmarks/mining_fused.py``); the fastest row that still fits
    ``vmem_bytes`` wins, falling back to the analytic choice when none fit.
    """
    ti = tj = 128
    chosen = None
    source = "analytic"
    if rows:
        fitting = [r for r in rows
                   if fused_kernel_vmem(int(r["pb"]), int(r.get("ti", ti)),
                                        int(r.get("tj", tj)), n_buckets_log2,
                                        max_events)
                   <= vmem_bytes]
        if fitting:
            best = min(fitting, key=lambda r: float(r["wall_s"]))
            chosen = (int(best["pb"]), int(best.get("ti", ti)),
                      int(best.get("tj", tj)))
            source = "measured"
    if chosen is None:
        # one 8-row sublane tile of patients: Mosaic unrolls the pair-tile
        # math per patient row, and its compile time grows faster than
        # linearly in pb (v5e compile: ~18 s at pb=8, ~66 s at pb=16)
        chosen = (8, ti, tj)
    pb, ti, tj = chosen
    e = max(ti, -(-max(int(max_events), 1) // ti) * ti)
    blk = max(pb, int(block_bytes // max(e * e * FUSED_BLOCK_BYTES_PER_PAIR, 1)))
    blk = min(-(-blk // pb) * pb, 4096)
    return MiningTilePlan(pb=pb, ti=ti, tj=tj, block_patients=blk,
                          vmem_bytes=fused_kernel_vmem(pb, ti, tj,
                                                       n_buckets_log2,
                                                       max_events),
                          source=source)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# optimized HLO: `%name = <shape|tuple> <kind>[-start](%operand_refs), ...`
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\)|[\w\[\],{}]+))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_NAME_RE = re.compile(r"%([\w.\-]+)")
_WHILE_RE = re.compile(r"\bwhile\(")
_WHILE_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


def shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _line_collective(line: str):
    """(kind, operand_bytes_per_device) for a collective op line, or None.

    Shapes in partitioned HLO are per-device; operand size is inferred from
    the output shape and the replica-group size:
      all-reduce / all-to-all / collective-permute: operand == output
      all-gather:     operand = output / group   (gathers g shards)
      reduce-scatter: operand = output * group
    """
    m = _OP_RE.search(line)
    if not m:
        return None
    out_shapes, kind = m.group(1), m.group(2)
    total = 0
    for sm in _SHAPE_RE.finditer(out_shapes):
        if sm.group(1) in _DTYPE_BYTES:
            total += shape_bytes(sm.group(1), sm.group(2))
    g = 1
    gm = _GROUPS_RE.search(line)
    if gm:
        g = int(gm.group(2))
    else:
        gm = _GROUPS_EXPL_RE.search(line)
        if gm:
            g = len(gm.group(1).split(","))
    if kind == "all-gather" and g:
        total //= g
    elif kind == "reduce-scatter":
        total *= g
    return kind, total


def _computations(hlo_text: str):
    """Split optimized HLO text into (name -> list of op lines) using brace
    depth — headers can wrap across lines, so regexes on single lines miss
    them."""
    comps: dict[str, list[str]] = {}
    depth = 0
    header: list[str] = []
    current = None
    for line in hlo_text.splitlines():
        opens, closes = line.count("{"), line.count("}")
        if depth == 0:
            header.append(line)
            if opens > closes:  # computation body starts
                m = _NAME_RE.search(" ".join(header))
                current = m.group(1) if m else f"anon{len(comps)}"
                comps[current] = []
                header = []
        else:
            if current is not None:
                comps[current].append(line)
        depth += opens - closes
        if depth == 0:
            current = None
            header = []
    return comps


def collective_bytes(hlo_text: str) -> dict:
    """Per-kind collective operand bytes (per device), EXACT loop scaling:
    XLA prints each while body once but annotates known_trip_count; we
    build the while-nesting graph and multiply collectives inside a body by
    the product of trip counts up the nesting chain."""
    comps = _computations(hlo_text)
    parent: dict[str, str] = {}
    trips: dict[str, int] = {}
    for cname, lines in comps.items():
        for line in lines:
            if _WHILE_RE.search(line):
                bm = _WHILE_BODY_RE.search(line)
                if not bm:
                    continue
                body = bm.group(1)
                tm = _TRIP_RE.search(line)
                parent[body] = cname
                trips[body] = int(tm.group(1)) if tm else 1

    def multiplier(cname: str) -> int:
        mult = 1
        seen = set()
        while cname in parent and cname not in seen:
            seen.add(cname)
            mult *= trips.get(cname, 1)
            cname = parent[cname]
        return mult

    out = {k: 0 for k in _COLLECTIVES}
    for cname, lines in comps.items():
        mult = multiplier(cname)
        for line in lines:
            got = _line_collective(line)
            if got is None:
                continue
            kind, nbytes = got
            out[kind] += nbytes * mult
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: float
    coll_breakdown: dict
    model_flops: float
    bytes_per_device: float | None = None

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / (self.chips * ICI_BW)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / max(self.hlo_flops, 1.0)

    @property
    def roofline_fraction(self) -> float:
        """model-useful compute time over the achievable step time
        (max of the three terms = the bound the step cannot beat)."""
        bound = max(self.t_compute, self.t_memory, self.t_collective)
        useful = self.model_flops / (self.chips * PEAK_FLOPS)
        return useful / max(bound, 1e-12)

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "chips": self.chips,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "coll_bytes": self.coll_bytes,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective, "dominant": self.dominant,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
            "coll_breakdown": self.coll_breakdown,
            "bytes_per_device": self.bytes_per_device,
        }


def count_params(cfg) -> tuple[int, int]:
    """(total, active) parameter counts via eval_shape (no allocation)."""
    import jax

    from repro.models import model as model_lib

    mdl = model_lib.build(cfg)
    shapes = jax.eval_shape(lambda: mdl.init(jax.random.PRNGKey(0))[0])
    total = sum(int(l.size) for l in jax.tree.leaves(shapes))
    active = total
    if cfg.n_experts:
        expert = 3 * cfg.d_model * cfg.moe_d_ff  # gate/up/down per expert
        n_moe_layers = cfg.n_layers // cfg.moe_interleave
        routed_all = n_moe_layers * cfg.n_experts * expert
        routed_active = n_moe_layers * cfg.experts_per_token * expert
        active = total - routed_all + routed_active
    return total, active


def model_flops(cfg, shape, active_params: int, embed_params: int = 0) -> float:
    """6*N*D for training; 2*N*D for prefill; 2*N*B for one decode step."""
    n = active_params - embed_params
    if shape.kind == "train":
        return 6.0 * n * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * n * shape.seq_len * shape.global_batch
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def format_table(rows: list[dict]) -> str:
    hdr = ("| arch | shape | t_compute | t_memory | t_coll | dominant | "
           "useful | roofline-frac |")
    sep = "|" + "---|" * 8
    lines = [hdr, sep]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']:.3e} | "
            f"{r['t_memory_s']:.3e} | {r['t_collective_s']:.3e} | "
            f"{r['dominant']} | {r['useful_ratio']:.2f} | "
            f"{r['roofline_fraction']:.3f} |")
    return "\n".join(lines)
