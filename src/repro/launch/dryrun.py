import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell the step function (train_step for train shapes, serve_step
for prefill/decode shapes) is jitted with explicit NamedShardings for
params / optimizer state / batch / caches, lowered against
ShapeDtypeStructs (no allocation), compiled for the production mesh, and
the compiled artifact's memory_analysis / cost_analysis / collective bytes
are recorded to JSON for EXPERIMENTS.md §Dry-run and §Roofline.

Usage:
  python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/]
"""
import argparse   # noqa: E402
import json       # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402

import jax        # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.analysis import costmodel                         # noqa: E402
from repro.analysis import roofline as rl                    # noqa: E402
from repro.configs import ARCHS, get_config                  # noqa: E402
from repro.configs.base import SHAPES, shape_applicable      # noqa: E402
from repro.distributed.sharding import axis_rules, param_shardings  # noqa: E402
from repro.launch import shardings as sh                     # noqa: E402
from repro.launch import specs                               # noqa: E402
from repro.launch.mesh import make_production_mesh           # noqa: E402
from repro.models import model as model_lib                  # noqa: E402
from repro.training import optimizer as opt_lib              # noqa: E402
from repro.training import train_loop                        # noqa: E402

ASSIGNED = [a for a in ARCHS if a != "tspm-mlho"]


def _abstract_state(mdl):
    def make():
        params, _ = mdl.init(jax.random.PRNGKey(0))
        return train_loop.TrainState(params, opt_lib.init(params))

    return jax.eval_shape(make)


def _parse_overrides(sets: list[str] | None) -> dict:
    out = {}
    for kv in sets or []:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        if v in ("true", "True"):
            v = True
        if v in ("false", "False"):
            v = False
        out[k] = v
    return out


def lower_cell(arch: str, shape_name: str, mesh, overrides: dict | None = None,
               microbatches: int = 1):
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    mdl = model_lib.build(cfg)
    params_struct, pspecs = model_lib.abstract_init(mdl)

    from repro.distributed.sharding import default_rules

    rules = default_rules(mesh)
    if not cfg.tp_internals:  # pure wide-DP: batch over every axis
        rules["batch"] = sh.batch_axes_of(mesh, cfg)
    if cfg.sp_residual:
        rules["seq_res"] = "model"
    with axis_rules(mesh, rules):
        p_shard = param_shardings(mesh, pspecs, params_struct)
        if shape.kind == "train":
            state_struct = _abstract_state(mdl)
            state_shard = train_loop.TrainState(
                p_shard, opt_lib.OptState(
                    p_shard, p_shard,
                    jax.sharding.NamedSharding(
                        mesh, jax.sharding.PartitionSpec())))
            batch_struct = specs.train_batch(cfg, shape)
            batch_shard = sh.to_shardings(
                mesh, sh.batch_pspecs(cfg, batch_struct, mesh), batch_struct)
            step = train_loop.make_train_step(
                mdl, opt_lib.OptConfig(), microbatches=microbatches)
            jitted = jax.jit(step,
                             in_shardings=(state_shard, batch_shard),
                             out_shardings=(state_shard, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_struct, batch_struct)
        else:
            cache_struct = specs.cache_specs(cfg, shape, mdl)
            cache_shard = sh.to_shardings(
                mesh, sh.cache_pspecs(cfg, cache_struct, mesh), cache_struct)
            if shape.kind == "prefill":
                batch_struct = specs.train_batch(cfg, shape)
                batch_struct.pop("labels")
                batch_struct.pop("loss_mask")
            else:
                batch_struct = specs.decode_batch(cfg, shape)
            batch_shard = sh.to_shardings(
                mesh, sh.batch_pspecs(cfg, batch_struct, mesh), batch_struct)

            def serve_step(params, batch, caches):
                mode = "prefill" if shape.kind == "prefill" else "decode"
                return mdl.apply(params, batch, mode=mode, caches=caches)

            jitted = jax.jit(serve_step,
                             in_shardings=(p_shard, batch_shard, cache_shard),
                             out_shardings=(None, cache_shard),
                             donate_argnums=(2,))
            lowered = jitted.lower(params_struct, batch_struct, cache_struct)
    return lowered, cfg, shape


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             skip_existing=False, overrides: dict | None = None,
             microbatches: int = 1, tag: str = "") -> dict:
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    name = f"{arch}__{shape_name}__{mesh_tag}" + (f"__{tag}" if tag else "")
    path = os.path.join(out_dir, name + ".json")
    if skip_existing and os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
           "tag": tag, "overrides": overrides or {},
           "microbatches": microbatches}
    if not shape_applicable(cfg, shape):
        rec["status"] = "skipped-by-rule"
        rec["reason"] = "full-attention arch: long_500k requires " \
                        "sub-quadratic sequence mixing (DESIGN.md)"
        _write(path, rec)
        return rec
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        lowered, cfg, shape = lower_cell(arch, shape_name, mesh, overrides,
                                         microbatches)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        coll = rl.collective_bytes(hlo)   # per-device, trip-scaled (exact)
        chips = mesh.devices.size
        total, active = rl.count_params(cfg)
        embed = cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2)
        # FLOPs/bytes: analytic model (XLA cost_analysis counts while
        # bodies once — see analysis/costmodel.py + its validation test);
        # raw cost_analysis kept alongside for transparency.
        flops = costmodel.step_flops(cfg, shape)
        hbm_bytes = costmodel.step_bytes(cfg, shape, active)
        roof = rl.Roofline(
            arch=arch, shape=shape_name, chips=chips,
            hlo_flops=flops,
            hlo_bytes=hbm_bytes,
            coll_bytes=float(sum(coll.values())) * chips,
            coll_breakdown=coll,
            model_flops=rl.model_flops(cfg, shape, active, embed),
            bytes_per_device=getattr(mem, "temp_size_in_bytes", None),
        )
        rec.update(status="ok", t_lower_s=t_lower, t_compile_s=t_compile,
                   params_total=total, params_active=active,
                   memory_analysis=_mem_dict(mem), roofline=roof.row(),
                   raw_cost_analysis={k: float(v) for k, v in cost.items()
                                      if isinstance(v, (int, float))},
                   hlo_bytes_len=len(hlo))
    except Exception as e:  # a failing cell is a bug; record it loudly
        rec.update(status="FAILED", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    _write(path, rec)
    return rec


def _mem_dict(mem):
    keys = ("temp_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(mem, k)) for k in keys if hasattr(mem, k)}


def _write(path, rec):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (perf variants)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--tag", default="", help="variant tag for the record")
    args = ap.parse_args()

    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.all or args.both_meshes) \
        else [args.multi_pod]
    overrides = _parse_overrides(args.set)

    n_fail = 0
    for multi_pod in meshes:
        for arch in archs:
            for shape_name in shapes:
                rec = run_cell(arch, shape_name, multi_pod, args.out,
                               args.skip_existing, overrides,
                               args.microbatches, args.tag)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" dom={r['dominant']} "
                             f"frac={r['roofline_fraction']:.3f} "
                             f"compile={rec['t_compile_s']:.0f}s")
                if status == "FAILED":
                    n_fail += 1
                    extra = " " + rec["error"][:160]
                print(f"[{rec['mesh']}] {arch} x {shape_name}: "
                      f"{status}{extra}", flush=True)
    print(f"dry-run complete; failures: {n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
