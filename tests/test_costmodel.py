"""Validate the analytic FLOP model against fully-unrolled compiled HLO.

With runtime_flags.UNROLL_SCANS every lax.scan unrolls, so XLA's cost
analysis counts every executed op — ground truth the analytic model must
match (tolerance covers elementwise ops the model ignores).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import costmodel
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.launch import specs
from repro.models import model as model_lib
from repro.models import runtime_flags
from repro.training import train_loop


@pytest.fixture
def unrolled():
    runtime_flags.UNROLL_SCANS = True
    yield
    runtime_flags.UNROLL_SCANS = False


def _hlo_flops(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis() or {}
    return float(cost.get("flops", 0.0))


FAMILIES = ["tspm-mlho", "gemma2-2b", "deepseek-moe-16b", "xlstm-125m",
            "zamba2-2.7b", "seamless-m4t-large-v2", "pixtral-12b"]


@pytest.mark.parametrize("arch", FAMILIES)
def test_train_flops_model_matches_unrolled_hlo(arch, unrolled):
    cfg = get_config(arch, reduced=True).replace(remat="none",
                                                 capacity_factor=1.25)
    mdl = model_lib.build(cfg)
    params, _ = mdl.init(jax.random.PRNGKey(0))
    shape = ShapeConfig("t", 64, 2, "train")
    batch = specs.train_batch(cfg, shape, concrete=True)
    loss_fn = train_loop.make_loss_fn(mdl, z_coef=0.0)

    got = _hlo_flops(
        lambda p, b: jax.value_and_grad(lambda q: loss_fn(q, b)[0])(p),
        params, batch)
    want = costmodel.step_flops(cfg, shape)
    ratio = got / want
    assert 0.75 < ratio < 1.45, (arch, got, want, ratio)


@pytest.mark.parametrize("arch", ["gemma2-2b", "xlstm-125m", "zamba2-2.7b"])
def test_decode_flops_model(arch, unrolled):
    cfg = get_config(arch, reduced=True)
    mdl = model_lib.build(cfg)
    params, _ = mdl.init(jax.random.PRNGKey(0))
    shape = ShapeConfig("d", 32, 2, "decode")
    caches = mdl.init_caches(2, 32)
    tok = jnp.zeros((2, 1), jnp.int32)

    got = _hlo_flops(
        lambda p, c: mdl.apply(p, {"tokens": tok}, mode="decode", caches=c),
        params, caches)
    want = costmodel.step_flops(cfg, shape)
    ratio = got / want
    assert 0.5 < ratio < 2.0, (arch, got, want, ratio)


def test_flops_scale_linearly_in_depth():
    cfg = get_config("tspm-mlho", reduced=True)
    s1 = costmodel.step_flops(cfg.replace(n_layers=2),
                              ShapeConfig("t", 128, 4, "train"))
    s2 = costmodel.step_flops(cfg.replace(n_layers=4),
                              ShapeConfig("t", 128, 4, "train"))
    per_layer = s2 - s1
    s3 = costmodel.step_flops(cfg.replace(n_layers=6),
                              ShapeConfig("t", 128, 4, "train"))
    assert abs((s3 - s2) - per_layer) / per_layer < 1e-6


def test_bytes_model_orders():
    """Train touches optimizer state; decode is weight-dominated."""
    cfg = get_config("gemma2-2b")
    _, active = __import__("repro.analysis.roofline",
                           fromlist=["count_params"]).count_params(cfg)
    train = costmodel.step_bytes(cfg, ShapeConfig("t", 4096, 256, "train"),
                                 active)
    decode = costmodel.step_bytes(cfg, ShapeConfig("d", 32768, 128, "decode"),
                                  active)
    assert train > active * 20          # adam states dominate
    assert decode > active * 2          # weights read once per token
