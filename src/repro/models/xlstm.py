"""xLSTM LM: mLSTM (matrix memory, chunked-parallel) + sLSTM blocks.

mLSTM uses the shared chunked scalar-decay recurrence (ssm_common) with the
xLSTM normalizer h = (q C) / max(|q n|, 1); gates are bounded
(sigmoid input / sigmoid forget) instead of exponential-with-stabilizer —
DESIGN.md §9 records the deviation.  O(1)-state decode => long_500k runs.

sLSTM is inherently sequential (the xLSTM paper says so) and is evaluated
with lax.scan over time, with per-head block-diagonal recurrent weights and
the stabilized exponential-gate formulation.

d_ff = 0 per the assignment: blocks carry their own expansion
(ssm_expand) and gating; there is no separate FFN.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import constrain, current_rules, fsdp_axis_for
from repro.models import layers, ssm_common
from repro.models.layers import linear, linear_init, rmsnorm
from repro.models import runtime_flags


def _dims(cfg):
    di = cfg.d_model * cfg.ssm_expand
    return di, cfg.n_heads, di // cfg.n_heads


# --- mLSTM block -------------------------------------------------------------
def mlstm_init(rng, cfg, fsdp_axis):
    d = cfg.d_model
    di, h, dh = _dims(cfg)
    r = jax.random.split(rng, 6)
    dtype = layers.dt(cfg)
    # tp_internals=False: pure DP/FSDP — a 125M model over-distributed on a
    # 16-way TP axis spends everything on per-chunk state all-reduces
    # (EXPERIMENTS.md §Perf iteration 2)
    tp = "model" if cfg.tp_internals else None
    p, s = {}, {}
    p["ln"], s["ln"] = layers.rmsnorm_init(d, dtype)
    for i, nm in enumerate(("wq", "wk", "wv", "wz")):
        p[nm], s[nm] = linear_init(r[i], d, di, dtype, P(fsdp_axis, tp))
    p["wg"], s["wg"] = linear_init(r[4], d, 2 * h, dtype, P(fsdp_axis, tp))
    p["wo"], s["wo"] = linear_init(r[5], di, d, dtype, P(tp, fsdp_axis))
    p["hn"], s["hn"] = layers.rmsnorm_init(di, dtype)
    return p, s


def _mlstm_qkv(p, xn, cfg):
    """Returns (q, i-scaled k, v, log_f); bounded gates (sigmoid i / f)."""
    di, h, dh = _dims(cfg)
    b, sq = xn.shape[:2]
    q = linear(p["wq"], xn).reshape(b, sq, h, dh) * dh ** -0.5
    k = linear(p["wk"], xn).reshape(b, sq, h, dh) * dh ** -0.5
    v = linear(p["wv"], xn).reshape(b, sq, h, dh)
    g = linear(p["wg"], xn).reshape(b, sq, h, 2).astype(jnp.float32)
    log_f = jax.nn.log_sigmoid(g[..., 0])
    i = jax.nn.sigmoid(g[..., 1])
    return q, k * i[..., None].astype(k.dtype), v, log_f


def _mlstm_out(p, x, xn, y, qn, cfg):
    b, sq = xn.shape[:2]
    di = _dims(cfg)[0]
    y = y / jnp.maximum(jnp.abs(qn), 1.0)[..., None]
    y = y.reshape(b, sq, di).astype(x.dtype)
    y = rmsnorm(p["hn"], y, cfg.norm_eps) * jax.nn.silu(linear(p["wz"], xn))
    return x + linear(p["wo"], y)


def mlstm_apply(p, x, cfg, state=None):
    xn = rmsnorm(p["ln"], x, cfg.norm_eps)
    q, k, v, log_f = _mlstm_qkv(p, xn, cfg)
    y, qn, new_state = ssm_common.chunked_scan(
        q, k, v, log_f, chunk=cfg.ssm_chunk, state=state, normalize=True)
    return _mlstm_out(p, x, xn, y, qn, cfg), new_state


def mlstm_decode(p, x, cfg, state):
    """x [B, 1, D]."""
    xn = rmsnorm(p["ln"], x, cfg.norm_eps)
    q, k, v, log_f = _mlstm_qkv(p, xn, cfg)
    y, qn, new_state = ssm_common.decode_step(
        q[:, 0], k[:, 0], v[:, 0], log_f[:, 0], state, normalize=True)
    return _mlstm_out(p, x, xn, y[:, None], qn[:, None], cfg), new_state


def mlstm_state(cfg, batch):
    di, h, dh = _dims(cfg)
    return ssm_common.init_state(batch, h, dh, dh)


# --- sLSTM block -------------------------------------------------------------
def slstm_init(rng, cfg, fsdp_axis):
    d = cfg.d_model
    di, h, dh = _dims(cfg)
    r = jax.random.split(rng, 7)
    dtype = layers.dt(cfg)
    p, s = {}, {}
    tp = "model" if cfg.tp_internals else None
    p["ln"], s["ln"] = layers.rmsnorm_init(d, dtype)
    p["wx"], s["wx"] = linear_init(r[0], d, 4 * di, dtype, P(fsdp_axis, tp))
    p["r"] = layers.truncnorm(r[1], (4, h, dh, dh), dh ** -0.5, dtype)
    s["r"] = P(None, tp, None, None)
    p["wo"], s["wo"] = linear_init(r[2], di, d, dtype, P(tp, fsdp_axis))
    p["hn"], s["hn"] = layers.rmsnorm_init(di, dtype)
    return p, s


def _slstm_cell(gates_x, r, h_prev, c, n, m):
    """One step.  gates_x [B,4,H,dh]; r [4,H,dh,dh]; states [B,H,dh]."""
    rec = jnp.einsum("bhd,ghde->bghe", h_prev, r.astype(jnp.float32))
    zi, ii, fi, oi = [gates_x[:, g].astype(jnp.float32) + rec[:, g]
                      for g in range(4)]
    log_f = jax.nn.log_sigmoid(fi)
    m_new = jnp.maximum(log_f + m, ii)
    i_g = jnp.exp(ii - m_new)
    f_g = jnp.exp(log_f + m - m_new)
    c_new = f_g * c + i_g * jnp.tanh(zi)
    n_new = f_g * n + i_g
    h_new = jax.nn.sigmoid(oi) * c_new / jnp.maximum(n_new, 1.0)
    return h_new, c_new, n_new, m_new


def slstm_state(cfg, batch):
    di, h, dh = _dims(cfg)
    z = jnp.zeros((batch, h, dh), jnp.float32)
    return {"h": z, "c": z, "n": z, "m": z - 10.0}


def _slstm_scan(gx, r, state):
    """The sequential cell, shard-local.  gx [B,S,4,H,dh]."""

    def step(st, g_t):
        hn, cn, nn, mn = _slstm_cell(g_t, r, st["h"], st["c"],
                                     st["n"], st["m"])
        return {"h": hn, "c": cn, "n": nn, "m": mn}, hn

    return jax.lax.scan(step, state, jnp.moveaxis(gx, 1, 0),
                        unroll=runtime_flags.scan_unroll())


def slstm_apply(p, x, cfg, state=None):
    b, sq, d = x.shape
    di, h, dh = _dims(cfg)
    xn = rmsnorm(p["ln"], x, cfg.norm_eps)
    gx = linear(p["wx"], xn).reshape(b, sq, 4, h, dh)
    if state is None:
        state = slstm_state(cfg, b)

    ctx = current_rules()
    if ctx is not None and sq > 1:
        # Manual SPMD around the sequential cell: under plain GSPMD the
        # recurrent-weight gradient dR is all-reduced EVERY time step
        # (4096x per layer!); inside shard_map the accumulation stays
        # shard-local and autodiff inserts ONE psum at the boundary
        # (EXPERIMENTS.md §Perf, xlstm iteration 2b).
        mesh, rules = ctx
        ba = rules.get("batch")

        def bspec(nd, batch_dim=0):
            spec = [None] * nd
            spec[batch_dim] = ba
            return P(*spec)

        state_specs = {k: bspec(3) for k in state}
        # check_vma=False: with VMA tracking on, the replicated-weight
        # cotangent is converted varying->invariant (psum) at every scan
        # step; classic semantics psums once at the shard_map exit.
        new_state, hs = jax.shard_map(
            _slstm_scan, mesh=mesh,
            in_specs=(bspec(5), P(None, None, None, None), state_specs),
            out_specs=(state_specs, bspec(4, batch_dim=1)),
            check_vma=False,
        )(gx, p["r"], state)
    else:
        new_state, hs = _slstm_scan(gx, p["r"], state)
    y = jnp.moveaxis(hs, 0, 1).reshape(b, sq, di).astype(x.dtype)
    y = rmsnorm(p["hn"], y, cfg.norm_eps)
    return x + linear(p["wo"], y), new_state


def slstm_decode(p, x, cfg, state):
    out, new_state = slstm_apply(p, x, cfg, state)
    return out, new_state


# --- full LM ----------------------------------------------------------------
def pattern_of(cfg) -> tuple[str, ...]:
    k = cfg.slstm_every
    if k:
        return ("m",) * (k - 1) + ("s",)
    return ("m",)


def init(rng, cfg):
    fsdp_axis = fsdp_axis_for(cfg)
    pattern = pattern_of(cfg)
    assert cfg.n_layers % len(pattern) == 0
    n_rep = cfg.n_layers // len(pattern)
    r = jax.random.split(rng, len(pattern) + 2)
    p, s = {}, {}
    # embed keeps vocab x 'model' sharding regardless of block TP (the
    # fsdp tuple would collide with the vocab axis)
    p["embed"], s["embed"] = layers.embed_init(
        r[0], cfg.vocab_size, cfg.d_model, layers.dt(cfg),
        "data" if cfg.fsdp else None)
    for i, kind in enumerate(pattern):
        fn = mlstm_init if kind == "m" else slstm_init
        p[f"blk{i}"], s[f"blk{i}"] = layers.stack_inits(
            r[1 + i], n_rep,
            functools.partial(fn, cfg=cfg, fsdp_axis=fsdp_axis))
    p["ln_f"], s["ln_f"] = layers.rmsnorm_init(cfg.d_model, layers.dt(cfg))
    return p, s


def init_caches(cfg, batch, max_len=None, dtype=None):
    pattern = pattern_of(cfg)
    n_rep = cfg.n_layers // len(pattern)

    def stack(tree):
        return jax.tree.map(lambda a: jnp.broadcast_to(
            a, (n_rep,) + a.shape).copy(), tree)

    caches = []
    for kind in pattern:
        one = (mlstm_state(cfg, batch) if kind == "m"
               else slstm_state(cfg, batch))
        caches.append(stack(one))
    return tuple(caches)  # tuple: matches the scan's output structure


def apply(p, batch, cfg, *, mode="train", caches=None):
    x = layers.embed_lookup(p["embed"], batch["tokens"], cfg.embed_scale)
    x = constrain(x, ("batch", None, None))
    pattern = pattern_of(cfg)
    stacked = tuple(p[f"blk{i}"] for i in range(len(pattern)))
    decode = mode == "decode"
    with_cache = caches is not None

    def body(carry, xs):
        x = carry
        lp = xs[: len(pattern)]
        lc = xs[len(pattern):] if with_cache else [None] * len(pattern)
        new_states = []
        for i, kind in enumerate(pattern):
            if kind == "m":
                fn = mlstm_decode if decode else mlstm_apply
            else:
                fn = slstm_decode if decode else slstm_apply
            x, st = fn(lp[i], x, cfg, lc[i])
            new_states.append(st)
        return x, tuple(new_states) if with_cache else None

    if cfg.remat != "none" and mode == "train":
        body = jax.checkpoint(body)
    xs = stacked + (tuple(caches) if with_cache else ())
    x, new_caches = jax.lax.scan(body, x, xs,
                                 unroll=runtime_flags.scan_unroll())
    if mode == "prefill":
        x = x[:, -1:]
    logits = layers.embed_logits(
        p["embed"], rmsnorm(p["ln_f"], x, cfg.norm_eps), cfg.final_softcap)
    if with_cache:
        return logits, new_caches
    return logits, jnp.zeros((), jnp.float32)