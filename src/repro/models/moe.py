"""Mixture-of-Experts with sort-based capacity dispatch (EP over 'model').

Routing reuses the paper's own idiom — sort once, then operate on
contiguous runs (tSPM+ screens sequences exactly this way): token->expert
assignments are argsorted by expert id, each token's slot is its rank
within the expert's run, tokens beyond capacity drop (standard
token-choice).  The dense [tokens, E, capacity] one-hot dispatch tensor of
the classic einsum formulation never materializes.

Covers deepseek-moe (2 shared + 64 routed, top-6, fine-grained) and
llama4-maverick (1 shared + 128 routed, top-1).  Experts are sharded over
the 'model' axis (EP); shared experts are a plain TP MLP.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_rules
from repro.models import layers
from repro.models.layers import truncnorm


def init(rng, cfg, fsdp_axis):
    d, e, ffe = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    r = jax.random.split(rng, 5)
    dtype = layers.dt(cfg)
    p = {"router": truncnorm(r[0], (d, e), d ** -0.5, jnp.float32)}
    s = {"router": P(fsdp_axis, "model")}
    p["w_gate"] = truncnorm(r[1], (e, d, ffe), d ** -0.5, dtype)
    p["w_up"] = truncnorm(r[2], (e, d, ffe), d ** -0.5, dtype)
    p["w_down"] = truncnorm(r[3], (e, ffe, d), ffe ** -0.5, dtype)
    s["w_gate"] = P("model", fsdp_axis, None)
    s["w_up"] = P("model", fsdp_axis, None)
    s["w_down"] = P("model", None, fsdp_axis)
    if cfg.n_shared_experts:
        p["shared"], s["shared"] = layers.mlp_init(
            r[4], d, cfg.n_shared_experts * ffe, dtype, fsdp_axis, cfg.mlp_act)
    return p, s


def _capacity(n_tokens: int, cfg) -> int:
    c = int(n_tokens * cfg.experts_per_token * cfg.capacity_factor
            / max(cfg.n_experts, 1))
    return max(8, -(-c // 8) * 8)


def _local_expert_ffn(xf, gate, eid, w_gate, w_up, w_down, cfg, e_base,
                      e_loc, c):
    """Sort-dispatch xf's tokens to the LOCAL expert slab [e_loc, ...].

    Same machinery as apply(), restricted to experts in
    [e_base, e_base + e_loc); non-local assignments drop out of the sort.
    Returns the partial output (zeros where tokens went elsewhere)."""
    n, d = xf.shape
    k = eid.shape[-1]
    flat_e = eid.reshape(-1).astype(jnp.int32)
    local = (flat_e >= e_base) & (flat_e < e_base + e_loc)
    key = jnp.where(local, flat_e - e_base, e_loc)       # non-local last
    order = jnp.argsort(key, stable=True)
    sorted_e = key[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank = jnp.arange(n * k, dtype=jnp.int32) - first.astype(jnp.int32)
    keep = (sorted_e < e_loc) & (rank < c)
    slot = jnp.where(keep, sorted_e * c + rank, e_loc * c)
    token = (order // k).astype(jnp.int32)

    buf = jnp.zeros((e_loc * c + 1, d), xf.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], xf[token], 0))
    h = buf[: e_loc * c].reshape(e_loc, c, d)
    act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
    hg = act(jnp.einsum("ecd,edf->ecf", h, w_gate.astype(xf.dtype)))
    hu = jnp.einsum("ecd,edf->ecf", h, w_up.astype(xf.dtype))
    ho = jnp.einsum("ecf,efd->ecd", hg * hu, w_down.astype(xf.dtype))
    ho_flat = jnp.concatenate([ho.reshape(e_loc * c, d),
                               jnp.zeros((1, d), xf.dtype)], 0)
    contrib = ho_flat[slot] * gate.reshape(-1)[order][:, None].astype(xf.dtype)
    return jnp.zeros((n, d), xf.dtype).at[token].add(
        jnp.where(keep[:, None], contrib, 0))


def apply_shard_map(p, x, cfg):
    """Replicated-routing expert parallelism (manual SPMD).

    Under plain GSPMD the sort-based dispatch scatters data-sharded tokens
    into a model-sharded buffer — XLA materializes TB-scale all-reduces
    (EXPERIMENTS.md §Perf, deepseek baseline).  Here every 'model' rank
    routes its data-shard's tokens locally (router matmul is redundant
    across ranks but tiny), keeps only assignments for its OWN expert slab
    — dispatch is a local slice, the paper's sort-then-scan idiom per
    shard — and one psum over 'model' combines partial outputs.  Expert
    weights enter pre-sliced (EP), so their gradients stay local."""
    mesh, rules = current_rules()
    ma = rules["model"]
    ba = rules["batch"]
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    m_size = mesh.shape[ma]
    e_loc = e // m_size
    n = b * s

    def block(xb, router, wg, wu, wd):
        xf = xb.reshape(-1, d)
        n_loc = xf.shape[0]
        logits = (xf.astype(jnp.float32) @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, eid = jax.lax.top_k(probs, k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        c = max(8, -(-int(n_loc * k * cfg.capacity_factor / e) // 8) * 8)
        r = jax.lax.axis_index(ma)
        y_part = _local_expert_ffn(xf, gate, eid, wg, wu, wd, cfg,
                                   r * e_loc, e_loc, c)
        # combine in the activation dtype (bf16 halves the psum bytes)
        y = jax.lax.psum(y_part.astype(xb.dtype), ma)
        me = jax.lax.pmean(probs.mean(0), ba)
        fe = jax.lax.pmean(
            jax.nn.one_hot(eid[:, 0], e, dtype=jnp.float32).mean(0), ba)
        aux = cfg.router_aux_coef * e * jnp.sum(me * fe)
        return y.reshape(xb.shape), aux

    from jax.sharding import PartitionSpec as P

    y, aux = jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(ba, None, None), P(None, None),
                  P(ma, None, None), P(ma, None, None), P(ma, None, None)),
        out_specs=(P(ba, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    if cfg.n_shared_experts:
        y = y + layers.mlp(p["shared"], x.reshape(-1, d),
                           cfg.mlp_act).reshape(x.shape)
    return y, aux


def apply(p, x, cfg):
    """x [B, S, D] -> (y [B, S, D], aux_loss scalar)."""
    if cfg.moe_dispatch == "shard_map_ep" and current_rules() is not None \
            and cfg.n_experts and x.shape[1] > 1:
        ctx = current_rules()
        m_size = ctx[0].shape[ctx[1]["model"]] if ctx[1]["model"] else 1
        if m_size > 1 and cfg.n_experts % m_size == 0:
            return apply_shard_map(p, x, cfg)
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    n = b * s
    xf = x.reshape(n, d)

    logits = (xf.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # [N, E]
    gate, eid = jax.lax.top_k(probs, k)                        # [N, k]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # --- sort-based dispatch (the tSPM+ sort-then-scan idiom) ---
    c = _capacity(n, cfg)
    flat_e = eid.reshape(-1).astype(jnp.int32)                 # [N*k]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank = jnp.arange(n * k, dtype=jnp.int32) - first.astype(jnp.int32)
    keep = rank < c
    slot = jnp.where(keep, sorted_e * c + rank, e * c)         # sentinel row
    token = (order // k).astype(jnp.int32)

    buf = jnp.zeros((e * c + 1, d), x.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], xf[token], 0))
    h = buf[: e * c].reshape(e, c, d)

    act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
    hg = act(jnp.einsum("ecd,edf->ecf", h, p["w_gate"].astype(x.dtype)))
    hu = jnp.einsum("ecd,edf->ecf", h, p["w_up"].astype(x.dtype))
    ho = jnp.einsum("ecf,efd->ecd", hg * hu, p["w_down"].astype(x.dtype))

    ho_flat = jnp.concatenate([ho.reshape(e * c, d),
                               jnp.zeros((1, d), x.dtype)], 0)
    contrib = ho_flat[slot] * gate.reshape(-1)[order][:, None].astype(x.dtype)
    y = jnp.zeros((n, d), x.dtype).at[token].add(
        jnp.where(keep[:, None], contrib, 0))

    if cfg.n_shared_experts:
        y = y + layers.mlp(p["shared"], xf, cfg.mlp_act)

    # Switch-style load-balance aux loss
    me = probs.mean(0)                                          # [E]
    one_hot_top1 = jax.nn.one_hot(eid[:, 0], e, dtype=jnp.float32)
    fe = one_hot_top1.mean(0)
    aux = cfg.router_aux_coef * e * jnp.sum(me * fe)
    return y.reshape(b, s, d), aux
