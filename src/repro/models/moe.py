"""Mixture-of-Experts FFN: top-k router + sort-based capacity dispatch.

Dispatch strategy (scales to DeepSeek's 160 experts without the O(T·E·C)
one-hot dispatch tensor): flatten (token, k) assignments, sort by expert id,
compute each assignment's position within its expert via cumulative counts,
scatter into an (E·C, d) buffer, run the per-expert SwiGLU as a batched
einsum with experts sharded over the 'model' mesh axis, and scatter-add the
weighted outputs back to tokens.  Over-capacity assignments are dropped
(standard capacity-factor semantics); an aux load-balancing loss is returned.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.parallel.sharding import constrain


def init_moe(cfg, key):
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    dt = L.pdtype_of(cfg)
    ks = jax.random.split(key, 5)
    p = {
        "router": L.dense_init(ks[0], d, E, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (E, d, ff)) / np.sqrt(d)).astype(dt),
        "w_up":   (jax.random.normal(ks[2], (E, d, ff)) / np.sqrt(d)).astype(dt),
        "w_down": (jax.random.normal(ks[3], (E, ff, d)) / np.sqrt(ff)).astype(dt),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.init_swiglu(
            ks[4], d, cfg.n_shared_experts * ff, dt)
    return p


def capacity(cfg, n_tokens: int) -> int:
    c = int(np.ceil(n_tokens * cfg.moe_top_k * cfg.capacity_factor
                    / cfg.n_experts))
    return max(c, 4)


def moe_block(cfg, p, x):
    """x: (B,S,d) -> (out (B,S,d), aux_loss scalar).

    Under a mesh, dispatch runs inside shard_map: every data shard routes
    its *local* tokens (no global sort — the global-dispatch path
    materializes gathered (T_global·k, d) buffers, +73 GB/device at the
    train_4k shape, found via the dry-run), experts live on the 'model'
    axis, and outputs combine with a psum_scatter.  Without a mesh the
    dense global path below runs (smoke tests, CPU executor)."""
    from repro.parallel.sharding import current_rules
    rules = current_rules()
    if (rules is not None and rules.mesh is not None
            and "model" in rules.mesh.axis_names
            and cfg.n_experts % rules.mesh.shape["model"] == 0):
        mesh = rules.mesh
        batch_axes = tuple(a for a in ("pod", "data")
                           if a in mesh.axis_names)
        n_batch = int(np.prod([mesh.shape[a] for a in batch_axes]))
        if x.shape[0] % n_batch == 0:
            return _moe_block_sharded(cfg, p, x, rules)
    return _moe_block_global(cfg, p, x)


def _moe_block_global(cfg, p, x):
    B, S, d = x.shape
    T = B * S
    E, k = cfg.n_experts, cfg.moe_top_k
    C = capacity(cfg, T)
    xt = x.reshape(T, d)

    logits = L.pdot(xt.astype(jnp.float32), p["router"])   # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                  # (T,k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)  # renormalize

    # aux load-balance loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], E), axis=0)
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

    # ---- sort-based dispatch --------------------------------------------
    TK = T * k
    flat_e = top_e.reshape(TK)
    flat_w = top_p.reshape(TK)
    tok_id = jnp.repeat(jnp.arange(T), k)
    order = jnp.argsort(flat_e)                             # stable
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(TK) - starts[sorted_e]
    keep = pos_in_e < C
    slot = jnp.where(keep, sorted_e * C + pos_in_e, E * C)  # E*C = drop bin

    buf = jnp.zeros((E * C + 1, d), x.dtype)
    buf = buf.at[slot].set(xt[tok_id[order]])
    buf = buf[:-1].reshape(E, C, d)
    buf = constrain(buf, "experts", None, "embed")

    # ---- expert computation (batched SwiGLU) -----------------------------
    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = constrain(h, "experts", None, "ffn")
    eo = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    eo = constrain(eo, "experts", None, "embed").reshape(E * C, d)

    # ---- combine ----------------------------------------------------------
    gathered = jnp.where(keep[:, None], eo[jnp.minimum(slot, E * C - 1)], 0.0)
    weighted = gathered * flat_w[order][:, None].astype(x.dtype)
    out = jnp.zeros((T, d), x.dtype).at[tok_id[order]].add(weighted)

    if cfg.n_shared_experts:
        out = out + L.swiglu(p["shared"], x).reshape(T, d)
    return out.reshape(B, S, d), aux


def _moe_block_sharded(cfg, p, x, rules):
    """shard_map expert-parallel MoE: tokens stay on their ('pod','data')
    shards, experts are partitioned over 'model'."""
    from jax.sharding import PartitionSpec as P

    mesh = rules.mesh
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_model = mesh.shape["model"]
    E, k = cfg.n_experts, cfg.moe_top_k
    E_loc = E // n_model
    B, S, d = x.shape
    T_loc = (B // int(np.prod([mesh.shape[a] for a in batch_axes]))) * S
    C = capacity(cfg, T_loc)
    all_axes = batch_axes + ("model",)

    d_shard = d % n_model == 0

    def local(x_blk, router, wg, wu, wd):
        # x_blk: (B_loc, S, d/n_model) if d shards else (B_loc, S, d)
        if d_shard:
            x_full = jax.lax.all_gather(x_blk, "model", axis=2, tiled=True)
        else:
            x_full = x_blk
        xt = x_full.reshape(T_loc, d)
        logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], E), axis=0)
        me = jax.lax.pmean(me, batch_axes)
        ce = jax.lax.pmean(ce, batch_axes)
        aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

        # local sort-based dispatch, keeping only this shard's experts
        TK = T_loc * k
        e0 = jax.lax.axis_index("model") * E_loc
        flat_e = top_e.reshape(TK)
        flat_w = top_p.reshape(TK)
        tok_id = jnp.repeat(jnp.arange(T_loc), k)
        order = jnp.argsort(flat_e)
        sorted_e = flat_e[order]
        counts = jnp.bincount(flat_e, length=E)
        starts = jnp.cumsum(counts) - counts
        pos_in_e = jnp.arange(TK) - starts[sorted_e]
        local_e = sorted_e - e0
        keep = (pos_in_e < C) & (local_e >= 0) & (local_e < E_loc)
        slot = jnp.where(keep, local_e * C + pos_in_e, E_loc * C)

        buf = jnp.zeros((E_loc * C + 1, d), x.dtype)
        buf = buf.at[slot].set(xt[tok_id[order]])
        buf = buf[:-1].reshape(E_loc, C, d)

        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        eo = jnp.einsum("ecf,efd->ecd", h, wd).reshape(E_loc * C, d)

        gathered = jnp.where(keep[:, None],
                             eo[jnp.minimum(slot, E_loc * C - 1)], 0.0)
        weighted = gathered * flat_w[order][:, None].astype(x.dtype)
        out = jnp.zeros((T_loc, d), jnp.float32).at[tok_id[order]].add(
            weighted.astype(jnp.float32))
        if d_shard:
            out = jax.lax.psum_scatter(out, "model", scatter_dimension=1,
                                       tiled=True)
            return (out.astype(x.dtype).reshape(x_blk.shape), aux)
        out = jax.lax.psum(out, "model")
        return (out.astype(x.dtype).reshape(x_blk.shape), aux)

    x_spec = P(batch_axes, None, "model" if d_shard else None)
    out, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if cfg.n_shared_experts:
        out = out + L.swiglu(p["shared"], x)
    return out, aux
