"""Attention: chunked-softmax GQA/MHA (flash-style, memory-bounded), MLA
(DeepSeek compressed-KV incl. absorbed decode), sliding windows, qk-norm,
QKV bias, M-RoPE, learned meta-token KV prefixes (Hymba), and decode paths
against (possibly ring-buffer) KV caches.

The chunked formulation keeps peak memory at O(q_chunk * k_chunk) per head
instead of O(S^2) — this is the pure-jnp oracle-equivalent of the Pallas
flash-attention kernel in ``repro.kernels.flash_attention``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.parallel.sharding import constrain

NEG_INF = -1e30


# ----------------------------------------------------------------- chunked --

def _chunk_sizes(sq, sk, q_chunk, k_chunk):
    qc = q_chunk if (q_chunk and sq % q_chunk == 0 and sq >= q_chunk) else sq
    kc = k_chunk if (k_chunk and sk % k_chunk == 0 and sk >= k_chunk) else sk
    return qc, kc


def chunked_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                      prefix_kv=None, q_chunk=256, k_chunk=512):
    """q: (B,Sq,H,Dk); k: (B,Sk,K,Dk); v: (B,Sk,K,Dv) with H % K == 0.

    Returns (B,Sq,H,Dv).  `window > 0` restricts attention to the last
    `window` keys (sliding window).  `q_offset` shifts query positions.
    `prefix_kv = (pk, pv)` with pk: (B,P,K,Dk) is an always-visible prefix
    (Hymba meta tokens).

    Memory-bounded form: an (optionally remat'd) scan over query chunks,
    each chunk scoring against the full key set with heads sharded over
    'model' — peak memory O(B_loc · H_loc · q_chunk · Sk) f32, and backward
    recomputes each chunk's scores instead of saving them.  This is the
    pure-jnp oracle twin of the Pallas ``kernels.flash_attention``."""
    B, Sq, H, Dk = q.shape
    K = k.shape[2]
    G = H // K
    Dv = v.shape[-1]
    Sk = k.shape[1]
    scale = 1.0 / np.sqrt(Dk)
    qc, _ = _chunk_sizes(Sq, Sk, q_chunk, k_chunk)
    nq = Sq // qc

    qr = (q.astype(jnp.float32) * scale).reshape(B, nq, qc, H, Dk)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if prefix_kv is not None:
        pk, pv_ = prefix_kv
        P = pk.shape[1]
        kf = jnp.concatenate([pk.astype(jnp.float32), kf], axis=1)
        vf = jnp.concatenate([pv_.astype(jnp.float32), vf], axis=1)
    else:
        P = 0
    if G > 1:
        # expand kv to full query heads: replicated-kv -> head-sharded is a
        # local slice (free), and every attention tensor then shards over
        # 'model' on the head dim.  Keeping the (K, G) grouped form instead
        # re-gathers kv per q-chunk per layer when K < mesh 'model' size
        # (measured 4.4 TB/step on qwen2-vl train — §Perf hillclimb A).
        kf = jnp.repeat(kf, G, axis=2)
        vf = jnp.repeat(vf, G, axis=2)
    kf = constrain(kf, "batch", "seq", "heads", "head_dim")
    vf = constrain(vf, "batch", "seq", "heads", "head_dim")

    kpos = jnp.arange(Sk + P) - P                     # prefix gets pos<0

    def q_block(qi, q_blk):
        # q_blk: (B,qc,H,Dk)
        s = jnp.einsum("bqhd,bshd->bhqs", q_blk, kf)
        s = constrain(s, "batch", "heads", None, None)
        qpos = q_offset + qi * qc + jnp.arange(qc)
        mask = jnp.ones((qc, Sk + P), bool)
        if causal:
            mask &= (kpos[None, :] <= qpos[:, None]) | (kpos[None, :] < 0)
        if window:
            mask &= (kpos[None, :] > qpos[:, None] - window) \
                | (kpos[None, :] < 0)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m) * mask[None, None]
        l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        out = jnp.einsum("bhqs,bshd->bqhd", p / l, vf)
        return out.reshape(B, qc, H, Dv)

    if nq == 1:
        out = q_block(0, qr[:, 0])
        return out.astype(v.dtype)
    _, out = jax.lax.scan(
        jax.checkpoint(lambda _, xs: (None, q_block(xs[0], xs[1]))),
        None, (jnp.arange(nq), qr.swapaxes(0, 1)))
    out = out.swapaxes(0, 1).reshape(B, Sq, H, Dv)
    return out.astype(v.dtype)


def decode_attention(q, k_cache, v_cache, valid, prefix_kv=None):
    """Single-token attention against a cache.

    q: (B,1,H,Dk); k_cache: (B,Smax,K,Dk); v_cache: (B,Smax,K,Dv);
    valid: (Smax,) bool — which cache slots participate (handles both
    growing caches and full ring buffers) — or (B,Smax) for per-request
    occupancy (the continuous-batching serving path, where every batch
    slot sits at its own position).

    Under a mesh with the cache sequence dim sharded this dispatches to an
    explicit shard_map flash-decode (partial scores per shard, pmax/psum
    LSE combine): manual collectives keep SPMD from resharding the cache,
    and the mul-reduce form never materializes an f32 cache copy."""
    from repro.parallel.sharding import current_rules
    rules = current_rules()
    if (prefix_kv is None and valid.ndim == 1 and rules is not None
            and rules.mesh is not None
            and "model" in rules.mesh.axis_names):
        mesh = rules.mesh
        batch_axes = tuple(a for a in ("pod", "data")
                           if a in mesh.axis_names)
        nb = int(np.prod([mesh.shape[a] for a in batch_axes]))
        nm = mesh.shape["model"]
        if q.shape[0] % nb == 0 and k_cache.shape[1] % nm == 0:
            return _decode_attention_sharded(q, k_cache, v_cache, valid,
                                             mesh, batch_axes)
    B, _, H, Dk = q.shape
    K = k_cache.shape[2]
    G = H // K
    scale = 1.0 / np.sqrt(Dk)
    # bf16 x bf16 dot with f32 accumulation.  Under pjit with the cache
    # sequence dim sharded this lowers to the flash-decode pattern: partial
    # scores per shard + small LSE-combine AllReduces (verified in the
    # dry-run HLO).  Note: the CPU backend emulates bf16 dots by converting
    # operands to f32 — the resulting f32 shadow of the cache inflates
    # temp_bytes in compile-only dry-runs; TPU MXUs consume bf16 natively.
    qc = (q.reshape(B, K, G, Dk) * scale).astype(k_cache.dtype)
    s = jnp.einsum("bkgd,bskd->bkgs", qc, k_cache,
                   preferred_element_type=jnp.float32)
    vmask = valid[:, None, None, :] if valid.ndim == 2 \
        else valid[None, None, None, :]
    s = jnp.where(vmask, s, NEG_INF)
    if prefix_kv is not None:
        pk, pv = prefix_kv
        sp = jnp.einsum("bkgd,bskd->bkgs", qc, pk.astype(k_cache.dtype),
                        preferred_element_type=jnp.float32)
        s = jnp.concatenate([sp, s], axis=-1)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    pw = (p / l).astype(v_cache.dtype)
    if prefix_kv is not None:
        pv_full = jnp.concatenate([prefix_kv[1].astype(v_cache.dtype),
                                   v_cache], axis=1)
        out = jnp.einsum("bkgs,bskd->bkgd", pw, pv_full,
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bkgs,bskd->bkgd", pw, v_cache,
                         preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, -1).astype(v_cache.dtype)


def _decode_attention_sharded(q, k_cache, v_cache, valid, mesh, batch_axes):
    """Explicit flash-decode under shard_map: each model shard scores its
    cache-sequence slice (fused multiply-reduce), then pmax/psum combine."""
    from jax.sharding import PartitionSpec as P

    B, _, H, Dk = q.shape
    K = k_cache.shape[2]
    G = H // K
    Dv = v_cache.shape[-1]
    scale = 1.0 / np.sqrt(Dk)

    def local(qb, kb, vb, validb):
        Bl = qb.shape[0]
        qc = (qb.reshape(Bl, K, G, Dk) * scale).astype(jnp.float32)
        s = jnp.sum(qc[:, None] * kb[:, :, :, None, :].astype(jnp.float32),
                    axis=-1)                          # (Bl, Sl, K, G)
        s = jnp.where(validb[None, :, None, None], s, NEG_INF)
        m_loc = jnp.max(s, axis=1)
        m = jax.lax.pmax(m_loc, "model")              # (Bl, K, G)
        p = jnp.exp(s - m[:, None])
        p = jnp.where(validb[None, :, None, None], p, 0.0)
        l = jax.lax.psum(jnp.sum(p, axis=1), "model")
        o = jnp.sum(p[..., None] * vb[:, :, :, None, :].astype(jnp.float32),
                    axis=1)                           # (Bl, K, G, Dv)
        o = jax.lax.psum(o, "model")
        return (o / jnp.maximum(l, 1e-30)[..., None]).astype(vb.dtype)

    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(batch_axes, None, None, None),
                  P(batch_axes, "model", None, None),
                  P(batch_axes, "model", None, None),
                  P("model")),
        out_specs=P(batch_axes, None, None, None),
        check_vma=False,
    )(q, k_cache, v_cache, valid)
    return out.reshape(B, 1, H, Dv)


# --------------------------------------------------------------- GQA block --

def init_attention(cfg, key):
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = L.pdtype_of(cfg)
    ks = jax.random.split(key, 8)
    p = {
        "wq": L.dense_init(ks[0], d, H * hd, dt),
        "wk": L.dense_init(ks[1], d, K * hd, dt),
        "wv": L.dense_init(ks[2], d, K * hd, dt),
        "wo": L.dense_init(ks[3], H * hd, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dt)
        p["bk"] = jnp.zeros((K * hd,), dt)
        p["bv"] = jnp.zeros((K * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = L.init_rmsnorm(hd, dt)
        p["k_norm"] = L.init_rmsnorm(hd, dt)
    if cfg.n_meta_tokens:
        p["meta_k"] = (jax.random.normal(ks[4], (cfg.n_meta_tokens, K, hd))
                       * 0.02).astype(dt)
        p["meta_v"] = (jax.random.normal(ks[5], (cfg.n_meta_tokens, K, hd))
                       * 0.02).astype(dt)
    return p


def _project_qkv(cfg, p, x):
    B, S, d = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = L.pdot(x, constrain(p["wq"], "w_in_use", "w_out"))
    k = L.pdot(x, constrain(p["wk"], "w_in_use", "w_out"))
    v = L.pdot(x, constrain(p["wv"], "w_in_use", "w_out"))
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, K, hd)
    v = v.reshape(B, S, K, hd)
    if cfg.qk_norm:
        q = L.rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = L.rmsnorm(p["k_norm"], k, cfg.norm_eps)
    return q, k, v


def _rope_qk(cfg, q, k, positions):
    if cfg.m_rope:
        q = L.apply_m_rope(q, positions, cfg.rope_theta, cfg.m_rope_sections)
        k = L.apply_m_rope(k, positions, cfg.rope_theta, cfg.m_rope_sections)
    else:
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k


def _meta_kv(cfg, p, B):
    if not cfg.n_meta_tokens:
        return None
    mk = jnp.broadcast_to(p["meta_k"][None], (B,) + p["meta_k"].shape)
    mv = jnp.broadcast_to(p["meta_v"][None], (B,) + p["meta_v"].shape)
    return mk, mv  # (B, P, K, hd)

def attention_block(cfg, p, x, positions, *, causal=True, window=0,
                    q_chunk=256, k_chunk=512, cross_kv=None):
    """Self-attention (causal or bidirectional) or cross-attention when
    `cross_kv=(k,v)` is given (always non-causal)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x)
    if cross_kv is not None:
        k, v = cross_kv
        causal = False
    else:
        q, k = _rope_qk(cfg, q, k, positions)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    out = chunked_attention(q, k, v, causal=causal, window=window,
                            prefix_kv=_meta_kv(cfg, p, B),
                            q_chunk=q_chunk, k_chunk=k_chunk)
    out = constrain(out, "batch", "seq", "heads", "head_dim")
    out = out.reshape(B, S, -1)
    out = constrain(L.pdot(out, constrain(p["wo"], "w_out", "w_in_use")),
                    "batch", "seq", "embed")
    return out, (k, v)


def project_cross_kv(cfg, p, enc_x):
    """Precompute cross-attention K/V from encoder output (used once per
    decode session and for every decoder layer during training)."""
    B, S, _ = enc_x.shape
    K, hd = cfg.n_kv_heads, cfg.head_dim
    k = L.pdot(enc_x, constrain(p["wk"], "w_in_use",
                                "w_out")).reshape(B, S, K, hd)
    v = L.pdot(enc_x, constrain(p["wv"], "w_in_use",
                                "w_out")).reshape(B, S, K, hd)
    if cfg.qk_norm:
        k = L.rmsnorm(p["k_norm"], k, cfg.norm_eps)
    return k, v


def _decode_positions(cfg, pos, B):
    """RoPE positions for the incoming token: scalar ``pos`` broadcasts to
    the whole batch (the uniform monolithic decode), a (B,) vector gives
    every batch slot its own absolute position (continuous batching)."""
    pos = pos.astype(jnp.int32)
    if jnp.ndim(pos) == 1:
        base = pos.reshape(B, 1)
    else:
        base = jnp.broadcast_to(pos.reshape(1, 1), (B, 1))
    if cfg.m_rope:
        return jnp.broadcast_to(base[..., None], (B, 1, 3))
    return base


def attention_decode(cfg, p, x, pos, cache_k, cache_v, slot, valid,
                     cross_kv=None):
    """One-token decode. x: (B,1,d); cache_k/v: (B,Smax,K,hd) — the layer's
    cache slice (read).  Returns (out, k_new, v_new) where k_new/v_new are
    the (B,1,K,hd) new-token entries: the caller writes them back with one
    small dynamic_update_slice (never rewriting the full cache — a 100x
    write-traffic difference found via the dry-run HLO analyzer).

    ``pos``/``slot`` may be scalars (uniform batch) or (B,) vectors with a
    (B,Smax) ``valid`` mask — the per-request serving layout."""
    B = x.shape[0]
    q, k, v = _project_qkv(cfg, p, x)
    if cross_kv is None:
        q, k = _rope_qk(cfg, q, k, _decode_positions(cfg, pos, B))
        cache_k = _write_slot(cache_k, k, slot)
        cache_v = _write_slot(cache_v, v, slot)
        out = decode_attention(q, cache_k, cache_v, valid,
                               prefix_kv=_meta_kv(cfg, p, B))
    else:
        ck, cv = cross_kv
        valid_c = jnp.ones((ck.shape[1],), bool)
        out = decode_attention(q, ck, cv, valid_c)
        k = v = None
    out = out.reshape(B, 1, -1)
    return L.pdot(out, constrain(p["wo"], "w_out", "w_in_use")), k, v


def _write_slot(cache, kv, slot):
    """cache: (B,Smax,K,hd); kv: (B,1,K,hd); write at sequence index slot
    (scalar: same slot for the whole batch; (B,) vector: per-slot scatter)."""
    if jnp.ndim(slot) == 1:
        B = cache.shape[0]
        return cache.at[jnp.arange(B), slot].set(
            kv[:, 0].astype(cache.dtype))
    return jax.lax.dynamic_update_slice(
        cache, kv.astype(cache.dtype), (0, slot, 0, 0))


# ----------------------------------------------------------------- MLA -------

def init_mla(cfg, key):
    d, H = cfg.d_model, cfg.n_heads
    hd, rd, r, vd = cfg.head_dim, cfg.rope_head_dim, cfg.kv_lora_rank, cfg.v_dim
    dt = L.pdtype_of(cfg)
    ks = jax.random.split(key, 8)
    p = {}
    if cfg.q_lora_rank:
        p["w_dq"] = L.dense_init(ks[0], d, cfg.q_lora_rank, dt)
        p["q_norm"] = L.init_rmsnorm(cfg.q_lora_rank, dt)
        p["w_uq"] = L.dense_init(ks[1], cfg.q_lora_rank, H * (hd + rd), dt)
    else:
        p["w_q"] = L.dense_init(ks[1], d, H * (hd + rd), dt)
    p["w_dkv"] = L.dense_init(ks[2], d, r + rd, dt)
    p["kv_norm"] = L.init_rmsnorm(r, dt)
    p["w_uk"] = L.dense_init(ks[3], r, H * hd, dt)
    p["w_uv"] = L.dense_init(ks[4], r, H * vd, dt)
    p["wo"] = L.dense_init(ks[5], H * vd, d, dt)
    return p


def _mla_q(cfg, p, x):
    B, S, _ = x.shape
    H, hd, rd = cfg.n_heads, cfg.head_dim, cfg.rope_head_dim
    if cfg.q_lora_rank:
        qc = L.rmsnorm(p["q_norm"], L.pdot(x, p["w_dq"]), cfg.norm_eps)
        q = L.pdot(qc, constrain(p["w_uq"], "w_in_use", "w_out"))
    else:
        q = L.pdot(x, constrain(p["w_q"], "w_in_use", "w_out"))
    q = q.reshape(B, S, H, hd + rd)
    return q[..., :hd], q[..., hd:]


def _mla_ckv(cfg, p, x, positions):
    r, rd = cfg.kv_lora_rank, cfg.rope_head_dim
    ckv_kpe = L.pdot(x, constrain(p["w_dkv"], "w_in_use", None))
    c_kv = L.rmsnorm(p["kv_norm"], ckv_kpe[..., :r], cfg.norm_eps)
    k_pe = ckv_kpe[..., None, r:]                       # (B,S,1,rd)
    k_pe = L.apply_rope(k_pe, positions, cfg.rope_theta)
    return c_kv, k_pe[:, :, 0]                          # (B,S,r), (B,S,rd)


def mla_block(cfg, p, x, positions, *, window=0, q_chunk=256, k_chunk=512):
    """MLA training/prefill attention (materialized K/V path)."""
    B, S, _ = x.shape
    H, hd, rd, vd = cfg.n_heads, cfg.head_dim, cfg.rope_head_dim, cfg.v_dim
    q_nope, q_pe = _mla_q(cfg, p, x)
    q_pe = L.apply_rope(q_pe, positions, cfg.rope_theta)
    c_kv, k_pe = _mla_ckv(cfg, p, x, positions)
    k_nope = L.pdot(c_kv, constrain(p["w_uk"], None,
                                    "w_out")).reshape(B, S, H, hd)
    v = L.pdot(c_kv, constrain(p["w_uv"], None,
                               "w_out")).reshape(B, S, H, vd)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None], (B, S, H, rd))], axis=-1)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "heads", "head_dim")
    v = constrain(v, "batch", "seq", "heads", "head_dim")
    out = chunked_attention(q, k, v, causal=True, window=window,
                            q_chunk=q_chunk, k_chunk=k_chunk)
    out = out.reshape(B, S, H * vd)
    out = constrain(L.pdot(out, constrain(p["wo"], "w_out", "w_in_use")),
                    "batch", "seq", "embed")
    return out, (c_kv, k_pe)


def mla_decode(cfg, p, x, pos, cache_ckv, cache_kpe, slot, valid):
    """Absorbed MLA decode: queries are projected into the compressed-KV
    space (q·W_uk), scores run directly against cached c_kv — per-token cost
    is O(S·r) instead of O(S·H·hd), and only (r + rd) floats are cached per
    position (the paper-model's KV-cache saving)."""
    B = x.shape[0]
    H, hd, rd, r, vd = (cfg.n_heads, cfg.head_dim, cfg.rope_head_dim,
                        cfg.kv_lora_rank, cfg.v_dim)
    positions = _decode_positions(cfg, pos, B)
    q_nope, q_pe = _mla_q(cfg, p, x)
    q_pe = L.apply_rope(q_pe, positions, cfg.rope_theta)       # (B,1,H,rd)
    c_kv_new, k_pe_new = _mla_ckv(cfg, p, x, positions)
    # local (read-slice) update for this step's attention; the caller writes
    # back only the (B,1,·) new-token entries.
    if jnp.ndim(slot) == 1:
        bidx = jnp.arange(B)
        cache_ckv = cache_ckv.at[bidx, slot].set(
            c_kv_new[:, 0].astype(cache_ckv.dtype))
        cache_kpe = cache_kpe.at[bidx, slot].set(
            k_pe_new[:, 0].astype(cache_kpe.dtype))
    else:
        cache_ckv = jax.lax.dynamic_update_slice(
            cache_ckv, c_kv_new.astype(cache_ckv.dtype), (0, slot, 0))
        cache_kpe = jax.lax.dynamic_update_slice(
            cache_kpe, k_pe_new.astype(cache_kpe.dtype), (0, slot, 0))
    w_uk = p["w_uk"].reshape(r, H, hd)
    q_c = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_uk,
                     preferred_element_type=jnp.float32)       # (B,1,H,r)
    scale = 1.0 / np.sqrt(hd + rd)
    dt = cache_ckv.dtype
    s = (jnp.einsum("bqhr,bsr->bhqs", q_c.astype(dt), cache_ckv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhd,bsd->bhqs", q_pe.astype(dt), cache_kpe,
                      preferred_element_type=jnp.float32)) * scale
    vmask = valid[:, None, None, :] if valid.ndim == 2 \
        else valid[None, None, None, :]
    s = jnp.where(vmask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    pw = jnp.exp(s - m)
    pw = pw / jnp.sum(pw, axis=-1, keepdims=True)
    ctx = jnp.einsum("bhqs,bsr->bqhr", pw.astype(dt), cache_ckv,
                     preferred_element_type=jnp.float32)
    w_uv = p["w_uv"].reshape(r, H, vd)
    out = jnp.einsum("bqhr,rhv->bqhv", ctx, w_uv.astype(jnp.float32))
    out = out.reshape(B, 1, H * vd).astype(x.dtype)
    return (L.pdot(out, constrain(p["wo"], "w_out", "w_in_use")),
            c_kv_new.astype(cache_ckv.dtype),
            k_pe_new.astype(cache_kpe.dtype))
