"""Model assembly: decoder-only LM for every assigned family, built from an
``ArchConfig``.  Uniform layers + stacked params + ``lax.scan`` over layers
(compile time independent of depth) + per-layer remat.

Public API
----------
init_params(cfg, key)                    -> params pytree
forward(cfg, params, batch, ...)         -> (logits_fn-ready final hidden, aux)
loss_fn(cfg, params, batch)              -> (loss, metrics)
prefill(cfg, params, batch, cache_len)   -> (last_logits, cache)
decode_step(cfg, params, cache, tokens, pos) -> (logits, cache)
init_cache(cfg, batch, cache_len, ...)   -> cache pytree
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import rwkv as R
from repro.models import ssm as SSM
from repro.parallel.sharding import constrain


# ------------------------------------------------------------------- inits --

def init_layer(cfg, key):
    ks = jax.random.split(key, 8)
    dt = L.pdtype_of(cfg)
    p = {}
    if cfg.rwkv:
        p["ln1"] = L.init_rmsnorm(cfg.d_model, dt)
        p["time_mix"] = R.init_time_mix(cfg, ks[0])
        p["ln2"] = L.init_rmsnorm(cfg.d_model, dt)
        p["channel_mix"] = R.init_channel_mix(cfg, ks[1])
        return p
    p["ln1"] = L.init_rmsnorm(cfg.d_model, dt)
    if cfg.mla:
        p["attn"] = A.init_mla(cfg, ks[0])
    elif not cfg.attn_free:
        p["attn"] = A.init_attention(cfg, ks[0])
    if cfg.hybrid_parallel or (cfg.ssm and not cfg.rwkv):
        p["ssm"] = SSM.init_ssm(cfg, ks[1])
    p["ln2"] = L.init_rmsnorm(cfg.d_model, dt)
    if cfg.moe:
        p["moe"] = MOE.init_moe(cfg, ks[2])
    else:
        p["mlp"] = L.init_swiglu(ks[2], cfg.d_model, cfg.d_ff, dt)
    return p


def init_params(cfg, key):
    k_emb, k_layers, k_head, k_enc, k_fin = jax.random.split(key, 5)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    params = {
        "embed": L.init_embedding(k_emb, cfg),
        "layers": jax.vmap(lambda k: init_layer(cfg, k))(layer_keys),
        "final_norm": L.init_rmsnorm(cfg.d_model, L.pdtype_of(cfg)),
        "head": L.init_lm_head(k_head, cfg),
    }
    if cfg.enc_dec:
        from repro.models import encdec
        params["encoder"] = encdec.init_encoder(cfg, k_enc)
        # decoder cross-attention params (stacked per decoder layer)
        ck = jax.random.split(k_fin, cfg.n_layers)
        params["cross"] = jax.vmap(
            lambda k: encdec.init_cross_layer(cfg, k))(ck)
    return params


# ------------------------------------------------------------ layer bodies --

def layer_forward(cfg, p, x, positions, *, window=0, q_chunk=256,
                  k_chunk=512, causal=True, ssm_chunk=64, cross_fn=None):
    """One decoder layer, training/prefill. Returns (x, aux, kv).
    `cross_fn`, if given, applies cross-attention between the self-attention
    and FFN sublayers (decoder-in-encoder-decoder)."""
    aux = jnp.zeros((), jnp.float32)
    kv = ()
    if cfg.rwkv:
        B = x.shape[0]
        hd = cfg.rwkv_head_dim
        H = cfg.d_model // hd
        s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
        zt = jnp.zeros((B, cfg.d_model), x.dtype)
        h1 = L.rmsnorm(p["ln1"], x)
        tm, tm_last, s_last = R.time_mix(cfg, p["time_mix"], h1, zt, s0,
                                         chunk=32)
        x = x + tm
        h2 = L.rmsnorm(p["ln2"], x)
        cm, cm_last = R.channel_mix(cfg, p["channel_mix"], h2, zt)
        x = x + cm
        return x, aux, (s_last, tm_last, cm_last)

    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    # fsdp mode: gather the residual's feature dim once per layer here
    # (instead of once per weight dot)
    h = constrain(h, "batch", "seq", "embed_use")
    branch_out = None
    if cfg.mla:
        ao, kv = A.mla_block(cfg, p["attn"], h, positions, window=window,
                             q_chunk=q_chunk, k_chunk=k_chunk)
        branch_out = ao
    elif not cfg.attn_free:
        ao, kv = A.attention_block(cfg, p["attn"], h, positions,
                                   causal=causal, window=window,
                                   q_chunk=q_chunk, k_chunk=k_chunk)
        branch_out = ao
    if cfg.hybrid_parallel:
        so = SSM.ssm_block(cfg, p["ssm"], h, chunk=ssm_chunk)
        branch_out = 0.5 * (branch_out + so)
    elif cfg.ssm and branch_out is None:
        branch_out = SSM.ssm_block(cfg, p["ssm"], h, chunk=ssm_chunk)
    x = x + branch_out

    if cross_fn is not None:
        x = cross_fn(x)

    h2 = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if cfg.moe:
        mo, a = MOE.moe_block(cfg, p["moe"], h2)
        aux = aux + a
        x = x + mo
    else:
        x = x + L.swiglu(p["mlp"], h2)
    return x, aux, kv


# ------------------------------------------------------------ input fusion --

def fuse_inputs(cfg, params, batch):
    """Token embedding + modality stubs -> (x, positions)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = L.embed_tokens(params["embed"], tokens, cfg)
    if cfg.modality == "vision" and "vision_embeds" in batch:
        ve = batch["vision_embeds"].astype(x.dtype)       # (B,Svis,d) prefix
        Svis = ve.shape[1]
        x = jnp.concatenate([ve, x[:, Svis:]], axis=1)
    if cfg.m_rope:
        positions = batch.get("positions_mrope")
        if positions is None:
            positions = L.default_m_positions(B, S)
    else:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    return constrain(x, "batch", "seq", "embed"), positions


# ----------------------------------------------------------------- forward --

def forward(cfg, params, batch, *, window=0, q_chunk=256, k_chunk=512,
            collect_kv=False, remat=True, scan_layers=True):
    """Full forward to final hidden states. Returns (x, aux, kv_stack).

    ``scan_layers=False`` unrolls the layer loop in Python (per-layer param
    slices, no ``lax.scan``, no remat) — the PS-centric fleet training path
    uses it so fleet GEMMs, which execute on concrete operands, never
    sit inside compiled control flow.  The unrolled path computes the same values as the scan; it does
    not collect KV (training/loss never reads it)."""
    x, positions = fuse_inputs(cfg, params, batch)

    cross_kv_all = None
    if cfg.enc_dec:
        from repro.models import encdec
        enc_out = encdec.encode(cfg, params["encoder"], batch["encoder_feats"])
        cross_kv_all = True  # handled inside the scan via params["cross"]

    def body(x, scanned):
        if cfg.enc_dec:
            lp, cp = scanned
            from repro.models import encdec
            cross_fn = lambda y: encdec.cross_layer(   # noqa: E731
                cfg, cp, y, enc_out, q_chunk=q_chunk, k_chunk=k_chunk)
        else:
            lp, cross_fn = scanned, None
        x, aux, kv = layer_forward(cfg, lp, x, positions, window=window,
                                   q_chunk=q_chunk, k_chunk=k_chunk,
                                   cross_fn=cross_fn)
        if not collect_kv:
            kv = ()
        return x, (aux, kv)

    if scan_layers:
        body_fn = jax.checkpoint(body) if remat else body
        scanned = ((params["layers"], params["cross"]) if cfg.enc_dec
                   else params["layers"])
        x, (auxs, kvs) = jax.lax.scan(body_fn, x, scanned)
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        aux = jnp.sum(auxs)
        return x, aux, kvs
    aux = jnp.zeros((), jnp.float32)
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda t: t[i], params["layers"])
        if cfg.enc_dec:
            cp = jax.tree.map(lambda t: t[i], params["cross"])
            x, (aux_i, _) = body(x, (lp, cp))
        else:
            x, (aux_i, _) = body(x, lp)
        aux = aux + aux_i
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, aux, ()


def _vocab_mask(cfg):
    vp = L.padded_vocab(cfg)
    m = np.zeros((vp,), np.float32)
    m[cfg.vocab_size:] = A.NEG_INF
    return jnp.asarray(m)


def loss_fn(cfg, params, batch, *, window=0, q_chunk=256, k_chunk=512,
            loss_chunk=256, scan_layers=True):
    """Mean cross-entropy over valid labels (labels < 0 are masked), computed
    in sequence chunks so the (B,S,V) logits tensor never materializes.
    ``scan_layers=False`` selects the unrolled, scan-free path (see
    :func:`forward`) — same values, fleet-GEMM-hookable."""
    x, aux, _ = forward(cfg, params, batch, window=window,
                        q_chunk=q_chunk, k_chunk=k_chunk,
                        scan_layers=scan_layers)
    labels = batch["labels"]
    B, S = labels.shape
    c = loss_chunk if (S % loss_chunk == 0 and S >= loss_chunk) else S
    nc = S // c
    xr = x.reshape(B, nc, c, -1).swapaxes(0, 1)
    lr = labels.reshape(B, nc, c).swapaxes(0, 1)
    vmask = _vocab_mask(cfg)

    def chunk_loss(carry, inp):
        xc, lc = inp
        logits = L.lm_logits(params["head"], params["embed"], xc, cfg)
        logits = logits.astype(jnp.float32) + vmask
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.maximum(lc, 0)
        picked = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        w = (lc >= 0).astype(jnp.float32)
        nll = (lse - picked) * w
        tot, cnt = carry
        return (tot + jnp.sum(nll), cnt + jnp.sum(w)), None

    if scan_layers:
        (tot, cnt), _ = jax.lax.scan(jax.checkpoint(chunk_loss),
                                     (jnp.zeros(()), jnp.zeros(())),
                                     (xr, lr))
    else:
        tot, cnt = jnp.zeros(()), jnp.zeros(())
        for j in range(nc):
            (tot, cnt), _ = chunk_loss((tot, cnt), (xr[j], lr[j]))
    loss = tot / jnp.maximum(cnt, 1.0)
    metrics = {"loss": loss, "aux_loss": aux, "tokens": cnt}
    return loss + aux, metrics


# ------------------------------------------------------------------- cache --

def init_cache(cfg, batch, cache_len, *, enc_len=0, kv_quant=False):
    """Decode cache pytree, stacked over layers (scan-compatible).

    kv_quant=True stores K/V int8 with per-(token, head) f16 scales —
    halves cache HBM (the §Perf hillclimb for MHA-heavy caches)."""
    dt = L.dtype_of(cfg)
    Lc = cfg.n_layers
    c = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.rwkv:
        hd = cfg.rwkv_head_dim
        H = cfg.d_model // hd
        c["wkv_state"] = jnp.zeros((Lc, batch, H, hd, hd), jnp.float32)
        c["tm_prev"] = jnp.zeros((Lc, batch, cfg.d_model), dt)
        c["cm_prev"] = jnp.zeros((Lc, batch, cfg.d_model), dt)
        return c
    if cfg.mla:
        c["ckv"] = jnp.zeros((Lc, batch, cache_len, cfg.kv_lora_rank), dt)
        c["kpe"] = jnp.zeros((Lc, batch, cache_len, cfg.rope_head_dim), dt)
    elif not cfg.attn_free:
        K, hd = cfg.n_kv_heads, cfg.head_dim
        kv_dt = jnp.int8 if kv_quant else dt
        c["k"] = jnp.zeros((Lc, batch, cache_len, K, hd), kv_dt)
        c["v"] = jnp.zeros((Lc, batch, cache_len, K, hd), kv_dt)
        if kv_quant:
            c["k_scale"] = jnp.zeros((Lc, batch, cache_len, K), jnp.float16)
            c["v_scale"] = jnp.zeros((Lc, batch, cache_len, K), jnp.float16)
    if cfg.hybrid_parallel or (cfg.ssm and not cfg.rwkv):
        c["ssm_h"] = jnp.zeros((Lc, batch, cfg.d_inner, cfg.ssm_state),
                               jnp.float32)
        c["ssm_conv"] = jnp.zeros((Lc, batch, cfg.ssm_conv - 1, cfg.d_inner), dt)
    if cfg.enc_dec:
        K, hd = cfg.n_kv_heads, cfg.head_dim
        c["cross_k"] = jnp.zeros((Lc, batch, enc_len, K, hd), dt)
        c["cross_v"] = jnp.zeros((Lc, batch, enc_len, K, hd), dt)
    return c


def constrain_cache(c):
    out = dict(c)
    for name in ("k", "v"):
        if name in c:
            out[name] = constrain(c[name], None, "cache_batch", "cache_seq",
                                  "kv_heads", "head_dim")
    for name in ("k_scale", "v_scale"):
        if name in c:
            out[name] = constrain(c[name], None, "cache_batch", "cache_seq",
                                  "kv_heads")
    for name in ("ckv", "kpe"):
        if name in c:
            out[name] = constrain(c[name], None, "cache_batch", "cache_seq",
                                  None)
    for name in ("cross_k", "cross_v"):
        if name in c:
            out[name] = constrain(c[name], None, "cache_batch", None,
                                  "kv_heads", "head_dim")
    if "wkv_state" in c:
        out["wkv_state"] = constrain(c["wkv_state"], None, "cache_batch",
                                     "heads", None, None)
    if "ssm_h" in c:
        out["ssm_h"] = constrain(c["ssm_h"], None, "cache_batch", "ffn", None)
    return out


def _kv_quantize(x):
    """Symmetric int8 per-(batch, token, head) quantization of (B,1,K,hd)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float16)


# ------------------------------------------------------------- decode step --

def decode_step(cfg, params, cache, tokens, *, window=0, scan_layers=True):
    """One-token decode. tokens: (B,1). cache["pos"] is the absolute position
    of the incoming token; slot = pos % cache_len (ring buffer when the cache
    is shorter than the context — the sliding-window variant).

    ``cache["pos"]`` may also be a (B,) vector — each batch slot then decodes
    at its own absolute position with its own occupancy mask (the
    continuous-batching serving layout, where admissions and retirements give
    every slot an independent history length).

    ``scan_layers=False`` unrolls the layer loop in Python (per-layer param
    slices, no ``lax.scan``) — the fleet serving path uses it so the
    ``pdot``/``fleet_dot`` GEMMs, which execute on concrete operands,
    never sit inside compiled control flow; same values as the scan."""
    B = tokens.shape[0]
    x = L.embed_tokens(params["embed"], tokens, cfg)
    pos = cache["pos"]
    vec_pos = jnp.ndim(pos) == 1
    cache = constrain_cache(cache)

    cache_len = None
    for nm in ("k", "ckv"):
        if nm in cache:
            cache_len = cache[nm].shape[2]
    slot = pos % cache_len if cache_len is not None else 0
    if cache_len is not None:
        n_valid = jnp.minimum(pos + 1, cache_len)
        if vec_pos:
            valid = jnp.arange(cache_len)[None, :] < n_valid[:, None]
        else:
            valid = jnp.arange(cache_len) < n_valid
    else:
        valid = None

    def body(x, scanned):
        lp = scanned["layer"]
        new = {}
        if cfg.rwkv:
            hq = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
            # single-token time-mix via the recurrence directly
            y, tm_prev, s_last = R.time_mix(
                cfg, lp["time_mix"], hq, scanned["tm_prev"],
                scanned["wkv_state"], chunk=1)
            x = x + y
            h2 = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
            cm, cm_prev = R.channel_mix(cfg, lp["channel_mix"], h2,
                                        scanned["cm_prev"])
            x = x + cm
            new.update(wkv_state=s_last, tm_prev=hq[:, -1], cm_prev=h2[:, -1])
            return x, new

        h = L.rmsnorm(lp["ln1"], x, cfg.norm_eps)
        branch = None
        if cfg.mla:
            ao, nckv, nkpe = A.mla_decode(cfg, lp["attn"], h, pos,
                                          scanned["ckv"], scanned["kpe"],
                                          slot, valid)
            new.update(ckv_new=nckv, kpe_new=nkpe)   # (B,1,·) new entries
            branch = ao
        elif not cfg.attn_free:
            ck, cv = scanned["k"], scanned["v"]
            if "k_scale" in scanned:
                # int8 KV: dequantize this layer's slice (fuses into the
                # attention reduction)
                ck = (ck.astype(jnp.bfloat16)
                      * scanned["k_scale"][..., None].astype(jnp.bfloat16))
                cv = (cv.astype(jnp.bfloat16)
                      * scanned["v_scale"][..., None].astype(jnp.bfloat16))
            ao, nk, nv = A.attention_decode(cfg, lp["attn"], h, pos,
                                            ck, cv, slot, valid)
            if "k_scale" in scanned:
                nk, nks = _kv_quantize(nk)
                nv, nvs = _kv_quantize(nv)
                new.update(k_scale_new=nks, v_scale_new=nvs)
            new.update(k_new=nk, v_new=nv)           # (B,1,K,hd) new entries
            branch = ao
        if cfg.hybrid_parallel or (cfg.ssm and not cfg.rwkv):
            so, nh, nconv = SSM.ssm_decode(cfg, lp["ssm"], h,
                                           scanned["ssm_h"],
                                           scanned["ssm_conv"])
            new.update(ssm_h=nh, ssm_conv=nconv)
            branch = 0.5 * (branch + so) if branch is not None else so
        x = x + branch
        if cfg.enc_dec:
            from repro.models import encdec
            x = encdec.cross_layer_decode(
                cfg, scanned["cross"], x,
                (scanned["cross_k"], scanned["cross_v"]))
        h2 = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
        if cfg.moe:
            mo, _ = MOE.moe_block(cfg, lp["moe"], h2)
            x = x + mo
        else:
            x = x + L.swiglu(lp["mlp"], h2)
        return x, new

    scanned = {"layer": params["layers"]}
    for nm in ("k", "v", "ckv", "kpe", "wkv_state", "tm_prev", "cm_prev",
               "ssm_h", "ssm_conv", "cross_k", "cross_v"):
        if nm in cache:
            scanned[nm] = cache[nm]
    if cfg.enc_dec:
        scanned["cross"] = params["cross"]

    if scan_layers:
        x, new_stacked = jax.lax.scan(body, x, scanned)
    else:
        news = []
        for i in range(cfg.n_layers):
            sl = jax.tree.map(lambda t: t[i], scanned)
            x, new_i = body(x, sl)
            news.append(new_i)
        new_stacked = {k: jnp.stack([n[k] for n in news])
                       for k in (news[0] if news else {})}
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.lm_logits(params["head"], params["embed"], x, cfg)
    logits = logits.astype(jnp.float32) + _vocab_mask(cfg)

    new_cache = dict(cache)
    # KV-style caches: one small write of the stacked (L,B,1,...) new-token
    # entries at `slot` — never rewrite the full cache.
    writes = {"k_new": "k", "v_new": "v", "ckv_new": "ckv",
              "kpe_new": "kpe", "k_scale_new": "k_scale",
              "v_scale_new": "v_scale"}
    for src, dst in writes.items():
        if src in new_stacked:
            upd = new_stacked[src].astype(cache[dst].dtype)
            if vec_pos:
                # per-slot scatter: each batch slot writes its own sequence
                # index (continuous batching)
                new_cache[dst] = cache[dst].at[:, jnp.arange(B), slot].set(
                    upd[:, :, 0])
            else:
                start = (0, 0, slot) + (0,) * (cache[dst].ndim - 3)
                new_cache[dst] = jax.lax.dynamic_update_slice(
                    cache[dst], upd, start)
    # recurrent states are replaced wholesale (they are small)
    for nm in ("wkv_state", "tm_prev", "cm_prev", "ssm_h", "ssm_conv"):
        if nm in new_stacked:
            new_cache[nm] = new_stacked[nm]
    new_cache["pos"] = pos + 1
    # cross-kv is read-only during decode
    for nm in ("cross_k", "cross_v"):
        if nm in cache:
            new_cache[nm] = cache[nm]
    return logits[:, :, :], constrain_cache(new_cache)


def prefill(cfg, params, batch, *, window=0, q_chunk=256, k_chunk=512):
    """Forward over a full prompt, returning last-position logits and the
    filled decode cache (dense/MLA families; recurrent families return their
    final states)."""
    x, aux, kvs = forward(cfg, params, batch, window=window, q_chunk=q_chunk,
                          k_chunk=k_chunk, collect_kv=True)
    logits = L.lm_logits(params["head"], params["embed"], x[:, -1:], cfg)
    logits = logits.astype(jnp.float32) + _vocab_mask(cfg)
    B, S = batch["tokens"].shape
    cache = init_cache(cfg, B, S)
    if cfg.rwkv:
        cache["wkv_state"] = kvs[0]
        cache["tm_prev"] = kvs[1].astype(cache["tm_prev"].dtype)
        cache["cm_prev"] = kvs[2].astype(cache["cm_prev"].dtype)
    elif cfg.mla:
        cache["ckv"] = cache["ckv"].at[:, :, :S].set(kvs[0].astype(cache["ckv"].dtype))
        cache["kpe"] = cache["kpe"].at[:, :, :S].set(kvs[1].astype(cache["kpe"].dtype))
    elif not cfg.attn_free and kvs:
        cache["k"] = kvs[0].astype(cache["k"].dtype)
        cache["v"] = kvs[1].astype(cache["v"].dtype)
    cache["pos"] = jnp.full((), S, jnp.int32)
    return logits, cache
