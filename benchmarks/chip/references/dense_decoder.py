"""Plain reference of the dense decoder that the OPT-width configurations
run: pre-norm blocks of RMSNorm, multi-head causal attention with rotary
positions, a gated SiLU MLP, a final RMSNorm and an output head, tied to
the token embedding where the configuration says so; AdamW with
global-norm clipping.

Straightforward ``jax.numpy`` in float32 with every matmul at HIGHEST
precision, no kernels, no cache and no batching tricks.  It imports
nothing of the program: its weights come from ``weights.make`` for the same
seed.  Parameters are stored in the configuration's ``param_dtype``
(bfloat16) between optimizer steps, as the configuration states.

``precision="fp8"`` is the control: every projection GEMM (forward and
both backward mirrors) takes float8_e4m3 operands with a per-tensor scale
and accumulates in float32 -- the step below bfloat16 that would tempt a
faster path.

The module also owns what is particular to this architecture: the weight
layout (``leaf_specs``, which ``weights.make`` builds from the seed) and
the operation counts (``matmul_params``, ``train_flops_per_token``,
``decode_flops``, which ``flops`` calls for the ``mfu.*`` readers).  A
configuration of another architecture names a reference module of its own
that defines ``leaf_specs``, ``train_flops_per_token`` and
``decode_flops``.
"""
from __future__ import annotations

import functools
import math
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.compare import leaf_norms

HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------ layout and counts --

def padded_vocab(config: dict) -> int:
    """The embedding's rows: the vocabulary rounded up to 256, as the
    program's initializer pads it."""
    return 256 * math.ceil(int(config["vocab_size"]) / 256)


def leaf_specs(config: dict) -> dict:
    """name path -> (shape, init): ``("normal", std)`` or ``("ones",)``."""
    L, d = int(config["n_layers"]), int(config["d_model"])
    H, K = int(config["n_heads"]), int(config["n_kv_heads"])
    hd, ff, V = int(config["head_dim"]), int(config["d_ff"]), padded_vocab(config)

    def dense(fan_in, fan_out, stacked=True):
        shape = (L, fan_in, fan_out) if stacked else (fan_in, fan_out)
        return shape, ("normal", 1.0 / math.sqrt(fan_in))

    specs = {
        ("embed", "tok"): ((V, d), ("normal", 0.02)),
        ("layers", "ln1", "scale"): ((L, d), ("ones",)),
        ("layers", "attn", "wq"): dense(d, H * hd),
        ("layers", "attn", "wk"): dense(d, K * hd),
        ("layers", "attn", "wv"): dense(d, K * hd),
        ("layers", "attn", "wo"): dense(H * hd, d),
        ("layers", "ln2", "scale"): ((L, d), ("ones",)),
        ("layers", "mlp", "w_gate"): dense(d, ff),
        ("layers", "mlp", "w_up"): dense(d, ff),
        ("layers", "mlp", "w_down"): dense(ff, d),
        ("final_norm", "scale"): ((d,), ("ones",)),
    }
    if not config.get("tie_embeddings"):
        specs[("head", "w")] = dense(d, V, stacked=False)
    return specs


def matmul_params(config: dict) -> int:
    """Parameters that take part in a matmul per token: every projection
    and the output head (over the published vocabulary)."""
    L, d = int(config["n_layers"]), int(config["d_model"])
    H, K = int(config["n_heads"]), int(config["n_kv_heads"])
    hd, ff = int(config["head_dim"]), int(config["d_ff"])
    attn = d * H * hd + 2 * d * K * hd + H * hd * d
    mlp = 3 * d * ff
    return L * (attn + mlp) + d * int(config["vocab_size"])


def train_flops_per_token(config: dict, seq: int) -> float:
    """PaLM's count (arXiv:2204.02311, appendix B): 6 N + 12 L H Q T, N the
    matmul parameters, L layers, H heads of size Q, T the sequence."""
    L, H, Q = (int(config["n_layers"]), int(config["n_heads"]),
               int(config["head_dim"]))
    return 6.0 * matmul_params(config) + 12.0 * L * H * Q * seq


def decode_flops(config: dict, contexts: Iterable[int]) -> float:
    """FLOPs of generating one token at each context length given: 2 N,
    plus 4 L d ctx for attention over the token's own context."""
    n2 = 2.0 * matmul_params(config)
    a = 4.0 * int(config["n_layers"]) * int(config["d_model"])
    return sum(n2 + a * c for c in contexts)


# -------------------------------------------------------------- matmuls --

def _mm_exact(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _q8(x):
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _mm_fp8(a, b):
    return jnp.matmul(_q8(a), _q8(b), precision=HIGHEST)


def _mm_fp8_fwd(a, b):
    return _mm_fp8(a, b), (a, b)


def _mm_fp8_bwd(res, g):
    a, b = res
    gq = _q8(g)
    return (jnp.matmul(gq, _q8(b).T, precision=HIGHEST),
            jnp.matmul(_q8(a).T, gq, precision=HIGHEST))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)

MATMULS = {"f32": _mm_exact, "fp8": _mm_fp8}


def _proj(mm, x, w):
    """x (..., n) @ w (n, q) as one 2-D GEMM."""
    lead = x.shape[:-1]
    return mm(x.reshape(-1, x.shape[-1]), w).reshape(lead + (w.shape[-1],))


# ---------------------------------------------------------------- model --

def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., None, None] * freq     # (B,S,1,half)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def hidden(config, params, tokens, mm):
    """Final normed hidden states (B, S, d) of a causal forward pass."""
    eps, theta = float(config["norm_eps"]), float(config["rope_theta"])
    H, K, hd = (int(config["n_heads"]), int(config["n_kv_heads"]),
                int(config["head_dim"]))
    B, S = tokens.shape
    p32 = jax.tree.map(lambda t: t.astype(jnp.float32), params)
    x = p32["embed"]["tok"][tokens]
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    causal = jnp.tril(jnp.ones((S, S), bool))
    lay = p32["layers"]
    for i in range(int(config["n_layers"])):
        h = _rmsnorm(x, lay["ln1"]["scale"][i], eps)
        q = _proj(mm, h, lay["attn"]["wq"][i]).reshape(B, S, H, hd)
        k = _proj(mm, h, lay["attn"]["wk"][i]).reshape(B, S, K, hd)
        v = _proj(mm, h, lay["attn"]["wv"][i]).reshape(B, S, K, hd)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k, v = jnp.repeat(k, H // K, 2), jnp.repeat(v, H // K, 2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
        s = jnp.where(causal, s / math.sqrt(hd), -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                       precision=HIGHEST).reshape(B, S, H * hd)
        x = x + _proj(mm, a, lay["attn"]["wo"][i])
        h = _rmsnorm(x, lay["ln2"]["scale"][i], eps)
        g = _proj(mm, h, lay["mlp"]["w_gate"][i])
        u = _proj(mm, h, lay["mlp"]["w_up"][i])
        x = x + _proj(mm, jax.nn.silu(g) * u, lay["mlp"]["w_down"][i])
    return _rmsnorm(x, p32["final_norm"]["scale"], eps)


def logits(config, params, x, mm):
    V = int(config["vocab_size"])
    w = (params["embed"]["tok"].T if config.get("tie_embeddings")
         else params["head"]["w"])
    return _proj(mm, x, w.astype(jnp.float32))[..., :V]


def loss(config, params, batch, mm):
    """Mean next-token cross-entropy over every label."""
    lg = logits(config, params, hidden(config, params, batch["tokens"], mm),
                mm)
    lse = jax.nn.logsumexp(lg, -1)
    picked = jnp.take_along_axis(lg, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


# ------------------------------------------------------------- training --

def _adamw(opt, params, grads, m, v, t):
    """One AdamW step (``t``, a float32 scalar, is 1-based); returns the
    clipped gradients too."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = float(opt["grad_clip"])
    scale = jnp.minimum(1.0, clip / (gn + 1e-12)) if clip else 1.0
    g = jax.tree.map(lambda x: x * scale, grads)
    b1, b2 = float(opt["b1"]), float(opt["b2"])
    warmup, total = int(opt["warmup_steps"]), int(opt["total_steps"])
    warm = jnp.minimum(t / max(warmup, 1), 1.0)
    prog = jnp.clip((t - warmup) / max(total - warmup, 1), 0.0, 1.0)
    lo = float(opt["min_lr_ratio"])
    lr = float(opt["lr"]) * warm * (lo + (1 - lo) * 0.5
                                    * (1 + jnp.cos(jnp.pi * prog)))
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)

    def upd(p, mi, vi):
        p32 = p.astype(jnp.float32)
        step = (mi / (1 - b1 ** t)) / (jnp.sqrt(vi / (1 - b2 ** t))
                                       + float(opt["eps"]))
        return (p32 - lr * (step + float(opt["weight_decay"]) * p32)
                ).astype(p.dtype)

    return jax.tree.map(upd, params, m, v), m, v, g


@functools.lru_cache(maxsize=None)
def _train_step(config_items, precision, rows):
    config = dict(config_items)
    opt = dict(config["optimizer"])
    mm = MATMULS[precision]

    def step(params, m, v, t, batch):
        if rows is not None:           # a fault: the mean over some rows
            batch = {k: a[:rows] for k, a in batch.items()}
        lval, grads = jax.value_and_grad(
            lambda p32: loss(config, p32, batch, mm))(
            jax.tree.map(lambda a: a.astype(jnp.float32), params))
        params, m, v, g = _adamw(opt, params, grads, m, v, t)
        return params, m, v, lval, g

    return jax.jit(step)


def _frozen(config):
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                         else v) for k, v in config.items()
                        if k in _MODEL_KEYS))


_MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
               "d_ff", "vocab_size", "norm_eps", "rope_theta",
               "tie_embeddings", "optimizer")


def train(config, params, batches, *, precision="f32", rows=None):
    """Follow the program's first ``len(batches)`` steps from ``params``.

    Returns the loss of each step, the norm of each leaf of the first
    step's gradient as the optimizer gets it (clipped), and the norm of
    each leaf's change over all the steps."""
    frozen = _frozen(config)
    p0 = params
    m = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    v = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    losses, grad1 = [], None
    for t, batch in enumerate(batches, start=1):
        fn = _train_step(frozen, precision, rows)
        params, m, v, lval, g = fn(
            params, m, v, jnp.float32(t),
            {k: jnp.asarray(a) for k, a in batch.items()})
        losses.append(float(lval))
        if grad1 is None:
            grad1 = leaf_norms(g)
        del g
    delta = leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        params, p0))
    return {"losses": losses, "grad_norms": grad1, "delta_norms": delta}


# --------------------------------------------------------------- decode --

@functools.lru_cache(maxsize=None)
def _logits_at(config_items, precision):
    config = dict(config_items)
    mm = MATMULS[precision]

    def fn(params, tokens, positions):
        x = hidden(config, params, tokens, mm)
        xs = jnp.take_along_axis(x, positions[..., None], 1)
        return logits(config, params, xs, mm)

    return jax.jit(fn)


def logits_at(config, params, tokens, positions, *, precision="f32"):
    """Logits (B, K, vocab) of a causal forward over ``tokens`` (B, S), read
    at ``positions`` (B, K)."""
    return _logits_at(_frozen(config), precision)(
        params, jnp.asarray(tokens), jnp.asarray(positions))


def served_logits(config, params, prompts, served, seq_len, *,
                  precision="f32", block=2):
    """For each request, the logits at every position whose next token was
    served: position P-1+i predicts served token i.  Returns a list of
    (n_i, vocab) float32 arrays."""
    n_max = max(len(s) for s in served)
    K = 64 * math.ceil(n_max / 64)
    out = []
    for lo in range(0, len(prompts), block):
        idx = list(range(lo, min(lo + block, len(prompts))))
        toks = np.zeros((block, seq_len), np.int32)
        pos = np.zeros((block, K), np.int32)
        for r, i in enumerate(idx):
            seq = np.concatenate([prompts[i], np.asarray(served[i][:-1],
                                                         np.int32)])
            toks[r, :len(seq)] = seq
            n = len(served[i])
            pos[r, :n] = len(prompts[i]) - 1 + np.arange(n)
        lg = np.asarray(logits_at(config, params, toks, pos,
                                  precision=precision))
        for r, i in enumerate(idx):
            out.append(lg[r, :len(served[i])])
    return out
