"""Pallas TPU flash-decode kernel: single-token attention over a long KV
cache (the decode_32k / long_500k hot path).

Grid: (B*H, cache_blocks) with the cache axis innermost/sequential; running
(max, denom, accumulator) live in VMEM scratch — the kernel analog of
``repro.models.attention.decode_attention`` / ``_decode_attention_sharded``
(per-shard partial scores + LSE combine; across devices the combine is the
shard_map pmax/psum, inside a device it is this kernel's sequential grid).

``flash_decode_paged`` is the serving variant: the same online softmax, but
the KV blocks come straight out of the paged page pools via scalar-prefetched
per-request page tables (``serving.PagedKVCache``) — no contiguous gather.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_HIGHEST = jax.lax.Precision.HIGHEST


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale, bs, ns):
    sj = pl.program_id(1)

    @pl.when(sj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (1, d)
    k = k_ref[0].astype(jnp.float32)                  # (bs, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (1, bs)
    ok = valid_ref[0].reshape(1, bs)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s))
    p = jnp.exp(s - m_new) * ok                       # (1, bs)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p)
    pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (1, d)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new

    @pl.when(sj == ns - 1)
    def _store():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_kernel(pt_ref, ln_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale, page, maxp):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale       # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)               # (page, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=_HIGHEST,
                            preferred_element_type=jnp.float32)  # (G, page)
    tok = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    ok = tok < ln_ref[b]
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new) * ok                       # (G, page)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p, v_ref[0, 0].astype(jnp.float32),
                             (((1,), (0,)), ((), ())),
                             precision=_HIGHEST,
                             preferred_element_type=jnp.float32)  # (G, D)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new

    @pl.when(j == maxp - 1)
    def _store():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_decode_paged(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                       page_table: jax.Array, lengths: jax.Array, *,
                       interpret: bool = False):
    """Paged-KV flash decode: attention reads the serving page pools **in
    place**, steered by scalar-prefetched per-request page tables — no
    contiguous gather (the TPU twin of ``serving.PagedKVCache.gather``).

    q: (B, K, G, D) grouped queries; k_pool/v_pool: (P, K, page, D) page
    pools of one layer, head-major within a page; page_table: (B, maxp)
    int32 page ids (entries past a request's allocation point anywhere —
    masked); lengths: (B,) int32 occupied tokens per request.  Returns
    (B, K, G, D).  Scores and values are contracted in float32.

    Grid (B, K, maxp), page axis innermost: the page table is prefetched
    (``PrefetchScalarGridSpec``), so each step's k/v block DMA is indexed
    ``pool[page_table[b, j], kv_head]`` — the kernel walks each request's
    scattered pages in order while the running (max, denom, acc) live in
    VMEM.  Head-major pages make each block a whole (page, D) tile, the
    shape the TPU's block tiling accepts (a (page, 1, D) slice of a
    token-major page is not)."""
    B, K, G, D = q.shape
    P, page = k_pool.shape[0], k_pool.shape[2]
    maxp = page_table.shape[1]
    scale = 1.0 / np.sqrt(D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, kh, j, pt, ln: (b, kh, 0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda b, kh, j, pt, ln: (pt[b, j], kh, 0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda b, kh, j, pt, ln: (pt[b, j], kh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, kh, j, pt, ln: (b, kh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, page=page, maxp=maxp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), v_pool.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), q,
      k_pool, v_pool)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 valid: jax.Array, *, bs: int = 512,
                 interpret: bool = False):
    """q: (BH, 1, D); k, v: (BH, S, D); valid: (BH, S) bool (ring-buffer
    occupancy mask).  Returns (BH, 1, D)."""
    BH, S, D = k.shape
    bs = min(bs, S)
    assert S % bs == 0, (S, bs)
    grid = (BH, S // bs)
    scale = 1.0 / np.sqrt(D)
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bs=bs, ns=grid[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda h, j: (h, 0, 0)),
            pl.BlockSpec((1, bs, D), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, bs, D), lambda h, j: (h, j, 0)),
            pl.BlockSpec((1, bs), lambda h, j: (h, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda h, j: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, 1, D), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, valid)
