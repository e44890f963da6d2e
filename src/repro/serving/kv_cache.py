"""PS-hosted paged KV cache for fleet-backed decode serving.

The parameter server owns one pool of fixed-size pages per cached tensor
(K/V for GQA families, compressed c_kv/k_pe for MLA), stacked over layers:

    k pool: (L, n_pages, K, page, hd)      v pool: same
    ckv pool: (L, n_pages, page, r)        kpe pool: (L, n_pages, page, rd)

K/V pages are head-major: one layer's page of one KV head is a whole
(page, hd) tile, the block the Pallas paged-decode kernel reads in place.

Each live request holds a page table — an ordered list of page ids — and a
token count.  Pages are reserved **at admission** for the request's whole
budget (prompt + max_new), so a request admitted once can never OOM
mid-decode; they return to the free list on retirement/eviction.

``gather`` materializes the per-step contiguous (L, B, Smax, ...) cache
views the decode step reads — the gather *is* the PS reading its own pages
(attention is PS-hosted; only projection GEMMs leave for the fleet).  The
same page tables drive the Pallas ``flash_decode_paged`` kernel
(``kernels.decode_attention``), which reads the pools **in place** on TPU —
``ServeSession(check_paged_read=True)`` cross-checks the two reads.

``kv_int8=True`` stores K/V int8 with per-(token, head) float16 scales —
the same symmetric quantization as ``models.model._kv_quantize`` (the
``--kv-int8`` monolithic path), so paged int8 decode is token-identical to
monolithic int8 decode.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core.spans import span

# pools whose pages are head-major: (L, n_pages, K, page, ...)
HEAD_MAJOR = ("k", "v", "k_scale", "v_scale")


def quantize_kv(x: np.ndarray):
    """Numpy twin of ``models.model._kv_quantize``: symmetric int8 over the
    trailing (head_dim) axis with per-(token, head) float16 scales."""
    scale = np.max(np.abs(x.astype(np.float32)), axis=-1) / 127.0
    scale = np.maximum(scale, 1e-8)
    q = np.clip(np.round(x.astype(np.float32) / scale[..., None]),
                -127, 127).astype(np.int8)
    return q, scale.astype(np.float16)


@dataclass
class PageTable:
    """One request's view of the pool: ordered page ids + token count."""
    rid: int
    pages: List[int]
    length: int = 0              # tokens written so far


@dataclass
class CacheStats:
    n_pages: int
    page_size: int
    n_free: int
    n_requests: int
    peak_pages_used: int

    @property
    def n_used(self) -> int:
        return self.n_pages - self.n_free

    @property
    def utilization(self) -> float:
        return self.n_used / max(self.n_pages, 1)


class PagedKVCache:
    """Fixed-page KV pool with per-request page tables (module docstring)."""

    def __init__(self, cfg, *, n_pages: int, page_size: int,
                 kv_int8: bool = False, dtype=np.float32):
        if cfg.rwkv or cfg.ssm or cfg.hybrid_parallel or cfg.attn_free \
                or cfg.enc_dec:
            raise ValueError(
                f"arch {cfg.name!r}: paged serving needs a KV-cache family "
                "(GQA/MHA or MLA); recurrent/enc-dec states are not paged")
        if kv_int8 and cfg.mla:
            raise ValueError("kv_int8 applies to K/V caches; MLA caches "
                             "the compressed c_kv/k_pe instead")
        self.cfg = cfg
        self.page = int(page_size)
        self.n_pages = int(n_pages)
        self.kv_int8 = bool(kv_int8)
        L = cfg.n_layers
        shp = (L, self.n_pages, self.page)
        if cfg.mla:
            self.pools: Dict[str, np.ndarray] = {
                "ckv": np.zeros(shp + (cfg.kv_lora_rank,), dtype),
                "kpe": np.zeros(shp + (cfg.rope_head_dim,), dtype),
            }
        else:
            K, hd = cfg.n_kv_heads, cfg.head_dim
            kv_dt = np.int8 if kv_int8 else dtype
            shp = (L, self.n_pages, K, self.page)
            self.pools = {
                "k": np.zeros(shp + (hd,), kv_dt),
                "v": np.zeros(shp + (hd,), kv_dt),
            }
            if kv_int8:
                self.pools["k_scale"] = np.zeros(shp, np.float16)
                self.pools["v_scale"] = np.zeros(shp, np.float16)
        self._free: List[int] = list(range(self.n_pages - 1, -1, -1))
        self.tables: Dict[int, PageTable] = {}
        self.peak_pages_used = 0

    # ------------------------------------------------------------ alloc/free --

    def pages_for(self, n_tokens: int) -> int:
        return max(1, math.ceil(n_tokens / self.page))

    def can_alloc(self, n_tokens: int) -> bool:
        return len(self._free) >= self.pages_for(n_tokens)

    def alloc(self, rid: int, n_tokens: int) -> PageTable:
        """Reserve pages for a request's full budget (prompt + max_new).
        Raises MemoryError when the free list is short — the batcher treats
        that as "not admissible yet"."""
        if rid in self.tables:
            raise ValueError(f"request {rid} already has pages")
        need = self.pages_for(n_tokens)
        if len(self._free) < need:
            raise MemoryError(
                f"request {rid}: {need} pages needed, "
                f"{len(self._free)} free")
        pt = PageTable(rid=rid, pages=[self._free.pop() for _ in range(need)])
        self.tables[rid] = pt
        used = self.n_pages - len(self._free)
        self.peak_pages_used = max(self.peak_pages_used, used)
        return pt

    def free(self, rid: int) -> None:
        """Retire a request: its pages return to the free list (zeroed lazily
        — the occupancy mask hides stale rows)."""
        pt = self.tables.pop(rid)
        self._free.extend(reversed(pt.pages))

    def stats(self) -> CacheStats:
        return CacheStats(n_pages=self.n_pages, page_size=self.page,
                          n_free=len(self._free),
                          n_requests=len(self.tables),
                          peak_pages_used=self.peak_pages_used)

    # --------------------------------------------------------------- writes --

    def _loc(self, rid: int, pos):
        """(page id, offset in page) of absolute position(s) ``pos`` of
        request ``rid``."""
        pos = np.asarray(pos)
        pages = np.asarray(self.tables[rid].pages, np.int64)
        return pages[pos // self.page], pos % self.page

    @staticmethod
    def _put(nm: str, pool: np.ndarray, pid, off, val) -> None:
        """Write token rows ``val`` (L, *idx, ...) at pages ``pid`` and
        offsets ``off``."""
        if nm in HEAD_MAJOR:
            # the page-id and offset indices sit either side of the head
            # slice, so numpy puts their dims first: (*idx, L, K, ...)
            pool[:, pid, :, off] = np.moveaxis(val, 1, 0)
        else:
            pool[:, pid, off] = val

    @staticmethod
    def _take(nm: str, pool: np.ndarray, pid, off) -> np.ndarray:
        """Token rows at pages ``pid`` and offsets ``off`` (both (B, S)),
        as (L, B, S, ...) with the head axis after the token axis."""
        if nm in HEAD_MAJOR:
            return np.moveaxis(pool[:, pid, :, off], 2, 0)
        return pool[:, pid, off]

    def write_prompt(self, rid: int, values: Dict[str, np.ndarray]) -> None:
        """Ingest a prefilled prompt: ``values[name]`` is (L, P, ...) —
        the per-layer new-token entries the prefill collected.  float K/V
        are quantized on write when the pool is int8."""
        values = dict(values)
        if self.kv_int8 and "k_scale" not in values:
            for nm in ("k", "v"):
                values[nm], values[nm + "_scale"] = quantize_kv(values[nm])
        P = next(iter(values.values())).shape[1]
        pid, off = self._loc(rid, np.arange(P))
        for nm, val in values.items():
            pool = self.pools[nm]
            self._put(nm, pool, pid, off, val.astype(pool.dtype, copy=False))
        self.tables[rid].length = max(self.tables[rid].length, P)

    def write_tokens(self, rids: Sequence[int], pos: Sequence[int],
                     values: Dict[str, np.ndarray]) -> None:
        """Scatter one step's new-token entries: ``values[name]`` is
        (L, B, ...) — already quantized when the pool is int8 (the decode
        step quantizes in-model, exactly like the monolithic path)."""
        if not len(rids):
            return
        locs = [self._loc(r, p) for r, p in zip(rids, pos)]
        pid = np.stack([pg for pg, _ in locs])
        off = np.stack([o for _, o in locs])
        for nm, val in values.items():
            pool = self.pools[nm]
            self._put(nm, pool, pid, off, val.astype(pool.dtype, copy=False))
        for r, p in zip(rids, pos):
            self.tables[r].length = max(self.tables[r].length, int(p) + 1)

    # -------------------------------------------------------------- gathers --

    def gather(self, rids: Sequence[Optional[int]], cache_len: int,
               phases: Optional[Dict[str, float]] = None
               ) -> Dict[str, np.ndarray]:
        """Contiguous (L, B, cache_len, ...) views for the decode step —
        one vectorized fancy-index per pool.  ``rids`` may contain ``None``
        (inactive batch slots → rows of page 0, hidden by the occupancy
        mask).  ``phases`` receives the ``cleave.kv.gather`` span's
        seconds under ``gather``."""
        with span("cleave.kv.gather", phases):
            pid = np.zeros((len(rids), cache_len), np.int64)
            off = np.zeros((len(rids), cache_len), np.int64)
            offs = np.arange(cache_len)
            for b, rid in enumerate(rids):
                if rid is None:
                    continue
                pt = self.tables[rid]
                cap = len(pt.pages) * self.page
                n = min(cache_len, cap)
                pid[b, :n], off[b, :n] = self._loc(rid, offs[:n])
            # (L, B, cache_len, ...)
            return {nm: self._take(nm, pool, pid, off)
                    for nm, pool in self.pools.items()}

    def page_table_array(self, rids: Sequence[Optional[int]]
                         ) -> "tuple[np.ndarray, np.ndarray]":
        """(B, max_pages) int32 page table + (B,) int32 lengths — the
        scalar-prefetch operands of ``kernels.flash_decode_paged``.
        Unused entries point at page 0 (masked by the length)."""
        maxp = max((len(self.tables[r].pages) for r in rids
                    if r is not None), default=1)
        pt = np.zeros((len(rids), maxp), np.int32)
        ln = np.zeros((len(rids),), np.int32)
        for b, rid in enumerate(rids):
            if rid is None:
                continue
            t = self.tables[rid]
            pt[b, :len(t.pages)] = t.pages
            ln[b] = t.length
        return pt, ln
