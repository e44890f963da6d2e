"""jit'd wrappers around the Pallas kernels: shape padding, GQA head
expansion, backend dispatch (compiled on TPU; interpret=True on CPU, where
kernels execute in Python for correctness validation; any other backend is
an error).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spans import span
from repro.kernels import block_gemm as _bg
from repro.kernels import flash_attention as _fa
from repro.kernels import wkv6 as _wkv


def _interpret() -> bool:
    """Pallas interpret mode: off on TPU, on for the CPU test path.  Any
    other backend has no compiled lowering here and is refused rather than
    silently interpreted."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run compiled on TPU or interpreted "
                       f"on CPU; the default backend is {backend!r}")


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def block_gemm(a, b, *, bm=128, bn=128, bk=128):
    """Padded/tiled C = A @ B through the Pallas sub-GEMM kernel."""
    m, k = a.shape
    _, n = b.shape
    bm2, bn2, bk2 = min(bm, m), min(bn, n), min(bk, k)
    a, pm = _pad_to(a, bm2, 0)
    a, pk = _pad_to(a, bk2, 1)
    b, _ = _pad_to(b, bk2, 0)
    b, pn = _pad_to(b, bn2, 1)
    out = _bg.block_gemm(a, b, bm=bm2, bn=bn2, bk=bk2,
                         interpret=_interpret())
    return out[:m, :n]


# ------------------------------------------------------- plan execution ----

class PadCache:
    """Small keyed cache of the device-resident zero-padded copies of host
    operands (device operands are padded in place, :func:`_staged_pad`).

    ``plan_gemm``'s padded ``a_pad``/``b_pad`` staging used to rebuild two
    full host copies (``np.zeros`` + fill + ``jnp.asarray``) on every call;
    a runtime step loop calls ``plan_gemm`` once per level GEMM with the
    same operands, so the padded device arrays are cached keyed by
    ``(role, source shape, padded shape)`` plus a full-buffer content
    fingerprint (adler32 over the raw bytes, read as ``uint8`` so that
    ml_dtypes sources such as bfloat16 hash too; ~40% of the staging cost).
    Content keying makes the cache safe under the common training pattern
    of *in-place* operand updates between steps — a mutated array simply
    fingerprints as a miss instead of serving a stale device copy.
    Non-contiguous sources skip the cache (fingerprinting them would cost
    a copy anyway).

    Access is serialized by an RLock: the dataflow dispatcher's prefetch
    pool stages the next node's operands (:func:`stage_plan_operands`)
    while the current node's compute thread reads the same cache, so the
    MRU list mutations must not race.  ``build`` runs under the lock —
    double-buffered staging relies on a prefetched entry being fully
    device-resident before a concurrent reader can hit its key.
    """

    def __init__(self, capacity: int = 8):
        import threading
        self.capacity = capacity
        self._slots: list = []      # (key, value), MRU first
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0

    @staticmethod
    def fingerprint(src) -> "int | None":
        import zlib
        if not src.flags.c_contiguous:
            return None
        return zlib.adler32(src.reshape(-1).view(np.uint8))

    def get(self, src, key, build):
        fp = self.fingerprint(src)
        if fp is None:
            return build()          # non-contiguous source: skip caching
        key = key + (fp,)
        with self._lock:
            for i, (k, val) in enumerate(self._slots):
                if k == key:
                    if i:
                        self._slots.insert(0, self._slots.pop(i))
                    self.hits += 1
                    return val
            val = build()
            self.misses += 1
            self._slots.insert(0, (key, val))
            del self._slots[self.capacity:]
            return val


def _operand(x):
    """A device array as it is; anything else as a host array."""
    return x if isinstance(x, jax.Array) else np.asarray(x)


@functools.partial(jax.jit, static_argnames=("rows", "cols", "dtype"))
def _device_pad(x, *, rows, cols, dtype):
    return jnp.pad(x.astype(dtype),
                   ((0, rows - x.shape[0]), (0, cols - x.shape[1])))


def _staged_pad(arr, rows: int, cols: int, role: str,
                cache: "PadCache | None", compute_dtype=None):
    """Zero-pad ``arr`` to (rows, cols) on the device.

    A device array (``jax.Array``) is padded where it lives, by one jitted
    pad per (shape, dtype, padded shape), in its own dtype or in
    ``compute_dtype`` where that is narrower: the kernels cast to the
    compute dtype before any arithmetic, so either gives them the bits a
    float32 pad would.  It comes back as it is where it needs neither pad
    nor cast, and skips the cache (a fingerprint would need the host copy
    this path avoids).  A host array is padded in float32 on the host and
    uploaded, through the cache when one is provided."""
    if isinstance(arr, jax.Array):
        dtype = jnp.dtype(compute_dtype)
        if dtype.itemsize >= arr.dtype.itemsize:
            dtype = arr.dtype
        if arr.shape == (rows, cols) and arr.dtype == dtype:
            return arr
        return _device_pad(arr, rows=rows, cols=cols, dtype=dtype)

    def build():
        padded = np.zeros((rows, cols), np.float32)
        padded[:arr.shape[0], :arr.shape[1]] = arr
        return jnp.asarray(padded)
    if cache is None:
        return build()
    return cache.get(arr, (role, arr.shape, rows, cols), build)


def resolve_plan_kernel(kernel: str = "auto") -> str:
    """``"pallas"`` on TPU (the compiled block_gemm grid), ``"xla"`` on
    hosts without one (batched dot through XLA — the meaningful compiled
    CPU path; ``kernel="pallas"`` off-TPU still works via interpret=True
    and is what the CPU parity tests pin)."""
    if kernel == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if kernel not in ("pallas", "xla"):
        raise ValueError(f"unknown plan_gemm kernel {kernel!r}; "
                         "expected 'auto', 'pallas', or 'xla'")
    return kernel


def _gather_bands(a_pad, r0s, pm, compute_dtype):
    nk = a_pad.shape[1]

    def ga(r0):
        return jax.lax.dynamic_slice(a_pad, (r0, 0), (pm, nk))

    return jax.vmap(ga)(r0s).astype(compute_dtype)


def _band_matmul(As, b_op, bm, bn, bk, kernel):
    if kernel == "xla":
        return jnp.einsum("gmk,kq->gmq", As, b_op,
                          preferred_element_type=jnp.float32)
    return _bg.block_gemm_batched_shared(As, b_op, bm=bm, bn=bn, bk=bk,
                                         out_dtype=jnp.float32,
                                         interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("pm", "bm", "bn", "bk", "kernel",
                                    "compute_dtype"))
def _bucket_gemm(a_pad, b_pad, r0s, *, pm, bm, bn, bk, kernel,
                 compute_dtype):
    """One band bucket: gather every row band's A rows on-device (vmapped
    dynamic_slice), cast to the policy compute dtype, and run the whole
    bucket as ONE batched kernel launch against the *shared* padded B with
    f32 accumulation.  A CLEAVE grid partition's rectangles tile each band
    across the full output width, so banding needs no B-side gather at all
    — per-rectangle blocks are column windows of the band products."""
    As = _gather_bands(a_pad, r0s, pm, compute_dtype)
    return _band_matmul(As, b_pad.astype(compute_dtype), bm, bn, bk, kernel)


@functools.partial(jax.jit,
                   static_argnames=("pm", "R", "bm", "bn", "bk", "kernel",
                                    "compute_dtype", "iters"))
def _bucket_gemm_verified(a_pad, b_pad, r0s, hs, bidx, slot, c0s, c1s,
                          corrupt, key, task_ids, *, pm, R, bm, bn, bk,
                          kernel, compute_dtype, iters):
    """:func:`_bucket_gemm` plus device-side batched Freivalds residuals in
    the same launch (§6 on the accelerator substrate).

    Per rectangle: sign vectors ``r`` (iters × band rows) and ``s``
    (iters × output cols) are drawn on device from the threaded ``key``
    folded with the rectangle's global task id (so draws are independent of
    bucketing), masked to the rectangle's rows/columns, and the check
    reduces to three extra batched matvec chains — ``t = B s``,
    ``lhs = r·(A t)`` vs ``rhs = (r·C)·s`` — plus the ``|r|·|C|·|s|`` noise
    scale (= Σ|C| over the rectangle).  Rectangles are grouped
    ``(band, slot)`` so the band-shared ``A`` and ``C`` contractions batch
    across the bucket.  ``corrupt`` models a poisoning device: flagged
    rectangles get the same ``C[0,0] += 1 + |C[0,0]|`` injection the numpy
    executor applies, so the residual sees exactly the block the PS would
    receive.  Returns ``(C_bands, lhs, rhs, scale)``; the executor compares
    against the dtype policy's per-block tolerance on the host (per-rect
    scalars, not blocks)."""
    As = _gather_bands(a_pad, r0s, pm, compute_dtype)
    b_op = b_pad.astype(compute_dtype)
    C = _band_matmul(As, b_op, bm, bn, bk, kernel)
    qk = C.shape[2]
    Gb = r0s.shape[0]
    # device-side poisoning: each corrupt rect's block origin is (band
    # row 0, its first column) in the band product
    c00 = C[bidx, 0, c0s]
    C = C.at[bidx, 0, c0s].add(corrupt * (1.0 + jnp.abs(c00)))

    def draw(ti):
        k = jax.random.fold_in(key, ti)
        kr, ks = jax.random.split(k)
        return (jax.random.rademacher(kr, (iters, pm), jnp.float32),
                jax.random.rademacher(ks, (iters, qk), jnp.float32))

    r, s = jax.vmap(draw)(task_ids)          # (Gr, iters, pm/qk)
    rowm = (jnp.arange(pm)[None, :] < hs[:, None]).astype(jnp.float32)
    cols = jnp.arange(qk)[None, :]
    colm = ((cols >= c0s[:, None]) & (cols < c1s[:, None])) \
        .astype(jnp.float32)                 # (Gr, qk)
    r = r * rowm[bidx][:, None, :]
    s = s * colm[:, None, :]
    Af = As.astype(jnp.float32)
    Bf = b_op.astype(jnp.float32)
    # lhs = r · (A_band (B s)): B s per rect, then one grouped contraction
    # against each band's shared A rows
    t = jnp.einsum("kq,riq->rki", Bf, s, preferred_element_type=jnp.float32)
    t_g = jnp.zeros((Gb, R) + t.shape[1:], jnp.float32) \
        .at[bidx, slot].set(t)
    u = jnp.einsum("bmk,brki->bmri", Af, t_g,
                   preferred_element_type=jnp.float32)
    r_g = jnp.zeros((Gb, R, iters, pm), jnp.float32).at[bidx, slot].set(r)
    lhs = jnp.einsum("brim,bmri->bri", r_g, u,
                     preferred_element_type=jnp.float32)[bidx, slot]
    # rhs = (r · C) · s, contracted s-first so the intermediate stays tiny
    s_g = jnp.zeros((Gb, R, iters, qk), jnp.float32).at[bidx, slot].set(s)
    Cs = jnp.einsum("bmq,briq->bmri", C, s_g,
                    preferred_element_type=jnp.float32)
    rhs = jnp.einsum("brim,bmri->bri", r_g, Cs,
                     preferred_element_type=jnp.float32)[bidx, slot]
    colm_g = jnp.zeros((Gb, R, qk), jnp.float32).at[bidx, slot].set(colm)
    Csa = jnp.einsum("bmq,brq->bmr", jnp.abs(C), colm_g,
                     preferred_element_type=jnp.float32)
    scale = jnp.einsum("bm,bmr->br", rowm, Csa,
                       preferred_element_type=jnp.float32)[bidx, slot]
    return C, lhs, rhs, scale


@dataclasses.dataclass
class BucketRun:
    """One band bucket's batched launch result.

    Bands (distinct ``(r0, r1)`` row ranges, padded to a common height
    ``pm``) carry the computed products; rectangles map onto them via
    ``bidx`` and their column windows."""
    idx: np.ndarray          # rect indices into the caller's rects
    pm: int                  # padded band height
    q: int                   # un-padded output width (out is (Gb, pm, qk))
    band_r0s: np.ndarray     # (Gb,) band origins
    band_hs: np.ndarray      # (Gb,) un-padded band heights
    bidx: np.ndarray         # (Gr,) band of each rect
    c0s: np.ndarray          # (Gr,) rect column windows
    c1s: np.ndarray
    out: np.ndarray          # (Gb, pm, qk) float32 band products
    lhs: Optional[np.ndarray] = None     # (Gr, iters) Freivalds residuals
    rhs: Optional[np.ndarray] = None
    scale: Optional[np.ndarray] = None   # (Gr,) Σ|C| noise scale
    padded_flops: float = 0.0    # 2 · bands · pm · nk · qk: what ran

    def block(self, g: int) -> np.ndarray:
        """Rect ``g``'s un-padded block view into its band product."""
        b = self.bidx[g]
        return self.out[b, :self.band_hs[b], self.c0s[g]:self.c1s[g]]


def _bucket_geometry(a_shape, b_shape, rects, block):
    """The shared band/bucket/padding geometry of a rect set: MXU-aligned
    padded depths (nk, qk), row bands, and padded-height buckets.  Single
    source for :func:`plan_gemm_buckets` and :func:`stage_plan_operands`,
    so a prefetched padded operand lands on exactly the key the launch
    will look up."""
    n = a_shape[1]
    q = b_shape[1]
    nk = max(-(-n // block) * block, block)
    qk = max(-(-q // block) * block, block)
    bands: dict = {}                     # (r0, r1) -> [rect index, ...]
    for i, (r0, r1, c0, c1) in enumerate(rects):
        if r1 - r0 <= 0 or c1 - c0 <= 0:
            continue
        bands.setdefault((r0, r1), []).append(i)
    buckets: dict = {}                   # pm -> [(r0, r1), ...]
    for (r0, r1) in bands:
        pm = -(-(r1 - r0) // block) * block
        buckets.setdefault(pm, []).append((r0, r1))
    return nk, qk, bands, buckets


def stage_plan_operands(a, b, rects, *, block=128,
                        pad_cache: Optional[PadCache] = None):
    """Pre-stage the padded device operands :func:`plan_gemm_buckets`
    would build for ``rects`` — same geometry, same cache keys — so the
    dataflow dispatcher's prefetch pool can double-buffer the next node's
    gathers against the current node's compute.  Returns
    ``(a_pad, b_pad)`` (or ``(None, None)`` for an empty rect set, or
    where an operand is already on the device: its pad is made at the
    launch, and nothing caches it)."""
    if isinstance(a, jax.Array) or isinstance(b, jax.Array):
        return None, None
    a = np.asarray(a)
    b = np.asarray(b)
    nk, qk, bands, buckets = _bucket_geometry(a.shape, b.shape, rects, block)
    if not bands:
        return None, None
    pmax = max(buckets)
    with span("cleave.fleet.stage"):
        a_pad = _staged_pad(a, a.shape[0] + pmax, nk, "a", pad_cache)
        b_pad = _staged_pad(b, nk, qk, "b", pad_cache)
    return a_pad, b_pad


def plan_gemm_buckets(a, b, rects, *, block=128, kernel="auto",
                      compute_dtype=None, verify_seed=None,
                      freivalds_iters: int = 2, corrupt=None,
                      pad_cache: Optional[PadCache] = None,
                      phases: Optional[dict] = None):
    """Bucketed execution of output rectangles of C = A @ B — the fleet
    executor's primitive.

    Rectangles (``(r0, r1, c0, c1)``; degenerate ones are skipped) are
    grouped into row *bands* (distinct row ranges — a CLEAVE grid
    partition's native structure), bands are bucketed by MXU-aligned padded
    height, and each bucket runs as ONE batched kernel launch of its
    gathered A row bands against the shared padded B
    (``kernels.block_gemm.block_gemm_batched_shared`` for
    ``kernel="pallas"``, a batched XLA dot for ``"xla"``).  Nothing on the
    B side is gathered or replicated, and the band products cover every
    rectangle in the band as column windows.

    With ``verify_seed`` set, the launch also emits per-rect Freivalds
    residuals (see :func:`_bucket_gemm_verified`); ``corrupt`` is an
    optional per-rect flag vector of simulated poisoning devices.
    Operands may be host arrays or device arrays (``jax.Array``); only
    their shapes and dtypes are read on the host.  A device operand is
    padded on the device, in its own dtype or the narrower compute dtype;
    a host operand is padded in float32 and uploaded, reusing
    device-resident padded operands across calls through ``pad_cache``
    (see :class:`PadCache`, :func:`_staged_pad`).  ``phases``, when given,
    receives the host seconds of the ``tasks`` (bucket geometry),
    ``stage`` (the pads of both operands and the verify key's upload: for
    a device operand, the pad's dispatch), ``kernel`` (each launch until
    its outputs, and what they wait on, are ready) and ``fetch`` (the
    outputs' copy to the host) spans.  Returns a list of
    :class:`BucketRun`.
    """
    kernel = resolve_plan_kernel(kernel)
    if compute_dtype is None:
        compute_dtype = ("bfloat16" if jax.default_backend() == "tpu"
                         else "float32")
    a, b = _operand(a), _operand(b)
    m, n = a.shape
    q = b.shape[1]
    with span("cleave.fleet.tasks", phases):
        nk, qk, bands, buckets = _bucket_geometry(a.shape, b.shape, rects,
                                                  block)
    runs: list = []
    if not bands:
        return runs
    # pad once: rows past the edge make every band gather legal
    pmax = max(buckets)
    with span("cleave.fleet.stage", phases):
        a_pad = _staged_pad(a, m + pmax, nk, "a", pad_cache, compute_dtype)
        b_pad = _staged_pad(b, nk, qk, "b", pad_cache, compute_dtype)
        key = (jax.random.PRNGKey(verify_seed) if verify_seed is not None
               else None)
    for pm, bucket_bands in buckets.items():
        with span("cleave.fleet.tasks", phases):
            r0s = np.asarray([r0 for r0, _ in bucket_bands], np.int32)
            hs = np.asarray([r1 - r0 for r0, r1 in bucket_bands], np.int32)
            ia, bidx, slot = [], [], []
            for bi, bk_ in enumerate(bucket_bands):
                for si, i in enumerate(bands[bk_]):
                    ia.append(i)
                    bidx.append(bi)
                    slot.append(si)
            ia = np.asarray(ia, np.int64)
            bidx = np.asarray(bidx, np.int32)
            slot = np.asarray(slot, np.int32)
            c0s = np.asarray([rects[i][2] for i in ia], np.int32)
            c1s = np.asarray([rects[i][3] for i in ia], np.int32)
            bm, bn, bk = min(block, pm), min(block, qk), min(block, nk)
            run = BucketRun(idx=ia, pm=pm, q=q, band_r0s=r0s, band_hs=hs,
                            bidx=bidx, c0s=c0s, c1s=c1s, out=None,
                            padded_flops=2.0 * len(r0s) * pm * nk * qk)
            if key is not None:
                corr = np.zeros(len(ia), np.float32) if corrupt is None \
                    else np.asarray(corrupt, np.float32)[ia]
                R = int(max(np.bincount(bidx))) if len(bidx) else 1
        # the launch, then a wait on its outputs: the fetch right after
        # would block on them anyway, so the wait adds no sync
        with span("cleave.fleet.kernel", phases):
            if key is None:
                outs = (_bucket_gemm(
                    a_pad, b_pad, jnp.asarray(r0s), pm=pm, bm=bm, bn=bn,
                    bk=bk, kernel=kernel, compute_dtype=compute_dtype),)
            else:
                outs = _bucket_gemm_verified(
                    a_pad, b_pad, jnp.asarray(r0s), jnp.asarray(hs),
                    jnp.asarray(bidx), jnp.asarray(slot), jnp.asarray(c0s),
                    jnp.asarray(c1s), jnp.asarray(corr), key,
                    jnp.asarray(ia, jnp.int32), pm=pm, R=R, bm=bm, bn=bn,
                    bk=bk, kernel=kernel, compute_dtype=compute_dtype,
                    iters=freivalds_iters)
            jax.block_until_ready(outs)
        with span("cleave.fleet.fetch", phases):
            outs = [np.asarray(x) for x in outs]
        run.out = outs[0]
        if key is not None:
            run.lhs, run.rhs, run.scale = outs[1:]
        runs.append(run)
    return runs


def plan_gemm(a, b, rects, *, block=128, kernel="auto",
              compute_dtype=None, pad_cache: Optional[PadCache] = None):
    """Execute output rectangles of C = A @ B as batched sub-GEMMs.

    ``rects`` is a sequence of ``(r0, r1, c0, c1)`` output rectangles (a
    CLEAVE plan's assignment grid).  Rectangles sharing a row range form a
    band; bands are bucketed by MXU-aligned padded height and each bucket
    runs as ONE batched kernel launch against the shared padded B (see
    :func:`plan_gemm_buckets` / :func:`resolve_plan_kernel`).  A is
    zero-padded once past its row edge, so an over-tall band gather reads
    either real neighbour rows or zeros — cropped away — and each kept
    window is exactly the rectangle's product.

    ``compute_dtype`` defaults to bfloat16 on TPU (MXU-native) and float32
    elsewhere; accumulation is float32 in both kernels.  Returns float32
    numpy blocks in ``rects`` order."""
    blocks: list = [None] * len(rects)
    for i, (r0, r1, c0, c1) in enumerate(rects):
        if r1 - r0 <= 0 or c1 - c0 <= 0:
            blocks[i] = np.zeros((max(r1 - r0, 0), max(c1 - c0, 0)),
                                 np.float32)
    for run in plan_gemm_buckets(a, b, rects, block=block, kernel=kernel,
                                 compute_dtype=compute_dtype,
                                 pad_cache=pad_cache):
        for g, i in enumerate(run.idx):
            blocks[i] = run.block(g)
    return blocks


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "bq", "bk"))
def mha_flash(q, k, v, *, causal=True, window=0, bq=128, bk=128):
    """GQA flash attention. q: (B,S,H,D); k,v: (B,S,K,D); H % K == 0.
    Returns (B,S,H,D)."""
    B, S, H, D = q.shape
    K = k.shape[2]
    G = H // K
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, S, D)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, S, D)
    out = _fa.flash_attention(qf, kf, vf, causal=causal, window=window,
                              bq=min(bq, S), bk=min(bk, S),
                              interpret=_interpret())
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("bs",))
def gqa_flash_decode(q, k, v, valid, *, bs=512):
    """Single-token GQA decode. q: (B,1,H,D); k,v: (B,S,K,D);
    valid: (S,) bool. Returns (B,1,H,D)."""
    from repro.kernels import decode_attention as _dec
    B, _, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, 1, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, S, D)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, S, D)
    vm = jnp.broadcast_to(valid[None], (B * H, S))
    out = _dec.flash_decode(qf, kf, vf, vm, bs=min(bs, S),
                            interpret=_interpret())
    return out.reshape(B, H, 1, D).transpose(0, 2, 1, 3)


def gqa_flash_decode_paged(q, k_pool, v_pool, page_table, lengths):
    """Paged-KV single-token GQA decode: attention reads the serving page
    pools in place through per-request page tables (no contiguous gather).
    q: (B,1,H,D); k_pool/v_pool: (P,K,page,D) — one layer's head-major
    pools from ``serving.PagedKVCache``; page_table: (B,maxp) int32;
    lengths: (B,) int32 occupancy.  Returns (B,1,H,D)."""
    from repro.kernels import decode_attention as _dec
    B, _, H, D = q.shape
    K = k_pool.shape[1]
    G = H // K
    qf = q.reshape(B, K, G, D)
    out = _dec.flash_decode_paged(qf, k_pool, v_pool, page_table, lengths,
                                  interpret=_interpret())
    return out.reshape(B, 1, H, D)


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6(r, k, v, w, u, *, chunk=32):
    """RWKV-6 recurrence. r,k,v,w: (B,S,H,hd); u: (H,hd) ->
    (B,S,H,hd) float32."""
    B, S, H, hd = r.shape
    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    uu = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, hd)
    y = _wkv.wkv6(flat(r), flat(k), flat(v), flat(w), uu, chunk=chunk,
                  interpret=_interpret())
    return y.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
