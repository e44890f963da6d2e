"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret
mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 512, 128),
                                   (64, 256, 512), (200, 300, 150),
                                   (33, 77, 129)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_gemm_sweep(m, k, n, dtype, rng):
    a = jnp.asarray(rng.standard_normal((m, k)), dtype)
    b = jnp.asarray(rng.standard_normal((k, n)), dtype)
    out = ops.block_gemm(a, b, bm=64, bn=64, bk=64)
    want = ref.matmul_ref(a, b)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 10)


@settings(max_examples=10, deadline=None)
@given(m=st.integers(1, 300), k=st.integers(1, 300), n=st.integers(1, 300),
       seed=st.integers(0, 10))
def test_block_gemm_property_arbitrary_shapes(m, k, n, seed):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    out = ops.block_gemm(a, b, bm=128, bn=128, bk=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.matmul_ref(a, b)),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("S,H,K,D,window", [
    (128, 4, 4, 32, 0), (256, 4, 2, 32, 0), (256, 8, 2, 64, 64),
    (128, 2, 1, 16, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, H, K, D, window, dtype, rng):
    B = 2
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, K, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, K, D)), dtype)
    out = ops.mha_flash(q, k, v, causal=True, window=window, bq=64, bk=64)
    G = H // K
    def flat(x, rep):
        x = x.transpose(0, 2, 1, 3)
        if rep:
            x = jnp.repeat(x, G, axis=1)
        return x.reshape(B * H, S, D)
    want = ref.attention_ref(flat(q, False), flat(k, True), flat(v, True),
                             causal=True, window=window)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(flat(out, False), np.float32),
        np.asarray(want, np.float32), rtol=tol, atol=tol * 10)


def test_flash_matches_model_chunked_attention(rng):
    """Kernel vs the model-side oracle (chunked_attention) — the two
    implementations of the same math must agree."""
    from repro.models.attention import chunked_attention
    B, S, H, K, D = 2, 128, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, K, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, K, D)), jnp.float32)
    a = ops.mha_flash(q, k, v, causal=True, bq=64, bk=64)
    b = chunked_attention(q, k, v, causal=True, q_chunk=32, k_chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-4)


@pytest.mark.parametrize("S,H,hd,chunk", [(64, 2, 16, 16), (128, 1, 32, 32),
                                          (96, 2, 16, 32)])
def test_wkv6_sweep(S, H, hd, chunk, rng):
    B = 2
    r = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.1, 0.999, (B, S, H, hd)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((H, hd)), jnp.float32)
    y = ops.wkv6(r, k, v, w, u, chunk=chunk)
    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    uu = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, hd)
    want = ref.wkv6_ref(flat(r), flat(k), flat(v), flat(w), uu)
    np.testing.assert_allclose(np.asarray(flat(y)), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_wkv6_matches_model_chunked(rng):
    from repro.models.rwkv import wkv_chunked
    B, S, H, hd = 2, 64, 2, 16
    r = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.1, 0.99, (B, S, H, hd)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((H, hd)), jnp.float32)
    y1 = ops.wkv6(r, k, v, w, u, chunk=16)
    s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    y2, _ = wkv_chunked(r, k, v, w, u, s0, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("S,H,K,D,n_valid", [(256, 4, 2, 32, 256),
                                             (512, 2, 2, 64, 300),
                                             (128, 4, 1, 16, 60)])
def test_flash_decode_kernel(S, H, K, D, n_valid, rng):
    """4th kernel: single-token flash-decode vs the model decode oracle."""
    from repro.models.attention import decode_attention
    B = 2
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, K, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, K, D)), jnp.float32)
    valid = jnp.arange(S) < n_valid
    out = ops.gqa_flash_decode(q, k, v, valid, bs=64)
    want = decode_attention(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("page,H,K,D", [(16, 4, 2, 32), (8, 4, 4, 16)])
def test_flash_decode_paged_kernel(page, H, K, D, rng):
    """Paged flash-decode: reads shuffled per-request page tables from the
    KV pool in place and matches the contiguous gathered-view oracle."""
    from repro.models.attention import decode_attention
    B, maxp, n_pages = 3, 3, 12
    lengths = np.asarray([page * maxp - 4, page, 2 * page + 3], np.int32)
    perm = rng.permutation(n_pages)
    pt = np.asarray([perm[:3], perm[3:6], perm[6:9]], np.int32)
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((n_pages, K, page, D)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((n_pages, K, page, D)),
                         jnp.float32)
    out = ops.gqa_flash_decode_paged(q, k_pool, v_pool, pt, lengths)
    # oracle: gather each request's head-major pages into a contiguous view
    S = page * maxp

    def contiguous(pool, b):
        return pool[pt[b]].transpose(0, 2, 1, 3).reshape(S, K, D)

    kc = jnp.stack([contiguous(k_pool, b) for b in range(B)])
    vc = jnp.stack([contiguous(v_pool, b) for b in range(B)])
    for b in range(B):
        valid = jnp.arange(S) < lengths[b]
        want = decode_attention(q[b:b + 1], kc[b:b + 1], vc[b:b + 1], valid)
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(want[0]),
                                   rtol=2e-4, atol=2e-4)
