"""Compile rehearsals for the TPU v5e: the Pallas kernels of the fleet
training and serving paths, compiled at opt-1.3b widths for a described
(not attached) v5e chip.  Nothing runs; the TPU compiler refuses what the
chip would refuse (block tiling, VMEM budget), at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import block_gemm as bg
from repro.kernels import decode_attention as dec
from repro.kernels import ops

# opt-1.3b: d_model 2048, 32 heads of 64, SwiGLU d_ff 5504
D_MODEL, D_FF, N_HEADS, HEAD_DIM = 2048, 5504, 32, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_block_gemm_batched_shared_bf16(one_chip):
    """The fleet's band-bucket kernel: 4 row bands of 512 against the
    shared SwiGLU up-projection, bf16 operands, f32 accumulation."""
    fn = functools.partial(bg.block_gemm_batched_shared, bm=128, bn=128,
                           bk=128, out_dtype=jnp.float32)
    text = _compiled_text(
        fn, _sds((4, 512, D_MODEL), jnp.bfloat16, one_chip),
        _sds((D_MODEL, D_FF), jnp.bfloat16, one_chip))
    assert "tpu_custom_call" in text


def _compile_verified_bucket(one_chip, monkeypatch, operand_dtype):
    """8 bands of 256 rows of a (2048, 2048) x (2048, 5504) GEMM, two
    rectangles per band, padded operands held in ``operand_dtype``.
    ``_interpret`` is read while tracing, so the test steers it to the
    compiled kernel; a fresh jit keeps an earlier CPU trace out."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    Gb, R, pm = 8, 2, 256
    Gr = Gb * R
    fn = jax.jit(ops._bucket_gemm_verified.__wrapped__,
                 static_argnames=("pm", "R", "bm", "bn", "bk", "kernel",
                                  "compute_dtype", "iters"))
    i32 = jnp.int32
    args = (_sds((D_MODEL + pm, D_MODEL), operand_dtype, one_chip),
            _sds((D_MODEL, D_FF), operand_dtype, one_chip),
            _sds((Gb,), i32, one_chip), _sds((Gb,), i32, one_chip),
            _sds((Gr,), i32, one_chip), _sds((Gr,), i32, one_chip),
            _sds((Gr,), i32, one_chip), _sds((Gr,), i32, one_chip),
            _sds((Gr,), jnp.float32, one_chip),
            _sds((2,), jnp.uint32, one_chip),
            _sds((Gr,), i32, one_chip))
    return fn.lower(*args, pm=pm, R=R, bm=128, bn=128, bk=128,
                    kernel="pallas", compute_dtype="bfloat16",
                    iters=2).compile().as_text()


def test_bucket_gemm_verified_pallas(one_chip, monkeypatch):
    """The whole verified bucket program (band gather, Pallas launch,
    device-side Freivalds) as the jax executor launches it on TPU from
    host operands, padded in float32."""
    assert "tpu_custom_call" in _compile_verified_bucket(
        one_chip, monkeypatch, jnp.float32)


def test_device_staged_bucket_gemm_verified_pallas(one_chip, monkeypatch):
    """The same program from device operands, padded on the device in
    bfloat16, and that pad itself at the OPT-13B head's width (5120 x
    50272 to 50304 columns)."""
    assert "tpu_custom_call" in _compile_verified_bucket(
        one_chip, monkeypatch, jnp.bfloat16)
    pad = ops._device_pad.lower(
        _sds((5120, 50272), jnp.bfloat16, one_chip), rows=5120, cols=50304,
        dtype=jnp.dtype(jnp.bfloat16)).compile()
    assert pad.memory_analysis().output_size_in_bytes == 5120 * 50304 * 2


def test_flash_decode_paged_serving_shapes(one_chip):
    """The paged decode kernel at the serving session's shapes: 4 slots,
    16-token pages, 128-token budget (8 pages a request, 32 in the pool),
    float32 head-major pools."""
    B, page, maxp = 4, 16, 8
    P = B * maxp
    fn = functools.partial(dec.flash_decode_paged, interpret=False)
    text = _compiled_text(
        fn, _sds((B, N_HEADS, 1, HEAD_DIM), jnp.float32, one_chip),
        _sds((P, N_HEADS, page, HEAD_DIM), jnp.float32, one_chip),
        _sds((P, N_HEADS, page, HEAD_DIM), jnp.float32, one_chip),
        _sds((B, maxp), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip))
    assert "tpu_custom_call" in text
