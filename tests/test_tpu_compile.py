"""Compile every serving kernel for a described TPU v5e at qwen3-0.6b widths.

Nothing runs: each kernel is lowered with ``interpret=False`` and compiled
by the TPU compiler for one chip of a ``v5e:2x2`` topology that is described,
not attached, so what Mosaic refuses on the chip (a 1-D gather, a tile not
aligned to (8, 128), a ``dynamic_slice``) fails here at no chip time. The
topology is described inside a fixture, never at import: only the worker
that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (fista_quant, paged_decode_attention, quant_matmul,
                           quant_matmul_stacked)

# qwen3-0.6b widths (configs/qwen3_0_6b.py), page 16, 4-bit codebooks
HQ, HKV, DH, D_MODEL, D_FF, N_LAYERS = 16, 8, 128, 1024, 3072, 28
BS, L, B, NB, MB = 16, 16, 8, 257, 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel did not lower"


@pytest.mark.parametrize("W", [1, 4])
@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "4bit"])
def test_paged_decode_compiles(one_chip, quantized, W):
    q = (B, HQ, DH) if W == 1 else (B, W, HQ, DH)
    pool = (NB, BS, HKV, DH)
    codes = (NB, BS // 2, HKV, DH)          # two 4-bit codes per byte
    _compile(lambda *a: paged_decode_attention(*a, quantized=quantized,
                                               interpret=False),
             one_chip, (q, jnp.bfloat16), (pool, jnp.bfloat16),
             (pool, jnp.bfloat16), (codes, jnp.uint8), (codes, jnp.uint8),
             ((NB, L), jnp.float32), ((NB, L), jnp.float32),
             ((NB,), jnp.int32), ((B, MB), jnp.int32), ((B,), jnp.int32))


@pytest.mark.parametrize("M", [8, 128])
def test_quant_matmul_compiles(one_chip, M):
    _compile(lambda x, i, c: quant_matmul(x, i, c, interpret=False),
             one_chip, ((M, D_MODEL), jnp.bfloat16),
             ((D_MODEL, D_FF), jnp.uint8), ((L,), jnp.float32))


@pytest.mark.parametrize("M", [8, 128])
def test_quant_matmul_stacked_compiles(one_chip, M):
    _compile(lambda x, i, c: quant_matmul_stacked(x, i, c, interpret=False),
             one_chip, ((N_LAYERS, M, D_MODEL), jnp.bfloat16),
             ((N_LAYERS, D_MODEL, D_FF), jnp.uint8),
             ((N_LAYERS, L), jnp.float32))


def test_fista_quant_compiles(one_chip):
    # one freeze event's rows: k/v x 28 layers x 4 pages, 128-point sketch
    rows = ((2 * N_LAYERS * 4, 1, 128), jnp.float32)
    _compile(lambda w, d, n, lam, eta: fista_quant(
        w, d, n, lam, eta, n_iters=100, block_t=128, interpret=False),
        one_chip, rows, rows, rows, rows,
        ((2 * N_LAYERS * 4, 1, 1), jnp.float32))
