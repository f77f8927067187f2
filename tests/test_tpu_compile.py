"""Compile every serving kernel for a described TPU v5e at qwen3-0.6b widths.

Nothing runs: each kernel is lowered with ``interpret=False`` and compiled
by the TPU compiler for one chip of a ``v5e:2x2`` topology that is described,
not attached, so what Mosaic refuses on the chip (a 1-D gather, a tile not
aligned to (8, 128), a ``dynamic_slice``) fails here at no chip time. The
topology is described inside a fixture, never at import: only the worker
that runs this file loads the TPU library.
"""
import importlib.util
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
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


def _compile_install(one_chip, L, packed, P=4):
    """``_install_leaf`` compiled at qwen3-0.6b widths (28 stacked layers)
    for a P-page bucket of L-value codebooks."""
    from repro.serving.kv_cache import PagedKVCache, _install_leaf

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = BS // 2 if packed else BS
    pool = (N_LAYERS, NB, BS, HKV, DH)
    codes = (N_LAYERS, NB, rows, HKV, DH)
    leaf = PagedKVCache(
        k_fp=arg(pool, jnp.bfloat16), v_fp=arg(pool, jnp.bfloat16),
        k_codes=arg(codes, jnp.uint8), v_codes=arg(codes, jnp.uint8),
        k_cb=arg((N_LAYERS, NB, L), jnp.float32),
        v_cb=arg((N_LAYERS, NB, L), jnp.float32),
        blk_q=arg((N_LAYERS, NB), jnp.bool_),
        block_table=arg((N_LAYERS, B, MB), jnp.int32),
        seq_lens=arg((N_LAYERS, B), jnp.int32),
        block_size=BS, quantized=True, packed=packed, fused=True)
    return _install_leaf.lower(
        leaf, arg((P,), jnp.int32), arg((P,), jnp.bool_),
        arg((2, N_LAYERS, P, rows, HKV, DH), jnp.uint8),
        arg((2, N_LAYERS, P, L), jnp.float32)).compile()


def test_freeze_install_dequantizes_without_a_gather(one_chip):
    """The freeze install at qwen3-0.6b widths (28 stacked layers, a
    4-page bucket) runs no per-element gather: no op of the optimized
    program comes from ``take_along_axis``, which the TPU runs as a scalar
    loop over every element of the frozen pages."""
    text = _compile_install(one_chip, L, packed=True).as_text()
    assert "_install_leaf" in text
    assert "take_along_axis" not in text


def test_freeze_install_widest_codebook_compiles(one_chip):
    """The install of the widest unpacked codebook (256 values, one byte
    per code) compiles for the chip with no per-element gather either."""
    text = _compile_install(one_chip, 256, packed=False).as_text()
    assert "_install_leaf" in text
    assert "take_along_axis" not in text


def test_freeze_install_leaves_fp_pools_alone(one_chip):
    """The freeze install at qwen3-0.6b widths writes codes, codebooks and
    flags only: no op or output of the optimized program is a bf16 array
    of an fp pool's size (a pool copy, in any shape), and its outputs
    together are smaller than one fp pool."""
    compiled = _compile_install(one_chip, L, packed=True)
    text = compiled.as_text()
    pool = N_LAYERS * NB * BS * HKV * DH
    sizes = {int(np.prod([int(d) for d in dims.split(",") if d]))
             for dims in re.findall(r"bf16\[([\d,]*)\]", text)}
    assert pool not in sizes
    out = compiled.memory_analysis().output_size_in_bytes
    assert 0 < out < pool * jnp.dtype(jnp.bfloat16).itemsize, out


@pytest.mark.parametrize("L,packed", [(16, True), (256, False)],
                         ids=["packed16", "unpacked256"])
def test_gather_read_dequantizes_without_a_gather(one_chip, L, packed):
    """The gather read of a longctx prompt's 240-page table at qwen3-0.6b
    widths dequantizes frozen pages by compare-and-select, behind a
    conditional on whether the table holds one: no op comes from
    ``take_along_axis``."""
    from repro.serving.kv_cache import PagedKVCache

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = BS // 2 if packed else BS
    nb, mb = 2113, 240
    leaf = PagedKVCache(
        k_fp=arg((nb, BS, HKV, DH), jnp.bfloat16), v_fp=None,
        k_codes=arg((nb, rows, HKV, DH), jnp.uint8), v_codes=None,
        k_cb=arg((nb, L), jnp.float32), v_cb=None,
        blk_q=arg((nb,), jnp.bool_), block_table=arg((1, mb), jnp.int32),
        seq_lens=None, block_size=BS, quantized=True, packed=packed)
    text = jax.jit(lambda c: c._gather(c.k_fp, c.k_codes, c.k_cb)).lower(
        leaf).compile().as_text()
    assert "conditional(" in text
    assert "take_along_axis" not in text


# ------------------------------------------------- names the trace shows

METRICS = Path(__file__).resolve().parents[1] / "bench" / "metrics"


def _pattern(metric: str, attr: str) -> str:
    """The regex a benchmark reader matches device-trace names with."""
    spec = importlib.util.spec_from_file_location(metric,
                                                  METRICS / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, attr)


def test_fused_decode_kernel_keeps_its_trace_name(one_chip):
    """The fused decode kernel's op carries the name the roofline reader
    matches, so a rename fails here instead of silencing the metric."""
    shapes = [((B, HQ, DH), jnp.bfloat16), ((NB, BS, HKV, DH), jnp.bfloat16),
              ((NB, BS, HKV, DH), jnp.bfloat16),
              ((NB, BS // 2, HKV, DH), jnp.uint8),
              ((NB, BS // 2, HKV, DH), jnp.uint8), ((NB, L), jnp.float32),
              ((NB, L), jnp.float32), ((NB,), jnp.int32), ((B, MB), jnp.int32),
              ((B,), jnp.int32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(lambda *a: paged_decode_attention(
        *a, quantized=True, interpret=False)).lower(*args).compile().as_text()
    kernel = _pattern("paged_decode_roofline", "KERNEL")
    assert re.search(rf"%{kernel}\w*(\.\d+)? = ", text), kernel


def test_serving_programs_keep_their_trace_names():
    """The jitted decode step and the two freeze programs lower to modules
    named as the benchmark's readers and reducer expect."""
    from repro import models
    from repro.configs import get_reduced_config
    from repro.serving.kv_cache import (_install_leaf, _solve_leaf_pages,
                                        init_paged_cache, map_layers,
                                        resolve_kv_spec, with_tables)
    from repro.serving.workers import _decode_step_fn

    cfg = get_reduced_config("qwen3_0_6b")
    params = jax.eval_shape(lambda: models.init_params(
        cfg, jax.random.PRNGKey(0)))
    tree = init_paged_cache(cfg, num_blocks=9, block_size=8, batch=2,
                            max_blocks=4, quantized=True, num_values=16,
                            fused=False)
    leaves = []
    map_layers(lambda leaf: leaves.append(leaf) or leaf, tree)
    spec = resolve_kv_spec("kmeans_ls@16").replace(seed=0)
    jb = jnp.arange(1, 3, dtype=jnp.int32)
    solve = _solve_leaf_pages.lower(leaves[0], jb, spec=spec)
    codes, cb = jax.eval_shape(
        lambda leaf: _solve_leaf_pages(leaf, jb, spec=spec), leaves[0])
    install = _install_leaf.lower(leaves[0], jb, jnp.ones(2, bool), codes,
                                  cb)
    lens = np.zeros(2, np.int32)
    step = _decode_step_fn.lower(
        params, jnp.zeros((2, 1), jnp.int32),
        with_tables(tree, np.zeros((2, 4), np.int32), lens),
        jnp.asarray(lens), cfg=cfg)
    freeze = _pattern("freeze_ms_per_step", "PROGRAMS")
    names = {f: re.search(r"^module @(\w+)", low.as_text(), re.M).group(1)
             for f, low in (("solve", solve), ("install", install),
                            ("step", step))}
    assert names == {"solve": "jit__solve_leaf_pages",
                     "install": "jit__install_leaf",
                     "step": "jit__decode_step_fn"}
    assert re.search(freeze, names["solve"])
    assert re.search(freeze, names["install"])
