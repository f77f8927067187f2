"""Fused codebook-dequant matmul Pallas TPU kernel (serving hot path).

Value-shared weights (the paper's output format) are stored as
(indices uintX, codebook fpN). Serving computes y = x @ W with W never
materialized in HBM: each (bk, bn) index tile is dequantized against the
SMEM-resident codebook (an L-way compare-and-select, exact — see
``paged_attention.codebook_lookup``) and fed straight to the MXU. This
keeps weight HBM traffic at ~1 byte/param (vs 2 for bf16), which is what
makes the decode step - memory-bound at batch*1 token - faster end to end.

Grid: (M/bm, N/bn, K/bk), k innermost ('arbitrary'); accumulation in an f32
VMEM scratch tile, written out on the last k step.

``quant_matmul_stacked`` is the same tile with a leading group axis as the
outermost grid dimension: stacked weights (codebook (G, L) / indices
(G, K, N), the ``stack_quantized`` form that rides through ``lax.scan``)
are served group-by-group against that group's row of the SMEM-resident
codebook — one call covers a whole scanned layer group with zero per-call
dequant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import codebook_lookup

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _kernel(L, x_ref, idx_ref, cb_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_tile = codebook_lookup(idx_ref[...].astype(jnp.int32),
                             lambda l: cb_ref[l], L)
    acc_ref[...] += jnp.dot(
        x_ref[...], w_tile.astype(x_ref.dtype), preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype", "interpret")
)
def quant_matmul(
    x: jax.Array,            # (M, K)
    idx: jax.Array,          # (K, N) integer codes
    codebook: jax.Array,     # (C,) fp values
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    M, K = x.shape
    K2, N = idx.shape
    assert K == K2, (x.shape, idx.shape)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (
        f"shapes ({M},{K},{N}) must tile by ({bm},{bk},{bn}); pad upstream")
    out_dtype = out_dtype or x.dtype
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_kernel, codebook.shape[0]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            _SMEM,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, idx, codebook.astype(jnp.float32))


def _stacked_kernel(L, x_ref, idx_ref, cb_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    base = pl.program_id(0) * L          # group g's row of the flat codebook
    w_tile = codebook_lookup(idx_ref[0].astype(jnp.int32),
                             lambda l: cb_ref[base + l], L)
    acc_ref[...] += jnp.dot(
        x_ref[0], w_tile.astype(x_ref.dtype),
        preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(3) == pl.num_programs(3) - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype", "interpret")
)
def quant_matmul_stacked(
    x: jax.Array,            # (G, M, K) per-group activations
    idx: jax.Array,          # (G, K, N) integer codes
    codebook: jax.Array,     # (G, L) per-group fp values
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Stacked-group fused dequant matmul: y[g] = x[g] @ codebook[g][idx[g]].

    The group axis is the outermost grid dimension; each (g, i, j, k) step
    dequantizes its (bk, bn) index tile against group g's (L,) codebook,
    read as scalars from the whole (G * L,) codebook held in SMEM (a (1, L)
    block of a (G, L) array is no legal TPU tile), so scanned layer groups
    serve from uint8 codes without any per-call dense materialization.
    """
    G, M, K = x.shape
    G2, K2, N = idx.shape
    assert G == G2 and K == K2, (x.shape, idx.shape)
    assert codebook.ndim == 2 and codebook.shape[0] == G, codebook.shape
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (
        f"shapes ({M},{K},{N}) must tile by ({bm},{bk},{bn}); pad upstream")
    out_dtype = out_dtype or x.dtype
    grid = (G, M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_stacked_kernel, codebook.shape[1]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda g, i, j, k: (g, i, k)),
            pl.BlockSpec((1, bk, bn), lambda g, i, j, k: (g, k, j)),
            _SMEM,
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")
        ),
        interpret=interpret,
    )(x, idx, codebook.astype(jnp.float32).reshape(-1))
