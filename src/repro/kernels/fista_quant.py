"""Batched FISTA sparse-LSQ quantization solver - Pallas TPU kernel.

TPU-native replacement for the paper's sequential coordinate descent
(DESIGN.md §3): every FISTA iteration on the cumulative design matrix V is

    recon   = cumsum(y * d)                  # V @ y
    r       = n * (w - recon)                # weighted residual
    grad    = -d * suffix_sum(r)             # V^T diag(n) r
    x       = shrink(y - eta*grad, eta*lam)

and both scans are lowered to *blocked triangular matmuls on the MXU*:
rows are laid out (nb, T) with T=128 lanes; within-block cumsum is
X @ triu_ones(T) (one MXU op), across-block offsets are a second tiny
(strictly lower) triangular matmul; the suffix sum reuses the same cumsum
(suffix = total - cumsum + x). One grid step = one tensor row, so a whole
model's PTQ is a single kernel launch.

Sequential-scan CD remains the host/CPU path (repro.core.cd); this kernel is
validated against ref.ref_fista (identical iterates, pure jnp) across
shapes/dtypes in tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _blocked_cumsum(x, triu_t, tril_nb_strict):
    """(nb, T) row-major cumulative sum via two triangular matmuls.

    Block b's offset is the sum of the earlier blocks' totals, i.e. lane
    T-1 of ``tril_strict @ within``; every slice is static (Mosaic lowers
    no dynamic_slice, which integer indexing like ``within[:, -1]`` emits).
    Both matmuls run at f32 contract precision: they stand in for an exact
    f32 scan (``ref.ref_fista``'s cumsum), not for a bf16 MXU pass.
    """
    T = x.shape[1]
    hi = lax.Precision.HIGHEST
    within = jnp.dot(x, triu_t, precision=hi,
                     preferred_element_type=jnp.float32)              # (nb, T)
    offsets = jnp.dot(tril_nb_strict, within, precision=hi,
                      preferred_element_type=jnp.float32)[:, T - 1:]  # (nb, 1)
    return within + offsets


def _kernel(nsteps, w_ref, d_ref, n_ref, lam_ref, eta_ref, triu_t_ref,
            tril_nb_ref, alpha_ref):
    w = w_ref[0]        # (nb, T)
    d = d_ref[0]
    n = n_ref[0]
    lam = lam_ref[0]
    eta = eta_ref[0]    # (1, 1), broadcasts
    triu_t = triu_t_ref[...]
    tril_nb = tril_nb_ref[...]
    nb, T = w.shape

    ones = jnp.ones_like(w)

    def body(i, carry):
        x_prev, y, t = carry
        recon = _blocked_cumsum(y * d, triu_t, tril_nb)
        r = n * (w - recon)
        cums = _blocked_cumsum(r, triu_t, tril_nb)
        total = cums[nb - 1:, T - 1:]                                 # (1, 1)
        suffix = total - cums + r
        grad = -d * suffix
        v = y - eta * grad
        thr = eta * lam
        x = jnp.sign(v) * jnp.maximum(jnp.abs(v) - thr, 0.0)
        t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        y_next = x + ((t - 1.0) / t_next) * (x - x_prev)
        return (x, y_next, t_next)

    x, _, _ = lax.fori_loop(0, nsteps, body, (ones, ones, jnp.float32(1.0)))
    alpha_ref[0] = x


@functools.partial(
    jax.jit, static_argnames=("n_iters", "block_t", "interpret")
)
def fista_quant(
    w: jax.Array,      # (B, nb, T) unique values (padded with zeros)
    d: jax.Array,      # (B, nb, T) column scales (0 on padding)
    n: jax.Array,      # (B, nb, T) weights (0 on padding)
    lam: jax.Array,    # (B, nb, T) per-coordinate l1 penalty
    eta: jax.Array,    # (B, 1, 1) step size 1/L per problem
    *,
    n_iters: int = 300,
    block_t: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Returns alpha (B, nb, T). See ops.solve_fista for the padded wrapper."""
    B, nb, T = w.shape
    assert T == block_t, (w.shape, block_t)
    triu_t = jnp.triu(jnp.ones((T, T), jnp.float32))
    # strict: block b sums blocks b' < b only
    tril_nb = jnp.tril(jnp.ones((nb, nb), jnp.float32), k=-1)
    row = pl.BlockSpec((1, nb, T), lambda b: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, n_iters),
        grid=(B,),
        in_specs=[row, row, row, row,
                  pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0)),
                  pl.BlockSpec((T, T), lambda b: (0, 0)),
                  pl.BlockSpec((nb, nb), lambda b: (0, 0))],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((B, nb, T), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
    )(w, d, n, lam, eta, triu_t, tril_nb)
