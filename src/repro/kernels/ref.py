"""Pure-jnp oracles for the Pallas kernels (numerically identical math)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def ref_quant_matmul(x, idx, codebook, out_dtype=None):
    """Dense reference: materialize W = codebook[idx], plain matmul."""
    w = jnp.take(codebook, idx.astype(jnp.int32), axis=0).astype(x.dtype)
    out = jnp.dot(x, w, preferred_element_type=jnp.float32)
    return out.astype(out_dtype or x.dtype)


def ref_quant_matmul_stacked(x, idx, codebook, out_dtype=None):
    """Per-group dense oracle for kernels.quant_matmul_stacked: materialize
    W[g] = codebook[g][idx[g]], batched matmul over the group axis."""
    G = idx.shape[0]
    flat = idx.reshape(G, -1).astype(jnp.int32)
    w = jnp.take_along_axis(codebook, flat, axis=1).reshape(idx.shape)
    out = jnp.einsum("gmk,gkn->gmn", x, w.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    return out.astype(out_dtype or x.dtype)


def ref_paged_pages(fp, codes, cb, blk_q, block_table, *, quantized=False,
                    packed=True):
    """Every table page at full width, (B, mb*bs, Hkv, Dh): frozen pages
    (``blk_q``) as ``cb[codes]`` by a per-element gather, cast to the pool
    dtype, the rest as their fp rows."""
    from .paged_attention import unpack4

    t = block_table
    B, mb = t.shape
    nb, bs, Hkv, Dh = fp.shape
    pages = fp[t]                                   # (B, mb, bs, H, D)
    if quantized:
        c = codes[t]
        if packed:
            c = unpack4(c)
        deq = jnp.take_along_axis(
            cb[t], c.reshape(B, mb, -1).astype(jnp.int32), axis=-1
        ).reshape(c.shape)
        frozen = blk_q.astype(bool)[t][:, :, None, None, None]
        pages = jnp.where(frozen, deq.astype(pages.dtype), pages)
    return pages.reshape(B, mb * bs, Hkv, Dh)


def ref_paged_decode(q, k_fp, v_fp, k_codes, v_codes, k_cb, v_cb, blk_q,
                     block_table, kv_valid_len, *, softcap=None,
                     quantized=False, packed=True):
    """Dense oracle for kernels.paged_attention: materialize every table
    page at full width (dequantizing frozen ones), then masked softmax.
    Numerically the same math as `PagedKVCache._gather` + decode-shaped
    `models.attention.sdpa`.

    ``q`` is (B, Hq, Dh) for a single decode step, or (B, W, Hq, Dh) for a
    speculative verify window whose query w sits at sequence position
    ``kv_valid_len - W + w`` (causal within the window).
    """
    from .paged_attention import BIG_NEG

    windowed = q.ndim == 4
    if not windowed:
        q = q[:, None]
    B, W, Hq, Dh = q.shape
    nb, bs, Hkv, _ = k_fp.shape
    G = Hq // Hkv
    mb = block_table.shape[1]
    k_all, v_all = (ref_paged_pages(fp, codes, cb, blk_q, block_table,
                                    quantized=quantized, packed=packed
                                    ).astype(jnp.float32)
                    for fp, codes, cb in ((k_fp, k_codes, k_cb),
                                          (v_fp, v_codes, v_cb)))
    qr = q.astype(jnp.float32).reshape(B, W, Hkv, G, Dh)
    s = jnp.einsum("bwhgd,bshd->bwhgs", qr, k_all,
                   preferred_element_type=jnp.float32) / jnp.sqrt(Dh * 1.0)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    pos = jnp.arange(mb * bs)[None, None]               # (1, 1, S)
    valid = jnp.asarray(kv_valid_len, jnp.int32)[:, None, None]
    valid_w = valid - (W - 1 - jnp.arange(W)[None, :, None])   # (B, W, 1)
    mask = pos < valid_w                                # (B, W, S)
    s = jnp.where(mask[:, :, None, None], s, BIG_NEG)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[:, :, None, None], p, 0.0)
    out = jnp.einsum("bwhgs,bshd->bwhgd", p, v_all)
    out = out.reshape(B, W, Hq, Dh).astype(q.dtype)
    return out if windowed else out[:, 0]


def ref_fista(w, d, n, lam, eta, *, n_iters: int = 300):
    """FISTA with the same iterates as kernels.fista_quant, on (B, M) arrays."""
    B, M = w.shape
    eta = eta.reshape(B, 1)

    def body(i, carry):
        x_prev, y, t = carry
        recon = jnp.cumsum(y * d, axis=1)
        r = n * (w - recon)
        cums = jnp.cumsum(r, axis=1)
        total = cums[:, -1:]
        suffix = total - cums + r
        grad = -d * suffix
        v = y - eta * grad
        thr = eta * lam
        x = jnp.sign(v) * jnp.maximum(jnp.abs(v) - thr, 0.0)
        t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        y_next = x + ((t - 1.0) / t_next) * (x - x_prev)
        return (x, y_next, t_next)

    ones = jnp.ones_like(w)
    x, _, _ = lax.fori_loop(0, n_iters, body, (ones, ones, jnp.float32(1.0)))
    return x
