"""Fused paged-attention flash-decode kernel - Pallas TPU (serving hot path).

One decode step reads the whole KV history of every batch slot. With the
paged cache (repro.serving.kv_cache) that history lives in two pools: an fp
pool for write-hot pages and a 4-bit codes + per-block codebook form for
frozen pages (the paper's sparse-LSQ quantizers). The pre-existing read path
(`PagedKVCache._gather`) dequantizes frozen pages to full width in HBM
before attention ever runs, so quantization compressed storage but decode
still crossed HBM at 32 bits/value.

This kernel walks each sequence's block table on-core instead:

  grid = (B,); block_table / kv_valid_len / blk_q ride in as scalar-prefetch
  (SMEM) so page ids are known before the body runs. Per page the kernel
  issues a *conditional* DMA - frozen pages copy packed codes + the two
  (L,) codebooks, hot pages copy the fp tile - so cold context crosses HBM
  at ~4 bits/value and is dequantized (`cb[codes]`) in VMEM. The codebooks
  land in SMEM and the dequant is an L-way compare-and-select against their
  scalars (Mosaic lowers no 1-D vector gather). A codebook is too narrow to
  slice out of a lane-tiled pool on its own, so the wrapper views each
  pool as 128-lane rows of ``128 // Lp`` codebooks (``codebook_rows``) and
  the kernel copies the row that holds the page's codebook. The DMA is
  double-buffered by default: two VMEM slots with ping-pong semaphore
  banks, page j+1's copy started before page j's wait so it overlaps the
  dequant + flash step (serial single-slot variant kept for the benchmark
  three-way). Attention is online-softmax (flash) over pages with
  per-sequence `kv_valid_len` masking; pages past `ceil(valid/bs)` skip
  their DMA entirely, which is what makes short sequences in a long-table
  batch cheap.

GQA is handled natively: a static per-kv-head loop computes (G, bs) score
tiles without repeating K/V across the group. `window` is not supported
(serving decodes are full-context); callers fall back to the gather path.

Query windows (speculative-decoding verify): ``q`` may carry a small extra
window axis (B, W, Hq, Dh). The W queries of one sequence are this step's
freshly written positions ``valid - W .. valid - 1``, so the kernel reads
each page ONCE and scores all W queries against it — the causal structure
is a per-query-row valid length ``valid - (W-1-w)`` folded into the same
online-softmax mask. Queries ride through the grid reordered kv-head-major
(``(Hkv, W, G)`` rows) so the static per-kv-head loop stays a contiguous
slice; W=1 reduces to the plain decode layout bit-for-bit.

The pure-jnp oracle is `ref.ref_paged_decode`; `_gather` + masked sdpa
remains the CPU fallback read path. `modeled_hbm_bytes_per_token` is the
analytic bytes model the paged-attention benchmark and tests use to compare
the two paths' HBM traffic.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG_NEG = -2.3819763e38


# ------------------------------------------------------------ 4-bit packing


def pack4(codes: jax.Array) -> jax.Array:
    """Pack two 4-bit codes per byte along a page's token axis.

    ``codes`` is page-shaped (..., bs, Hkv, Dh) with bs even. Split-half
    layout: byte (t, h, d) holds codes[t, h, d] (low nibble) and
    codes[t + bs/2, h, d] (high nibble), so the packed page keeps the full
    Dh lane width (a TPU tile is 128 lanes wide; a half-width lane dim can
    be neither sliced per page nor DMA'd) and unpacking is a concatenate
    over the untiled token axis.
    """
    bs = codes.shape[-3]
    assert bs % 2 == 0, f"pack4 needs an even page size, got {bs}"
    lo, hi = codes[..., : bs // 2, :, :], codes[..., bs // 2:, :, :]
    return (lo.astype(jnp.uint8) | (hi.astype(jnp.uint8) << 4))


def unpack4(packed: jax.Array) -> jax.Array:
    """Inverse of pack4: (..., bs/2, Hkv, Dh) uint8 -> (..., bs, Hkv, Dh)
    int32 codes."""
    wide = packed.astype(jnp.int32)     # Mosaic has no 8-bit shifts
    return jnp.concatenate([wide & 0xF, wide >> 4], axis=-3)


# ------------------------------------------------------------ kernel body


LANES = 128


def codebook_lookup(idx: jax.Array, cb_at, L: int) -> jax.Array:
    """``cb[idx]`` for an (L,) codebook whose entries ``cb_at(l)`` are
    scalars (SMEM reads in-kernel) or arrays that broadcast against ``idx``
    (one codebook per page), as an L-way compare-and-select.

    Mosaic lowers no vector gather from a 1-D table. Exactly one of the L
    selects matches each element, so the result equals ``jnp.take``
    bit-for-bit."""
    out = jnp.zeros(idx.shape, jnp.float32)
    for l in range(L):
        out = jnp.where(idx == l, cb_at(l), out)
    return out


def codebook_rows(cb: jax.Array) -> tuple[jax.Array, int]:
    """View an (n, L) codebook pool as (rows, 128) f32 lane rows, each
    holding ``128 // Lp`` codebooks padded to Lp (the next power of two
    >= L). Returns (rows, Lp); codebook i starts at lane (i * Lp) % 128 of
    row (i * Lp) // 128."""
    n, L = cb.shape
    assert L <= LANES, f"codebooks wider than {LANES} entries: {L}"
    Lp = 1 << (L - 1).bit_length()
    flat = jnp.pad(cb.astype(jnp.float32), ((0, 0), (0, Lp - L))).reshape(-1)
    flat = jnp.pad(flat, (0, (-flat.size) % LANES))
    return flat.reshape(-1, LANES), Lp


def _kernel(bs, Hkv, G, W, Dh, L, Lp, scale, softcap, quantized, packed,
            double_buffer,
            table_ref, valid_ref, blkq_ref,
            q_ref, kfp_ref, vfp_ref, kc_ref, vc_ref, kcb_ref, vcb_ref,
            o_ref,
            k_tile, v_tile, kc_tile, vc_tile, cb_smem, sems):
    b = pl.program_id(0)
    mb = table_ref.shape[1]
    WG = W * G                    # query rows per kv head ((Hkv, W, G) major)
    Hq = Hkv * WG
    valid = valid_ref[b]
    n_pages = lax.div(valid + bs - 1, bs)

    # Scratch tiles carry a leading slot axis: 2 slots in double-buffer
    # mode (page j computes out of slot j%2 while page j+1's DMA fills the
    # other), 1 slot serial. Each slot owns a bank of 4 DMA semaphores.

    def fp_copies(page, s):
        return [pltpu.make_async_copy(kfp_ref.at[page], k_tile.at[s],
                                      sems.at[s, 0]),
                pltpu.make_async_copy(vfp_ref.at[page], v_tile.at[s],
                                      sems.at[s, 1])]

    def code_copies(page, s):
        # ~4 bits/value across the wire: packed codes + the two 128-lane
        # codebook rows holding this page's codebooks, those into SMEM
        # where the lookup reads them as scalars
        row = page * Lp // LANES
        return [pltpu.make_async_copy(kc_ref.at[page], kc_tile.at[s],
                                      sems.at[s, 0]),
                pltpu.make_async_copy(vc_ref.at[page], vc_tile.at[s],
                                      sems.at[s, 1]),
                pltpu.make_async_copy(kcb_ref.at[row], cb_smem.at[2 * s],
                                      sems.at[s, 2]),
                pltpu.make_async_copy(vcb_ref.at[row], cb_smem.at[2 * s + 1],
                                      sems.at[s, 3])]

    def start_page(j, s):
        page = table_ref[b, j]
        if not quantized:
            for c in fp_copies(page, s):
                c.start()
            return
        frozen = blkq_ref[page] != 0

        @pl.when(frozen)
        def _():
            for c in code_copies(page, s):
                c.start()

        @pl.when(jnp.logical_not(frozen))
        def _():
            for c in fp_copies(page, s):
                c.start()

    def finish_page(j, s):
        page = table_ref[b, j]
        if not quantized:
            for c in fp_copies(page, s):
                c.wait()
            return
        frozen = blkq_ref[page] != 0

        @pl.when(frozen)
        def _():
            for c in code_copies(page, s):
                c.wait()
            kc = kc_tile[s]
            vc = vc_tile[s]
            k_idx = unpack4(kc) if packed else kc.astype(jnp.int32)
            v_idx = unpack4(vc) if packed else vc.astype(jnp.int32)
            off = page * Lp % LANES
            k_tile[s] = codebook_lookup(
                k_idx, lambda l: cb_smem[2 * s, off + l], L
            ).astype(k_tile.dtype)
            v_tile[s] = codebook_lookup(
                v_idx, lambda l: cb_smem[2 * s + 1, off + l], L
            ).astype(v_tile.dtype)

        @pl.when(jnp.logical_not(frozen))
        def _():
            for c in fp_copies(page, s):
                c.wait()

    q = q_ref[0].astype(jnp.float32)                       # (Hq, Dh)

    if double_buffer:
        # warm-up: page 0's DMA is in flight before the loop body runs
        @pl.when(n_pages > 0)
        def _():
            start_page(0, 0)

    def body(j, carry):
        m, l, acc = carry
        s = lax.rem(j, 2) if double_buffer else 0

        if double_buffer:
            # start page j+1 into the other slot, then wait page j: the
            # copy overlaps this iteration's wait+dequant+flash step
            @pl.when(j + 1 < n_pages)
            def _():
                start_page(j + 1, lax.rem(j + 1, 2))

            @pl.when(j < n_pages)
            def _():
                finish_page(j, s)
        else:
            @pl.when(j < n_pages)
            def _():
                start_page(j, 0)
                finish_page(j, 0)

        # Positions >= valid are masked to BIG_NEG below, contributing
        # exp(BIG_NEG-m) = 0. Pages past n_pages never DMA'd into this
        # slot, so zero the tiles outright: stale (or, double-buffered
        # with n_pages == 1, never-written) VMEM must not reach the
        # matmuls — 0 * garbage is 0 but 0 * NaN is NaN.
        live = j < n_pages
        kt = jnp.where(live, k_tile[s].astype(jnp.float32), 0.0)
        vt = jnp.where(live, v_tile[s].astype(jnp.float32), 0.0)
        s = jnp.concatenate(
            [lax.dot_general(q[h * WG:(h + 1) * WG], kt[:, h, :],
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
             for h in range(Hkv)], axis=0) * scale         # (Hq, bs)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        pos = j * bs + lax.broadcasted_iota(jnp.int32, (Hq, bs), 1)
        # query row r sits at sequence position valid - (W-1-w): older
        # window rows see strictly shorter prefixes (causal within the
        # window); W=1 collapses to the plain `pos < valid` decode mask
        w_row = lax.rem(lax.broadcasted_iota(jnp.int32, (Hq, bs), 0),
                        WG) // G
        mask = pos < valid - (W - 1 - w_row)
        s = jnp.where(mask, s, BIG_NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jnp.concatenate(
            [lax.dot_general(p[h * WG:(h + 1) * WG], vt[:, h, :],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
             for h in range(Hkv)], axis=0)                 # (Hq, Dh)
        return m_new, l_new, acc * corr + pv

    init = (jnp.full((Hq, 1), BIG_NEG, jnp.float32),
            jnp.zeros((Hq, 1), jnp.float32),
            jnp.zeros((Hq, Dh), jnp.float32))
    _, l, acc = lax.fori_loop(0, mb, body, init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


# ------------------------------------------------------------ entry point


@functools.partial(
    jax.jit, static_argnames=("softcap", "quantized", "packed",
                              "double_buffer", "interpret")
)
def paged_decode_attention(
    q: jax.Array,            # (B, Hq, Dh) queries, or (B, W, Hq, Dh) window
    k_fp: jax.Array,         # (nb, bs, Hkv, Dh) fp page pool
    v_fp: jax.Array,         # (nb, bs, Hkv, Dh)
    k_codes: jax.Array,      # (nb, bs/2, Hkv, Dh) packed 4-bit codes
    v_codes: jax.Array,      # (or (nb, bs, Hkv, Dh) u8 when not packed)
    k_cb: jax.Array,         # (nb, L) per-block codebooks, f32
    v_cb: jax.Array,         # (nb, L)
    blk_q: jax.Array,        # (nb,) page is served from codes
    block_table: jax.Array,  # (B, mb) page ids (0 = null page)
    kv_valid_len: jax.Array,  # (B,) tokens valid per sequence (>= 1)
    *,
    softcap: float | None = None,
    quantized: bool = False,
    packed: bool = True,
    double_buffer: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Fused flash-decode over the paged pools.

    ``q`` may be a single decode step (B, Hq, Dh) -> (B, Hq, Dh), or a
    speculative verify window (B, W, Hq, Dh) -> (B, W, Hq, Dh) whose W
    queries sit at positions ``kv_valid_len - W .. kv_valid_len - 1``
    (causal within the window); each page is still read once per sequence.

    ``double_buffer`` ping-pongs the per-page DMA across two VMEM slots so
    page j+1's copy overlaps page j's dequant + flash step; the serial
    variant (one slot, copy-then-compute) is kept selectable for the
    paged-attention benchmark's three-way row. Both variants run the exact
    same per-page arithmetic, so results are bitwise identical.
    """
    windowed = q.ndim == 4
    if not windowed:
        q = q[:, None]
    B, W, Hq, Dh = q.shape
    nb, bs, Hkv, _ = k_fp.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    G = Hq // Hkv
    L = k_cb.shape[1]
    scale = float(1.0 / np.sqrt(Dh))
    # kv-head-major query rows ((Hkv, W, G)) keep the kernel's static
    # per-kv-head loop a contiguous slice; identity when W == 1
    HqW = Hkv * W * G
    qr = q.reshape(B, W, Hkv, G, Dh).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(B, HqW, Dh)

    nslots = 2 if double_buffer else 1
    qspec = pl.BlockSpec((1, HqW, Dh), lambda b, *_: (b, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[qspec, hbm, hbm, hbm, hbm, hbm, hbm],
        out_specs=pl.BlockSpec((1, HqW, Dh), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nslots, bs, Hkv, Dh), k_fp.dtype),
            pltpu.VMEM((nslots, bs, Hkv, Dh), v_fp.dtype),
            pltpu.VMEM((nslots,) + k_codes.shape[1:], jnp.uint8),
            pltpu.VMEM((nslots,) + v_codes.shape[1:], jnp.uint8),
            pltpu.SMEM((2 * nslots, LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((nslots, 4)),
        ],
    )
    k_rows, Lp = codebook_rows(k_cb)
    v_rows, _ = codebook_rows(v_cb)
    kern = functools.partial(_kernel, bs, Hkv, G, W, Dh, L, Lp, scale,
                             softcap, quantized, packed, double_buffer)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, HqW, Dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # the device trace names the kernel's op by this; the benchmark's
        # paged_decode_roofline finds its calls by it
        name="paged_decode_attention",
    )(block_table.astype(jnp.int32), kv_valid_len.astype(jnp.int32),
      blk_q.astype(jnp.int32), qr, k_fp, v_fp, k_codes, v_codes, k_rows,
      v_rows)
    out = out.reshape(B, Hkv, W, G, Dh).transpose(0, 2, 1, 3, 4)
    out = out.reshape(B, W, Hq, Dh)
    return out if windowed else out[:, 0]


# ------------------------------------------------------------ prefill entry


def paged_prefill_attention(
    q: jax.Array,            # (B, C, Hq, Dh) one prompt chunk of C queries
    k_fp: jax.Array,
    v_fp: jax.Array,
    k_codes: jax.Array,
    v_codes: jax.Array,
    k_cb: jax.Array,
    v_cb: jax.Array,
    blk_q: jax.Array,
    block_table: jax.Array,
    q_offset: jax.Array,     # (B,) chunk start position per sequence
    *,
    softcap: float | None = None,
    quantized: bool = False,
    packed: bool = True,
    double_buffer: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Fused chunked-prefill: score one prompt chunk against its prefix.

    The chunk's C queries sit at positions ``q_offset .. q_offset + C - 1``
    (the chunk's own K/V already written to the pool), attending causally
    over every earlier page through the *same* conditional-DMA + in-VMEM
    dequant path as decode — a pre-frozen prefix (shared context restored
    as codes) crosses HBM at ~4 bits/value instead of being gathered fp.

    This is exactly the decode kernel's query-window layout with W = C and
    ``kv_valid_len = q_offset + C``: row w's causal chunk mask
    ``pos < valid - (C-1-w)`` reduces to ``pos <= q_offset + w``. Because
    the online-softmax carry is per query row and pages are walked in the
    same order whatever the window size, chunked calls are bitwise
    identical to one whole-prompt call (the PR 5 verify-window discipline
    applied to prefill).
    """
    assert q.ndim == 4, "prefill queries are (B, C, Hq, Dh) chunks"
    C = q.shape[1]
    valid = jnp.asarray(q_offset, jnp.int32) + C
    return paged_decode_attention(
        q, k_fp, v_fp, k_codes, v_codes, k_cb, v_cb, blk_q, block_table,
        valid, softcap=softcap, quantized=quantized, packed=packed,
        double_buffer=double_buffer, interpret=interpret)


# ------------------------------------------------------------ bytes model


def modeled_hbm_bytes_per_token(
    block_table, seq_lens, blk_q, *, block_size: int, n_kv_heads: int,
    head_dim: int, num_values: int, quantized: bool, packed: bool,
    path: str, fp_bytes: int = 4,
) -> float:
    """Analytic HBM read bytes per decoded token, one attention layer.

    ``seq_lens`` are pre-write lengths (the kernel sees valid = len + 1).
    The gather path materializes every table column for every row at full
    width (it gathers every page's fp rows, frozen ones included, before
    selecting the frozen pages' dequantized codes, so every page crosses
    HBM at fp_bytes/value; the codes it also reads are not counted); the
    fused path reads, per sequence, only ``ceil((len+1)/bs)`` pages, each
    as *either* codes+codebooks (frozen, ~4 bits/value) or fp (hot). K and
    V both counted; q/output traffic is identical for both paths and
    excluded.
    """
    table = np.asarray(block_table)
    lens = np.asarray(seq_lens)
    bq = np.asarray(blk_q).astype(bool).reshape(-1)
    B, mb = table.shape
    bs = block_size
    elems = bs * n_kv_heads * head_dim
    fp_page = 2 * elems * fp_bytes
    Dc = head_dim // 2 if packed else head_dim
    code_page = 2 * (bs * n_kv_heads * Dc + num_values * 4)
    if path == "gather":
        return float(mb * fp_page)
    assert path == "fused", path
    total = 0
    for b in range(B):
        n_pages = -(-(int(lens[b]) + 1) // bs)
        for j in range(min(n_pages, mb)):
            frozen = quantized and bq[table[b, j]]
            total += code_page if frozen else fp_page
    return total / B


def modeled_prefill_hbm_bytes_per_token(
    block_table, prompt_lens, blk_q, *, chunk: int, block_size: int,
    n_kv_heads: int, head_dim: int, num_values: int, quantized: bool,
    packed: bool, path: str, fp_bytes: int = 4,
) -> float:
    """Analytic HBM read bytes per *prompt* token for chunked prefill, one
    attention layer.

    Prefill in chunks of ``chunk`` tokens re-reads the growing prefix once
    per chunk. The gather path materializes the sequence's whole block
    table at fp width for every chunk (what ``update`` + sdpa does); the
    fused path reads, per chunk, only the ``ceil((off + C) / bs)`` pages
    covering that chunk's prefix, each as either codes + codebooks (frozen
    shared context) or fp (hot). K and V both counted; q/output traffic is
    identical for both paths and excluded.
    """
    table = np.asarray(block_table)
    lens = np.asarray(prompt_lens)
    bq = np.asarray(blk_q).astype(bool).reshape(-1)
    B, mb = table.shape
    bs = block_size
    elems = bs * n_kv_heads * head_dim
    fp_page = 2 * elems * fp_bytes
    Dc = head_dim // 2 if packed else head_dim
    code_page = 2 * (bs * n_kv_heads * Dc + num_values * 4)
    total = 0
    n_tok = 0
    for b in range(B):
        P = int(lens[b])
        n_tok += P
        for off in range(0, P, chunk):
            C = min(chunk, P - off)
            if path == "gather":
                total += mb * fp_page
                continue
            assert path == "fused", path
            n_pages = -(-(off + C) // bs)
            for j in range(min(n_pages, mb)):
                frozen = quantized and bq[table[b, j]]
                total += code_page if frozen else fp_page
    return total / max(n_tok, 1)
