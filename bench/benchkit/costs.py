"""The benchmark's own count of operations and bytes, and the chip's peaks.

Everything here is computed from the configuration file's sizes and from
what the engine did (lengths, pages), never from the program's own
models of itself.
"""
from __future__ import annotations

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def layer_matmul_params(m: dict) -> int:
    """Weights one decoder layer multiplies by, per token."""
    d, H, Hkv, Dh, F = m["d"], m["H"], m["Hkv"], m["Dh"], m["F"]
    return d * H * Dh + 2 * d * Hkv * Dh + H * Dh * d + 3 * d * F


def attention_flops(m: dict, context: int) -> int:
    """QK^T and PV for one query over ``context`` keys, all layers."""
    return 4 * m["L"] * m["H"] * m["Dh"] * context


def prefill_flops(m: dict, prompt_len: int) -> int:
    """A whole-prompt prefill: every layer for every prompt token, causal
    attention, and the output head for the one row that is sampled."""
    P = prompt_len
    return (2 * m["L"] * layer_matmul_params(m) * P
            + 4 * m["L"] * m["H"] * m["Dh"] * P * (P + 1) // 2
            + 2 * m["d"] * m["V"])


def decode_flops(m: dict, prompt_len: int, n_decoded: int) -> int:
    """``n_decoded`` tokens decoded after a prompt: token k (1-based)
    attends over prompt_len + k keys."""
    n = n_decoded
    ctx = n * prompt_len + n * (n + 1) // 2
    return (n * (2 * m["L"] * layer_matmul_params(m) + 2 * m["d"] * m["V"])
            + 4 * m["L"] * m["H"] * m["Dh"] * ctx)


def decode_step_flops(m: dict, lens) -> int:
    """One decode step over live sequences at pre-step lengths ``lens``:
    every layer and the output head for one token each, attention over
    len + 1 keys."""
    per_token = 2 * m["L"] * layer_matmul_params(m) + 2 * m["d"] * m["V"]
    return sum(per_token + 4 * m["L"] * m["H"] * m["Dh"] * (int(n) + 1)
               for n in lens)


def paged_decode_call(m: dict, lens, frozen_pages, *, block_size: int,
                      num_values: int, kv_bytes: int = 2) -> tuple[int, int]:
    """(bytes, flops) one decode step's paged-attention kernel needs, all
    layers, for the live sequences at pre-step lengths ``lens``.

    Each live sequence reads ceil((len + 1) / block_size) pages of K and of
    V: a frozen page as packed 4-bit codes plus its two codebooks, any
    other page at ``kv_bytes`` a value. The query is read and the output
    written once per sequence. ``frozen_pages[i]`` counts sequence i's
    frozen pages among those it reads."""
    Hkv, Dh, H, L = m["Hkv"], m["Dh"], m["H"], m["L"]
    page_vals = block_size * Hkv * Dh
    fp_page = 2 * page_vals * kv_bytes
    code_page = 2 * (page_vals // 2 + num_values * 4)
    nbytes = flops = 0
    for n, fz in zip(lens, frozen_pages):
        pages = -(-(int(n) + 1) // block_size)
        nbytes += fz * code_page + (pages - fz) * fp_page
        nbytes += 2 * H * Dh * kv_bytes
        flops += 4 * H * Dh * (int(n) + 1)
    return L * nbytes, L * flops
