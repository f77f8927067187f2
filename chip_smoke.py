"""Chip smoke run: every serving kernel compiled for the chip, then the
continuous-batching server at qwen3-0.6b's published widths.

    python chip_smoke.py

Runs in one process on one TPU. It exits non-zero, printing no result, when
JAX finds no TPU (there is no CPU fallback) or when the repository's
``src/`` is not next to this file. Phases:

  kernels   each Pallas kernel of the serving path, compiled
            (``interpret=False``) at qwen3-0.6b widths (Hq 16, Hkv 8,
            head_dim 128, page 16, d_model 1024, d_ff 3072), against its
            ``kernels/ref.py`` oracle: paged decode attention with fp and
            4-bit frozen pages (one token and a 4-token verify window),
            ``quant_matmul``, ``quant_matmul_stacked`` and ``fista_quant``.
  serve     ``repro.launch.serve.main`` with ``get_config("qwen3_0_6b")``
            and random weights from seed 0: 8 Poisson requests, prompt 128,
            gen 32, 8 slots x 512 tokens, first with fp KV, then with
            ``--kv-quant kmeans_ls@16`` and its fp-vs-quantized replay.
            Every request must complete, decode must run through the fused
            kernel, no page may be solved on the host, and the replay must
            pass.

Every phase runs even when an earlier one failed; the script fails if any
did. Earlier lines report compile seconds per phase, the device kind, peak
device bytes, each oracle check, and the replay's max|dlogit| and
greedy-token agreement. The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import json
import sys
import time
import traceback
from pathlib import Path

SERVE_ARGV = ["--arch", "qwen3_0_6b", "--engine", "continuous",
              "--num-requests", "8", "--request-rate", "8",
              "--prompt-len", "128", "--gen", "32",
              "--max-slots", "8", "--max-seq-len", "512", "--seed", "0"]
KV_QUANT = "kmeans_ls@16"

# qwen3-0.6b widths (configs/qwen3_0_6b.py)
HQ, HKV, DH, D_MODEL, D_FF, N_LAYERS = 16, 8, 128, 1024, 3072, 28
BS, L = 16, 16                     # page size, codebook values (4-bit)


def _fail(msg: str) -> None:
    print(f"[smoke] FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


class _CompileClock:
    """Sums the seconds JAX spends compiling programs for the backend (XLA
    and Mosaic; a cache hit adds nothing). Tracing is left out: a jit
    traced inside another jit's trace would be counted twice."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, monitoring):
        self.total = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == self.EVENT:
            self.total += secs


def _rel_err(out, ref) -> float:
    import numpy as np

    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))


def _kernel_checks(rng):
    """(name, error, tolerance) per kernel run against its oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import (fista_quant, pack4, paged_decode_attention,
                               power_iter_lipschitz, quant_matmul,
                               quant_matmul_stacked, ref_fista,
                               ref_paged_decode, ref_quant_matmul,
                               ref_quant_matmul_stacked)

    out = []
    # -- paged decode attention: 80-page bf16 pool, 8 ragged sequences
    nb, B, mb = 80, 8, 8
    normal = lambda *s: rng.normal(size=s).astype(np.float32)
    k_fp = jnp.asarray(normal(nb, BS, HKV, DH), jnp.bfloat16)
    v_fp = jnp.asarray(normal(nb, BS, HKV, DH), jnp.bfloat16)
    codes = lambda: pack4(jnp.asarray(rng.integers(0, L, (nb, BS, HKV, DH)),
                                      jnp.uint8))
    k_codes, v_codes = codes(), codes()
    k_cb = jnp.asarray(np.sort(normal(nb, L), axis=1))
    v_cb = jnp.asarray(np.sort(normal(nb, L), axis=1))
    blk_q = jnp.asarray(rng.integers(0, 2, nb), jnp.int32)
    table = jnp.asarray(rng.permutation(np.arange(1, nb))[:B * mb]
                        .reshape(B, mb), jnp.int32)
    valid = jnp.asarray([128, 97, 4, 64, 33, 120, 16, 17], jnp.int32)
    state = (k_fp, v_fp, k_codes, v_codes, k_cb, v_cb, blk_q, table, valid)
    for W in (1, 4):
        shape = (B, HQ, DH) if W == 1 else (B, W, HQ, DH)
        q = jnp.asarray(normal(*shape), jnp.bfloat16)
        for quantized in (False, True):
            got = paged_decode_attention(q, *state, quantized=quantized,
                                         interpret=False)
            with jax.default_matmul_precision("highest"):
                want = ref_paged_decode(q, *state, quantized=quantized)
            # bf16 output: one rounding of ~2^-9 relative per element
            out.append((f"paged_decode_attention "
                        f"{'4bit' if quantized else 'fp'} W={W}",
                        _rel_err(got, want), 1e-2))
    # -- fused dequant matmuls: decode (M=8) and prefill (M=128) rows
    for M in (8, 128):
        x = jnp.asarray(normal(M, D_MODEL), jnp.bfloat16)
        idx = jnp.asarray(rng.integers(0, L, (D_MODEL, D_FF)), jnp.uint8)
        cb = jnp.asarray(normal(L))
        got = quant_matmul(x, idx, cb, interpret=False)
        with jax.default_matmul_precision("highest"):
            want = ref_quant_matmul(x, idx, cb)
        # bf16 output; k-blocked f32 accumulation order differs
        out.append((f"quant_matmul M={M}", _rel_err(got, want), 1e-2))
        xs = jnp.asarray(normal(N_LAYERS, M, D_MODEL), jnp.bfloat16)
        idxs = jnp.asarray(rng.integers(0, L, (N_LAYERS, D_MODEL, D_FF)),
                           jnp.uint8)
        cbs = jnp.asarray(normal(N_LAYERS, L))
        got = quant_matmul_stacked(xs, idxs, cbs, interpret=False)
        with jax.default_matmul_precision("highest"):
            want = ref_quant_matmul_stacked(xs, idxs, cbs)
        out.append((f"quant_matmul_stacked G={N_LAYERS} M={M}",
                    _rel_err(got, want), 1e-2))
    # -- FISTA: one freeze event's rows (k/v x 28 layers x 4 pages), each
    # a 128-point sorted sketch, as kernels/page_quant.py lays them out
    R, T = 2 * N_LAYERS * 4, 128
    w = np.sort(normal(R, T), axis=1)
    d = np.diff(w, axis=1, prepend=0.0).astype(np.float32)
    n = np.ones((R, T), np.float32)
    lam = np.full((R, T), 0.05, np.float32)
    eta = (1.0 / (power_iter_lipschitz(d, n) * 1.01)).astype(np.float32)
    blk = lambda a: jnp.asarray(a.reshape(R, 1, T))
    got = fista_quant(blk(w), blk(d), blk(n), blk(lam),
                      jnp.asarray(eta.reshape(R, 1, 1)), n_iters=50,
                      block_t=T, interpret=False)
    with jax.default_matmul_precision("highest"):
        want = ref_fista(*(jnp.asarray(a) for a in (w, d, n, lam, eta)),
                         n_iters=50)
    got = np.asarray(got).reshape(R, T)
    # the interpret-mode test's bound (tests/test_kernels.py), as one number
    err = float(np.max(np.abs(got - np.asarray(want))
                       / (2e-4 + 1e-3 * np.abs(np.asarray(want)))))
    out.append((f"fista_quant R={R}", err, 1.0))
    return out


def _serve_checks(summary: dict, quantized: bool) -> list[str]:
    """Reasons the serving summary fails the smoke contract."""
    bad = []
    if summary.get("attn_impl") != "fused":
        bad.append(f"attn_impl={summary.get('attn_impl')} (decode must run "
                   f"through the fused kernel)")
    if summary.get("completed") != 8 or summary.get("rejected"):
        bad.append(f"completed {summary.get('completed')}/8, rejected "
                   f"{summary.get('rejected')}")
    if summary.get("host_page_solves"):
        bad.append(f"{summary['host_page_solves']} host page solves")
    if quantized:
        v = summary.get("verify") or {}
        if not v.get("ok") or v.get("host_page_solves"):
            bad.append(f"verification replay failed: {v}")
    return bad


def main() -> None:
    root = Path(__file__).resolve().parent
    sys.path.insert(0, str(root / "src"))
    try:
        from repro.launch.compile_cache import init_compile_cache
    except ImportError as e:
        _fail(f"the repository's src/ is not next to {Path(__file__).name}: "
              f"{e}")
    cache_dir = init_compile_cache()

    import jax
    import numpy as np
    from jax import monitoring

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _fail(f"JAX found no TPU (platform {dev.platform!r}); this check "
              f"runs on the chip only")
    print(f"[smoke] device {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}, compile cache {cache_dir}", flush=True)
    clock = _CompileClock(monitoring)
    failures: list[str] = []

    def phase_done(name, t0, c0):
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        print(f"[smoke] phase {name}: {time.perf_counter() - t0:.1f}s wall, "
              f"{clock.total - c0:.1f}s backend compile, peak_bytes_in_use "
              f"{peak}",
              flush=True)

    t0, c0 = time.perf_counter(), clock.total
    try:
        for name, err, tol in _kernel_checks(np.random.default_rng(0)):
            ok = err <= tol
            print(f"[smoke] kernel {name}: error {err:.3e} (bound {tol:g}) "
                  f"-> {'OK' if ok else 'MISMATCH'}", flush=True)
            if not ok:
                failures.append(f"kernel {name} off its oracle")
    except Exception as e:                       # report, run the rest
        failures.append(f"kernel phase raised {type(e).__name__}: {e}")
        traceback.print_exc()
    phase_done("kernels", t0, c0)

    from repro.launch import serve

    for kv in (None, KV_QUANT):
        name = f"serve kv={kv or 'fp'}"
        argv = SERVE_ARGV + (["--kv-quant", kv] if kv else [])
        t0, c0 = time.perf_counter(), clock.total
        try:
            s = serve.main(argv)
        except SystemExit as e:
            s = None
            failures.append(f"{name} exited {e.code}")
        except Exception as e:                   # report, run the rest
            s = None
            failures.append(f"{name} raised {type(e).__name__}: {e}")
            traceback.print_exc()
        phase_done(name, t0, c0)
        if s is None:
            continue
        v = s.get("verify")
        if v:
            print(f"[smoke] {name} replay: max|dlogit| {v['max_dlogit']:.4f} "
                  f"(rel {v['rel_dlogit']:.3%}; bound abs {v['tol_abs']}, "
                  f"rel {v['tol_rel']:.0%}), greedy-token agreement "
                  f"{v['agree']}/{v['total']}", flush=True)
        failures += [f"{name}: {b}" for b in _serve_checks(s, kv is not None)]

    if failures:
        _fail("; ".join(failures))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
