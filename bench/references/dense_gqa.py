"""Plain reference for dense grouped-query-attention decoders: the Llama
family layout that Yi-34B uses, and Qwen3 (the same block with RMSNorm on
each query and key head).

It follows the published modelling code (Hugging Face ``LlamaForCausalLM``
and ``Qwen3ForCausalLM``): pre-norm RMSNorm blocks, rotary embeddings in the
rotate-half layout, grouped-query softmax attention with a causal mask,
SwiGLU feed-forward, a final RMSNorm and the output head (the transposed
embedding when ``tie_word_embeddings``). Everything runs in float32 at
``Precision.HIGHEST`` with no kernels and no cache: whole sequences from
position 0, a few at a time, layer by layer, so that a model whose weights
fill the chip in bfloat16 never holds more than one layer in float32.

The weights come from ``init_weights`` and are made by the benchmark from
its seed, never by the program under test. Norm scales are stored as their
offset from 1 (a published scale ``g`` is kept as ``g - 1``), which is
also how the program keeps them, so the program is handed these leaves
without any arithmetic.

``precision="fp8"`` rounds every matmul operand to float8 (e4m3, one
scale per tensor) before the float32 product, the step below the
configuration's bfloat16. ``precision="fp8_kv3"``, the benchmark's
control, also rounds the keys (after rotary) and values of each page of
``block_size`` positions, per layer, to an 8-value codebook (3 bits, the
step below the configuration's 16-value pages), fitted by a few rounds of
Lloyd's k-means: every page, the one being written included.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0       # largest finite float8_e4m3fn


def dims(conf: dict) -> dict:
    """The sizes the forward needs, read from a configuration file."""
    d = conf["hidden_size"]
    H = conf["num_attention_heads"]
    return dict(
        L=conf["num_hidden_layers"], d=d, H=H,
        Hkv=conf["num_key_value_heads"],
        Dh=conf.get("head_dim") or d // H,
        F=conf["intermediate_size"], V=conf["vocab_size"],
        theta=float(conf["rope_theta"]), eps=float(conf["rms_norm_eps"]),
        tied=bool(conf["tie_word_embeddings"]),
        qk_norm="Qwen3ForCausalLM" in conf["architectures"],
        page=int(conf["serving"]["block_size"]))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that keeps all 64 bits of ``seed``."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# ------------------------------------------------------------------ weights


def init_weights(conf: dict, seed: int, dtype=None) -> dict:
    """Random weights from ``seed``, made on the device in one jitted call
    in the type they are served in (``torch_dtype`` unless ``dtype``).

    Layer leaves are stacked on a leading layer axis. Projections are
    N(0, 1/fan_in), so hidden states and logits stay near unit scale; the
    embedding is N(0, 1/d) and norm-scale offsets N(0, 0.1^2)."""
    dt = jnp.dtype(dtype or conf["torch_dtype"])
    return _init(seed_key(seed), tuple(sorted(dims(conf).items())), dt)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init(key, dims_items, dt):
    m = dict(dims_items)
    L, d, H, Hkv, Dh, F, V = (m[k] for k in ("L", "d", "H", "Hkv", "Dh",
                                             "F", "V"))
    k_emb, k_head, k_fin, k_layers = jax.random.split(key, 4)

    def normal(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dt)

    def layer(k):
        ks = jax.random.split(k, 11)
        out = {
            "attn_norm": normal(ks[0], (d,), 0.1),
            "wq": normal(ks[1], (d, H * Dh), d ** -0.5),
            "wk": normal(ks[2], (d, Hkv * Dh), d ** -0.5),
            "wv": normal(ks[3], (d, Hkv * Dh), d ** -0.5),
            "wo": normal(ks[4], (H * Dh, d), (H * Dh) ** -0.5),
            "mlp_norm": normal(ks[5], (d,), 0.1),
            "w_gate": normal(ks[6], (d, F), d ** -0.5),
            "w_up": normal(ks[7], (d, F), d ** -0.5),
            "w_down": normal(ks[8], (F, d), F ** -0.5),
        }
        if m["qk_norm"]:
            out["q_norm"] = normal(ks[9], (Dh,), 0.1)
            out["k_norm"] = normal(ks[10], (Dh,), 0.1)
        return out

    w = {"embed": normal(k_emb, (V, d), d ** -0.5),
         "final_norm": normal(k_fin, (d,), 0.1),
         # one layer at a time keeps the generator's temporaries per layer
         "layers": jax.lax.map(layer, jax.random.split(k_layers, L))}
    if not m["tied"]:
        w["lm_head"] = normal(k_head, (d, V), d ** -0.5)
    return w


# ------------------------------------------------------------------ forward


PRECISIONS = ("f32", "fp8", "fp8_kv3")
KV_VALUES = 8        # codebook size of fp8_kv3's pages
LLOYD_ROUNDS = 8


def _cast(x, precision: str):
    """Round a matmul operand to the precision under test."""
    assert precision in PRECISIONS, precision
    x = x.astype(jnp.float32)
    if precision == "f32":
        return x
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq, a, b, precision):
    return jnp.einsum(eq, _cast(a, precision), _cast(b, precision),
                      precision=HIGHEST)


def _page_codebook(x, page: int):
    """x (B, S, heads, Dh) with each page of ``page`` positions rounded to
    the nearest of its own ``KV_VALUES`` values (k-means, started at
    evenly spaced quantiles of the page)."""
    B, S = x.shape[:2]
    rows = x.reshape(B, S // page, -1)
    srt = jnp.sort(rows, axis=-1)
    n = rows.shape[-1]
    at = ((jnp.arange(KV_VALUES) + 0.5) * n / KV_VALUES).astype(jnp.int32)
    cb = srt[..., at]

    def nearest(cb):
        return jnp.argmin(jnp.abs(rows[..., None] - cb[..., None, :]), -1)

    def lloyd(cb, _):
        hot = jax.nn.one_hot(nearest(cb), KV_VALUES, dtype=jnp.float32)
        cnt = hot.sum(-2)
        tot = jnp.einsum("bpn,bpnv->bpv", rows, hot, precision=HIGHEST)
        return jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1.0), cb), None

    cb, _ = jax.lax.scan(lloyd, cb, None, length=LLOYD_ROUNDS)
    q = jnp.take_along_axis(cb, nearest(cb), axis=-1)
    return q.reshape(x.shape)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * inv * (1.0 + w.astype(jnp.float32))


def _rope(x, pos, theta):
    """Rotate-half rotary embedding; x (S, heads, Dh), pos (S,)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _qblock(S: int) -> int:
    for qb in (512, 384, 256, 128):
        if S % qb == 0:
            return qb
    return S


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _layer(x, lw, *, m, precision):
    """One decoder layer over a batch of whole sequences x (B, S, d)."""
    m = dict(m)
    B, S, _ = x.shape
    H, Hkv, Dh, eps = m["H"], m["Hkv"], m["Dh"], m["eps"]
    G = H // Hkv
    pos = jnp.arange(S)
    h = _rms(x, lw["attn_norm"], eps)
    q = _mm("bsd,de->bse", h, lw["wq"], precision).reshape(B, S, H, Dh)
    k = _mm("bsd,de->bse", h, lw["wk"], precision).reshape(B, S, Hkv, Dh)
    v = _mm("bsd,de->bse", h, lw["wv"], precision).reshape(B, S, Hkv, Dh)
    if m["qk_norm"]:
        q = _rms(q, lw["q_norm"], eps)
        k = _rms(k, lw["k_norm"], eps)
    q = jax.vmap(_rope, (0, None, None))(q, pos, m["theta"])
    k = jax.vmap(_rope, (0, None, None))(k, pos, m["theta"])
    if precision == "fp8_kv3":
        k = _page_codebook(k, m["page"])
        v = _page_codebook(v, m["page"])
    q = q.reshape(B, S, Hkv, G, Dh)
    qb = _qblock(S)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        s = _mm("bqhgd,bkhd->bhgqk", qi, k, precision) / math.sqrt(Dh)
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(S)[None]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return _mm("bhgqk,bkhd->bqhgd", p, v, precision).reshape(
            B, qb, H * Dh)

    att = jax.lax.map(block, jnp.arange(S // qb))          # (nb, B, qb, E)
    att = att.transpose(1, 0, 2, 3).reshape(B, S, H * Dh)
    x = x + _mm("bse,ed->bsd", att, lw["wo"], precision)
    h = _rms(x, lw["mlp_norm"], eps)
    gate = _mm("bsd,df->bsf", h, lw["w_gate"], precision)
    up = _mm("bsd,df->bsf", h, lw["w_up"], precision)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, lw["w_down"],
                   precision)


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _logits(x_rows, final_norm, head, *, m, precision):
    h = _rms(x_rows, final_norm, dict(m)["eps"])
    eq = "rd,vd->rv" if dict(m)["tied"] else "rd,dv->rv"
    return _mm(eq, h, head, precision)


def hidden_states(weights: dict, conf: dict, tokens, *,
                  precision: str = "f32") -> jax.Array:
    """Final-layer hidden states (B, S, d), float32, of a batch of token
    sequences (B, S), each from position 0."""
    m = tuple(sorted(dims(conf).items()))
    x = jnp.take(weights["embed"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(jnp.float32)
    layers = weights["layers"]
    for i in range(dict(m)["L"]):
        lw = {k: v[i] for k, v in layers.items()}
        x = _layer(x, lw, m=m, precision=precision)
    return x


def logits(weights: dict, conf: dict, x_rows, *,
           precision: str = "f32") -> jax.Array:
    """Output logits (R, V), float32, for hidden-state rows (R, d)."""
    m = tuple(sorted(dims(conf).items()))
    head = weights["embed"] if dict(m)["tied"] else weights["lm_head"]
    return _logits(x_rows, weights["final_norm"], head, m=m,
                   precision=precision)


def forward(weights: dict, conf: dict, tokens, *,
            precision: str = "f32") -> np.ndarray:
    """Logits (S, V) of every position of one sequence: the whole model."""
    x = hidden_states(weights, conf, np.asarray(tokens)[None],
                      precision=precision)[0]
    return np.asarray(logits(weights, conf, x, precision=precision))
