"""Module-level jitted steps for the static (one-shot fixed-batch) serve
path.

serve.py imports jax only inside its run functions (argument errors and
``--help`` never load it), so its jits cannot live at its module scope —
they live here instead (imported lazily by ``_run_static``), keeping the
shared-jit convention:
one compile cache per step shape, keyed on the hashable cfg, shared by
every caller instead of re-created per invocation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("cfg", "gen_len"))
def static_prefill(params, cfg, tokens, enc, gen_len: int):
    """Prefill ``tokens`` (B, P) and sample the first greedy token; the
    cache is sized for ``gen_len`` further decode steps."""
    from repro import models

    B, P = tokens.shape
    cache = models.init_cache(cfg, B, P + gen_len, enc_len=P)
    batch = {"tokens": tokens}
    if enc is not None:
        batch["enc_embeds"] = enc
    logits, cache = models.prefill(params, cfg, batch, cache)
    return jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32), cache


@functools.partial(jax.jit, static_argnames=("cfg",))
def static_decode_step(params, cfg, tok, cache, idx):
    """One greedy decode step at ring-cache position ``idx``."""
    from repro import models

    logits, cache = models.decode_step(params, cfg, tok, cache, idx)
    return jnp.argmax(logits[:, -1:], -1).astype(jnp.int32), cache
