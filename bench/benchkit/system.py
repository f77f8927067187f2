"""The system under test, as the benchmark drives it: the repository's
``repro`` package, imported from ``src/`` beside the benchmark.

This is the only module of the benchmark that imports the program. It
builds the program's configuration from a configuration file (and checks
that every size the file states is the size the program runs), hands the
benchmark's weights over in the program's layout, builds the engine, and
turns the benchmark's requests into the program's ``Request``s.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def import_program():
    """Put ``src/`` on the path; fail loudly when it is not there."""
    src = REPO / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"the system under test is not at {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import repro  # noqa: F401


def program_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    program's own entry for the architecture, with the depth the file
    states; every other size must already agree."""
    import_program()
    import jax.numpy as jnp
    from repro.configs import get_config

    from repro.configs import get_reduced_config

    prog = conf["program"]
    base = (get_reduced_config if prog.get("preset") == "reduced"
            else get_config)(prog["arch"])
    cfg = dataclasses.replace(base,
                              n_layers=conf["num_hidden_layers"])
    cfg = cfg.validate()
    dt = str(jnp.dtype(conf["torch_dtype"]))
    want = {
        "d_model": conf["hidden_size"], "n_heads": conf["num_attention_heads"],
        "n_kv_heads": conf["num_key_value_heads"],
        "head_dim": conf.get("head_dim") or (conf["hidden_size"]
                                             // conf["num_attention_heads"]),
        "d_ff": conf["intermediate_size"], "vocab": conf["vocab_size"],
        "rope_theta": float(conf["rope_theta"]),
        "tie_embeddings": bool(conf["tie_word_embeddings"]),
        "qk_norm": "Qwen3ForCausalLM" in conf["architectures"],
        "param_dtype": dt, "compute_dtype": dt,
    }
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"program config departs from {conf['name']}: "
                         f"(program, file) = {bad}")
    return cfg


def program_params(weights: dict) -> dict:
    """The benchmark's weights in the program's parameter layout (the
    same arrays, renamed; no copy)."""
    lw = weights["layers"]
    mixer = {k: lw[k] for k in ("wq", "wk", "wv", "wo")}
    for k in ("q_norm", "k_norm"):
        if k in lw:
            mixer[k] = lw[k]
    out = {"embed": weights["embed"], "final_norm": weights["final_norm"],
           "groups": {"l0": {"ln1": lw["attn_norm"], "mixer": mixer,
                             "ln2": lw["mlp_norm"],
                             "ffn": {k: lw[k] for k in
                                     ("w_gate", "w_up", "w_down")}}}}
    if "lm_head" in weights:
        out["lm_head"] = weights["lm_head"]
    return out


def build_engine(cfg, params, conf: dict, mix: dict, tracer):
    import_program()
    from repro.serving import ContinuousBatchingEngine

    s = conf["serving"]
    return ContinuousBatchingEngine(
        params, cfg, max_slots=mix["slots"], block_size=s["block_size"],
        max_seq_len=mix["max_seq_len"], kv_quant=s["kv_quant"],
        attn_impl=s["attn_impl"], freeze_page_budget=s["freeze_page_budget"],
        max_queue=s["max_queue"], tracer=tracer)


def requests(items) -> list:
    from repro.serving.scheduler import Request

    return [Request(id=it.id, prompt=it.prompt,
                    max_new_tokens=it.max_new_tokens, arrival_time=it.due)
            for it in items]


def warm_decode_widths(engine, widths) -> None:
    """Run the engine's own decode-step program once at each decode table
    width, as its decode step dispatches it, on the idle engine (every
    slot empty, so the step writes only the null page) and throw the
    result away."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serving.kv_cache import merge_pools, with_tables

    w = engine.worker
    toks = jnp.asarray(np.zeros((len(w.slots), 1), np.int32))
    for mb in widths:
        tree = with_tables(w.tree, w.table[:, :mb], w.lens)
        logits, new = w._decode_fn(w.params, toks, tree, jnp.asarray(w.lens))
        merge_pools(w.tree, new)
        np.asarray(jnp.argmax(logits[:, -1], -1))


def warm_page_programs(engine, page_counts, freeze_budget: int) -> int:
    """Load the programs the window can reach that no short warm-up run
    visits: clearing the frozen flags of a finished request's pages (one
    program per page count) and each freeze bucket (1, 2, 4, ... up to the
    budget). Results are thrown away; the engine's state is untouched.
    Returns how many calls were made."""
    import jax
    from repro.serving.kv_cache import (dispatch_freeze, install_freeze,
                                        thaw_blocks)

    w = engine.worker
    calls = 0
    for n in page_counts:
        jax.block_until_ready(thaw_blocks(w.tree, list(range(1, n + 1))))
        calls += 1
    b, top = 1, 1 << (freeze_budget - 1).bit_length()
    while b <= top:
        pend = dispatch_freeze(w.tree, list(range(1, b + 1)), w.kv_spec)
        jax.block_until_ready(install_freeze(w.tree, pend))
        calls += 1
        b *= 2
    return calls
