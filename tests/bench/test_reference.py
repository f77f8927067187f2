"""The benchmark's plain float32 reference agrees with the program.

At the program's reduced presets (float32, CPU), the same weights made by
the benchmark go through the program's own serving path (paged prefill,
then decode through the paged cache, fp KV) and through the reference's
full forward; the logits of every served position agree to float32
round-off. Both architectures the benchmark serves are covered: Qwen3
(qk-norm, tied embeddings) and the Llama layout of Yi (untied head).
"""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "bench"))

from benchkit import harness, system  # noqa: E402

REDUCED = {
    "qwen3_0_6b": dict(architectures=["Qwen3ForCausalLM"], hidden_size=64,
                       intermediate_size=128, num_hidden_layers=4,
                       num_attention_heads=4, num_key_value_heads=2,
                       head_dim=32, vocab_size=499, rms_norm_eps=1e-6,
                       rope_theta=1e6, tie_word_embeddings=True),
    "yi_34b": dict(architectures=["LlamaForCausalLM"], hidden_size=64,
                   intermediate_size=160, num_hidden_layers=3,
                   num_attention_heads=8, num_key_value_heads=2,
                   head_dim=8, vocab_size=257, rms_norm_eps=1e-6,
                   rope_theta=5e6, tie_word_embeddings=False),
}


def _conf(arch):
    return dict(REDUCED[arch], name=f"{arch}-reduced", torch_dtype="float32",
                reference="dense_gqa",
                program={"arch": arch, "preset": "reduced"},
                serving={"kv_quant": None, "block_size": 16,
                         "freeze_page_budget": 4, "attn_impl": "gather",
                         "max_queue": 16})


@pytest.mark.parametrize("arch", sorted(REDUCED))
def test_reference_matches_program_prefill_then_decode(arch):
    conf = _conf(arch)
    ref = harness.reference(conf)
    cfg = system.program_config(conf)
    weights = ref.init_weights(conf, seed=2**36 + 3)
    from repro.serving import ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(
        system.program_params(weights), cfg, max_slots=2, block_size=16,
        max_seq_len=64, record_logits=True, attn_impl="gather")
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, conf["vocab_size"], n)) for n in (21, 9)]
    outs = eng.generate(prompts, max_new_tokens=12)
    for i, p in enumerate(prompts):
        served = outs[i]
        got = np.asarray(eng.request_logits[i])          # (12, V)
        want = ref.forward(weights, conf, p + served[:-1])[len(p) - 1:]
        assert got.shape == want.shape
        scale = np.abs(want).max()
        assert np.abs(got - want).max() <= 2e-4 * scale, arch
        # greedy serving of an exact model picks the reference's argmax
        assert served == list(np.argmax(want, -1))


def test_weights_are_deterministic_and_keep_64_bit_seeds():
    conf = _conf("qwen3_0_6b")
    ref = harness.reference(conf)
    a = ref.init_weights(conf, seed=2**40 + 1)
    b = ref.init_weights(conf, seed=2**40 + 1)
    c = ref.init_weights(conf, seed=1)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool((a["embed"] == c["embed"]).all())


def test_control_is_one_precision_lower():
    """The float8 control perturbs logits well beyond float32 round-off."""
    conf = _conf("qwen3_0_6b")
    ref = harness.reference(conf)
    w = ref.init_weights(conf, seed=5)
    toks = np.arange(32) % conf["vocab_size"]
    exact = ref.forward(w, conf, toks)
    low = ref.forward(w, conf, toks, precision="fp8")
    rel = np.abs(low - exact).max() / np.abs(exact).max()
    assert 1e-3 < rel < 0.5
