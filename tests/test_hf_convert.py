"""HF Llama-family checkpoint import: logits parity against
transformers (ref: the reference's HF integrations; conversion is
tested on a RANDOMLY INITIALIZED LlamaForCausalLM — no downloads)."""
import dataclasses

import numpy as np
import pytest

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")


def _tiny_llama(tie=False, n_kv=2):
    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=n_kv, max_position_embeddings=256,
        rms_norm_eps=1e-5, rope_theta=10000.0, tie_word_embeddings=tie,
        attention_bias=False, mlp_bias=False)
    torch.manual_seed(0)
    return transformers.LlamaForCausalLM(cfg).eval()


def test_logits_match_transformers():
    import jax.numpy as jnp

    from ray_tpu.models.hf_convert import from_hf
    from ray_tpu.models.transformer import forward

    model = _tiny_llama()
    cfg, params = from_hf(model, name="tiny-llama-test")
    assert cfg.n_kv_heads == 2 and cfg.n_layers == 2
    cfg = dataclasses.replace(cfg, compute_dtype=jnp.float32, remat=False)

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (2, 16))
    with torch.no_grad():
        ref = model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(forward(params, jnp.asarray(tokens), cfg))
    np.testing.assert_allclose(ours, ref, atol=2e-3, rtol=1e-3)


def test_tied_embeddings_and_generation():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.hf_convert import from_hf
    from ray_tpu.models.transformer import forward

    model = _tiny_llama(tie=True)
    cfg, params = from_hf(model)
    assert cfg.tie_embeddings and "lm_head" not in params
    cfg = dataclasses.replace(cfg, compute_dtype=jnp.float32, remat=False)
    tokens = jnp.asarray([[1, 2, 3, 4]])
    with torch.no_grad():
        ref = model(torch.tensor(np.asarray(tokens))).logits.numpy()
    ours = np.asarray(forward(params, tokens, cfg))
    np.testing.assert_allclose(ours, ref, atol=2e-3, rtol=1e-3)
    # greedy next-token agrees
    assert int(jnp.argmax(ours[0, -1])) == int(np.argmax(ref[0, -1]))


def test_rejects_unsupported_architectures():
    from ray_tpu.models.hf_convert import config_from_hf

    cfg = transformers.LlamaConfig(hidden_act="gelu")
    with pytest.raises(ValueError, match="SwiGLU"):
        config_from_hf(cfg)
    cfg = transformers.LlamaConfig(attention_bias=True)
    with pytest.raises(ValueError, match="bias"):
        config_from_hf(cfg)


def test_bf16_checkpoint_imports():
    """Real checkpoints ship bf16; torch bf16 has no direct .numpy()."""
    import jax.numpy as jnp

    from ray_tpu.models.hf_convert import from_hf
    from ray_tpu.models.transformer import forward

    model = _tiny_llama().to(torch.bfloat16)
    cfg, params = from_hf(model)
    out = forward(params, jnp.asarray([[1, 2, 3]]),
                  dataclasses.replace(cfg, remat=False))
    assert np.isfinite(np.asarray(out)).all()


def test_rejects_silent_divergence_cases():
    from ray_tpu.models.hf_convert import config_from_hf, from_hf

    with pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf(transformers.LlamaConfig(
            rope_scaling={"rope_type": "llama3", "factor": 8.0,
                          "original_max_position_embeddings": 8192,
                          "low_freq_factor": 1.0,
                          "high_freq_factor": 4.0}))
    with pytest.raises(ValueError, match="sliding_window"):
        config_from_hf(transformers.MistralConfig(
            sliding_window=128, max_position_embeddings=4096))
    # bias tensors in the state dict are refused, not dropped
    qcfg = transformers.Qwen2Config(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=2)
    qwen = transformers.Qwen2ForCausalLM(qcfg)
    with pytest.raises(ValueError, match="bias"):
        from_hf(qwen)


def test_serve_engine_matches_transformers_generate():
    """The continuous-batching engine serving converted HF weights must
    produce token-exact greedy continuations vs transformers.generate —
    end-to-end validation of prefill/decode against an independent
    implementation."""
    import dataclasses

    import jax.numpy as jnp

    from ray_tpu.models.hf_convert import from_hf
    from ray_tpu.serve.llm import PagedLLMEngine

    model = _tiny_llama()
    cfg, params = from_hf(model)
    cfg = dataclasses.replace(cfg, compute_dtype=jnp.float32, remat=False)
    eng = PagedLLMEngine(cfg, params, num_slots=2, max_len=64,
                         block_size=4, prefill_chunk=16)
    try:
        prompt = [3, 17, 42, 7]
        ours = eng.generate(prompt, max_tokens=6, temperature=0.0,
                            timeout=300)
        with torch.no_grad():
            ref = model.generate(torch.tensor([prompt]), max_new_tokens=6,
                                 do_sample=False)[0, len(prompt):].tolist()
        assert ours == ref, (ours, ref)
    finally:
        eng.shutdown()
