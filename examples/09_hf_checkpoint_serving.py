"""Serve a Hugging Face checkpoint: from_hf -> serve.run -> generate.

The weights here are a randomly initialized tiny Llama (no downloads in
this environment); with a real checkpoint directory, replace the model
construction with `LlamaForCausalLM.from_pretrained(path)` — the
conversion and serving path is identical, and greedy outputs are
token-exact vs transformers (see tests/test_hf_convert.py).
"""
import dataclasses

import torch
import transformers

import jax
import jax.numpy as jnp

import ray_tpu
from ray_tpu import serve
from ray_tpu.models import from_hf
from ray_tpu.serve.llm import LLMDeployment

# seeded: a draw whose greedy tokens hit the config's EOS (id 2) stops
# transformers early and not the engine, once in ~30 runs
torch.manual_seed(0)
hf_model = transformers.LlamaForCausalLM(transformers.LlamaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    attention_bias=False, mlp_bias=False)).eval()

cfg, params = from_hf(hf_model, name="tiny-llama-demo")
cfg = dataclasses.replace(cfg, compute_dtype=jnp.float32, remat=False)

ray_tpu.init(num_cpus=2)
handle = serve.run(
    serve.deployment(LLMDeployment).bind(
        cfg, num_slots=2, max_len=64, params_loader=lambda: params),
    name="hf_demo")

prompt = [11, 42, 7, 99]
out = handle.remote({"tokens": prompt, "max_tokens": 8,
                     "temperature": 0.0}).result(timeout=300)
with torch.no_grad():
    ref = hf_model.generate(
        torch.tensor([prompt]), max_new_tokens=8,
        do_sample=False)[0, len(prompt):].tolist()
print("served tokens:", out["tokens"])
print("transformers :", ref)
assert out["tokens"] == ref, "greedy outputs must be token-exact"
serve.delete("hf_demo")
ray_tpu.shutdown()
print("HF checkpoint served with token-exact parity")
