"""Generative inference with deepspeed_tpu — the init_inference example.

    python examples/generate.py                      # random-weight tiny model
    python examples/generate.py --hf gpt2            # HF checkpoint via injection

With ``--hf`` the model weights come from a HuggingFace checkpoint through
the injection policies (module_inject/replace_policy.py) — the
`deepspeed.init_inference(..., replace_with_kernel_inject=True)` analogue.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hf", default=None, help="HF model name (e.g. gpt2)")
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--top-p", type=float, default=0.95)
    args = ap.parse_args()

    platform = jax.default_backend()
    on_tpu = platform == "tpu"
    print(f"platform={platform}" + ("" if on_tpu else
          " — not a TPU: float32 weights instead of bf16"), flush=True)
    if args.hf:
        from transformers import AutoModelForCausalLM, AutoTokenizer

        hf_model = AutoModelForCausalLM.from_pretrained(args.hf)
        engine = deepspeed_tpu.init_inference(
            hf_model=hf_model, config={"dtype": "bf16" if on_tpu else "fp32"})
        tok = AutoTokenizer.from_pretrained(args.hf)
        prompt = tok("DeepSpeed on TPU is", return_tensors="np")["input_ids"]
    else:
        from deepspeed_tpu.models.transformer import Model, TransformerConfig

        model = Model(TransformerConfig(
            vocab_size=1024, max_seq_len=256, num_layers=4, num_heads=8,
            hidden_size=256, dtype=jnp.bfloat16 if on_tpu else jnp.float32))
        engine = deepspeed_tpu.init_inference(
            model=model, config={"dtype": "bf16" if on_tpu else "fp32"})
        tok = None
        prompt = np.random.default_rng(0).integers(0, 1024, (1, 16)).astype(np.int32)

    out = engine.generate(
        prompt, max_new_tokens=args.tokens, temperature=args.temperature,
        top_p=args.top_p, rng=jax.random.PRNGKey(0))
    print(f"generated token ids (platform={platform}):", out[0].tolist())
    if tok is not None:
        print("text:", tok.decode(np.concatenate([prompt[0], out[0]])))


if __name__ == "__main__":
    main()
