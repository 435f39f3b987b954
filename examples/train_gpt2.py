"""Train a GPT-2-class model with deepspeed_tpu — the 'cifar10_deepspeed.py'
style end-to-end example, TPU-native.

    python examples/train_gpt2.py                 # tiny model, synthetic data
    python examples/train_gpt2.py --layers 12 --hidden 768 --steps 100

Shows the full surface a DeepSpeed user expects: a JSON-style config with
ZeRO + bf16 + activation checkpointing, one `train_batch` call per step,
periodic checkpointing, and resume.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models.transformer import Model, TransformerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--zero-stage", type=int, default=1)
    ap.add_argument("--optimizer", default="AdamW",
                    choices=["AdamW", "Adam", "Lamb",
                             "OneBitAdam", "OneBitLamb", "ZeroOneAdam"],
                    help="1-bit family = error-feedback compressed comm "
                         "(docs/config.md 'Optimizer')")
    ap.add_argument("--ckpt-dir", default="/tmp/dstpu_example_ckpt")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    platform = jax.default_backend()
    on_tpu = platform == "tpu"
    print(f"platform={platform}" + ("" if on_tpu else
          " — not a TPU: float32 + XLA attention instead of bf16 + flash; "
          "a functional run, not a speed reading"), flush=True)
    is_onebit = args.optimizer in ("OneBitAdam", "OneBitLamb", "ZeroOneAdam")
    zero_stage = args.zero_stage
    if is_onebit and zero_stage > 1:
        print(f"{args.optimizer} needs replicated momenta: zero stage "
              f"{zero_stage} -> 1")
        zero_stage = 1
    opt_params = {"lr": 3e-4, "weight_decay": 0.1}
    if is_onebit:
        # dense warmup length before compressed communication kicks in.
        # A CONSTANT (not derived from --steps): the freeze boundary is part
        # of the optimizer's identity across checkpoint resume — resuming
        # with a different --steps must not move it.
        key = "var_freeze_step" if args.optimizer == "ZeroOneAdam" else "freeze_step"
        opt_params[key] = 10
    model = Model(TransformerConfig(
        vocab_size=args.vocab, max_seq_len=args.seq, num_layers=args.layers,
        num_heads=args.heads, hidden_size=args.hidden,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
        attn_impl="flash" if on_tpu else "xla",
    ))

    world = jax.device_count()
    gas = 2 if args.batch % (2 * world) == 0 else 1
    ds_config = {
        # train_batch = micro x gas x data-parallel world (config validates)
        "train_batch_size": args.batch,
        "train_micro_batch_size_per_gpu": args.batch // (gas * world),
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": args.optimizer, "params": opt_params},
        "scheduler": {"type": "WarmupLR",
                      "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 3e-4,
                                 "warmup_num_steps": 10}},
        "zero_optimization": {"stage": zero_stage},
        "bf16": {"enabled": on_tpu},
        "gradient_clipping": 1.0,
        "activation_checkpointing": {"enabled": True},
        "steps_per_print": 10,
        "mesh": {"data": -1},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds_config)

    if args.resume:
        tag, _ = engine.load_checkpoint(args.ckpt_dir)
        if tag is None:
            print(f"no checkpoint found in {args.ckpt_dir}; training from scratch")
        else:
            print(f"resumed from {tag} at step {engine.global_steps}")

    rng = np.random.default_rng(0)
    for step in range(args.steps):
        tokens = rng.integers(0, args.vocab,
                              size=(args.batch, args.seq + 1)).astype(np.int32)
        metrics = engine.train_batch({"tokens": tokens})
        if (step + 1) % 10 == 0:
            m = jax.device_get(metrics)
            print(f"step {engine.global_steps}: loss={float(m['loss']):.4f} "
                  f"lr={float(m['lr']):.2e} platform={platform}")

    engine.save_checkpoint(args.ckpt_dir)
    print(f"saved checkpoint to {args.ckpt_dir} "
          f"(resume with --resume; export fp32 weights with "
          f"'python {args.ckpt_dir}/zero_to_fp32.py <tag-dir> weights.npz')")


if __name__ == "__main__":
    main()
