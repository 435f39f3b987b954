"""ZeRO-Infinity parameter-tier hardware validation (round 4).

Trains decoder models whose parameter working set approaches/exceeds the
single chip's HBM with offload_param=cpu + offload_optimizer=cpu: bf16
params, fp32 masters and Adam moments all live in the TPU host's pinned
memory; each scanned layer streams its slice into HBM just-in-time
(runtime/zero/param_offload.py). Records step time, tokens/s, and the
device memory high-water mark.

Usage: python experiments/offload_param_r4.py [preset] [steps] [unroll]
(unroll=2 batches two layers per scan body so the next layer's
host->HBM stream overlaps the current layer's compute -- scan_unroll)
Presets: 1b3 | 2b7 | 6b7
"""

import json
import sys
import time

sys.path.insert(0, "/root/repo")

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models.transformer import Model, TransformerConfig

PRESETS = {
    # name: (layers, d, heads, seq, batch)
    "125m": (12, 768, 12, 1024, 8),
    "1b3": (24, 2048, 16, 1024, 4),
    "2b7": (32, 2560, 32, 1024, 4),
    "6b7": (32, 4096, 32, 1024, 2),
}


def main(preset: str = "1b3", steps: int = 4, unroll: int = 1):
    L, d, H, S, B = PRESETS[preset]
    steps, unroll = int(steps), int(unroll)
    tcfg = TransformerConfig(
        vocab_size=50304, max_seq_len=S, num_layers=L, num_heads=H,
        hidden_size=d, dtype=jnp.bfloat16, attn_impl="flash",
        remat=True, remat_policy="save_flash", loss_chunk_size=512,
        # unroll=2: two layers per loop body lets XLA overlap layer i+1's
        # host->HBM param stream with layer i's compute (scan_unroll doc)
        scan_unroll=unroll,
    )
    model = Model(tcfg)
    n_params = (
        tcfg.vocab_size * d + L * (4 * d * d + 2 * d * tcfg.ffn_size)
        + L * 4 * d + 2 * d + S * d
    )
    cfg = {
        "train_batch_size": B,
        "train_micro_batch_size_per_gpu": B,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "zero_optimization": {
            "stage": 1,
            "offload_optimizer": {"device": "cpu"},
            "offload_param": {"device": "cpu"},
        },
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 10**9,
        "mesh": {"data": 1},
    }
    print(f"preset={preset}: ~{n_params/1e9:.2f}B params "
          f"(bf16 {2*n_params/1e9:.1f} GB, fp32 states {12*n_params/1e9:.1f} GB host)")
    t0 = time.time()
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    print(f"engine+init: {time.time()-t0:.1f}s")
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, 50304, size=(B, S + 1)).astype(np.int32)}

    t0 = time.time()
    m = engine.train_batch(batch)
    loss0 = float(jax.device_get(m["loss"]))
    print(f"step 1 (compile+run): {time.time()-t0:.1f}s loss={loss0:.3f}")
    times = []
    for i in range(steps):
        t0 = time.time()
        m = engine.train_batch(batch)
        loss = float(jax.device_get(m["loss"]))  # sync
        times.append(time.time() - t0)
        print(f"step {i+2}: {times[-1]:.2f}s loss={loss:.3f}")
    dev = jax.local_devices()[0]
    stats = dev.memory_stats() or {}
    hbm_peak = stats.get("peak_bytes_in_use", 0)
    if not hbm_peak:
        # a backend that exposes no runtime stats: use the compiled step's
        # own memory analysis (device temp + args high-water)
        try:
            ma = engine._train_step.lower(engine.state, batch).compile().memory_analysis()
            hbm_peak = (getattr(ma, "temp_size_in_bytes", 0)
                        + getattr(ma, "argument_size_in_bytes", 0)
                        + getattr(ma, "output_size_in_bytes", 0))
        except Exception as e:  # noqa: BLE001
            print("memory_analysis unavailable:", e)
    step_s = float(np.median(times))
    rec = {
        "preset": preset,
        "scan_unroll": unroll,
        "n_params_b": round(n_params / 1e9, 3),
        "step_s": round(step_s, 3),
        "tokens_per_s": round(B * S / step_s, 1),
        "hbm_peak_gb": round(hbm_peak / 2**30, 2),
        "loss_first": round(loss0, 3),
        "loss_last": round(loss, 3),
        "host_state_gb": round(14 * n_params / 2**30, 1),
    }
    print(json.dumps(rec))
    return rec


if __name__ == "__main__":
    main(*(sys.argv[1:] or ["1b3"]))
