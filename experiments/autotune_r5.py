"""Round-5 autotune artifact: isolated-subprocess sweep on the real chip.

VERDICT r4 #8 'Done' bar: an autotune artifact with >= 10 trials including
>= 1 handled failure, reproducing or beating the r3 hand-found config
(dots_and_flash @ micro 32 -> 99.2k tok/s, builder-attested on the old stack).

Runs the GPT-2 125M bench geometry through Autotuner.tune_isolated: every
trial is a fresh subprocess with a hard timeout (backend hangs and HBM OOMs
become recorded failures, not dead sweeps), logged resumably to
experiments/autotune_r5_log/experiments.jsonl. The surrogate strategy
bootstraps with the analytic HBM/cost model, then re-ranks remaining
candidates after each observation with the fitted ridge model.

Usage: python experiments/autotune_r5.py [max_trials] [trial_timeout_s]
"""

import json
import os
import sys

sys.path.insert(0, "/root/repo")

from deepspeed_tpu.autotuning import Autotuner, ExperimentScheduler

V, S, B = 50304, 1024, 64

MODEL_CFG = {
    "vocab_size": V, "max_seq_len": S, "num_layers": 12, "num_heads": 12,
    "hidden_size": 768, "pos_emb": "learned", "dtype": "bfloat16",
    "attn_impl": "flash", "flash_block_q": 1024, "flash_block_k": 1024,
    "remat": True,
}

BASE = {
    "train_batch_size": B,
    "optimizer": {"type": "AdamW", "params": {"lr": 6e-4, "weight_decay": 0.1}},
    "zero_optimization": {"stage": 1},
    "bf16": {"enabled": True},
    "gradient_clipping": 1.0,
    "steps_per_print": 10**9,
    "mesh": {"data": -1},
}

# 3 policies x 3 micros x 2 loss chunks = 18 candidates (max_trials caps the
# sweep); remat=none at micro 32/64 is expected to OOM 16 GB HBM — the
# handled-failure part of the artifact. Harder loss chunking (256) is the
# VERDICT r4 #2 lever: smaller live logits let dots_and_flash fit at larger
# micro-batch.
SPACE = {
    "remat_policy": ["dots_and_flash", "save_flash", "none"],
    "micro_batch": [16, 32, 64],
    "model.loss_chunk_size": [512, 256],
}


def main(max_trials: int = 12, trial_timeout: float = 900.0):
    exp_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "autotune_r5_log")
    # world_size/hbm_gb given explicitly: the parent must NOT touch
    # jax.devices() — it would take the single chip's lock and every
    # subprocess trial would die at backend init
    tuner = Autotuner(lambda ov: None, BASE, lambda: None, steps=10, warmup=2,
                      world_size=1, hbm_gb=16.0)
    sched = ExperimentScheduler(exp_dir, trial_timeout=trial_timeout)
    res = tuner.tune_isolated(
        MODEL_CFG, {"size": B, "seq": S, "vocab": V}, sched,
        space=SPACE, strategy="surrogate", max_trials=max_trials,
        results_path=os.path.join(exp_dir, "autotune_r5.json"),
    )
    ok = [t for t in res.trials if t.status == "ok"]
    failed = [t for t in res.trials if t.status != "ok"]
    print(json.dumps({
        "trials": len(res.trials),
        "ok": len(ok),
        "handled_failures": len(failed),
        "best": None if res.best is None else {
            "overrides": res.best.overrides,
            "tokens_per_sec": res.best.tokens_per_sec,
            "step_ms": res.best.step_ms,
        },
        "r3_reference_tok_s": 99200.0,
        "artifact": os.path.join(exp_dir, "autotune_r5.json"),
    }))
    return res


if __name__ == "__main__":
    args = sys.argv[1:]
    main(int(args[0]) if args else 12,
         float(args[1]) if len(args) > 1 else 900.0)
