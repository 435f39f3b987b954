#!/usr/bin/env python3
"""Writes ``chipbench/configs/sdar-30b-a3b-L7.json``: the published keys of
JetLM/SDAR-30B-A3B-Chat copied whole from the catalog beside the ``model-configs`` guide
(no network here), the cut to 7 of 48 layers, what is assumed, the program the system
runs, the two tiny twins, and the arithmetic of the file's ``notes``, recomputed here.

    python3 experiments/sdar_config.py [--catalog PATH]    # rewrites the file
"""

import argparse
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME, LAYERS, CONTEXT = "sdar-30b-a3b-L7", 7, 3072
BLOCK, STEPS, MASK_ID = 4, 4, 151669


def published(path: str) -> dict:
    with open(path) as f:
        for line in f:
            entry = json.loads(line)
            if entry["name"] == "SDAR-30B-A3B-Chat":
                return entry
    raise SystemExit(f"{path} has no SDAR-30B-A3B-Chat")


def program(c: dict, layers: int) -> dict:
    return {
        "vocab_size": c["vocab_size"], "max_seq_len": CONTEXT, "num_layers": layers,
        "num_heads": c["num_attention_heads"], "num_kv_heads": c["num_key_value_heads"],
        "qk_head_dim": c["head_dim"], "hidden_size": c["hidden_size"],
        "intermediate_size": c["moe_intermediate_size"], "pos_emb": "rotary",
        "rotary_base": float(c["rope_theta"]), "tie_embeddings": c["tie_word_embeddings"],
        "use_bias": c["attention_bias"], "layernorm_epsilon": c["rms_norm_eps"],
        "norm_kind": "rms", "activation": "swiglu", "decode_attn": "xla", "qk_norm": "head",
        "moe_every": c["decoder_sparse_step"], "moe_routing": "dropless",
        "moe_norm_topk_prob": c["norm_topk_prob"], "moe_aux_coeff": 0.0,
        "num_experts": c["num_experts"], "moe_top_k": c["num_experts_per_tok"],
        "attn_block_length": BLOCK, "mask_token_id": MASK_ID,
    }


def twin(**over) -> dict:
    tiny = {"vocab_size": 768, "max_seq_len": 256, "num_heads": 4, "num_kv_heads": 2,
            "qk_head_dim": 24, "hidden_size": 64, "intermediate_size": 32, "pos_emb": "rotary",
            "rotary_base": 10000.0, "tie_embeddings": False, "use_bias": False,
            "layernorm_epsilon": 1e-06, "norm_kind": "rms", "activation": "swiglu",
            "decode_attn": "xla", "qk_norm": "head", "moe_every": 1, "moe_routing": "dropless",
            "moe_norm_topk_prob": True, "moe_aux_coeff": 0.0, "num_experts": 8, "moe_top_k": 2,
            "num_layers": 3}
    return {**tiny, **over}


def arithmetic(p: dict, layers_published: int) -> dict:
    d, H, Hkv, D = p["hidden_size"], p["num_heads"], p["num_kv_heads"], p["qk_head_dim"]
    f, E, k, V, L = (p["intermediate_size"], p["num_experts"], p["moe_top_k"], p["vocab_size"],
                     p["num_layers"])
    attention = d * H * D + 2 * d * Hkv * D + H * D * d
    router, expert, norms = d * E, 3 * d * f, 2 * d + 2 * D
    layer = attention + router + E * expert + norms
    ends = 2 * V * d
    held = L * layer + ends + d
    kv_position = L * 2 * Hkv * D * 2  # bytes a position over the held layers, bf16
    return {"attention": attention, "router": router, "expert": expert, "experts": E * expert,
            "norms": norms, "layer": layer, "embedding_and_head": ends, "held": held,
            "held_gb": held * 2 / 1e9,
            "whole": layers_published * layer + ends + d,
            "active": layers_published * (attention + router + k * expert + norms) + ends + d,
            "kv_bytes_a_position": kv_position,
            "cache_gb_64x3072": 64 * CONTEXT * kv_position / 1e9}


def build(entry: dict) -> dict:
    c = entry["config"]
    p = program(c, LAYERS)
    a = arithmetic(p, c["num_hidden_layers"])
    n = lambda x: f"{x:,}"  # noqa: E731
    notes = (
        "published keys as the catalog beside the model-configs guide has them (no network "
        f"here). Cut: num_hidden_layers {c['num_hidden_layers']} -> {LAYERS} and nothing else: "
        f"hidden {n(p['hidden_size'])}, {p['num_heads']} query and {p['num_kv_heads']} key/value "
        f"heads of {p['qk_head_dim']}, {p['num_experts']} experts of width "
        f"{p['intermediate_size']}, {p['moe_top_k']} a token (renormalised), "
        f"{n(p['vocab_size'])} vocabulary rows. The arithmetic (bf16, recomputed by "
        f"experiments/sdar_config.py, which wrote this file): attention {n(a['attention'])} a "
        f"layer; router {n(a['router'])}; one expert 3 x {n(p['hidden_size'])} x "
        f"{p['intermediate_size']} = {n(a['expert'])}, {p['num_experts']} of them "
        f"{n(a['experts'])}; norms {n(a['norms'])}: a layer is {n(a['layer'])}. Embedding + "
        f"untied head {n(a['embedding_and_head'])}. {LAYERS} layers + embedding + head + final "
        f"norm = {n(a['held'])} parameters = {a['held_gb']:.2f} GB held (the whole model by the "
        f"same count: {a['whole'] / 1e9:.2f} B, {a['active'] / 1e9:.2f} B active a token). The "
        f"slot cache holds {n(a['kv_bytes_a_position'])} B a position over the {LAYERS} layers "
        f"({p['num_kv_heads']} K/V heads x {p['qk_head_dim']} x 2 values x 2 B a layer): 64 "
        f"slots of {n(CONTEXT)} are {a['cache_gb_64x3072']:.2f} GB. rehearse_program is the "
        "tiny twin at block length 1 (the causal backbone: chipbench/parity.py's three "
        "surfaces take it with no edit there); rehearse_blocks_program is the twin WITH the "
        "block mask (B = 4, the mask token the last id), what the cell's driver rehearses and "
        "the tests use.")
    return {
        "name": NAME, "source": entry["source_url"], **c,
        "num_hidden_layers": LAYERS,
        "reduced": ["num_hidden_layers"],
        "published": {"num_hidden_layers": c["num_hidden_layers"]},
        "assumed": {
            "serving_context": CONTEXT,
            "serving_context_why": (
                f"{n(CONTEXT)} of the {n(c['max_position_embeddings'])} positions the config "
                "declares: chat-sized prompts (up to 2,048) and answers of a few hundred tokens; "
                "the plain rotary's table does not depend on the context served"),
            "qk_norm": (
                "head (a per-head RMSNorm on q and k before the rotary, one [128] scale each "
                "for all heads: the config's key set, attention_bias false, an explicit "
                "head_dim, norm_topk_prob, moe_intermediate_size, decoder_sparse_step, "
                "mlp_only_layers, is Qwen3-MoE's, whose attention has it; model_type sdar_moe "
                "is in no installed transformers (4.57.6 has qwen3_moe and no sdar))"),
            "logit_shift": (
                "none: the logits at position i score the token OF position i (a masked "
                "position predicts itself, as a masked-diffusion head does); the model's own "
                "modeling_sdar_moe.py is not on this machine"),
            "block_length": (
                f"{BLOCK} (the catalog lists it as not given by the config; the family's "
                "published sampler's default as far as it is known here)"),
            "denoising_steps": (
                f"{STEPS}: one position of a block of {BLOCK} a pass (the deployment's choice; "
                "serving.block_generation.denoising_steps)"),
            "mask_token_id": (
                f"{MASK_ID} (the family's <|MASK|> as far as it is known here; inside the "
                "vocabulary of 151,936; which positions are masked is the engine's state, "
                "never token == this id)"),
            "strategy": (
                "low_confidence_static (the B / T masked positions of largest confidence a "
                "pass); low_confidence_dynamic (threshold) is the family's other strategy and "
                "runs through the same program"),
            "noise_schedule": (
                "not given by the config, and not needed to serve; training under the "
                "diffusion objective needs it and is refused by name (causal_lm_loss)"),
            "intermediate_size": (
                "6,144 is the published dense width; decoder_sparse_step is 1 and "
                "mlp_only_layers is empty, so no layer uses it (program.intermediate_size is "
                "the EXPERT width, moe_intermediate_size 768)"),
            "weights": (
                "seeded noise. Agreement with the published weights and with the model's own "
                "modeling_sdar_moe.py / generate.py waits until those files are in the "
                "repository"),
        },
        "deployment": (
            f"one chip holding layers 0-{LAYERS - 1} of {c['num_hidden_layers']} WHOLE, as the "
            f"first of the pipeline stages a {c['num_hidden_layers']}-layer deployment would "
            "cut the stack into (the other layers lie on further chips); every one of the "
            f"{p['num_experts']} experts of each held layer, every head, the whole "
            f"{n(p['vocab_size'])}-row vocabulary (embedding and untied head), bf16, serving. "
            "Nothing stands in for the later stages or their traffic. Fewer layers make the "
            "host's share of a step larger than in a deployment, so serve_host_gap_pct and "
            "device_idle_pct.doc read high here"),
        "notes": notes,
        "arithmetic": a,
        "reference": "sdar_moe",
        "program": p,
        "rehearse_program": twin(),
        "rehearse_blocks_program": twin(attn_block_length=4, mask_token_id=767),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--catalog", default=CATALOG)
    args = ap.parse_args()
    out = os.path.join(ROOT, "chipbench", "configs", f"{NAME}.json")
    with open(out, "w") as f:
        json.dump(build(published(args.catalog)), f, indent=1)
        f.write("\n")
    print(out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
