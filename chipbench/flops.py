"""Operations and bytes computed from shapes — the benchmark's conventions.

Training FLOPs per token (``train_flops_per_token``): 6 x the parameters that
sit in a matrix multiplication on a token's path (the four attention
projections and the two feed-forward matrices of every layer, and the output
head), plus attention itself at its causal half: forward 2*S*d per layer per
token (QK^T and PV over the S/2 keys a token sees on average), times 3 for
forward + backward. The embedding LOOKUP is a gather and counts nothing; a
tied head still counts once, as the head. Recomputed operations (remat, the
flash backward's second pass over the scores) do not count: this is the work
the model requires, so tokens/s times it over the chips' peak is an
end-to-end utilisation (MFU), not a kernel's roofline share.

Kernel costs (``flash_cost``, ``decode_attention_cost``) are what the
algorithm needs for one call, for a roofline share of that kernel's own
device time: ``roofline`` takes the larger of ops/peak and bytes/bandwidth.
"""

from __future__ import annotations


def param_counts(program: dict) -> dict:
    """Parameter counts of this decoder family from the program's sizes."""
    d, L, V = program["hidden_size"], program["num_layers"], program["vocab_size"]
    f = program.get("intermediate_size") or 4 * d
    bias = program.get("use_bias", True)
    matmul_layer = 4 * d * d + 2 * d * f
    other_layer = 4 * d + ((3 * d + d + f + d) if bias else 0)  # two LayerNorms, biases
    embedding = V * d
    head = 0 if program.get("tie_embeddings", True) else d * V
    other = 2 * d + (2 * d if program.get("embed_ln") else 0)  # final LN, embedding LN
    if program.get("pos_emb") == "learned":
        other += program["max_seq_len"] * d
    return {
        "matmul_per_layer": matmul_layer,
        "matmul_on_token_path": L * matmul_layer + d * V,  # the head counts tied or not
        "total": L * (matmul_layer + other_layer) + embedding + head + other,
    }


def train_flops_per_token(program: dict, seq_len: int) -> float:
    d, L = program["hidden_size"], program["num_layers"]
    attention = 3 * L * 2 * seq_len * d  # causal half, fwd + bwd
    return 6.0 * param_counts(program)["matmul_on_token_path"] + attention


def flash_cost(batch: int, seq: int, heads: int, head_dim: int, *, causal: bool = True,
               backward: bool = False, itemsize: int = 2) -> dict:
    """One flash-attention call on [batch, seq, heads, head_dim]. Forward: two
    matmuls over the (causal: half of the) score matrix; reads q, k, v, writes
    o. Backward: five matmuls (the scores again, dV, dP, dQ, dK — a kernel
    pair that recomputes more is charged the same); reads q, k, v, o, do,
    writes dq, dk, dv."""
    scores = batch * heads * seq * seq * (0.5 if causal else 1.0)
    tensor = batch * seq * heads * head_dim * itemsize
    if backward:
        return {"flops": 5 * 2 * scores * head_dim, "bytes": 8 * tensor}
    return {"flops": 2 * 2 * scores * head_dim, "bytes": 4 * tensor}


def decode_attention_cost(live_tokens: int, heads: int, head_dim: int, layers: int,
                          itemsize: int = 2) -> dict:
    """Decode attention of one step over all layers: every live cached token
    is read once as a key and once as a value (the bytes that bound it) and
    takes one multiply-add in QK^T and one in PV per head dimension."""
    per_layer = live_tokens * heads * head_dim
    return {"flops": layers * 4 * per_layer, "bytes": layers * 2 * per_layer * itemsize}


def roofline(cost: dict, seconds: float, peak: dict) -> dict:
    """Share of the roofline reached: the least time the chip could take over
    the time taken, and which of the two bounds it."""
    t_flops = cost["flops"] / peak["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    return {"pct": 100.0 * max(t_flops, t_bytes) / seconds,
            "bound": "compute" if t_flops >= t_bytes else "memory"}
