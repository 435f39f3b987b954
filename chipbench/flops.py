"""Operations and bytes computed from shapes — the benchmark's conventions.

Training FLOPs per token (``train_flops_per_token``): 6 x the parameters that
sit in a matrix multiplication on ONE token's path, plus attention itself at
its causal half: forward 2*S*d per layer per token (QK^T and PV over the S/2
keys a token sees on average), times 3 for forward + backward. Which
parameters those are is the architecture's to say: the count comes from the
configuration's reference (``references/<name>.py::param_counts``,
``matmul_on_token_path``: the projections and feed-forward matrices of every
layer the token visits and the output head; a sparse model counts the experts
a token is routed to). The embedding LOOKUP is a gather and counts nothing; a
tied head still counts once, as the head. Recomputed operations (remat, the
flash backward's second pass over the scores) do not count: this is the work
the model requires, so tokens/s times it over the chips' peak is an
end-to-end utilisation (MFU), not a kernel's roofline share.

Kernel costs (``flash_cost``, ``decode_attention_cost``) are what the
algorithm needs for one call, for a roofline share of that kernel's own
device time: ``roofline`` takes the larger of ops/peak and bytes/bandwidth.
"""

from __future__ import annotations

from .references import Program, load_reference


def param_counts(program: Program) -> dict:
    """Parameter counts from the program's sizes, as its reference counts them."""
    return load_reference(program).param_counts(program)


def train_flops_per_token(program: Program, seq_len: int) -> float:
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
