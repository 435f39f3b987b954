"""Operations and bytes of the serving programs, computed from shapes: the
counting functions of the readers ``decode_hbm_floor_pct``, ``prefill_mfu_pct``
and ``moe_gemm_roofline_pct`` (``flops.py``'s conventions, kept beside it).

The counts come from the configuration's reference
(``references/<name>.py::param_counts``). A dense architecture gives
``matmul_on_token_path`` alone: every such parameter is read once by a decode
step and multiplied by every prefill row. A routed one also gives
``matmul_per_expert``, ``matmul_outside_experts`` and ``routed_layers``: a
decode step reads the experts its rows TOUCHED, a prefill row multiplies
through the ``moe_top_k`` it is routed to.
"""

from __future__ import annotations

from . import flops


def decode_min_bytes(program, live_tokens: float, experts_touched=None,
                     itemsize: int = 2) -> float:
    """The least one decode step must read: every matmul parameter outside the
    experts once (the head among them), the experts the step touched
    (``experts_touched``: mean over the routed layers, from the decode span;
    a dense model has none), in the compute dtype, and the live keys and
    values. Biases, norms and the embedding rows looked up count nothing."""
    counts = flops.param_counts(program)
    if "matmul_per_expert" in counts:
        if experts_touched is None:
            raise ValueError("a routed model's floor needs the experts its decode steps touched")
        weights = (counts["matmul_outside_experts"]
                   + counts["routed_layers"] * experts_touched * counts["matmul_per_expert"])
    else:
        weights = counts["matmul_on_token_path"]
    heads = program["num_heads"]
    kv = flops.decode_attention_cost(live_tokens, heads, program["hidden_size"] // heads,
                                     program["num_layers"], itemsize)["bytes"]
    return weights * itemsize + kv


def prefill_flops(program, rows: int) -> float:
    """One prefill of ``rows`` (the bucket: padding is work done): 2 x the
    parameters on a token's path x rows, the head for ONE row (only the last
    live position is projected to the vocabulary), and causal attention at its
    half (``flops.py``: 2 x rows x d a layer a row, forward only)."""
    d, L = program["hidden_size"], program["num_layers"]
    head = d * program["vocab_size"]
    body = flops.param_counts(program)["matmul_on_token_path"] - head
    return 2.0 * body * rows + 2.0 * head + L * 2.0 * rows * rows * d


def grouped_gemm_cost(program, rows: int, itemsize: int = 2) -> dict:
    """The three grouped matmuls of every routed layer over the ``rows x
    moe_top_k`` token-expert pairs of one call: 2 operations a parameter a pair;
    every expert's weights read once, the pairs' activations in and out."""
    counts = flops.param_counts(program)
    d, f = program["hidden_size"], program["intermediate_size"]
    pairs = rows * program["moe_top_k"]
    layers = counts["routed_layers"]
    weights = program["num_experts"] * counts["matmul_per_expert"]
    activations = pairs * (2 * d + 2 * f + f + d)  # x twice in; gate, up out; h in; y out
    return {"flops": layers * 2.0 * pairs * counts["matmul_per_expert"],
            "bytes": layers * (weights + activations) * itemsize}
