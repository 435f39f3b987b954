"""Operations and bytes of the serving programs of a model whose layers are of
two OPERATORS (``layer_operators``: attention, or a gated short convolution in
its place: LFM2) with a routed feed-forward: the counting functions of the
readers ``kv_bytes_per_token_model``, ``conv_decode_hbm_floor_pct`` and
``conv_prefill_mfu_pct`` (``flops.py``'s conventions; ``moe_cost.py``'s twin for
what that file counts as every layer caching ``2 x num_heads x (hidden_size //
num_heads)`` values for every token and attending to them).

What is counted is what the MODEL requires: K/V and causal attention in the
ATTENTION layers alone; in a conv layer two projections (in the parameter
counts), the filter at 2 x taps x channels a row, and 2 rows of state a sequence
that a step reads and writes (the decode span's own ``state_bytes``). The
parameter counts come from the configuration's reference
(``references/<name>.py::param_counts``), the sizes from the program's own keys.
"""

from __future__ import annotations

from . import flops


def head_dim(program) -> int:
    return program.get("qk_head_dim") or program["hidden_size"] // program["num_heads"]


def layers_by_operator(program) -> tuple:
    """(attention layers, conv layers), as the configuration's reference counts them."""
    counts = flops.param_counts(program)
    return counts["attn_layers"], counts["conv_layers"]


def kv_bytes_per_token_layer(program, itemsize: int = 2) -> int:
    """What the cache holds a position in ONE attention layer: the keys and the
    values of the K/V heads (8 x 64 x 2 values: 2,048 B in bf16)."""
    return 2 * program["num_kv_heads"] * head_dim(program) * itemsize


def kv_bytes_per_token_model(program, itemsize: int = 2) -> int:
    """What the cache holds a position over the WHOLE model: the attention layers'
    K/V alone (2 x 2,048 = 4,096 B for the nine layers C A C C C A C C C; K/V in
    every layer would be 18,432)."""
    return layers_by_operator(program)[0] * kv_bytes_per_token_layer(program, itemsize)


def state_bytes_per_slot(program, itemsize: int = 2) -> int:
    """What the cache holds a SEQUENCE of conv state: ``conv_kernel - 1`` rows of
    ``hidden_size`` values in every conv layer (7 x 2 x 2048 x 2 B = 57,344)."""
    return (layers_by_operator(program)[1] * (program["conv_kernel"] - 1)
            * program["hidden_size"] * itemsize)


def decode_min_bytes(program, cached_tokens: float, state_bytes: float, experts_touched: float,
                     itemsize: int = 2) -> float:
    """The least one decode step must move: every matmul parameter outside the
    experts once (the tied head among them), the experts the step TOUCHED (mean
    over the routed layers, from the decode span), ``cached_tokens`` live
    positions in every ATTENTION layer (the span's count is ONE layer's) and the
    conv state of the rows the step advanced, read and written (``state_bytes``:
    the span's own count, 2 x rows x ``state_bytes_per_slot``). Norms, taps and the
    embedding rows looked up count nothing."""
    counts = flops.param_counts(program)
    weights = (counts["matmul_outside_experts"]
               + counts["routed_layers"] * experts_touched * counts["matmul_per_expert"])
    kv = layers_by_operator(program)[0] * cached_tokens * kv_bytes_per_token_layer(program,
                                                                                   itemsize)
    return weights * itemsize + kv + state_bytes


def filter_flops_per_row(program) -> float:
    """The depthwise filter, a row a conv layer: a multiply and an add a tap a
    channel (2 x 3 x 2048)."""
    return 2.0 * program["conv_kernel"] * program["hidden_size"]


def prefill_flops(program, rows: int) -> float:
    """One prefill of ``rows`` (the bucket: padding is work done): 2 x the
    parameters on a token's path x rows (the conv layers' two projections and the
    ``moe_top_k`` experts a row is routed to among them), the head for ONE row,
    causal attention at its half over the query heads in the ATTENTION layers
    alone (QK^T and PV: 2 x rows^2 / 2 x Hq x 2 D a layer) and the filter in the
    conv layers."""
    n_attn, n_conv = layers_by_operator(program)
    head = program["hidden_size"] * program["vocab_size"]
    body = flops.param_counts(program)["matmul_on_token_path"] - head
    attention = n_attn * float(rows) * rows * program["num_heads"] * 2 * head_dim(program)
    return (2.0 * body * rows + 2.0 * head + attention
            + n_conv * rows * filter_flops_per_row(program))
