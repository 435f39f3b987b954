"""Operations and bytes of the serving programs of a model whose layers are of
two OPERATORS (``layer_operators``: attention, or a gated delta rule in its
place: Qwen3-Next) with a routed feed-forward of which the program holds a share:
the counting functions of the readers ``delta_decode_hbm_floor_pct`` and
``delta_prefill_mfu_pct`` (``flops.py``'s conventions; ``ssm_cost.py``'s and
``conv_cost.py``'s twin).

What is counted is what the MODEL requires: K/V and causal attention in the
ATTENTION layers alone; in a delta layer three projections (in the parameter
counts), the filter at 2 x taps x channels a row, the rule at its RECURRENT cost
(not the chunked form's own arithmetic) and a float32 matrix a value head that a
step reads and writes (the decode span's own ``state_bytes``). The parameter
counts come from the configuration's reference
(``references/<name>.py::param_counts``), the sizes from the program's own keys.
"""

from __future__ import annotations

from . import flops

STATE_ITEMSIZE = 4  # the rule's matrix state is held in float32 (the configuration's ``assumed``)


def head_dim(program) -> int:
    return program.get("qk_head_dim") or program["hidden_size"] // program["num_heads"]


def layers_by_operator(program) -> tuple:
    """(attention layers, delta layers), as the configuration's reference counts them."""
    counts = flops.param_counts(program)
    return counts["attn_layers"], counts["delta_layers"]


def conv_dim(program) -> int:
    """The channels a delta layer's filter runs over: q | k | v."""
    return ((2 * program["delta_key_heads"] + program["delta_value_heads"])
            * program["delta_head_dim"])


def kv_bytes_per_token_layer(program, itemsize: int = 2) -> int:
    """What the cache holds a position in ONE attention layer: the keys and the
    values of the K/V heads (2 x 256 x 2 values: 2,048 B in bf16)."""
    return 2 * program["num_kv_heads"] * head_dim(program) * itemsize


def kv_bytes_per_token(program, itemsize: int = 2) -> int:
    """What the cache holds a position over the WHOLE model: the attention layers'
    K/V alone (2 x 2,048 = 4,096 B for D D D A D D D A; K/V in all eight: 16,384)."""
    return layers_by_operator(program)[0] * kv_bytes_per_token_layer(program, itemsize)


def state_bytes_per_slot(program, itemsize: int = 2) -> int:
    """What the cache holds a SEQUENCE of delta state, all layers: the float32
    matrix [value heads, head width, head width] and the filter's tail, the last
    ``conv_kernel - 1`` rows of q | k | v in the compute dtype (6 x (2,097,152 +
    49,152) = 12,877,824 B)."""
    width = program["delta_head_dim"]
    matrix = program["delta_value_heads"] * width * width * STATE_ITEMSIZE
    tail = (program["conv_kernel"] - 1) * conv_dim(program) * itemsize
    return layers_by_operator(program)[1] * (matrix + tail)


def decode_min_bytes(program, cached_tokens: float, state_bytes: float, experts_touched: float,
                     itemsize: int = 2) -> float:
    """The least one decode step must move: every matmul parameter outside the
    experts once (both operators', the routers, the shared experts, the head), the
    HELD experts the step TOUCHED (mean over the layers, from the decode span),
    ``cached_tokens`` live positions in every ATTENTION layer (the span's count is
    ONE layer's) and the delta state of the rows the step advanced, read AND written
    (``state_bytes``: the span's own count, 2 x rows x ``state_bytes_per_slot``).
    Norms, taps, ``A_log`` / ``dt_bias`` and the embedding rows looked up count
    nothing."""
    counts = flops.param_counts(program)
    weights = (counts["matmul_outside_experts"]
               + counts["routed_layers"] * experts_touched * counts["matmul_per_expert"])
    kv = layers_by_operator(program)[0] * cached_tokens * kv_bytes_per_token_layer(program,
                                                                                   itemsize)
    return weights * itemsize + kv + state_bytes


def rule_flops_per_row(program) -> float:
    """The gated delta rule at its RECURRENT cost, a row a delta layer: per value
    head, D x D multiply-adds to read the state against k, D x D to add k (x) d and
    D x D to read it against q: 6 x Hv x D x D operations (the decay rides on the
    first). The chunked form the program runs does other arithmetic (a [64, 64]
    inverse a chunk a head, one state a chunk); what the model requires is this."""
    width = program["delta_head_dim"]
    return 6.0 * program["delta_value_heads"] * width * width


def prefill_flops(program, rows: int) -> float:
    """One prefill of ``rows`` LIVE rows: 2 x the parameters on a token's path x
    rows (both operators' projections, the router, the shared expert and the
    ``moe_top_k`` x held / ``num_experts`` experts a row is dispatched to here), the
    head for ONE row, causal attention at its half over the query heads in the
    ATTENTION layers alone (QK^T and PV: 2 x rows^2 / 2 x Hq x 2 D a layer), and in
    the delta layers the rule at its recurrent cost and the filter (2 x taps x
    channels a row)."""
    n_attn, n_delta = layers_by_operator(program)
    head = program["hidden_size"] * program["vocab_size"]
    body = flops.param_counts(program)["matmul_on_token_path"] - head
    attention = n_attn * float(rows) * rows * program["num_heads"] * 2 * head_dim(program)
    delta = n_delta * rows * (rule_flops_per_row(program)
                              + 2.0 * program["conv_kernel"] * conv_dim(program))
    return 2.0 * body * rows + 2.0 * head + attention + delta
