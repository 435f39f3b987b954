"""Operations and bytes of the serving programs of a model whose layers are of
two KINDS (a sliding window of ``local_attn_window`` positions, or the whole
context) and whose program holds a SHARE of the routed experts
(``moe_experts_held``): the counting functions of the readers
``slot_cache_bytes_per_slot``, ``kinds_decode_hbm_floor_pct``,
``kinds_prefill_mfu_pct``, ``held_moe_gemm_roofline_pct`` and
``kinds_flash_roofline_pct`` (``flops.py``'s conventions; ``moe_cost.py``'s twin
for what that file counts as every layer caching ``2 x num_heads x (hidden_size
// num_heads)`` values for every token and every row dispatched to ``moe_top_k``
experts here).

What is counted is what the MODEL requires: a window layer's query sees
min(position + 1, window) keys, whatever grid a kernel runs; a routed layer
multiplies the pairs whose expert is held, which the program's own spans count
(``expert_rows_held``). The parameter counts come from the configuration's
reference (``references/<name>.py::param_counts``), the sizes from the program's
own keys.
"""

from __future__ import annotations

from . import flops

# the rows up to which the program takes a routed layer densely (every held expert on every
# row: no grouped matmul runs); ``deepspeed_tpu/moe/dropless.py::DENSE_ROWS``
SORTED_FORM_ROWS = 512


def layers_by_kind(program) -> tuple:
    """(whole-context layers, window layers)."""
    local = program.get("local_attn_layers") or [0] * program["num_layers"]
    return len(local) - sum(local), sum(local)


def kv_bytes_per_token(program, itemsize: int = 2) -> int:
    """What the cache holds a position a layer: the keys and the values of the K/V
    heads (8 x 128 x 2 values: 4,096 B in bf16)."""
    return 2 * program["num_kv_heads"] * program["qk_head_dim"] * itemsize


def slot_cache_bytes(program, smax: int, itemsize: int = 2) -> int:
    """What the slot cache holds a SLOT: ``smax`` positions in every
    whole-context layer, ``local_attn_window`` in every window layer (16,384 x
    4,096 + 4 x 128 x 4,096 = 69,206,016 B for the five layers S S S G S; one
    length for every layer would be 335,544,320)."""
    whole, window = layers_by_kind(program)
    return (whole * smax + window * program["local_attn_window"]) * kv_bytes_per_token(
        program, itemsize)


def window_pairs(rows: float, window: int) -> float:
    """Query-key pairs of ``rows`` causal queries from position 0 that each see
    min(position + 1, window) keys."""
    full = min(rows, window)
    return full * (full + 1) / 2.0 + max(rows - window, 0) * window


def attention_flops(program, rows: int) -> float:
    """Attention of one prefill of ``rows`` at what the model requires: QK^T and PV
    (4 x head width operations a pair a query head) over rows^2 / 2 pairs in a
    whole-context layer and ``window_pairs`` in a window layer."""
    whole, window = layers_by_kind(program)
    pairs = whole * rows * rows / 2.0 + window * window_pairs(rows, program["local_attn_window"])
    return 4.0 * program["qk_head_dim"] * program["num_heads"] * pairs


def decode_min_bytes(program, cached_tokens: float, ring_tokens: float, experts_touched: float,
                     itemsize: int = 2) -> float:
    """The least one decode step must read: every matmul parameter outside the
    experts once (the head among them), the held experts the step TOUCHED (mean
    over the routed layers, from the decode span), ``cached_tokens`` live
    positions in every whole-context layer and ``ring_tokens`` in every window
    layer (both the decode span's own counts, of ONE layer of their kind)."""
    counts = flops.param_counts(program)
    whole, window = layers_by_kind(program)
    weights = (counts["matmul_outside_experts"]
               + counts["routed_layers"] * experts_touched * counts["matmul_per_expert"])
    kv = (whole * cached_tokens + window * ring_tokens) * kv_bytes_per_token(program, itemsize)
    return weights * itemsize + kv


def prefill_flops(program, rows: int, expert_rows_held: float) -> float:
    """One prefill of ``rows`` (the bucket: padding is work done): 2 x the
    parameters outside the experts x rows, the head for ONE row, 2 x one expert's
    parameters x the pairs the call dispatched to held experts
    (``expert_rows_held``: the span's count, over all routed layers) and attention
    at what the model requires."""
    counts = flops.param_counts(program)
    head = program["hidden_size"] * program["vocab_size"]
    return (2.0 * (counts["matmul_outside_experts"] - head) * rows + 2.0 * head
            + 2.0 * expert_rows_held * counts["matmul_per_expert"]
            + attention_flops(program, rows))


def grouped_gemm_cost(program, expert_rows_held: float, itemsize: int = 2) -> dict:
    """The three grouped matmuls of every routed layer over the pairs one call
    dispatched to held experts (``expert_rows_held``, over all routed layers): 2
    operations a parameter a pair; every HELD expert's weights read once a layer,
    the pairs' activations in and out."""
    counts = flops.param_counts(program)
    d, f = program["hidden_size"], program["intermediate_size"]
    weights = counts["routed_layers"] * counts["experts_held"] * counts["matmul_per_expert"]
    activations = expert_rows_held * (2 * d + 2 * f + f + d)  # x twice in; gate, up out; h in; y out
    return {"flops": 2.0 * expert_rows_held * counts["matmul_per_expert"],
            "bytes": (weights + activations) * itemsize}


def flash_cost(program, rows: int, itemsize: int = 2) -> dict:
    """The flash forward calls of one prefill of ``rows`` (one a layer, of either
    kind) at what the model requires: ``attention_flops``; q and o of the query
    heads and k and v of the K/V heads read or written once a layer."""
    heads = 2 * program["num_heads"] + 2 * program["num_kv_heads"]
    return {"flops": attention_flops(program, rows),
            "bytes": program["num_layers"] * rows * heads * program["qk_head_dim"] * itemsize}
