"""Operations and bytes of the BLOCK STEP of a model that generates by diffusion over
blocks (``attn_block_length`` B > 1: SDAR): the counting functions of the readers
``block_step_hbm_floor_pct``, ``block_attn_roofline_pct`` and
``block_expert_gemm_roofline_pct`` (``flops.py``'s conventions).

What is counted is what the MODEL requires of one device step over the open blocks of the
active slots, whatever implements it. Every one of a block's B rows sees every key up to
its block's last position, so a slot whose block starts at ``pos`` requires ``pos + B`` keys
of each of its B rows in every layer: the program's own ``block_step`` spans carry that sum
over the active slots (``live_keys``: rows x keys of ONE layer; ``live_keys / B`` is the
positions of K/V the step must read a layer). The parameter counts come from the
configuration's reference (``references/<name>.py::param_counts``), the sizes from the
program's own keys.
"""

from __future__ import annotations

from . import flops


def kv_bytes_per_token(program, itemsize: int = 2) -> int:
    """K and V of one position in ONE layer."""
    return 2 * program["num_kv_heads"] * program["qk_head_dim"] * itemsize


def step_min_bytes(program, live_keys: float, experts_touched: float, itemsize: int = 2) -> float:
    """The bytes ONE block step must read: every matmul weight outside the experts (the
    attention, the router and the head; the embedding is a gather of its rows), the banks
    of the experts its rows chose (``experts_touched`` a layer, three matrices each), and
    the K/V of the positions its blocks see (``live_keys / B`` a layer)."""
    counts = flops.param_counts(program)
    positions = live_keys / program["attn_block_length"]
    return (itemsize * (counts["matmul_outside_experts"]
                        + counts["routed_layers"] * experts_touched * counts["matmul_per_expert"])
            + program["num_layers"] * positions * kv_bytes_per_token(program, itemsize))


def attention_cost(program, rows: int, live_keys: float, itemsize: int = 2) -> dict:
    """The attention of one block step over ``rows`` block rows (idle slots' among them: q
    and o are made for every row): QK^T and PV over the (row, key) pairs the model requires
    (4 x head width operations a pair a query head); q and o written or read once a layer,
    and the K/V of the positions the blocks see."""
    heads, width, layers = program["num_heads"], program["qk_head_dim"], program["num_layers"]
    positions = live_keys / program["attn_block_length"]
    return {"flops": 4.0 * width * heads * live_keys * layers,
            "bytes": layers * (2 * rows * heads * width * itemsize
                               + positions * kv_bytes_per_token(program, itemsize))}


def expert_gemm_cost(program, live_rows: int, experts_touched: float, itemsize: int = 2) -> dict:
    """The routed experts of one block step over the ``live_rows`` rows of its active slots
    (an idle slot's rows are routed by the program and required by no one): the three
    matmuls of every routed layer over ``live_rows x moe_top_k`` (row, expert) pairs, 2
    operations a parameter a pair; the banks of the ``experts_touched`` experts a layer
    those rows chose read once, the pairs' activations in and out
    (``moe_cost.grouped_gemm_cost``'s count, at the banks the step REQUIRES and not all)."""
    counts = flops.param_counts(program)
    d, f = program["hidden_size"], program["intermediate_size"]
    pairs = live_rows * program["moe_top_k"]
    weights = experts_touched * counts["matmul_per_expert"]
    activations = pairs * (2 * d + 2 * f + f + d)  # x twice in; gate, up out; h in; y out
    return {"flops": counts["routed_layers"] * 2.0 * pairs * counts["matmul_per_expert"],
            "bytes": counts["routed_layers"] * (weights + activations) * itemsize}
