"""Operations and bytes of the CHUNK programs of a model whose layers are of two
kinds (a sliding window of ``local_attn_window`` positions, or the whole context)
and whose prompts enter the slot cache in chunks (``chunked_prefill``): the counting
functions of the readers ``chunk_prefill_mfu_pct`` and ``chunk_attn_roofline_pct``
(``flops.py``'s conventions; ``kinds_cost.py``'s twin for a block that enters at its
own position instead of at 0).

What is counted is what the MODEL requires, whatever implements it: the query at
position p sees p + 1 keys in a whole-context layer and min(p + 1, window) in a
window layer, and the program's own chunk spans carry those sums over their LIVE
rows (``whole_keys``, ``ring_tokens``: of ONE layer of their kind; a bucket's padded
rows require nothing of attention). The matrix products are counted over the chunk's
``width``, padding among it (work done, as ``kinds_cost.prefill_flops`` counts a
bucket), the experts over the (row, expert) pairs the span states
(``expert_rows_held``). The parameter counts come from the configuration's reference
(``references/<name>.py::param_counts``), the sizes from the program's own keys.
"""

from __future__ import annotations

from . import flops, kinds_cost


def attention_flops(program, whole_keys: float, ring_tokens: float) -> float:
    """QK^T and PV (4 x head width operations a query-key pair a query head) over the
    pairs one chunk requires: ``whole_keys`` in every whole-context layer,
    ``ring_tokens`` in every window layer."""
    whole, window = kinds_cost.layers_by_kind(program)
    pairs = whole * whole_keys + window * ring_tokens
    return 4.0 * program["qk_head_dim"] * program["num_heads"] * pairs


def chunk_flops(program, width: int, expert_rows_held: float, whole_keys: float,
                ring_tokens: float) -> float:
    """One chunk of ``width`` rows: 2 x the parameters outside the experts x the rows,
    the head for ONE row (a chunk's program projects its last live row alone), 2 x one
    expert's parameters x the pairs dispatched, and ``attention_flops``."""
    counts = flops.param_counts(program)
    head = program["hidden_size"] * program["vocab_size"]
    return (2.0 * (counts["matmul_outside_experts"] - head) * width + 2.0 * head
            + 2.0 * expert_rows_held * counts["matmul_per_expert"]
            + attention_flops(program, whole_keys, ring_tokens))


def attention_cost(program, start: int, width: int, whole_keys: float, ring_tokens: float,
                   itemsize: int = 2) -> dict:
    """The attention of one chunk of ``width`` rows entering at ``start``:
    ``attention_flops``; q and o of the query heads written or read once a layer, and
    the keys and values the chunk must read: the prefix and itself (``start + width``
    positions) in a whole-context layer, the window's positions before it and itself
    (min(start, window) + width) in a window layer."""
    whole, window = kinds_cost.layers_by_kind(program)
    rows = program["num_layers"] * width * 2 * program["num_heads"] * program["qk_head_dim"]
    positions = (whole * (start + width)
                 + window * (min(start, program["local_attn_window"]) + width))
    return {"flops": attention_flops(program, whole_keys, ring_tokens),
            "bytes": rows * itemsize + positions * kinds_cost.kv_bytes_per_token(program, itemsize)}
