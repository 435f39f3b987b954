"""Operations and bytes of the serving programs of a model with latent
attention (MLA), computed from shapes: the counting functions of the readers
``latent_decode_hbm_floor_pct``, ``latent_prefill_mfu_pct`` and
``mla_flash_roofline_pct`` (``flops.py``'s conventions; ``moe_cost.py``'s twin
for what that file counts as plain multi-head attention: its cache at ``2 x
num_heads x (hidden_size // num_heads)`` values a token a layer and its
attention at ``2 x rows^2 x hidden_size`` a layer).

The parameter counts come from the configuration's reference
(``references/<name>.py::param_counts``), the head sizes and the latent's from
the program's own keys.
"""

from __future__ import annotations

from . import flops


def cache_values_per_token(program) -> int:
    """What the slot cache holds a token a layer: the normed latent and the
    rotary key every head shares."""
    return program["kv_lora_rank"] + program["qk_rope_head_dim"]


def decode_min_bytes(program, cached_tokens: float, experts_touched: float,
                     itemsize: int = 2) -> float:
    """The least one decode step must read: every matmul parameter outside the
    experts once (the shared expert, the leading dense layer and the head among
    them), the experts the step touched (mean over the routed layers, from the
    decode span), in the compute dtype, and the live latent cache ONCE (the
    absorbed form's keys and values are the same rows). Norms, the selection
    bias and the embedding rows looked up count nothing."""
    counts = flops.param_counts(program)
    weights = (counts["matmul_outside_experts"]
               + counts["routed_layers"] * experts_touched * counts["matmul_per_expert"])
    cache = program["num_layers"] * cached_tokens * cache_values_per_token(program)
    return (weights + cache) * itemsize


def prefill_flops(program, rows: int) -> float:
    """One prefill of ``rows`` (the bucket: padding is work done): 2 x the
    parameters on a token's path x rows (``W_kv_b``'s expansion of the block's
    latent to every head's keys and values is among them), the head for ONE
    row, and expanded causal attention at its half: QK^T over q/k heads of
    ``qk_head_dim`` and PV over value heads of ``v_head_dim``, 2 x rows^2 / 2 x
    H x (Dqk + Dv) a layer."""
    head = program["hidden_size"] * program["vocab_size"]
    body = flops.param_counts(program)["matmul_on_token_path"] - head
    widths = program["num_heads"] * (program["qk_head_dim"] + program["v_head_dim"])
    attention = program["num_layers"] * float(rows) * rows * widths
    return 2.0 * body * rows + 2.0 * head + attention


def flash_cost(program, rows: int, itemsize: int = 2) -> dict:
    """The flash forward kernel's calls of one prefill of ``rows``: a call a
    layer on [1, rows, H, Dqk] q and k and [1, rows, H, Dv] v: two matmuls over
    the causal half of the score matrix, one Dqk deep and one Dv; reads q, k, v,
    writes o."""
    H, L = program["num_heads"], program["num_layers"]
    dqk, dv = program["qk_head_dim"], program["v_head_dim"]
    scores = H * float(rows) * rows * 0.5
    return {"flops": L * 2 * scores * (dqk + dv),
            "bytes": L * rows * H * (2 * dqk + 2 * dv) * itemsize}
