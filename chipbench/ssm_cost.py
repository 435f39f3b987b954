"""Operations and bytes of the serving programs of a model with a state-space
mixer beside grouped-query attention in every layer (Falcon-H1), computed from
shapes: the counting functions of the readers ``ssm_decode_hbm_floor_pct`` and
``ssm_prefill_mfu_pct`` (``flops.py``'s conventions; ``moe_cost.py``'s twin for
what that file counts as plain multi-head attention: its cache at ``2 x
num_heads x (hidden_size // num_heads)`` values a token a layer, no per-sequence
state, and its attention at ``2 x rows^2 x hidden_size`` a layer).

The parameter counts come from the configuration's reference
(``references/<name>.py::param_counts``), the head and state sizes from the
program's own keys.
"""

from __future__ import annotations

from . import flops

STATE_ITEMSIZE = 4  # the recurrent state is held in float32 (the configuration's ``assumed``)


def kv_bytes_per_token(program, itemsize: int = 2) -> int:
    """What the slot cache holds a token a layer: the keys and the values of the
    K/V heads (4 x 128 x 2 values for Falcon-H1-34B: 2,048 B in bf16)."""
    return 2 * program["num_kv_heads"] * program["qk_head_dim"] * itemsize


def state_bytes_per_slot(program, itemsize: int = 2) -> int:
    """What the slot cache holds a SEQUENCE, all layers: the float32 state
    [heads, head width, state size] and the convolution's tail, the last
    ``ssm_conv_kernel - 1`` rows of x | B | C in the compute dtype
    (4 x (4,194,304 + 30,720) = 16,900,096 B for four Falcon-H1-34B layers)."""
    H, P, N = program["ssm_heads"], program["ssm_head_dim"], program["ssm_state_size"]
    conv_dim = H * P + 2 * program["ssm_groups"] * N
    tail = (program["ssm_conv_kernel"] - 1) * conv_dim
    return program["num_layers"] * (H * P * N * STATE_ITEMSIZE + tail * itemsize)


def decode_min_bytes(program, cached_tokens: float, state_bytes: float,
                     itemsize: int = 2) -> float:
    """The least one decode step must move: every matmul parameter once (the
    head among them) in the compute dtype, the recurrent state of the rows the
    step advanced READ AND WRITTEN (``state_bytes``: the decode span's own
    count, 2 x rows x ``state_bytes_per_slot``) and the live keys and values
    read once. Norms, the convolution's taps, ``dt_bias`` / ``A_log`` / ``D`` and
    the embedding rows looked up count nothing."""
    weights = flops.param_counts(program)["matmul_on_token_path"] * itemsize
    kv = program["num_layers"] * cached_tokens * kv_bytes_per_token(program, itemsize)
    return weights + state_bytes + kv


def scan_flops_per_row(program) -> float:
    """The selective scan at its RECURRENT cost, a row a layer: per head, P x N
    multiply-adds to decay the state, P x N to add dt x (x) B and P x N to read it
    against C: 6 x H x P x N operations. The chunked form the program runs does
    other arithmetic (pairs within a chunk, one state a chunk); what the model
    requires is this."""
    return 6.0 * program["ssm_heads"] * program["ssm_head_dim"] * program["ssm_state_size"]


def prefill_flops(program, rows: int) -> float:
    """One prefill of ``rows`` (the bucket: padding is work done): 2 x the
    parameters on a token's path x rows (the mixer's two projections among
    them), the head for ONE row, causal attention at its half over the QUERY
    heads (QK^T and PV: 2 x rows^2 / 2 x Hq x 2 Dh a layer), the scan at its
    recurrent cost and the depthwise convolution (2 x taps x channels a row)."""
    L = program["num_layers"]
    head = program["hidden_size"] * program["vocab_size"]
    body = flops.param_counts(program)["matmul_on_token_path"] - head
    attention = L * float(rows) * rows * program["num_heads"] * 2 * program["qk_head_dim"]
    conv_dim = (program["ssm_heads"] * program["ssm_head_dim"]
                + 2 * program["ssm_groups"] * program["ssm_state_size"])
    mixer = L * rows * (scan_flops_per_row(program)
                        + 2.0 * program["ssm_conv_kernel"] * conv_dim)
    return 2.0 * body * rows + 2.0 * head + attention + mixer
