"""Operations and bytes of the serving programs of a model whose layer stack runs
several times over the SAME weights (``layer_passes``: Ouro), from shapes alone:
the counting functions of the readers ``loop_decode_hbm_floor_pct`` and
``loop_prefill_mfu_pct`` (``flops.py``'s conventions; ``moe_cost.py`` knows no
passes and would read a quarter of this work).

What is counted is what the MODEL requires. A token multiplies through every
layer's matrices once a PASS and through the head once
(``references/ouro.py::param_counts``: ``matmul_on_token_path``). A decode step
reads the layers' matrices once a pass too: the 12 layers' 1.23 GB cannot stay on
the chip between passes (its fast memory holds a few MB), so the floor counts
them ``layer_passes`` times and the head once. A (pass, layer) keeps K/V of its
own, so a live token is read in ``layer_passes`` x layers cache layers. The norms,
the exit gate's 2,049 parameters and the embedding rows looked up count nothing.
"""

from __future__ import annotations

from . import flops


def passes(program) -> int:
    return int(program.get("layer_passes", 1))


def head_dim(program) -> int:
    return program["hidden_size"] // program["num_heads"]


def kv_bytes_per_token(program, itemsize: int = 2) -> int:
    """What the cache holds a position over the WHOLE model: keys and values of
    every head in every (pass, layer): passes x layers x 2 x heads x width x
    itemsize (4 x 12 x 2 x 16 x 128 x 2 = 393,216 B; one pass's: 98,304)."""
    return (passes(program) * program["num_layers"] * 2 * program["num_heads"]
            * head_dim(program) * itemsize)


def decode_min_bytes(program, live_tokens: float, itemsize: int = 2) -> float:
    """The least one decode step must read: the layers' matmul parameters once a
    PASS, the head once, and the live tokens' K/V in all passes x layers, in the
    compute dtype."""
    counts = flops.param_counts(program)
    return counts["matmul_on_token_path"] * itemsize + live_tokens * kv_bytes_per_token(
        program, itemsize)


def prefill_flops(program, rows: int) -> float:
    """One prefill of ``rows`` (the bucket: padding is work done): 2 x the layers'
    parameters on a token's path (every pass's) x rows, the head for ONE row (only
    the last live position is projected to the vocabulary), and causal attention at
    its half in passes x layers (``flops.py``: 2 x rows x d a layer a row, forward
    only)."""
    d = program["hidden_size"]
    head = d * program["vocab_size"]
    body = flops.param_counts(program)["matmul_on_token_path"] - head
    attention = passes(program) * program["num_layers"] * 2.0 * rows * rows * d
    return 2.0 * body * rows + 2.0 * head + attention
