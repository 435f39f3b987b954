"""The plain reference of the 2022 GPT family (GPT-NeoX / Pythia, BLOOM):
its forward pass and language-model loss in straightforward float32
``jax.numpy`` — no kernels, no cache, no scan, one layer at a time (several
sequences go through a layer together only by ``jax.vmap`` of the one-sequence
function, so that each layer's weights are fetched once) — and its parameter
counts. The protocol is stated in ``references/__init__.py``.

It covers (``COVERS``) pre-LayerNorm decoder blocks with biases, full
multi-head attention and a two-matrix GELU feed-forward; rotary position
embedding on the first ``rotary_pct`` of each head (NeoX half-split, base
10000) or alibi; parallel (GPT-NeoX) or sequential (BLOOM) residual; optional
LayerNorm after the embedding; tied or untied output head; exact or tanh
GELU. Anything else in a ``program`` is refused by its key's name. It shares
no code with ``deepspeed_tpu.models.transformer``; it reads that module's
parameter LAYOUT (one stack ``params["layers"]`` of ``wq`` [L, d, H, Dh],
``wo`` [L, H, Dh, d], ...; everything else a top-level leaf), because it is
run on the system's own weights.

On a TPU a float32 matmul runs in lower precision unless asked otherwise, so
everything here runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

ANY = None
COVERS = {
    "vocab_size": ANY, "max_seq_len": ANY, "num_layers": ANY, "num_heads": ANY,
    "hidden_size": ANY, "intermediate_size": ANY, "layernorm_epsilon": ANY, "rotary_pct": ANY,
    "pos_emb": ("rotary", "alibi"),
    "parallel_residual": (False, True), "embed_ln": (False, True),
    "tie_embeddings": (False, True), "activation": ("gelu", "gelu_exact"),
    # switches of the program's model that this block does not implement,
    # accepted only at the value that leaves the block as it is written here
    "use_bias": (True,), "norm_style": ("pre",), "final_ln": (True,), "causal": (True,),
    "rotary_interleaved": (False,), "local_attn_window": (0,), "local_attn_layers": (None,),
    "moe_every": (0,),
}


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _rotary(x, rotary_dims):
    """x [S, H, Dh]: rotate the first ``rotary_dims`` of every head, pairing
    dimension i with i + rotary_dims/2 (the NeoX convention)."""
    half = rotary_dims // 2
    inv_freq = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary_dims], x[..., rotary_dims:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _alibi_slopes(heads: int):
    closest = 2 ** math.floor(math.log2(heads))
    slopes = [2.0 ** (-8.0 * (i + 1) / closest) for i in range(closest)]
    slopes += [2.0 ** (-4.0 * (i + 1) / closest) for i in range(heads - closest)]
    return jnp.asarray(slopes, jnp.float32)


@partial(jax.jit, static_argnames=("pos_emb", "rotary_dims", "parallel", "eps", "exact_gelu"))
def _layer(x, lp, *, pos_emb, rotary_dims, parallel, eps, exact_gelu):
    """One decoder block on x [S, d] (float32)."""
    S = x.shape[0]
    h = _layer_norm(x, lp["ln1_scale"], lp["ln1_bias"], eps)
    q = jnp.einsum("sd,dhk->shk", h, lp["wq"]) + lp["bq"]
    k = jnp.einsum("sd,dhk->shk", h, lp["wk"]) + lp["bk"]
    v = jnp.einsum("sd,dhk->shk", h, lp["wv"]) + lp["bv"]
    if pos_emb == "rotary":
        q, k = _rotary(q, rotary_dims), _rotary(k, rotary_dims)
    scores = jnp.einsum("qhk,shk->hqs", q, k) / math.sqrt(q.shape[-1])
    qpos, kpos = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    if pos_emb == "alibi":
        scores = scores + _alibi_slopes(q.shape[1])[:, None, None] * (kpos - qpos)[None]
    scores = jnp.where((kpos <= qpos)[None], scores, -jnp.inf)
    attn = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v)
    attn_out = jnp.einsum("qhk,hkd->qd", attn, lp["wo"]) + lp["bo"]

    def ffn(y):
        u = jax.nn.gelu(y @ lp["wi"] + lp["bi"], approximate=not exact_gelu)
        return u @ lp["wo_mlp"] + lp["bo_mlp"]

    if parallel:
        return x + attn_out + ffn(_layer_norm(x, lp["ln2_scale"], lp["ln2_bias"], eps))
    x = x + attn_out
    return x + ffn(_layer_norm(x, lp["ln2_scale"], lp["ln2_bias"], eps))


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _fetch_top(params: dict, fetch) -> dict:
    """The leaves outside the layer stack: embedding, LayerNorms, head."""
    return fetch({k: v for k, v in params.items() if k != "layers"})


def _hidden_states(program: dict, top: dict, layers: dict, tokens, fetch) -> jax.Array:
    """Final-LayerNormed hidden states [S, d] of one token sequence, or
    [N, S, d] of N sequences of one length. ``layers`` is the system's stack
    of [L, ...] leaves; layer i's slices are fetched as it is reached (a
    sharded leaf is gathered by the slice), so one layer is resident at once."""
    eps = float(program.get("layernorm_epsilon", 1e-5))
    head_dim = program["hidden_size"] // program["num_heads"]
    with jax.default_matmul_precision("highest"):
        x = _f32(top["wte"])[jnp.asarray(tokens)]
        if program.get("embed_ln"):
            x = _layer_norm(x, _f32(top["emb_ln_scale"]), _f32(top["emb_ln_bias"]), eps)
        layer = partial(_layer, pos_emb=program["pos_emb"],
                        rotary_dims=int(head_dim * program.get("rotary_pct", 1.0)),
                        parallel=bool(program.get("parallel_residual")), eps=eps,
                        exact_gelu=program.get("activation") == "gelu_exact")
        if x.ndim == 3:
            layer = jax.vmap(layer, in_axes=(0, None))
        for i in range(program["num_layers"]):
            lp = fetch({k: v[i] for k, v in layers.items()})
            x = layer(x, {k: _f32(v) for k, v in lp.items()})
        return _layer_norm(x, _f32(top["lnf_scale"]), _f32(top["lnf_bias"]), eps)


def _head(program: dict, top: dict):
    return _f32(top["wte"]).T if program.get("tie_embeddings", True) else _f32(top["lm_head"])


def logits_at(program: dict, params: dict, tokens, rows, *, fetch) -> np.ndarray:
    """Float32 logits [len(rows), vocab] at the given positions."""
    top = _fetch_top(params, fetch)
    x = _hidden_states(program, top, params["layers"], tokens, fetch)
    with jax.default_matmul_precision("highest"):
        return np.asarray(x[jnp.asarray(rows)] @ _head(program, top))


def lm_loss(program: dict, params: dict, tokens, *, fetch) -> float:
    """Mean next-token cross-entropy of ``tokens`` [S + 1] or [N, S + 1]."""
    top = _fetch_top(params, fetch)
    tokens = np.asarray(tokens)
    x = _hidden_states(program, top, params["layers"], tokens[..., :-1], fetch)
    head = _head(program, top)
    with jax.default_matmul_precision("highest"):
        losses = []
        for xs, labels in zip(x.reshape((-1,) + x.shape[-2:]),
                              tokens[..., 1:].reshape(-1, tokens.shape[-1] - 1)):
            logits = xs @ head  # one sequence's [S, vocab] at a time
            picked = jnp.take_along_axis(logits, jnp.asarray(labels)[:, None], axis=-1)[:, 0]
            losses.append(jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked))
        return float(jnp.mean(jnp.stack(losses)))


def param_counts(program: dict) -> dict:
    """Four attention projections and two feed-forward matrices a layer, all on
    every token's path; two LayerNorms and six biases a layer beside them."""
    d, L, V = program["hidden_size"], program["num_layers"], program["vocab_size"]
    f = program.get("intermediate_size") or 4 * d
    matmul_layer = 4 * d * d + 2 * d * f
    other_layer = 4 * d + (3 * d + d + f + d)  # two LayerNorms; biases of q, k, v, o, ffn
    embedding = V * d
    head = 0 if program.get("tie_embeddings", True) else d * V
    other = 2 * d + (2 * d if program.get("embed_ln") else 0)  # final LN, embedding LN
    return {
        "matmul_per_layer": matmul_layer,
        "matmul_on_token_path": L * matmul_layer + d * V,  # the head counts tied or not
        "total": L * (matmul_layer + other_layer) + embedding + head + other,
    }
