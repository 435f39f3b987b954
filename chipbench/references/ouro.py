"""The plain reference of Ouro (ByteDance/Ouro-2.6B: ``model_type: ouro``; "Scaling
Latent Reasoning via Looped Language Models", 2025): its forward pass, exit
distribution and language-model loss in straightforward float32 ``jax.numpy`` —
one sequence, ONE PASS and one layer at a time, as plain Python loops over the
passes and over the layers, the whole sequence attended densely in every pass: no
cache, no scan, no kernel — and its parameter counts. The protocol is stated in
``references/__init__.py``; it shares no code with ``deepspeed_tpu/``.

The model, as its public ``config.json`` gives the sizes and its paper (section 3)
the structure. A layer has SANDWICH norms, four RMSNorm scales, a norm before and
a second norm after each sublayer, the second on the residual BRANCH:

    a = rmsnorm(x; ln1)                                          input_layernorm
    q, k, v = a W_q, a W_k, a W_v; rotary(q, k) over the whole head at the token's position
    x = x + rmsnorm(softmax(q k^T / sqrt(Dh), causal) v W_o; ln1_post)
    m = rmsnorm(x; ln2)                                          post_attention_layernorm
    x = x + rmsnorm((silu(m W_gate) * (m W_up)) W_down; ln2_post)

The SAME ``num_layers`` layers run ``layer_passes`` (``total_ut_steps``) times:

    h_0 = Emb[t]
    for r in 1 .. R:   h_r = rmsnorm(layer_L( ... layer_1(h_{r-1}) ... ); lnf)     ONE final norm, after
                       lambda_r = sigmoid(h_r . w_g + b_g)                          EVERY pass; the exit gate
    logits = h_R W_head

``h_r``, normed, is what pass r + 1 starts from. In pass r a layer's attention at
position i sees the keys and values pass r of that layer made at positions <= i:
here simply the pass's own whole-sequence attention. The gate gives a distribution
over the pass at which a token would stop, p_r = lambda_r prod_{s<r} (1 - lambda_s)
for r < R and p_R the rest; at the published ``early_exit_threshold`` 1.0 no token
stops before pass R, so the logits are the last pass's and ``p`` decides nothing.

Assumptions, each because the catalog's row of the configuration says nothing
else and the model's own ``modeling_ouro.py`` (remote code) is not in this
repository; the file that would settle each is that one:

* no bias on any projection and no norm on q or k (the row has no
  ``attention_bias`` key; the paper describes a Llama-style block);
* RMSNorm multiplies by its scale ``w`` (drawn at 1), in float32;
* the exit gate reads the NORMED ``h_r``; its weight is drawn as any [d, 1]
  matrix and its bias at 0;
* the exit rule above (stop at the first pass whose cumulative ``p`` reaches the
  threshold) is ISSUE 56's reading of ``early_exit_threshold``;
* rotary is the NeoX half-split over the whole head at ``rotary_base``;
* the training loss is next-token cross-entropy on the last pass's logits: the
  model's own objective (the expected loss under the exit distribution with an
  entropy term) is not what the system computes and is not here.

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
    "hidden_size": ANY, "intermediate_size": ANY, "layernorm_epsilon": ANY,
    "rotary_base": ANY, "layer_passes": ANY,
    # what makes the block Ouro's, each at the one value this file implements
    "norm_style": ("sandwich",), "exit_gate": (True,), "norm_kind": ("rms",),
    "activation": ("swiglu",), "use_bias": (False,), "pos_emb": ("rotary",),
    "rotary_pct": (1.0,), "tie_embeddings": (False,),
}
MATRICES = ("wq", "wk", "wv", "wo", "wg", "wi", "wo_mlp")
NORMS = ("ln1_scale", "ln1_post_scale", "ln2_scale", "ln2_post_scale")


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x, base):
    """x [S, H, Dh]: rotate every head whole, pairing dimension i with
    i + Dh/2 (the NeoX convention), at positions 0 .. S - 1."""
    half = x.shape[-1] // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@partial(jax.jit, static_argnames=("eps", "base"))
def _layer(x, lp, *, eps, base):
    """One layer on one sequence x [S, d], the whole sequence attended densely."""
    S = x.shape[0]
    a = _rms(x, lp["ln1_scale"], eps)
    q = _rotary(jnp.einsum("sd,dhk->shk", a, lp["wq"]), base)
    k = _rotary(jnp.einsum("sd,dhk->shk", a, lp["wk"]), base)
    v = jnp.einsum("sd,dhk->shk", a, lp["wv"])
    scores = jnp.einsum("qhk,shk->hqs", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v)
    x = x + _rms(jnp.einsum("qhk,hkd->qd", attn, lp["wo"]), lp["ln1_post_scale"], eps)
    m = _rms(x, lp["ln2_scale"], eps)
    down = (jax.nn.silu(m @ lp["wg"]) * (m @ lp["wi"])) @ lp["wo_mlp"]
    return x + _rms(down, lp["ln2_post_scale"], eps)


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def _passes(program: dict, params: dict, sequences, fetch) -> tuple:
    """Every sequence (a list of [S] token arrays) through every pass -> (per
    sequence the normed output of EVERY pass [R, S, d], the top-level leaves).
    Four plain loops: passes, layers, sequences, and inside ``_layer`` the dense
    attention over all positions. A layer's leaves are fetched once a pass."""
    eps = float(program.get("layernorm_epsilon", 1e-5))
    base = float(program.get("rotary_base", 10000.0))
    L, R = int(program["num_layers"]), int(program.get("layer_passes", 1))
    layers = params["layers"]
    top = _f32(fetch({k: v for k, v in params.items() if k != "layers"}))
    xs = [top["wte"][jnp.asarray(t)] for t in sequences]
    every = [[] for _ in sequences]
    for _ in range(R):
        for i in range(L):
            lp = _f32(fetch({k: layers[k][i] for k in MATRICES + NORMS}))
            xs = [_layer(x, lp, eps=eps, base=base) for x in xs]
        xs = [_rms(x, top["lnf_scale"], eps) for x in xs]  # the next pass starts from the NORMED h
        for j, x in enumerate(xs):
            every[j].append(x)
    return [jnp.stack(h) for h in every], top


def logits_of(program: dict, params: dict, sequences, rows, *, fetch) -> list:
    """Float32 logits [len(rows[j]), vocab] of each of several sequences, in one
    walk over the passes and layers (each layer's leaves fetched once a pass)."""
    with jax.default_matmul_precision("highest"):
        hidden, top = _passes(program, params, [np.asarray(t) for t in sequences], fetch)
        return [np.asarray(h[-1][jnp.asarray(r)] @ top["lm_head"]) for h, r in zip(hidden, rows)]


def logits_at(program: dict, params: dict, tokens, rows, *, fetch) -> np.ndarray:
    """Float32 logits [len(rows), vocab] of one sequence at the given positions:
    the LAST pass's (no token stops early at the published threshold)."""
    return logits_of(program, params, [tokens], [rows], fetch=fetch)[0]


def exit_distribution(program: dict, params: dict, tokens, rows, *, fetch) -> np.ndarray:
    """p [len(rows), layer_passes] float32: the exit gate's distribution over the
    pass at which each token at ``rows`` would stop (module docstring); every row
    sums to 1."""
    with jax.default_matmul_precision("highest"):
        hidden, top = _passes(program, params, [np.asarray(tokens)], fetch)
        h = hidden[0][:, jnp.asarray(rows)]  # [R, rows, d]
        lam = jax.nn.sigmoid(h @ top["exit_gate"]["w"][:, 0] + top["exit_gate"]["b"])
        p, running = [], jnp.ones_like(lam[0])
        for r in range(lam.shape[0] - 1):
            p.append(lam[r] * running)
            running = running * (1.0 - lam[r])
        p.append(running)  # the last pass takes what is left
        return np.asarray(jnp.stack(p, axis=-1))


def lm_loss(program: dict, params: dict, tokens, *, fetch) -> float:
    """Mean next-token cross-entropy of ``tokens`` [S + 1] or [N, S + 1] on the
    last pass's logits, as a float; ``lm_loss_traced`` is the same as a traced
    scalar for ``jax.grad``."""
    return float(lm_loss_traced(program, params, tokens, fetch=fetch))


def lm_loss_traced(program: dict, params: dict, tokens, *, fetch=lambda leaves: leaves):
    tokens = np.asarray(tokens).reshape(-1, np.shape(tokens)[-1])
    losses = []
    with jax.default_matmul_precision("highest"):
        hidden, top = _passes(program, params, list(tokens[:, :-1]), fetch)
        for h, labels in zip(hidden, tokens[:, 1:]):
            logits = h[-1] @ top["lm_head"]  # one sequence's [S, vocab] at a time
            picked = jnp.take_along_axis(logits, jnp.asarray(labels)[:, None], axis=-1)[:, 0]
            losses.append(jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked))
        return jnp.mean(jnp.stack(losses))


def param_counts(program: dict) -> dict:
    """A layer: four attention projections and three feed-forward matrices, four
    norms. A token multiplies through every layer's matrices once a PASS and
    through the head once; what is HELD counts a layer once."""
    d, L, V = program["hidden_size"], program["num_layers"], program["vocab_size"]
    f, R = program["intermediate_size"], int(program.get("layer_passes", 1))
    layer = 4 * d * d + 3 * d * f
    return {
        "matmul_per_layer": layer,
        "layer_passes": R,
        "matmul_on_token_path": R * L * layer + d * V,
        "total": L * (layer + 4 * d) + 2 * V * d + d + (d + 1),
    }
