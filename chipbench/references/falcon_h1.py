"""The plain reference of the Falcon-H1 architecture (``model_type: "falcon_h1"``,
``FalconH1ForCausalLM``; tiiuae/Falcon-H1-34B-Instruct is the configuration it
was written for): its forward pass and language-model loss in straightforward
float32 ``jax.numpy`` — one sequence and one layer at a time, the state-space
scan AS THE RECURRENCE IS WRITTEN (a ``lax.scan`` over time: no chunked form, no
cache, no one-step program), attention on K/V repeated to the query heads — and
its parameter counts. The protocol is stated in ``references/__init__.py``; it
shares no code with ``deepspeed_tpu/``.

The layer, as published (``modeling_falcon_h1.py`` of ``transformers``; every
multiplier is a key of the program's ``multipliers``, 1 where not stated). Every
layer runs a Mamba-2 mixer and grouped-query attention side by side on the same
normed input, adds both to the residual, then a gated MLP; RMSNorm (scale only),
no bias but the convolution's, untied head::

    x0      = embed[token] * embedding_multiplier
    h       = rmsnorm(x; ln1)
    q, k, v = Wq a, (Wk a) * key_multiplier, Wv a      a = h * attention_in_multiplier
    q, k    = rotary(q), rotary(k)      half rotation over the whole head, base rotary_base
    attn    = Wo(causal softmax(q k / sqrt(Dh)) v) * attention_out_multiplier
              query head i attends K/V head i // (Hq // Hkv)
    [z | xBC | dt] = (W_in (h * ssm_in_multiplier)) * ssm_multipliers[z, x, B, C, dt]
    xBC_t   = silu(sum_j w_conv[j] * xBC_{t-K+1+j} + b_conv)   depthwise, zeros before t = 0
    [xs | B | C] = xBC;  xs -> [H, P];  B, C -> [G, N];  head h reads group h // (H // G)
    dt_t    = softplus(dt_t + dt_bias);   A = -exp(A_log)
    S_t     = exp(dt_t A) S_{t-1} + dt_t xs_t (x) B_t          S_{-1} = 0
    y_t     = S_t C_t + D xs_t
    mixed   = W_out(grouped_rmsnorm(y * silu(z); G groups)) * ssm_out_multiplier
    x       = x + attn + mixed
    f       = rmsnorm(x; ln2)
    x       = x + W_down(W_up f * silu(W_gate f * mlp_multipliers[0])) * mlp_multipliers[1]
    logits  = W_head rmsnorm(x; lnf) * lm_head_multiplier

``time_step_limit`` is (0, inf) as published: no clamp. The gate comes BEFORE the
grouped norm (``mamba_norm_before_gate: false``).

Departures from the published code: none in the arithmetic. In the form: (1) the
scan is the recurrence, where ``transformers``' fallback takes a chunked form
(equal in exact arithmetic; ``tests/test_falcon_h1.py`` holds this file to
``FalconH1ForCausalLM`` on copied weights); (2) RMSNorm multiplies by its scale
in float32; (3) so that the published widths fit beside the served model, the
embedding and the head are never float32 whole (the rows looked up are gathered
and cast; the head is taken ``VOCAB_BLOCK`` columns at a time) and a layer's
matrices are cast one group at a time (attention, mixer, then each of the MLP's
three). The weights are random from a seed.

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
    "num_kv_heads": ANY, "qk_head_dim": ANY, "hidden_size": ANY, "intermediate_size": ANY,
    "layernorm_epsilon": ANY, "rotary_base": ANY, "multipliers": ANY,
    "ssm_state_size": ANY, "ssm_heads": ANY, "ssm_head_dim": ANY, "ssm_groups": ANY,
    "ssm_conv_kernel": ANY,
    # what makes the block this architecture's, each at the one value this file implements
    "pos_emb": ("rotary",), "tie_embeddings": (False,), "use_bias": (False,),
    "norm_kind": ("rms",), "activation": ("swiglu",),
    # how the SYSTEM computes (its scan's chunk, its decode step's attention); nothing
    # of the model, so nothing here reads them
    "ssm_chunk_size": ANY, "decode_attn": ("xla",),
}
QUERY_BLOCK = 512  # queries a block of the causal score matrix (each row's softmax whole)
VOCAB_BLOCK = 32768  # columns of the head cast to float32 at a time
MULTIPLIERS = {"embedding_multiplier": 1.0, "attention_in_multiplier": 1.0, "key_multiplier": 1.0,
               "attention_out_multiplier": 1.0, "ssm_in_multiplier": 1.0,
               "ssm_multipliers": (1.0,) * 5, "ssm_out_multiplier": 1.0,
               "mlp_multipliers": (1.0, 1.0), "lm_head_multiplier": 1.0}
ATTENTION = ("ln1_scale", "wq", "wk", "wv", "wo")
MIXER = ("ssm_in", "ssm_conv", "ssm_conv_bias", "ssm_dt_bias", "ssm_a_log", "ssm_d",
         "ssm_norm_scale", "ssm_out")


def _multipliers(program: dict) -> dict:
    stated = program.get("multipliers") or {}
    unknown = set(stated) - set(MULTIPLIERS)
    if unknown:
        raise NotImplementedError(f"multipliers {sorted(unknown)} are not Falcon-H1's")
    return {**MULTIPLIERS, **stated}


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x, base):
    """x [S, heads, D]: the half rotation (``rotate_half``): dimension i pairs
    with i + D / 2, both turned by position x base^(-2i / D)."""
    S, D = x.shape[0], x.shape[-1]
    inv_freq = base ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv_freq[None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    lo, hi = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


@partial(jax.jit, static_argnames=("eps", "base", "in_m", "key_m", "out_m"))
def _attention(x, lp, *, eps, base, in_m, key_m, out_m):
    """x [S, d] -> the attention branch's output [S, d] (not yet added)."""
    S = x.shape[0]
    a = _rms(x, lp["ln1_scale"], eps) * in_m
    q = _rotary(jnp.einsum("sd,dhk->shk", a, lp["wq"]), base)  # [S, Hq, Dh]
    k = _rotary(jnp.einsum("sd,dhk->shk", a, lp["wk"]) * key_m, base)  # [S, Hkv, Dh]
    v = jnp.einsum("sd,dhk->shk", a, lp["wv"])
    group = q.shape[1] // k.shape[1]  # query head i attends K/V head i // group
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    blocks = []
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, S)
        scores = jnp.einsum("qhk,shk->hqs", q[lo:hi], k[:hi]) / math.sqrt(q.shape[-1])
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        blocks.append(jnp.einsum("hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v[:hi]))
    return jnp.einsum("qhk,hkd->qd", jnp.concatenate(blocks, axis=0), lp["wo"]) * out_m


@partial(jax.jit, static_argnames=("eps", "H", "P", "G", "N", "in_m", "seg_m", "out_m"))
def _mixer(x, ln1_scale, lp, *, eps, H, P, G, N, in_m, seg_m, out_m):
    """x [S, d] -> the mixer branch's output [S, d] (not yet added): the
    projection, the causal depthwise convolution, the recurrence over time, the
    gated grouped norm, the projection back."""
    S = x.shape[0]
    inner, gn = H * P, G * N
    h = _rms(x, ln1_scale, eps)
    proj = (h * in_m) @ lp["ssm_in"]
    proj = proj * np.repeat(np.asarray(seg_m, np.float32), (inner, inner, gn, gn, H))
    z, xBC, dt = proj[:, :inner], proj[:, inner:2 * inner + 2 * gn], proj[:, 2 * inner + 2 * gn:]
    K = lp["ssm_conv"].shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, xBC.shape[1]), jnp.float32), xBC], axis=0)
    conv = sum(padded[j:j + S] * lp["ssm_conv"][j] for j in range(K)) + lp["ssm_conv_bias"]
    xBC = jax.nn.silu(conv)
    xs = xBC[:, :inner].reshape(S, H, P)
    B = xBC[:, inner:inner + gn].reshape(S, G, N)
    C = xBC[:, inner + gn:].reshape(S, G, N)
    dt = jax.nn.softplus(dt + lp["ssm_dt_bias"])  # [S, H]
    A = -jnp.exp(lp["ssm_a_log"])  # [H]
    of_head = jnp.arange(H) // (H // G)  # the group a head reads

    def step(state, row):
        x_t, B_t, C_t, dt_t = row
        B_h, C_h = B_t[of_head], C_t[of_head]  # [H, N]
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + dt_t[:, None, None] * x_t[:, :, None] * B_h[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, C_h)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32), (xs, B, C, dt))
    y = (y + lp["ssm_d"][None, :, None] * xs).reshape(S, inner)
    gated = (y * jax.nn.silu(z)).reshape(S, G, inner // G)  # the gate BEFORE the norm
    gated = gated / jnp.sqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
    return (gated.reshape(S, inner) * lp["ssm_norm_scale"]) @ lp["ssm_out"] * out_m


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def _forward(program: dict, params: dict, sequences, fetch) -> list:
    """Every sequence (a list of [S] token arrays of any lengths) through the
    layers -> the final normed hidden states, per sequence [S, d]. A layer's
    leaves are fetched and cast a group at a time, each group once for all the
    sequences."""
    eps, base = float(program["layernorm_epsilon"]), float(program["rotary_base"])
    m = _multipliers(program)
    layers = params["layers"]
    attend = partial(_attention, eps=eps, base=base, in_m=float(m["attention_in_multiplier"]),
                     key_m=float(m["key_multiplier"]),
                     out_m=float(m["attention_out_multiplier"]))
    # a program without the ssm_* keys is the block without its mixer (the twin that
    # ``parity.py``'s cache case can take: configs/falcon-h1-34b-L4.json says why)
    mix = program.get("ssm_state_size") and partial(
        _mixer, eps=eps, H=int(program["ssm_heads"]), P=int(program["ssm_head_dim"]),
        G=int(program["ssm_groups"]), N=int(program["ssm_state_size"]),
        in_m=float(m["ssm_in_multiplier"]), seg_m=tuple(float(v) for v in m["ssm_multipliers"]),
        out_m=float(m["ssm_out_multiplier"]))
    gate_m, down_m = (float(v) for v in m["mlp_multipliers"])
    one = lambda name, i: _f32(fetch({name: layers[name][i]}))[name]
    with jax.default_matmul_precision("highest"):
        # the rows looked up, not the table: the embedding is never float32 whole
        xs = [_f32(fetch({"rows": params["wte"][np.asarray(t)]}))["rows"]
              * float(m["embedding_multiplier"]) for t in sequences]
        for i in range(int(program["num_layers"])):
            lp = _f32(fetch({k: layers[k][i] for k in ATTENTION}))
            branches = [attend(x, lp) for x in xs]
            ln1 = lp["ln1_scale"]
            if program.get("ssm_state_size"):
                lp = _f32(fetch({k: layers[k][i] for k in MIXER}))
                branches = [a + mix(x, ln1, lp) for x, a in zip(xs, branches)]
            xs = [x + b for x, b in zip(xs, branches)]
            del lp, branches
            fs = [_rms(x, one("ln2_scale", i), eps) for x in xs]
            w = one("wg", i)  # the MLP's three matrices, one at a time
            gates = [jax.nn.silu(f @ w * gate_m) for f in fs]
            w = one("wi", i)
            ups = [(f @ w) * g for f, g in zip(fs, gates)]
            del gates
            w = one("wo_mlp", i)
            xs = [x + (u @ w) * down_m for x, u in zip(xs, ups)]
            del w, ups
        lnf = _f32(fetch({"lnf_scale": params["lnf_scale"]}))["lnf_scale"]
        return [_rms(x, lnf, eps) for x in xs]


def _head_blocks(params, fetch):
    """The head [d, vocab], ``VOCAB_BLOCK`` columns at a time in float32."""
    head = params["lm_head"]
    for lo in range(0, head.shape[1], VOCAB_BLOCK):
        yield _f32(fetch({"block": head[:, lo:lo + VOCAB_BLOCK]}))["block"]


def logits_of(program: dict, params: dict, sequences, rows, *, fetch) -> list:
    """``logits_at`` of several sequences in ONE pass over the layers: per
    sequence float32 [len(rows_j), vocab]."""
    hidden = _forward(program, params, [np.asarray(t) for t in sequences], fetch)
    picked = [x[jnp.asarray(r)] for x, r in zip(hidden, rows)]
    scale = float(_multipliers(program)["lm_head_multiplier"])
    blocks = [[] for _ in picked]
    with jax.default_matmul_precision("highest"):
        for w in _head_blocks(params, fetch):
            for out, x in zip(blocks, picked):
                out.append(np.asarray(x @ w * scale))
    return [np.concatenate(b, axis=-1) for b in blocks]


def logits_at(program: dict, params: dict, tokens, rows, *, fetch) -> np.ndarray:
    """Float32 logits [len(rows), vocab] of one sequence at the given positions."""
    return logits_of(program, params, [tokens], [rows], fetch=fetch)[0]


def lm_loss(program: dict, params: dict, tokens, *, fetch) -> float:
    """Mean next-token cross-entropy of ``tokens`` [S + 1] or [N, S + 1] (the
    architecture's loss has no other term); the log-sum-exp is gathered over the
    head's blocks, so [S, vocab] is never whole."""
    tokens = np.asarray(tokens).reshape(-1, np.shape(tokens)[-1])
    hidden = _forward(program, params, list(tokens[:, :-1]), fetch)
    scale = float(_multipliers(program)["lm_head_multiplier"])
    lse = [jnp.full((x.shape[0],), -jnp.inf) for x in hidden]
    gold = [jnp.zeros((x.shape[0],)) for x in hidden]
    with jax.default_matmul_precision("highest"):
        lo = 0
        for w in _head_blocks(params, fetch):
            for j, (x, labels) in enumerate(zip(hidden, tokens[:, 1:])):
                logits = x @ w * scale
                lse[j] = jnp.logaddexp(lse[j], jax.nn.logsumexp(logits, axis=-1))
                inside = (labels >= lo) & (labels < lo + w.shape[1])
                at = jnp.asarray(np.where(inside, labels - lo, 0))
                gold[j] = gold[j] + jnp.where(
                    jnp.asarray(inside), jnp.take_along_axis(logits, at[:, None], axis=-1)[:, 0], 0.0)
            lo += w.shape[1]
    return float(jnp.mean(jnp.stack([jnp.mean(l - g) for l, g in zip(lse, gold)])))


def param_counts(program: dict) -> dict:
    """Attention: W_q, W_k, W_v (the few K/V heads), W_o. Mixer: W_in (z | x | B |
    C | dt) and W_out are matmuls on a token's path; its depthwise convolution,
    ``dt_bias`` / ``A_log`` / ``D`` and the gated norm are held and are not
    matmuls. MLP: three matrices. A token multiplies through all of a layer."""
    d, L, V = program["hidden_size"], program["num_layers"], program["vocab_size"]
    Hq, Hkv, Dh = program["num_heads"], program["num_kv_heads"], program["qk_head_dim"]
    f = program["intermediate_size"]
    attention = d * Hq * Dh + 2 * d * Hkv * Dh + Hq * Dh * d
    mixer = mixer_held = 0
    if program.get("ssm_state_size"):
        H, P, G, N = (program["ssm_heads"], program["ssm_head_dim"], program["ssm_groups"],
                      program["ssm_state_size"])
        inner, conv_dim = H * P, H * P + 2 * G * N
        mixer = d * (inner + conv_dim + H) + inner * d
        mixer_held = conv_dim * program["ssm_conv_kernel"] + conv_dim + 3 * H + inner
    mlp = 3 * d * f
    per_layer = attention + mixer + mlp
    return {
        "matmul_attention_per_layer": attention,
        "matmul_mixer_per_layer": mixer,
        "matmul_mlp_per_layer": mlp,
        "matmul_on_token_path": L * per_layer + d * V,
        "total": L * (per_layer + mixer_held + 2 * d) + 2 * V * d + d,
    }
