"""The plain reference of the ``lfm2_moe`` architecture as LiquidAI/LFM2-24B-A2B
configures it: its forward pass and language-model loss in straightforward
float32 ``jax.numpy`` — one sequence, one layer and ONE EXPERT at a time, the
short convolution as an explicit sum over its shifted copies, the causal score
matrix taken ``QUERY_BLOCK`` whole rows and one K/V head's group of query heads
at a time, the feed-forwards ``ROW_BLOCK`` rows at a time, the tied head
``HEAD_BLOCK`` columns at a time: no cache, no state, no kernel, no sort, no
grouped matmul, no scan over layers — and its parameter counts. The protocol is
stated in ``references/__init__.py``; it shares no code with ``deepspeed_tpu/``.

The layer (each symbol a key of the published ``config.json``; the operator, the
attention sublayer, the layer, the final norm and the tied head are
``Lfm2ShortConv``, ``Lfm2Attention``, ``Lfm2DecoderLayer`` and
``Lfm2Model.embedding_norm`` of ``transformers``' dense LFM2, to which
``tests/test_lfm2.py`` holds this file on copied weights): RMSNorm (scale only,
``operator_norm``) before the operator and (``ffn_norm``) before the
feed-forward, a final RMSNorm, no biases, sequential residual, the head the
embedding's transpose.

*The operator of layer l is ``layer_operators[l]``* (the published
``layer_types``). **conv**, K = ``conv_kernel`` taps (``conv_L_cache``), no bias:
``[B | C | z] = h W_in`` (three chunks of d, in that order); ``u = B * z``;
``c_t = sum_j w[j] u_{t-K+1+j}`` per channel (u before the sequence's start is 0;
no activation); ``x += (C * c) W_out``. **attn**, H query heads and Hkv key/value
heads of width D: ``q = h W_q`` -> [H, D], ``k``, ``v`` -> [Hkv, D]; ``q =
RMSNorm_D(q) g_q``, ``k = RMSNorm_D(k) g_k`` on every head by itself, one [D]
scale each; rotary on q and k at absolute positions, half rotation (dimension i
with i + D / 2), base ``rotary_base``, all D; query head i attends K/V head i //
(H / Hkv), causal, scale D^-1/2; the heads' outputs through ``W_o``. A program
with no ``layer_operators`` attends in every layer.

*Feed-forward.* The first ``moe_first_dense`` layers: ``down(silu(gate(h)) *
up(h))`` at ``dense_intermediate_size``. Every later layer: ``s = sigmoid(h
W_r)`` over all ``num_experts`` in float32; the ``moe_top_k`` experts with the
largest ``s + b`` (``expert_bias``: it selects and does not weigh); weights ``s_e /
(sum of the chosen s + 1e-6) x moe_routed_scale`` (the PUBLISHED denominator: the
program's is ``+ 1e-20``, about 5e-7 relative at a sum of 2; the configuration
states the departure); each expert a gated MLP of ``intermediate_size``. No
shared expert.

The parameter LAYOUT read (the system's, because the reference runs on the
system's own weights): with ``layer_operators`` the layers lie in STACKS BY
OPERATOR, ``params["layers"]["attn"]`` as long as the attention layers are many
and ``params["layers"]["conv"]`` as the conv layers, the two norms [L] beside
them; this file slices each itself, a layer's operator at its index among the
layers of ITS operator.

Beyond the protocol, for the check of a routed model (as ``deepseek_v3.py``):
``routed_passes`` can be given the experts the SYSTEM chose (``routing`` [routed
layers, tokens, k]); each token then goes through those, weighted by the
reference's own float32 scores, and the pass reports ``slack`` (the largest
selection score left out minus the smallest chosen, over the standard deviation
of the layer's selection scores) and on how many (layer, token) pairs the two
sets ``differ``.

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
    "dense_intermediate_size": ANY, "layernorm_epsilon": ANY, "rotary_base": ANY,
    "layer_operators": ANY, "conv_kernel": ANY,
    "num_experts": ANY, "moe_top_k": ANY, "moe_routed_scale": ANY,
    "moe_first_dense": ANY, "moe_norm_topk_prob": (True,),
    # what makes the layer this architecture's, each at the one value this file implements
    "pos_emb": ("rotary",), "tie_embeddings": (True,), "use_bias": (False,),
    "norm_kind": ("rms",), "activation": ("swiglu",), "qk_norm": ("head",),
    "moe_every": (1,), "moe_routing": ("dropless",), "moe_score_fn": ("sigmoid",),
    "moe_select_bias": (True,), "moe_aux_coeff": (0.0,),
    # how the SYSTEM attends in a decode step; nothing of the model, so nothing here reads it
    "decode_attn": ("xla",),
}
QUERY_BLOCK = 512  # queries a score matrix is taken for at a time (each row's softmax whole)
ROW_BLOCK = 2048  # rows a feed-forward is taken for at a time
HEAD_BLOCK = 8192  # columns of the tied head cast to float32 at a time
RENORM_EPS = 1e-6  # the published router's: routing_weights / (their sum + 1e-6)
ATTN = ("wq", "wk", "wv", "wo", "q_norm_scale", "k_norm_scale")
CONV = ("conv_in", "conv_w", "conv_out")
NORMS = ("ln1_scale", "ln2_scale")


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x, base):
    """x [S, heads, D]: dimension i rotated with i + D / 2 by position x base^(-2i / D)."""
    S, D = x.shape[0], x.shape[-1]
    inv_freq = base ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]  # [S, D / 2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@partial(jax.jit, static_argnames=("eps", "base"))
def _attend(x, lp, *, eps, base):
    """An attention layer's operator: x [S, d] -> x after its residual."""
    S = x.shape[0]
    h = _rms(x, lp["ln1_scale"], eps)
    q = _rms(jnp.einsum("sd,dhk->shk", h, lp["wq"]), lp["q_norm_scale"], eps)  # [S, H, D]
    k = _rms(jnp.einsum("sd,dhk->shk", h, lp["wk"]), lp["k_norm_scale"], eps)  # [S, Hkv, D]
    v = jnp.einsum("sd,dhk->shk", h, lp["wv"])
    q, k = _rotary(q, base), _rotary(k, base)
    H, Hkv, D = q.shape[1], k.shape[1], q.shape[2]
    q = q.reshape(S, Hkv, H // Hkv, D)  # query head i with K/V head i // (H / Hkv)
    blocks = []  # QUERY_BLOCK queries at a time against the keys they can see
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, S)
        seen = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]

        def group(qkv):  # one K/V head and its query heads: [q, g, D], [s, D], [s, D]
            qg, kg, vg = qkv
            scores = jnp.einsum("qgk,sk->gqs", qg, kg) / math.sqrt(D)
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("gqs,sk->qgk", probs, vg)

        out = jax.lax.map(group, (q[lo:hi].transpose(1, 0, 2, 3),
                                  k[:hi].transpose(1, 0, 2), v[:hi].transpose(1, 0, 2)))
        blocks.append(out.transpose(1, 0, 2, 3).reshape(hi - lo, H, D))
    return x + jnp.einsum("qhk,hkd->qd", jnp.concatenate(blocks, axis=0), lp["wo"])


@partial(jax.jit, static_argnames=("eps",))
def _short_conv(x, lp, *, eps):
    """A conv layer's operator: x [S, d] -> x after its residual. The filter is the
    sum over its K taps of u shifted: tap j multiplies u_{t - (K - 1 - j)}, zero
    before the sequence's start."""
    S, d = x.shape
    K = lp["conv_w"].shape[0]
    proj = _rms(x, lp["ln1_scale"], eps) @ lp["conv_in"]
    gate_in, gate_out, z = proj[:, :d], proj[:, d:2 * d], proj[:, 2 * d:]
    u = gate_in * z
    c = jnp.zeros_like(u)
    for j in range(K):
        back = K - 1 - j  # how many positions behind t this tap reads
        shifted = jnp.concatenate([jnp.zeros((back, d), u.dtype), u[:S - back]], axis=0)
        c = c + lp["conv_w"][j] * shifted
    return x + (gate_out * c) @ lp["conv_out"]


@jax.jit
def _gated_rows(h2, wg, wi, wo, share):
    return share[:, None] * ((jax.nn.silu(h2 @ wg) * (h2 @ wi)) @ wo)


def _gated_mlp(h2, wg, wi, wo, share):
    """One gated MLP on every token of h2 [S, d], ``ROW_BLOCK`` rows at a time,
    weighted by ``share`` [S]: an expert with the token's weight for it (zero where
    it was not routed to it), or a dense layer with ones."""
    return jnp.concatenate([_gated_rows(h2[lo:lo + ROW_BLOCK], wg, wi, wo,
                                        share[lo:lo + ROW_BLOCK])
                            for lo in range(0, h2.shape[0], ROW_BLOCK)], axis=0)


def _route(program: dict, logits, bias, chosen):
    """Router logits [S, E], the selection bias [E] (and, or None, the experts
    someone else chose [S, k]) -> what the layer needs and what the check reports.
    ``mix`` [S, E]: every chosen expert's weight."""
    k = int(program["moe_top_k"])
    scores = jax.nn.sigmoid(logits)
    select = scores + bias
    own = jnp.argsort(-select, axis=-1)[:, :k]
    used = own if chosen is None else jnp.asarray(chosen, jnp.int32)
    weights = jnp.take_along_axis(scores, used, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + RENORM_EPS)
    weights = weights * float(program.get("moe_routed_scale", 1.0))
    S, E = scores.shape
    taken = jnp.zeros((S, E), bool).at[jnp.arange(S)[:, None], used].set(True)
    left_out = jnp.max(jnp.where(taken, -jnp.inf, select), axis=-1)
    smallest = jnp.min(jnp.where(taken, select, jnp.inf), axis=-1)
    return {
        "mix": jnp.zeros((S, E), jnp.float32).at[jnp.arange(S)[:, None], used].set(weights),
        "own": np.asarray(own),
        "slack": float(jnp.max(left_out - smallest) / jnp.std(select)),
        "differ": int(np.sum(np.any(np.sort(np.asarray(own)) != np.sort(np.asarray(used)),
                                    axis=-1))),
    }


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def operators(program: dict) -> list:
    """Per layer "attn" or "conv"."""
    return list(program.get("layer_operators") or ["attn"] * int(program["num_layers"]))


def _operator_leaves(program: dict, layers: dict, i: int) -> dict:
    """Layer ``i``'s two norms and its operator's leaves, each sliced out of the
    stack it lies in: the norms at ``i``; the operator's at the layer's index
    among the layers of its operator (one stack for all where the program states
    no ``layer_operators``)."""
    ops = operators(program)
    leaves = {k: layers[k][i] for k in NORMS}
    if not program.get("layer_operators"):
        return {**leaves, **{k: layers[k][i] for k in ATTN}}
    at = ops[:i].count(ops[i])
    names, stack = (CONV, layers["conv"]) if ops[i] == "conv" else (ATTN, layers["attn"])
    return {**leaves, **{k: stack[k][at] for k in names}}


def _forward(program: dict, params: dict, sequences, fetch, routing=None) -> dict:
    """Every sequence (a list of [S] token arrays of any lengths) through the
    layers, each layer's leaves fetched once: its operator's together, then its
    feed-forward one MLP at a time. ``routing``: per sequence, the experts to use
    [routed layers, S, k], or None for the reference's own. ``hidden`` is the
    final norm of the residual stream behind the last layer."""
    eps, base = float(program["layernorm_epsilon"]), float(program["rotary_base"])
    L, lead = int(program["num_layers"]), int(program.get("moe_first_dense") or 0)
    ops, layers, moe = operators(program), params["layers"], params.get("moe")
    out = {"own": [[] for _ in sequences], "slack": -np.inf, "differ": 0, "pairs": 0}
    ones = lambda h: jnp.ones((h.shape[0],), jnp.float32)
    with jax.default_matmul_precision("highest"):
        # the rows looked up, not the table: the embedding is never float32 whole
        xs = [_f32(fetch({"rows": params["wte"][np.asarray(t)]}))["rows"] for t in sequences]
        for i in range(L):
            lp = _f32(fetch(_operator_leaves(program, layers, i)))
            for j, x in enumerate(xs):
                xs[j] = (_short_conv(x, lp, eps=eps) if ops[i] == "conv"
                         else _attend(x, lp, eps=eps, base=base))
            h2s = [_rms(x, lp["ln2_scale"], eps) for x in xs]
            if i < lead:
                w = _f32(fetch({k: v[i] for k, v in params["dense_ffn"].items()}))
                for j in range(len(xs)):
                    xs[j] = xs[j] + _gated_mlp(h2s[j], w["wg"], w["wi"], w["wo_mlp"], ones(h2s[j]))
                continue
            r = i - lead  # the routed layer's position in the moe stacks
            router = _f32(fetch({"gate": moe["gate"][r], "bias": moe["bias"][r]}))
            routes = []
            for j, h2 in enumerate(h2s):
                route = _route(program, h2 @ router["gate"], router["bias"],
                               None if routing is None else routing[j][r])
                routes.append(route)
                out["own"][j].append(route["own"])
                out["slack"] = max(out["slack"], route["slack"])
                out["differ"] += route["differ"]
                out["pairs"] += h2.shape[0]
            for e in range(int(program["num_experts"])):
                w = _f32(fetch({k: v[r, e] for k, v in moe["experts"].items()}))
                for j in range(len(xs)):
                    xs[j] = xs[j] + _gated_mlp(h2s[j], w["wg"], w["wi"], w["wo"],
                                               routes[j]["mix"][:, e])
        top = _f32(fetch({"lnf_scale": params["lnf_scale"]}))
        out["hidden"] = [_rms(x, top["lnf_scale"], eps) for x in xs]
    out["own"] = [np.stack(o) for o in out["own"]] if L > lead else out["own"]
    return out


def _head_logits(params, fetch, hidden):
    """hidden [n, d] through the tied head (the embedding's transpose),
    ``HEAD_BLOCK`` columns (rows of the embedding) cast at a time."""
    table = params["wte"]
    parts = []
    for lo in range(0, table.shape[0], HEAD_BLOCK):
        block = _f32(fetch({"rows": table[lo:lo + HEAD_BLOCK]}))["rows"]
        parts.append(hidden @ block.T)
    return jnp.concatenate(parts, axis=-1)


def routed_passes(program: dict, params: dict, sequences, rows, *, fetch, routing=None) -> dict:
    """Several sequences in ONE pass over the layers (every expert is fetched once
    for all of them): ``sequences`` a list of [S_j] tokens, ``rows`` the positions
    wanted of each, ``routing`` a list of [routed layers, S_j, k] or None ->
    ``logits`` a list of [len(rows_j), vocab], ``own`` a list, ``slack`` the largest
    over all of them, ``differ`` the share over all (layer, token) pairs."""
    f = _forward(program, params, [np.asarray(t) for t in sequences], fetch,
                 None if routing is None else [np.asarray(r) for r in routing])
    with jax.default_matmul_precision("highest"):
        logits = [np.asarray(_head_logits(params, fetch, x[jnp.asarray(r)]))
                  for x, r in zip(f["hidden"], rows)]
    return {"logits": logits, "own": f["own"], "slack": f["slack"],
            "differ": f["differ"] / max(f["pairs"], 1)}


def routed_pass(program: dict, params: dict, tokens, rows, *, fetch, routing=None) -> dict:
    """One sequence's float32 ``logits`` [len(rows), vocab] at ``rows`` under
    ``routing`` ([routed layers, S, k]; None: the reference routes for itself), the
    reference's ``own`` choices, the ``slack`` of the routing used and the share of
    (layer, token) pairs on which the two sets ``differ``."""
    out = routed_passes(program, params, [tokens], [rows], fetch=fetch,
                        routing=None if routing is None else [routing])
    return {**out, "logits": out["logits"][0], "own": out["own"][0]}


def logits_at(program: dict, params: dict, tokens, rows, *, fetch, routing=None) -> np.ndarray:
    """Float32 logits [len(rows), vocab] at the given positions."""
    return routed_pass(program, params, tokens, rows, fetch=fetch, routing=routing)["logits"]


def lm_loss(program: dict, params: dict, tokens, *, fetch, routing=None) -> float:
    """Mean next-token cross-entropy of ``tokens`` [S + 1] or [N, S + 1] (the
    architecture's loss has no other term)."""
    tokens = np.asarray(tokens).reshape(-1, np.shape(tokens)[-1])
    f = _forward(program, params, list(tokens[:, :-1]), fetch, routing)
    losses = []
    with jax.default_matmul_precision("highest"):
        for x, labels in zip(f["hidden"], tokens[:, 1:]):
            logits = _head_logits(params, fetch, x)  # one sequence's [S, vocab] at a time
            picked = jnp.take_along_axis(logits, jnp.asarray(labels)[:, None], axis=-1)[:, 0]
            losses.append(jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked))
    return float(jnp.mean(jnp.stack(losses)))


def param_counts(program: dict) -> dict:
    """A conv layer's operator: ``W_in`` d x 3d, ``W_out`` d x d (+ K x d taps, in no
    matmul). An attention layer's: W_q, W_o d x H D, W_k, W_v d x Hkv D (+ two head
    norms). A leading layer adds a gated MLP of ``dense_intermediate_size``; a
    routed one the router and ``num_experts`` gated experts, of which a token
    multiplies through ``moe_top_k``. The head is the embedding: counted once in
    ``total``, and on a token's path as the head."""
    d, L, V = program["hidden_size"], program["num_layers"], program["vocab_size"]
    H, Hkv = program["num_heads"], program["num_kv_heads"]
    D = program.get("qk_head_dim") or d // H
    f, E, k = program["intermediate_size"], program["num_experts"], program["moe_top_k"]
    K = program.get("conv_kernel") or 0
    lead = program.get("moe_first_dense") or 0
    routed = L - lead
    ops = operators(program)
    n_conv, n_attn = ops.count("conv"), ops.count("attn")
    attention = 2 * d * H * D + 2 * d * Hkv * D
    conv = 3 * d * d + d * d
    expert = 3 * d * f
    dense = 3 * d * program["dense_intermediate_size"]
    operators_matmul = n_attn * attention + n_conv * conv
    outside = operators_matmul + lead * dense + routed * d * E + d * V
    small = n_attn * 2 * D + n_conv * K * d + L * 2 * d + d + routed * E  # norms, taps, biases
    return {
        "matmul_attention_per_layer": attention,
        "matmul_conv_per_layer": conv,
        "conv_layers": n_conv,
        "attn_layers": n_attn,
        "matmul_per_expert": expert,
        "matmul_outside_experts": outside,
        "routed_layers": routed,
        "matmul_on_token_path": outside + routed * k * expert,
        "total": outside + routed * E * expert + small,
    }
