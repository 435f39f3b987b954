"""The plain reference of the ``qwen3_next`` architecture as
Qwen/Qwen3-Next-80B-A3B-Instruct configures it: its forward pass and its
language-model loss in straightforward float32 ``jax.numpy`` — one sequence, one
layer and ONE EXPERT at a time, the gated delta rule as its RECURRENCE, one token
at a time (no chunk, no inverse, no carried cache), the causal score matrix taken
``QUERY_BLOCK`` whole rows and one K/V head's group of query heads at a time, the
feed-forwards ``ROW_BLOCK`` rows at a time, the head ``HEAD_BLOCK`` columns at a
time: no kernel, no sort, no grouped matmul, no scan over layers — and its
parameter counts. The protocol is stated in ``references/__init__.py``; it shares
no code with ``deepspeed_tpu/``.

The block (``transformers/models/qwen3_next/modeling_qwen3_next.py``; each symbol a
key of the published ``config.json``): RMSNorm (scale only) before the operator
and before the feed-forward, a final RMSNorm, no biases, sequential residual,
untied head. Layer i attends where ``layer_operators[i]`` is "attn" and runs the
gated delta rule where it is "delta" (published: attention where (i + 1) % 4 == 0).

*Delta layer* (``Qwen3NextGatedDeltaNet``; Hk key heads and Hv value heads of D,
``conv_kernel`` taps), on the normed h:

    [q | k | v | z] = h W_in        Hk D, Hk D, Hv D, Hv D channels
    [b | a]         = h W_ba        Hv + Hv
    [q | k | v]     = silu(filter([q | k | v]))    depthwise, causal, no bias
    q, k            -> Hk heads, each x * rsqrt(sum x^2 + 1e-6), q scaled by D^-1/2,
                       key head i serving value heads i Hv/Hk ... (i + 1) Hv/Hk - 1
    beta_t = sigmoid(b_t);  g_t = -exp(A_log) * softplus(a_t + dt_bias)
    S_t  = exp(g_t) S_{t-1}                      S in R^{D x D} a value head, from zero
    d_t  = beta_t (v_t - S_t^T k_t);  S_t += k_t (x) d_t;  o_t = S_t^T q_t
    out  = (w * rmsnorm_D(o) * silu(z)) W_out    the norm BEFORE the gate

*Attention layer* (``Qwen3NextAttention``; H query and Hkv key/value heads of D):
``[query | gate] = h W_q`` a head (2 D wide), ``k = h W_k``, ``v = h W_v``; ``q =
RMSNorm_D(query) g_q``, ``k = RMSNorm_D(k) g_k``, one [D] scale each for all heads;
rotary, half rotation, base ``rotary_base``, on the first ``rotary_pct`` x D of
each head; query head i attends K/V head i // (H / Hkv), scale D^-1/2, causal; the
heads' outputs times sigmoid(gate), through ``W_o``.

*Feed-forward, every layer* (``Qwen3NextSparseMoeBlock``): softmax over ALL
``num_experts`` router logits in float32, the ``moe_top_k`` largest, their weights
renormalised to sum 1; SiLU-gated experts; plus, on every token, ``sigmoid(x .
w_gate)`` x one shared gated MLP of ``moe_shared_size``. **The share**
(``moe_experts_held`` = (first, count)): the parameter tree holds the banks of
experts first ... first + count - 1 alone, the choices are made over all
``num_experts`` and the sum runs over the chosen experts THAT ARE HELD; what the
others would add is another chip's. ``None``: all of them (the uncut layer). The
vocabulary is whatever ``vocab_size`` says (a deployment's slice of the rows).

Departures from the published files, each stated in the configuration's
``assumed``: the published RMSNorms multiply by ``1 + w`` with w drawn at 0 (the
gated norm inside the delta layer by w drawn at 1); the parameter tree holds the
SCALE itself, so a converter adds 1 (``tests/test_qwen3_next.py`` does). ``W_in``
and ``W_ba`` are published interleaved by key head
(``fix_query_key_value_ordering``); the tree lays them [q | k | v | z] and [b | a]:
a permutation of columns. The program renormalises the chosen weights over (their
sum + 1e-20) where the published code has their sum. The seeded draw takes
``dt_bias`` from Gated DeltaNet's own initialiser (the inverse softplus of a
log-uniform step in [0.001, 0.1]) where the published class fills 1 (the
configuration's ``assumed`` says why); this file reads whatever the tree holds.
The multi-token-prediction module is not held.

Beyond the protocol, for the check of a routed model (as ``exaone_moe.py``):
``routed_passes`` can be given the experts the SYSTEM chose (``routing`` [layers,
tokens, k], over ALL the router's experts); each token then goes through those of
them that are held, weighted by the reference's own float32 scores, and the pass
reports ``slack`` (the largest score left out minus the smallest chosen, over the
standard deviation of the layer's scores) and on how many (layer, token) pairs
the two sets ``differ``.

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
    "layernorm_epsilon": ANY, "rotary_base": ANY, "rotary_pct": ANY,
    "layer_operators": ANY, "conv_kernel": ANY, "delta_key_heads": ANY,
    "delta_value_heads": ANY, "delta_head_dim": ANY,
    "num_experts": ANY, "moe_top_k": ANY, "moe_shared_size": ANY, "moe_experts_held": ANY,
    # what makes the block this architecture's, each at the one value this file implements
    "pos_emb": ("rotary",), "tie_embeddings": (False,), "use_bias": (False,),
    "norm_kind": ("rms",), "activation": ("swiglu",), "qk_norm": ("head",),
    "attn_output_gate": (True,), "moe_shared_gate": (True,), "moe_norm_topk_prob": (True,),
    "moe_every": (1,), "moe_routing": ("dropless",), "moe_score_fn": ("softmax",),
    "moe_aux_coeff": (0.0,),
    # how the SYSTEM attends in a decode step; nothing of the model, so nothing here reads it
    "decode_attn": ("xla",),
}
QUERY_BLOCK = 512  # queries a score matrix is taken for at a time (each row's softmax whole)
ROW_BLOCK = 2048  # rows a feed-forward is taken for at a time
HEAD_BLOCK = 4096  # columns of the head cast to float32 at a time
ATTN = ("wq", "wk", "wv", "wo", "q_norm_scale", "k_norm_scale")
DELTA = ("delta_in", "delta_ba", "delta_conv", "delta_a_log", "delta_dt_bias",
         "delta_norm_scale", "delta_out")
NORMS = ("ln1_scale", "ln2_scale")


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x, base, dims):
    """x [S, heads, D]: of its first ``dims`` dimensions, i rotated with i + dims / 2
    by position x base^(-2i / dims); the rest pass."""
    S = x.shape[0]
    inv_freq = base ** (-jnp.arange(0, dims, 2, dtype=jnp.float32) / dims)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]  # [S, dims / 2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :dims // 2], x[..., dims // 2:dims]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., dims:]], axis=-1)


@partial(jax.jit, static_argnames=("eps", "base", "rotary_dims"))
def _attend(x, lp, *, eps, base, rotary_dims):
    """An attention layer's operator: x [S, d] -> x after its residual."""
    S = x.shape[0]
    h = _rms(x, lp["ln1_scale"], eps)
    both = jnp.einsum("sd,dhk->shk", h, lp["wq"])  # [S, H, 2 D]: query | gate, a head
    D = both.shape[-1] // 2
    q, gate = both[..., :D], both[..., D:]
    q = _rms(q, lp["q_norm_scale"], eps)
    k = _rms(jnp.einsum("sd,dhk->shk", h, lp["wk"]), lp["k_norm_scale"], eps)  # [S, Hkv, D]
    v = jnp.einsum("sd,dhk->shk", h, lp["wv"])
    q, k = _rotary(q, base, rotary_dims), _rotary(k, base, rotary_dims)
    H, Hkv = q.shape[1], k.shape[1]
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
    attn = jnp.concatenate(blocks, axis=0) * jax.nn.sigmoid(gate)
    return x + jnp.einsum("qhk,hkd->qd", attn, lp["wo"])


@partial(jax.jit, static_argnames=("eps", "key_heads", "value_heads"))
def _delta(x, lp, *, eps, key_heads, value_heads):
    """A delta layer's operator: x [S, d] -> x after its residual. The filter is the
    sum over its K taps of its input shifted (tap j multiplies row t - (K - 1 - j),
    zero before the sequence's start); the rule is the recurrence, ``lax.scan`` over
    the tokens one at a time from a zero state."""
    S = x.shape[0]
    K, conv_dim = lp["delta_conv"].shape
    Hk, Hv = key_heads, value_heads
    D = conv_dim // (2 * Hk + Hv)
    h = _rms(x, lp["ln1_scale"], eps)
    proj, ba = h @ lp["delta_in"], h @ lp["delta_ba"]
    u, z = proj[:, :conv_dim], proj[:, conv_dim:].reshape(S, Hv, D)
    c = jnp.zeros_like(u)
    for j in range(K):
        back = K - 1 - j  # how many positions behind t this tap reads
        shifted = jnp.concatenate([jnp.zeros((back, conv_dim), u.dtype), u[:S - back]], axis=0)
        c = c + lp["delta_conv"][j] * shifted
    c = jax.nn.silu(c)
    unit = lambda y: y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)
    q = unit(c[:, :Hk * D].reshape(S, Hk, D)) / math.sqrt(D)
    k = unit(c[:, Hk * D:2 * Hk * D].reshape(S, Hk, D))
    v = c[:, 2 * Hk * D:].reshape(S, Hv, D)
    q, k = (jnp.repeat(y, Hv // Hk, axis=1) for y in (q, k))  # key head i: value heads i r ...
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(lp["delta_a_log"]) * jax.nn.softplus(ba[:, Hv:] + lp["delta_dt_bias"])

    def token(state, row):  # state [Hv, D (key), D (value)]
        q_t, k_t, v_t, g_t, beta_t = row
        state = state * jnp.exp(g_t)[:, None, None]
        d_t = beta_t[:, None] * (v_t - jnp.sum(state * k_t[:, :, None], axis=1))
        state = state + k_t[:, :, None] * d_t[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    _, o = jax.lax.scan(token, jnp.zeros((Hv, D, D), jnp.float32), (q, k, v, g, beta))
    o = _rms(o, lp["delta_norm_scale"], eps) * jax.nn.silu(z)
    return x + o.reshape(S, Hv * D) @ lp["delta_out"]


@jax.jit
def _gated_rows(h2, wg, wi, wo, share):
    return share[:, None] * ((jax.nn.silu(h2 @ wg) * (h2 @ wi)) @ wo)


def _gated_mlp(h2, wg, wi, wo, share):
    """One gated MLP on every token of h2 [S, d], ``ROW_BLOCK`` rows at a time,
    weighted by ``share`` [S]: an expert with the token's weight for it (zero where
    it was not routed to it), or the shared expert with its gate."""
    return jnp.concatenate([_gated_rows(h2[lo:lo + ROW_BLOCK], wg, wi, wo,
                                        share[lo:lo + ROW_BLOCK])
                            for lo in range(0, h2.shape[0], ROW_BLOCK)], axis=0)


def _route(program: dict, logits, chosen):
    """Router logits [S, E] (and, or None, the experts someone else chose [S, k]) ->
    what the layer needs and what the check reports. ``mix`` [S, E]: every chosen
    expert's weight, held here or not."""
    k = int(program["moe_top_k"])
    scores = jax.nn.softmax(logits, axis=-1)
    own = jnp.argsort(-scores, axis=-1)[:, :k]
    used = own if chosen is None else jnp.asarray(chosen, jnp.int32)
    weights = jnp.take_along_axis(scores, used, axis=-1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    S, E = scores.shape
    taken = jnp.zeros((S, E), bool).at[jnp.arange(S)[:, None], used].set(True)
    left_out = jnp.max(jnp.where(taken, -jnp.inf, scores), axis=-1)
    smallest = jnp.min(jnp.where(taken, scores, jnp.inf), axis=-1)
    return {
        "mix": jnp.zeros((S, E), jnp.float32).at[jnp.arange(S)[:, None], used].set(weights),
        "own": np.asarray(own),
        "slack": float(jnp.max(left_out - smallest) / jnp.std(scores)),
        "differ": int(np.sum(np.any(np.sort(np.asarray(own)) != np.sort(np.asarray(used)),
                                    axis=-1))),
    }


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def held(program: dict) -> tuple:
    """(first, count) of the experts the parameter tree holds banks of."""
    first, count = program.get("moe_experts_held") or (0, int(program["num_experts"]))
    return int(first), int(count)


def operators(program: dict) -> list:
    """Per layer "attn" or "delta"."""
    return list(program.get("layer_operators") or ["attn"] * int(program["num_layers"]))


def _operator_leaves(program: dict, layers: dict, i: int) -> dict:
    """Layer ``i``'s two norms and its operator's leaves, each sliced out of the
    stack it lies in: the norms at ``i``; the operator's at the layer's index among
    the layers of its operator (one stack for all where the program states no
    ``layer_operators``)."""
    ops = operators(program)
    leaves = {k: layers[k][i] for k in NORMS}
    if not program.get("layer_operators"):
        return {**leaves, **{k: layers[k][i] for k in ATTN}}
    at = ops[:i].count(ops[i])
    names, stack = (DELTA, layers["delta"]) if ops[i] == "delta" else (ATTN, layers["attn"])
    return {**leaves, **{k: stack[k][at] for k in names}}


def _head_dim(program: dict) -> int:
    return program.get("qk_head_dim") or program["hidden_size"] // program["num_heads"]


def _forward(program: dict, params: dict, sequences, fetch, routing=None) -> dict:
    """Every sequence (a list of [S] token arrays of any lengths) through the
    layers, each layer's leaves fetched once: its operator's together, then its
    feed-forward one MLP at a time. ``routing``: per sequence, the experts to use
    [layers, S, k], or None for the reference's own. ``hidden`` is the final norm of
    the residual stream behind the last layer."""
    eps, base = float(program["layernorm_epsilon"]), float(program["rotary_base"])
    rotary_dims = int(_head_dim(program) * float(program.get("rotary_pct", 1.0)))
    L, ops = int(program["num_layers"]), operators(program)
    layers, moe = params["layers"], params["moe"]
    first, count = held(program)
    out = {"own": [[] for _ in sequences], "slack": -np.inf, "differ": 0, "pairs": 0}
    with jax.default_matmul_precision("highest"):
        # the rows looked up, not the table: the embedding is never float32 whole
        xs = [_f32(fetch({"rows": params["wte"][np.asarray(t)]}))["rows"] for t in sequences]
        for i in range(L):
            lp = _f32(fetch(_operator_leaves(program, layers, i)))
            for j, x in enumerate(xs):
                xs[j] = (_delta(x, lp, eps=eps, key_heads=int(program["delta_key_heads"]),
                                value_heads=int(program["delta_value_heads"]))
                         if ops[i] == "delta"
                         else _attend(x, lp, eps=eps, base=base, rotary_dims=rotary_dims))
            h2s = [_rms(x, lp["ln2_scale"], eps) for x in xs]
            router = _f32(fetch({"gate": moe["gate"][i]}))
            routes = []
            for j, h2 in enumerate(h2s):
                route = _route(program, h2 @ router["gate"],
                               None if routing is None else routing[j][i])
                routes.append(route)
                out["own"][j].append(route["own"])
                out["slack"] = max(out["slack"], route["slack"])
                out["differ"] += route["differ"]
                out["pairs"] += h2.shape[0]
            for e in range(count):  # bank e is expert first + e
                w = _f32(fetch({k: v[i, e] for k, v in moe["experts"].items()}))
                for j in range(len(xs)):
                    xs[j] = xs[j] + _gated_mlp(h2s[j], w["wg"], w["wi"], w["wo"],
                                               routes[j]["mix"][:, first + e])
            w = _f32(fetch({k: v[i] for k, v in moe["shared"].items()}))
            for j in range(len(xs)):
                xs[j] = xs[j] + _gated_mlp(h2s[j], w["wg"], w["wi"], w["wo"],
                                           jax.nn.sigmoid(h2s[j] @ w["w_gate"]))
        top = _f32(fetch({"lnf_scale": params["lnf_scale"]}))
        out["hidden"] = [_rms(x, top["lnf_scale"], eps) for x in xs]
    out["own"] = [np.stack(o) for o in out["own"]]
    return out


def _head_logits(params, fetch, hidden):
    """hidden [n, d] through the head, ``HEAD_BLOCK`` columns cast at a time."""
    head = params["lm_head"]
    parts = []
    for lo in range(0, head.shape[1], HEAD_BLOCK):
        block = _f32(fetch({"lm_head": head[:, lo:lo + HEAD_BLOCK]}))["lm_head"]
        parts.append(hidden @ block)
    return jnp.concatenate(parts, axis=-1)


def routed_passes(program: dict, params: dict, sequences, rows, *, fetch, routing=None) -> dict:
    """Several sequences in ONE pass over the layers (every expert is fetched once
    for all of them): ``sequences`` a list of [S_j] tokens, ``rows`` the positions
    wanted of each, ``routing`` a list of [layers, S_j, k] or None -> ``logits`` a
    list of [len(rows_j), vocab], ``own`` a list, ``slack`` the largest over all of
    them, ``differ`` the share over all (layer, token) pairs."""
    f = _forward(program, params, [np.asarray(t) for t in sequences], fetch,
                 None if routing is None else [np.asarray(r) for r in routing])
    with jax.default_matmul_precision("highest"):
        logits = [np.asarray(_head_logits(params, fetch, x[jnp.asarray(r)]))
                  for x, r in zip(f["hidden"], rows)]
    return {"logits": logits, "own": f["own"], "slack": f["slack"],
            "differ": f["differ"] / max(f["pairs"], 1)}


def routed_pass(program: dict, params: dict, tokens, rows, *, fetch, routing=None) -> dict:
    """One sequence's float32 ``logits`` [len(rows), vocab] at ``rows`` under
    ``routing`` ([layers, S, k]; None: the reference routes for itself), the
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
    architecture's loss has no other term: ``moe_aux_coeff`` is 0)."""
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
    """A delta layer's operator: ``W_in`` d x (2 Hk D + 2 Hv D), ``W_ba`` d x 2 Hv,
    ``W_out`` Hv D x d (+ K x (2 Hk + Hv) D taps, A_log, dt_bias and the gated norm's
    scale, in no matmul). An attention layer's: W_q d x 2 H D (query | gate), W_k,
    W_v d x Hkv D, W_o H D x d (+ two head norms). Every layer: the router d x E, the
    HELD gated experts, the shared expert and its gate vector. A token multiplies
    through every layer's operator, router, shared expert and gate, the head, and, of
    the ``moe_top_k`` experts a layer chooses for it, those that are held:
    ``moe_top_k`` x held / ``num_experts`` of them on average. The shared expert
    counts OUTSIDE the experts: every token reads it."""
    d, L, V = program["hidden_size"], program["num_layers"], program["vocab_size"]
    H, Hkv, D = program["num_heads"], program["num_kv_heads"], _head_dim(program)
    f, E, k = program["intermediate_size"], program["num_experts"], program["moe_top_k"]
    ops = operators(program)
    n_delta, n_attn = ops.count("delta"), ops.count("attn")
    Hk, Hv = program.get("delta_key_heads") or 0, program.get("delta_value_heads") or 0
    Dd, K = program.get("delta_head_dim") or 0, program.get("conv_kernel") or 0
    _, count = held(program)
    attention = 2 * d * H * D + 2 * d * Hkv * D + H * D * d
    delta = d * (2 * Hk + 2 * Hv) * Dd + d * 2 * Hv + Hv * Dd * d
    expert = 3 * d * f
    shared = 3 * d * program["moe_shared_size"] + d
    outside = n_attn * attention + n_delta * delta + L * (d * E + shared) + d * V
    small = (n_attn * 2 * D + n_delta * (K * (2 * Hk + Hv) * Dd + 2 * Hv + Dd)
             + L * 2 * d + d)  # head norms, taps, A_log, dt_bias, gated norm, layer norms, final
    return {
        "matmul_attention_per_layer": attention,
        "matmul_delta_per_layer": delta,
        "delta_layers": n_delta,
        "attn_layers": n_attn,
        "matmul_per_expert": expert,
        "matmul_outside_experts": outside,
        "routed_layers": L,
        "experts_held": count,
        "held_pairs_per_token_per_layer": k * count / E,
        "matmul_on_token_path": outside + L * k * count * expert // E,
        "total": outside + L * count * expert + V * d + small,
    }
