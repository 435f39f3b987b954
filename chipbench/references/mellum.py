"""The plain reference of the ``mellum`` architecture as
JetBrains/Mellum2-12B-A2.5B-Instruct configures it: its forward pass and its
language-model loss in straightforward float32 ``jax.numpy`` — one sequence, one
layer and ONE EXPERT at a time, the causal (or windowed) score matrix taken
``QUERY_BLOCK`` whole rows and one K/V head's group of query heads at a time
(12,000 positions fit beside the served model), the experts ``ROW_BLOCK`` rows at
a time, the head ``HEAD_BLOCK`` columns at a time: no cache, no ring, no chunk, no
kernel, no sort, no grouped matmul, no scan over layers — and its parameter
counts. The protocol is stated in ``references/__init__.py``; it shares no code
with ``deepspeed_tpu/``.

The block (each symbol a key of the published ``config.json``): ``x += Attn(RMSNorm(x));
x += MoE(RMSNorm(x))``, RMSNorm with a scale only, no bias anywhere, a final
RMSNorm, an untied head. Every layer is routed.

*Attention, H query heads and Hkv key/value heads of width D* (H x D need not be
the hidden size). ``q = h W_q`` -> [H, D], ``k = h W_k``, ``v = h W_v`` -> [Hkv,
D]; ``q = RMSNorm_D(q) g_q``, ``k = RMSNorm_D(k) g_k`` on every head by itself, one
[D] scale each for all heads; rotary on q and k at absolute positions, half-split
pairing (dimension i with i + D / 2), BY THE LAYER'S KIND (``rotary_by_kind``: the
published ``rope_parameters`` has one block a kind). A layer is SLIDING or FULL
(``local_attn_layers``: the published ``layer_types``):

- sliding: ``inv_freq_i = base^(-2i / D)``; key j visible to query i iff 0 <= i - j <
  ``local_attn_window``;
- full: YaRN's blend (Peng et al. 2023, as ``transformers`` computes it). With ``d(n) =
  D ln(original_max_position_embeddings / (2 pi n)) / (2 ln base)``, ``low =
  floor(d(beta_fast))``, ``high = ceil(d(beta_slow))`` (clipped to 0 .. D - 1), ``r_i =
  clip((i - low) / (high - low), 0, 1)``: ``inv_freq_i = (1 - r_i) base^(-2i / D) + r_i
  base^(-2i / D) / factor``, and cos and sin times ``attention_factor`` (0.1 ln(factor) +
  1 where not stated), so a full layer's scores carry that factor squared; every key j
  <= i visible.

Query head i attends K/V head i // (H / Hkv); scale D^-1/2; the heads' outputs
through ``W_o``.

*Routed feed-forward.* ``p = softmax(h W_r)`` over ALL ``num_experts`` in float32;
the ``moe_top_k`` largest kept and, with ``moe_norm_topk_prob``, renormalised to sum
1; ``y = sum_e p_e W_down_e (silu(W_gate_e h) * W_up_e h)``. No shared expert, no
leading dense layer, no selection bias, dropless.

Departures from the published description, each stated in the configuration's file
under ``assumed``: the per-head q/k RMSNorm (``model_type: mellum`` is in no installed
``transformers``; the config's key set is Qwen3-MoE's, whose attention has it); no
multi-token-prediction head (``described_as`` lists one, the ``config`` has no key for
one, and the ``config`` is trusted).

Beyond the protocol, for the check of a routed model (as ``olmoe.py`` and
``exaone_moe.py``): ``routed_passes`` can be given the experts the SYSTEM chose
(``routing`` [layers, tokens, k]); each token then goes through those, weighted by
the reference's own float32 probabilities, and the pass reports ``slack`` (the
largest router logit left out minus the smallest chosen, over the standard
deviation of the layer's logits) and on how many (layer, token) pairs the two sets
``differ``.

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
    "layernorm_epsilon": ANY, "rotary_base": ANY, "rotary_by_kind": ANY,
    "local_attn_window": ANY, "local_attn_layers": ANY,
    "num_experts": ANY, "moe_top_k": ANY, "moe_norm_topk_prob": (False, True),
    # what makes the block this architecture's, each at the one value this file implements
    "pos_emb": ("rotary",), "tie_embeddings": (False,), "use_bias": (False,),
    "norm_kind": ("rms",), "activation": ("swiglu",), "qk_norm": ("head",),
    "moe_every": (1,), "moe_routing": ("dropless",), "moe_aux_coeff": (0.0,),
    # how the SYSTEM attends in a decode step; nothing of the model, so nothing here reads it
    "decode_attn": ("xla",),
}
QUERY_BLOCK = 512  # queries a score matrix is taken for at a time (each row's softmax whole)
ROW_BLOCK = 4096  # rows an expert is taken for at a time
HEAD_BLOCK = 8192  # columns of the head cast to float32 at a time
ATTENTION = ("ln1_scale", "ln2_scale", "wq", "wk", "wv", "wo", "q_norm_scale", "k_norm_scale")


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary_of(program: dict, window: bool) -> tuple:
    """The rotary a layer of one kind states, as a hashable tuple: ("plain", base) or
    ("yarn", base, factor, original context, beta_fast, beta_slow, attention factor,
    truncate). A kind that ``rotary_by_kind`` leaves out turns by ``rotary_base``."""
    spec = (program.get("rotary_by_kind") or {}).get("window" if window else "whole")
    if spec is None:
        return ("plain", float(program["rotary_base"]))
    if spec.get("type", "plain") == "plain":
        return ("plain", float(spec["base"]))
    factor = float(spec["factor"])
    stated = spec.get("attention_factor")
    scale = float(stated) if stated is not None else (
        0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0)
    return ("yarn", float(spec["base"]), factor,
            float(spec["original_max_position_embeddings"]), float(spec.get("beta_fast", 32)),
            float(spec.get("beta_slow", 1)), scale, bool(spec.get("truncate", True)))


def inv_freq(rotary: tuple, D: int):
    """-> (inv_freq [D / 2] float32, the factor on cos and sin), the equations of the
    module docstring written out."""
    i = jnp.arange(0, D // 2, dtype=jnp.float32)
    plain = rotary[1] ** (-2.0 * i / D)
    if rotary[0] == "plain":
        return plain, 1.0
    _, base, factor, span, beta_fast, beta_slow, scale, truncate = rotary
    d = lambda n: D * math.log(span / (2 * math.pi * n)) / (2 * math.log(base))  # noqa: E731
    low, high = d(beta_fast), d(beta_slow)
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, D - 1)
    if low == high:
        high += 0.001
    r = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (1.0 - r) * plain + r * plain / factor, scale


def _rotary(x, rotary):
    """x [S, heads, D]: dimension i rotated with i + D / 2 by position x inv_freq_i."""
    S, D = x.shape[0], x.shape[-1]
    freq, scale = inv_freq(rotary, D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None, :]  # [S, D / 2]
    cos, sin = scale * jnp.cos(ang)[:, None, :], scale * jnp.sin(ang)[:, None, :]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@partial(jax.jit, static_argnames=("eps", "rotary", "window"))
def _attend(x, lp, gate, *, eps, rotary, window):
    """x [S, d] -> (x after the attention residual, the normalised input of the
    feed-forward, the router's logits). ``window``: 0 for a full layer."""
    S = x.shape[0]
    h = _rms(x, lp["ln1_scale"], eps)
    q = _rms(jnp.einsum("sd,dhk->shk", h, lp["wq"]), lp["q_norm_scale"], eps)  # [S, H, D]
    k = _rms(jnp.einsum("sd,dhk->shk", h, lp["wk"]), lp["k_norm_scale"], eps)  # [S, Hkv, D]
    v = jnp.einsum("sd,dhk->shk", h, lp["wv"])
    q, k = _rotary(q, rotary), _rotary(k, rotary)
    H, Hkv, D = q.shape[1], k.shape[1], q.shape[2]
    q = q.reshape(S, Hkv, H // Hkv, D)  # query head i with K/V head i // (H / Hkv)
    blocks = []  # QUERY_BLOCK queries at a time against the keys they can see
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, S)
        first = max(0, lo - window + 1) if window else 0  # no key before it is visible
        rows, cols = jnp.arange(lo, hi)[:, None], jnp.arange(first, hi)[None, :]
        seen = cols <= rows
        if window:
            seen = seen & (rows - cols < window)

        def group(qkv):  # one K/V head and its query heads: [q, g, D], [s, D], [s, D]
            qg, kg, vg = qkv
            scores = jnp.einsum("qgk,sk->gqs", qg, kg) / math.sqrt(D)
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("gqs,sk->qgk", probs, vg)

        out = jax.lax.map(group, (q[lo:hi].transpose(1, 0, 2, 3),
                                  k[first:hi].transpose(1, 0, 2), v[first:hi].transpose(1, 0, 2)))
        blocks.append(out.transpose(1, 0, 2, 3).reshape(hi - lo, H, D))
    attn = jnp.concatenate(blocks, axis=0)
    x = x + jnp.einsum("qhk,hkd->qd", attn, lp["wo"])
    h2 = _rms(x, lp["ln2_scale"], eps)
    return x, h2, h2 @ gate


@jax.jit
def _expert_rows(h2, wg, wi, wo, share):
    return share[:, None] * ((jax.nn.silu(h2 @ wg) * (h2 @ wi)) @ wo)


def _expert(h2, w, share):
    """One gated expert on every token of h2 [S, d], ``ROW_BLOCK`` rows at a time,
    weighted by ``share`` [S]: the token's weight for it (zero where it was not routed
    to it)."""
    return jnp.concatenate([_expert_rows(h2[lo:lo + ROW_BLOCK], w["wg"], w["wi"], w["wo"],
                                         share[lo:lo + ROW_BLOCK])
                            for lo in range(0, h2.shape[0], ROW_BLOCK)], axis=0)


def _route(program: dict, logits, chosen):
    """Router logits [S, E] (and, or None, the experts someone else chose [S, k]) ->
    what the layer needs and what the check reports. ``mix`` [S, E]: every chosen
    expert's weight."""
    k = int(program["moe_top_k"])
    probs = jax.nn.softmax(logits, axis=-1)
    own = jnp.argsort(-probs, axis=-1)[:, :k]
    used = own if chosen is None else jnp.asarray(chosen, jnp.int32)
    weights = jnp.take_along_axis(probs, used, axis=-1)
    if program.get("moe_norm_topk_prob"):
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    S, E = probs.shape
    taken = jnp.zeros((S, E), bool).at[jnp.arange(S)[:, None], used].set(True)
    left_out = jnp.max(jnp.where(taken, -jnp.inf, logits), axis=-1)
    smallest = jnp.min(jnp.where(taken, logits, jnp.inf), axis=-1)
    return {
        "mix": jnp.zeros((S, E), jnp.float32).at[jnp.arange(S)[:, None], used].set(weights),
        "own": np.asarray(own),
        "slack": float(jnp.max(left_out - smallest) / jnp.std(logits)),
        "differ": int(np.sum(np.any(np.sort(np.asarray(own)) != np.sort(np.asarray(used)),
                                    axis=-1))),
    }


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def _forward(program: dict, params: dict, sequences, fetch, routing=None) -> dict:
    """Every sequence (a list of [S] token arrays of any lengths) through the
    layers, each layer's leaves fetched once: its attention leaves and router
    together, then one expert at a time (an expert is cast to float32 once for all
    the sequences). ``routing``: per sequence, the experts to use [layers, S, k], or
    None for the reference's own."""
    eps = float(program["layernorm_epsilon"])
    L, E = int(program["num_layers"]), int(program["num_experts"])
    window = int(program.get("local_attn_window") or 0)
    local = program.get("local_attn_layers") or [0] * L
    layers, moe = params["layers"], params["moe"]
    out = {"own": [[] for _ in sequences], "slack": -np.inf, "differ": 0, "pairs": 0}
    with jax.default_matmul_precision("highest"):
        # the rows looked up, not the table: the embedding is never float32 whole
        xs = [_f32(fetch({"rows": params["wte"][np.asarray(t)]}))["rows"] for t in sequences]
        for i in range(L):
            lp = _f32(fetch({**{k: layers[k][i] for k in ATTENTION}, "gate": moe["gate"][i]}))
            gate = lp.pop("gate")
            routes, h2s = [], []
            for j, x in enumerate(xs):
                xs[j], h2, logits = _attend(
                    x, lp, gate, eps=eps, rotary=rotary_of(program, bool(local[i])),
                    window=window if local[i] else 0)
                r = _route(program, logits, None if routing is None else routing[j][i])
                routes.append(r)
                h2s.append(h2)
                out["own"][j].append(r["own"])
                out["slack"] = max(out["slack"], r["slack"])
                out["differ"] += r["differ"]
                out["pairs"] += logits.shape[0]
            for e in range(E):
                w = _f32(fetch({k: v[i, e] for k, v in moe["experts"].items()}))
                for j in range(len(xs)):
                    xs[j] = xs[j] + _expert(h2s[j], w, routes[j]["mix"][:, e])
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
    wanted of each, ``routing`` a list of [layers, S_j, k] or None -> ``logits`` a list
    of [len(rows_j), vocab], ``own`` a list, ``slack`` the largest over all of them,
    ``differ`` the share over all (layer, token) pairs."""
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
    program's ``moe_aux_coeff`` is 0: the loss has no other term)."""
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
    """A layer: W_q, W_k, W_v, W_o (H x D need not be the hidden size), the router and
    E gated experts of three matrices; two RMSNorms and the two head norms. A token
    multiplies through the attention, the router, ``moe_top_k`` experts and the head;
    the chip holds all E."""
    d, L, V = program["hidden_size"], program["num_layers"], program["vocab_size"]
    H, Hkv, D = program["num_heads"], program["num_kv_heads"], program["qk_head_dim"]
    f, E, k = program["intermediate_size"], program["num_experts"], program["moe_top_k"]
    attention = d * H * D + 2 * d * Hkv * D + H * D * d
    expert = 3 * d * f
    outside = L * (attention + d * E) + d * V
    return {
        "matmul_attention_per_layer": attention,
        "matmul_per_expert": expert,
        "matmul_outside_experts": outside,
        "routed_layers": L,
        "experts_held": E,
        "held_pairs_per_token_per_layer": k,
        "matmul_on_token_path": outside + L * k * expert,
        "total": outside + L * (E * expert + 2 * d + 2 * D) + V * d + d,
    }
