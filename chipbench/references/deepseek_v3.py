"""The plain reference of the DeepSeek-V3 architecture as
kakaocorp/kanana-2-30b-a3b-instruct-2601 configures it (``model_type:
"deepseek_v3"``, ``DeepseekV3ForCausalLM``; no query compression, one expert
group): its forward pass and language-model loss in straightforward float32
``jax.numpy`` — one sequence, one layer and ONE EXPERT at a time, EXPANDED
attention only (every head's keys and values are made from the latent and the
causal score matrix is taken, ``QUERY_BLOCK`` whole rows at a time: no cache, no absorbed form, no kernel, no
sort, no grouped matmul, no scan) — and its parameter counts. The protocol is
stated in ``references/__init__.py``; it shares no code with ``deepspeed_tpu/``.

The block, as published (``modeling_deepseek_v3.py`` of ``transformers``):
pre-RMSNorm (scale only) before attention and before the feed-forward, a final
RMSNorm, no biases, sequential residual, untied head.

*Attention (multi-head latent attention), H heads.* ``q = h W_q`` -> [H, Dn + Dr]
a token, split into ``q_nope`` [H, Dn] and ``q_pe`` [H, Dr]. ``h W_kv_a`` -> R + Dr
values: ``c = RMSNorm(first R; learned scale)`` and ``k_pe = last Dr``, one for
all heads. Rotary on ``q_pe`` and ``k_pe`` alone, base ``rotary_base``, pairing
dimension 2i with 2i + 1 (``rope_interleave``), no scaling. ``c W_kv_b`` ->
[H, Dn + Dv] = ``k_nope``, ``v``. ``k_h = [k_nope_h, k_pe]``, ``q_h = [q_nope_h,
q_pe_h]``; causal softmax of ``q_h . k_h / sqrt(Dn + Dr)``; the heads' outputs
[H, Dv] through ``W_o``.

*Feed-forward.* The first ``moe_first_dense`` layers: ``down(silu(gate(h)) *
up(h))`` at ``dense_intermediate_size``. Every later layer: ``s = sigmoid(h
W_gate)`` over all experts in float32; the ``moe_top_k`` experts with the
largest ``s + b`` (``e_score_correction_bias``: it selects and does not weigh);
weights ``s_i / (sum of the chosen s + 1e-20) x moe_routed_scale``; experts
``down(silu(gate) * up)`` at ``intermediate_size``; plus, on every token, one
shared gated MLP of width ``moe_shared_size`` (``n_shared_experts x
moe_intermediate_size``); routed + shared.

Departures from the published code: none in the arithmetic. Two in the form,
neither visible in a logit: (1) ``transformers`` rotates after de-interleaving
q_pe and k_pe into [even dims | odd dims], so its rotated vectors are a fixed
permutation of the ones here; q_pe . k_pe is the same sum. (2) RMSNorm
multiplies by its scale in float32 (``transformers`` casts to the input dtype
first; the same in float32). The published model has no load-balancing term in
its loss (``topk_method: "noaux_tc"``: the bias is moved by the load, outside the
loss), so ``moe_aux_coeff`` is covered at 0.0 alone.
``tests/test_kanana.py::test_reference_agrees_with_transformers`` holds this
file to ``DeepseekV3ForCausalLM`` on copied weights.

Beyond the protocol, for the check of a routed model: ``routed_pass`` can be
given the experts the SYSTEM chose (``routing`` [routed layers, tokens, k]);
each token then goes through those experts, weighted by the reference's own
float32 scores for them, and the pass reports how far those choices are from
the reference's own router: ``slack``, the largest SELECTION score ``s + b``
(the quantity the top-k is taken of) left out minus the smallest chosen, over
the standard deviation of the layer's selection scores (at most 0 where the
system chose the reference's top k), and on how many (layer, token) pairs the
two sets ``differ``.

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
    "hidden_size": ANY, "intermediate_size": ANY, "dense_intermediate_size": ANY,
    "layernorm_epsilon": ANY, "rotary_base": ANY,
    "qk_head_dim": ANY, "v_head_dim": ANY, "kv_lora_rank": ANY, "qk_rope_head_dim": ANY,
    "num_experts": ANY, "moe_top_k": ANY, "moe_routed_scale": ANY, "moe_shared_size": ANY,
    "moe_first_dense": ANY, "moe_norm_topk_prob": (False, True),
    # what makes the block this architecture's, each at the one value this file implements
    "pos_emb": ("rotary",), "rotary_interleaved": (True,), "tie_embeddings": (False,),
    "use_bias": (False,), "norm_kind": ("rms",), "activation": ("swiglu",),
    "moe_every": (1,), "moe_routing": ("dropless",), "moe_score_fn": ("sigmoid",),
    "moe_select_bias": (True,), "moe_aux_coeff": (0.0,),
    # how the SYSTEM attends in a decode step; nothing of the model, so nothing here reads it
    "decode_attn": ("xla",),
}
# The causal score matrix is taken QUERY_BLOCK queries at a time (every key up to
# the block's end in one piece: each row's softmax is whole, so the arithmetic is
# the unblocked one); 32 heads x 2,500 x 2,500 float32 scores and their softmax
# would not fit beside the served model.
QUERY_BLOCK = 512
ATTENTION = ("ln1_scale", "ln2_scale", "wq", "wkv_a", "kv_norm_scale", "wkv_b", "wo")


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x, base):
    """x [S, ..., D]: rotate the pairs (2i, 2i + 1) of the last axis by
    position x base^(-2i / D)."""
    S, D = x.shape[0], x.shape[-1]
    inv_freq = base ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (D // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


@partial(jax.jit, static_argnames=("eps", "base", "rank", "rope"))
def _attend(x, lp, *, eps, base, rank, rope):
    """x [S, d] -> (x after the attention residual, the normalised input of the
    feed-forward)."""
    S = x.shape[0]
    h = _rms(x, lp["ln1_scale"], eps)
    q = jnp.einsum("sd,dhk->shk", h, lp["wq"])  # [S, H, Dn + Dr]
    nope = q.shape[-1] - rope
    kv = h @ lp["wkv_a"]  # [S, R + Dr]
    c = _rms(kv[:, :rank], lp["kv_norm_scale"], eps)
    k_pe = _rotary(kv[:, rank:], base)  # [S, Dr], every head's
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], base)], axis=-1)
    expanded = jnp.einsum("sr,rhk->shk", c, lp["wkv_b"])  # [S, H, Dn + Dv]
    k = jnp.concatenate(
        [expanded[..., :nope], jnp.broadcast_to(k_pe[:, None, :], (S, q.shape[1], rope))], axis=-1)
    v = expanded[..., nope:]
    blocks = []  # QUERY_BLOCK queries at a time against the keys up to the block's end
    for lo in range(0, S, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, S)
        scores = jnp.einsum("qhk,shk->hqs", q[lo:hi], k[:hi]) / math.sqrt(q.shape[-1])
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        blocks.append(jnp.einsum("hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v[:hi]))
    attn = jnp.concatenate(blocks, axis=0)
    x = x + jnp.einsum("qhk,hkd->qd", attn, lp["wo"])
    return x, _rms(x, lp["ln2_scale"], eps)


@jax.jit
def _gated_mlp(h2, wg, wi, wo, share):
    """One gated MLP on every token of h2 [S, d], weighted by ``share`` [S]: an
    expert with the token's weight for it (zero where it was not routed to
    it), or the shared expert / a dense layer with ones."""
    return share[:, None] * ((jax.nn.silu(h2 @ wg) * (h2 @ wi)) @ wo)


def _route(program: dict, logits, bias, chosen):
    """Router logits [S, E], the selection bias [E] (and, or None, the experts
    someone else chose [S, k]) -> what the layer needs and what the check
    reports."""
    k = int(program["moe_top_k"])
    scores = jax.nn.sigmoid(logits)
    select = scores + bias
    own = jnp.argsort(-select, axis=-1)[:, :k]
    used = own if chosen is None else jnp.asarray(chosen, jnp.int32)
    weights = jnp.take_along_axis(scores, used, axis=-1)
    if program.get("moe_norm_topk_prob"):
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
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


def _forward(program: dict, params: dict, sequences, fetch, routing=None) -> dict:
    """Every sequence (a list of [S] token arrays of any lengths) through the
    layers, each layer's leaves fetched once: its attention leaves (and router)
    together, then its feed-forward one MLP at a time. ``routing``: per
    sequence, the experts to use [routed layers, S, k], or None for the
    reference's own."""
    eps, base = float(program["layernorm_epsilon"]), float(program["rotary_base"])
    L, E, lead = int(program["num_layers"]), int(program["num_experts"]), int(
        program["moe_first_dense"])
    layers, moe = params["layers"], params["moe"]
    attend = partial(_attend, eps=eps, base=base, rank=int(program["kv_lora_rank"]),
                     rope=int(program["qk_rope_head_dim"]))
    out = {"own": [[] for _ in sequences], "slack": -np.inf, "differ": 0, "pairs": 0}
    with jax.default_matmul_precision("highest"):
        # the rows looked up, not the table: the embedding is never float32 whole
        xs = [_f32(fetch({"rows": params["wte"][np.asarray(t)]}))["rows"] for t in sequences]
        for i in range(L):
            lp = _f32(fetch({k: layers[k][i] for k in ATTENTION}))
            h2s = []
            for j, x in enumerate(xs):
                xs[j], h2 = attend(x, lp)
                h2s.append(h2)
            ones = [jnp.ones((h2.shape[0],), jnp.float32) for h2 in h2s]
            if i < lead:
                w = _f32(fetch({k: v[i] for k, v in params["dense_ffn"].items()}))
                for j in range(len(xs)):
                    xs[j] = xs[j] + _gated_mlp(h2s[j], w["wg"], w["wi"], w["wo_mlp"], ones[j])
                continue
            r = i - lead  # this layer's index in the routed stacks
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
            for e in range(E):
                w = _f32(fetch({k: v[r, e] for k, v in moe["experts"].items()}))
                for j in range(len(xs)):
                    xs[j] = xs[j] + _gated_mlp(h2s[j], w["wg"], w["wi"], w["wo"],
                                               routes[j]["mix"][:, e])
            w = _f32(fetch({k: v[r] for k, v in moe["shared"].items()}))
            for j in range(len(xs)):
                xs[j] = xs[j] + _gated_mlp(h2s[j], w["wg"], w["wi"], w["wo"], ones[j])
        top = _f32(fetch({"lnf_scale": params["lnf_scale"]}))
        out["hidden"] = [_rms(x, top["lnf_scale"], eps) for x in xs]
    out["own"] = [np.stack(o) for o in out["own"]]
    return out


def _head(params, fetch):
    return _f32(fetch({"lm_head": params["lm_head"]}))["lm_head"]


def routed_passes(program: dict, params: dict, sequences, rows, *, fetch, routing=None) -> dict:
    """``routed_pass`` of several sequences in ONE pass over the layers (every
    expert is fetched once for all of them): ``sequences`` a list of [S_j]
    tokens, ``rows`` the positions wanted of each, ``routing`` a list of
    [routed layers, S_j, k] or None -> ``logits`` a list of [len(rows_j), vocab],
    ``own`` a list, ``slack`` the largest over all of them, ``differ`` the share
    over all (layer, token) pairs."""
    f = _forward(program, params, [np.asarray(t) for t in sequences], fetch,
                 None if routing is None else [np.asarray(r) for r in routing])
    head = _head(params, fetch)
    with jax.default_matmul_precision("highest"):
        logits = [np.asarray(x[jnp.asarray(r)] @ head) for x, r in zip(f["hidden"], rows)]
    return {"logits": logits, "own": f["own"], "slack": f["slack"],
            "differ": f["differ"] / f["pairs"]}


def routed_pass(program: dict, params: dict, tokens, rows, *, fetch, routing=None) -> dict:
    """One sequence's float32 ``logits`` [len(rows), vocab] at ``rows`` under
    ``routing`` ([routed layers, S, k]; None: the reference routes for itself),
    the reference's ``own`` choices [routed layers, S, k], the ``slack`` of the
    routing used against the reference's router (module docstring) and the
    share of (layer, token) pairs on which the two sets ``differ``."""
    out = routed_passes(program, params, [tokens], [rows], fetch=fetch,
                        routing=None if routing is None else [routing])
    return {**out, "logits": out["logits"][0], "own": out["own"][0]}


def logits_at(program: dict, params: dict, tokens, rows, *, fetch, routing=None) -> np.ndarray:
    """Float32 logits [len(rows), vocab] at the given positions."""
    return routed_pass(program, params, tokens, rows, fetch=fetch, routing=routing)["logits"]


def lm_loss(program: dict, params: dict, tokens, *, fetch, routing=None) -> float:
    """Mean next-token cross-entropy of ``tokens`` [S + 1] or [N, S + 1] (the
    architecture's loss has no other term). ``routing`` (per sequence [routed
    layers, S, k]) holds the experts fixed, as differentiating the loss does."""
    tokens = np.asarray(tokens).reshape(-1, np.shape(tokens)[-1])
    f = _forward(program, params, list(tokens[:, :-1]), fetch, routing)
    head = _head(params, fetch)
    losses = []
    with jax.default_matmul_precision("highest"):
        for x, labels in zip(f["hidden"], tokens[:, 1:]):
            logits = x @ head  # one sequence's [S, vocab] at a time
            picked = jnp.take_along_axis(logits, jnp.asarray(labels)[:, None], axis=-1)[:, 0]
            losses.append(jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked))
    return float(jnp.mean(jnp.stack(losses)))


def param_counts(program: dict) -> dict:
    """Attention: W_q, W_kv_a, W_kv_b, W_o. A leading layer adds a gated MLP of
    ``dense_intermediate_size``; a routed one the router, E gated experts and
    the shared expert. A token multiplies through the attention of every layer,
    the dense MLPs, the routers, the shared experts, ``moe_top_k`` experts a
    routed layer and the head; the chip holds all E. The shared expert and the
    leading layers count OUTSIDE the experts: every token reads them."""
    d, L, V = program["hidden_size"], program["num_layers"], program["vocab_size"]
    H, Dqk, Dv = program["num_heads"], program["qk_head_dim"], program["v_head_dim"]
    R, Dr = program["kv_lora_rank"], program["qk_rope_head_dim"]
    f, E, k = program["intermediate_size"], program["num_experts"], program["moe_top_k"]
    lead, routed = program["moe_first_dense"], L - program["moe_first_dense"]
    attention = d * H * Dqk + d * (R + Dr) + R * H * (Dqk - Dr + Dv) + H * Dv * d
    expert = 3 * d * f
    dense = 3 * d * program["dense_intermediate_size"]
    shared = 3 * d * program["moe_shared_size"]
    outside = lead * (attention + dense) + routed * (attention + d * E + shared) + d * V
    norms = L * (2 * d + R) + d
    return {
        "matmul_attention_per_layer": attention,
        "matmul_per_expert": expert,
        "matmul_outside_experts": outside,
        "routed_layers": routed,
        "matmul_on_token_path": outside + routed * k * expert,
        "total": outside + routed * (E * expert + E) + V * d + norms,
    }
