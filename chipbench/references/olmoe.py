"""The plain reference of OLMoE (allenai/OLMoE-1B-7B: ``OlmoeForCausalLM``):
its forward pass and training loss in straightforward float32 ``jax.numpy`` —
one sequence, one layer and ONE EXPERT at a time (a loop over all the experts,
each applied to every token and weighted by a mask that is zero where the token
was not routed to it: no sort, no grouped matmul, no capacity, no cache, no
scan) — and its parameter counts. The protocol is stated in
``references/__init__.py``; it shares no code with ``deepspeed_tpu/``.

The block, as published (``modeling_olmoe.py`` and the OLMoE paper, 2409.02060):
pre-RMSNorm (scale only) before attention and before the feed-forward and a
final RMSNorm; full multi-head attention without biases; RMSNorm with a
learned scale over the WHOLE query and the whole key projection (all heads
together) before the head split and the rotary (``q_norm`` / ``k_norm``); full
rotary, NeoX half-split, base 10000; sequential residual; every layer's
feed-forward a mixture of ``num_experts`` SiLU-gated experts
``down(silu(gate(x)) * up(x))``, the router a bias-free linear map whose
softmax over ALL experts gives the probabilities, the ``moe_top_k`` largest
chosen, their weights the raw probabilities (``norm_topk_prob: false``) or
renormalised to sum to one; untied output head.

Departures from the published model, each because the system under test
differs and the reference is run on the system's own weights and loss:

* The load-balancing term of the training loss. Here, as in the system
  (``deepspeed_tpu/moe/dropless.py::load_balance_loss``), every routed layer
  adds ``E x sum_e(share of the token-expert pairs sent to e x mean router
  probability of e)`` over the tokens of the batch, the layers are SUMMED and
  the sum weighted by ``moe_aux_coeff`` (0.01). OLMoE's training code
  (megablocks) takes the same per-layer term and AVERAGES it over the layers;
  ``transformers``' ``load_balancing_loss_func`` concatenates the layers'
  tokens before taking the two means and does not divide the share by k. So
  this term is ``num_layers`` x the first and about ``num_layers / k`` x the
  second at the same coefficient.
* OLMoE was also trained with a router z-loss (weight 0.001); neither the
  system nor ``transformers``' model has it, and it is not here.
* RMSNorm multiplies by its scale in float32 (``transformers`` casts back to
  the input dtype first; the same in float32).

Beyond the protocol, for the check of a routed model
(``drivers/serve_routed.py``): ``routed_pass`` can be given the experts the
SYSTEM chose (``routing`` [routed layers, tokens, k]); each token then goes
through those experts, weighted by the reference's own float32 probabilities
for them, and the pass reports how far those choices are from the reference's
own router (``slack``: the largest router logit left out minus the smallest
chosen, over the layer's router-logit standard deviation; at most 0 where the
system chose the reference's top k) and on how many (layer, token) pairs the
two sets differ.

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
    "num_experts": ANY, "moe_top_k": ANY, "moe_aux_coeff": ANY,
    "moe_norm_topk_prob": (False, True),
    # what makes the block OLMoE's, each at the one value this file implements
    "pos_emb": ("rotary",), "rotary_pct": (1.0,), "tie_embeddings": (False,),
    "use_bias": (False,), "norm_kind": ("rms",), "qk_norm": (True,),
    "activation": ("swiglu",), "moe_every": (1,), "moe_routing": ("dropless",),
}
ATTENTION = ("ln1_scale", "ln2_scale", "wq", "wk", "wv", "wo", "q_norm_scale", "k_norm_scale")


def _rms(x, scale, eps, axes=-1):
    return x / jnp.sqrt(jnp.mean(x * x, axis=axes, keepdims=True) + eps) * scale


def _rotary(x):
    """x [S, H, Dh]: rotate every head whole, pairing dimension i with
    i + Dh/2 (the NeoX convention)."""
    half = x.shape[-1] // 2
    inv_freq = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@partial(jax.jit, static_argnames=("eps",))
def _attend(x, lp, gate, *, eps):
    """x [S, d] -> (x after the attention residual, the normalised input of the
    feed-forward, the router's logits [S, E])."""
    S = x.shape[0]
    h = _rms(x, lp["ln1_scale"], eps)
    q = jnp.einsum("sd,dhk->shk", h, lp["wq"])
    k = jnp.einsum("sd,dhk->shk", h, lp["wk"])
    v = jnp.einsum("sd,dhk->shk", h, lp["wv"])
    q = _rms(q, lp["q_norm_scale"], eps, axes=(-2, -1))  # over all heads together
    k = _rms(k, lp["k_norm_scale"], eps, axes=(-2, -1))
    q, k = _rotary(q), _rotary(k)
    scores = jnp.einsum("qhk,shk->hqs", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v)
    x = x + jnp.einsum("qhk,hkd->qd", attn, lp["wo"])
    h2 = _rms(x, lp["ln2_scale"], eps)
    return x, h2, h2 @ gate


@jax.jit
def _expert(h2, w, share):
    """One expert on every token of h2 [S, d], weighted by ``share`` [S]: the
    token's weight for this expert, zero where it was not routed to it."""
    return share[:, None] * ((jax.nn.silu(h2 @ w["wg"]) * (h2 @ w["wi"])) @ w["wo"])


def _route(program: dict, logits, chosen):
    """Router logits [S, E] (and, or None, the experts someone else chose
    [S, k]) -> what the layer needs and what the check reports."""
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
        # the two factors of the load-balancing term, over this sequence's tokens
        "pair_share": jnp.mean(taken.astype(jnp.float32), axis=0) / k,
        "mean_prob": jnp.mean(probs, axis=0),
    }


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def _forward(program: dict, params: dict, sequences, fetch, routing=None) -> dict:
    """Every sequence (a list of [S] token arrays of any lengths) through the
    layers, each layer's leaves fetched once: its attention leaves and router
    together, then one expert at a time. ``routing``: per sequence, the experts
    to use [layers, S, k], or None for the reference's own."""
    eps = float(program.get("layernorm_epsilon", 1e-5))
    L, E = int(program["num_layers"]), int(program["num_experts"])
    layers, moe = params["layers"], params["moe"]
    top = _f32(fetch({k: v for k, v in params.items() if k not in ("layers", "moe")}))
    out = {"own": [[] for _ in sequences], "slack": -np.inf, "differ": 0, "pairs": 0,
           "balance": 0.0, "top": top}
    with jax.default_matmul_precision("highest"):
        xs = [top["wte"][jnp.asarray(t)] for t in sequences]
        for i in range(L):
            lp = _f32(fetch({**{k: layers[k][i] for k in ATTENTION}, "gate": moe["gate"][i]}))
            gate = lp.pop("gate")
            routes, h2s = [], []
            for j, x in enumerate(xs):
                xs[j], h2, logits = _attend(x, lp, gate, eps=eps)
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
            # this layer's load-balancing term over ALL the sequences' tokens
            # (they have one length wherever the loss is asked for)
            share = jnp.mean(jnp.stack([r["pair_share"] for r in routes]), axis=0)
            prob = jnp.mean(jnp.stack([r["mean_prob"] for r in routes]), axis=0)
            out["balance"] += float(E * jnp.sum(share * prob))
        out["hidden"] = [_rms(x, top["lnf_scale"], eps) for x in xs]
    out["own"] = [np.stack(o) for o in out["own"]]
    return out


def routed_pass(program: dict, params: dict, tokens, rows, *, fetch, routing=None) -> dict:
    """One sequence's float32 ``logits`` [len(rows), vocab] at ``rows`` under
    ``routing`` ([layers, S, k]; None: the reference routes for itself), the
    reference's ``own`` choices [layers, S, k], the ``slack`` of the routing
    used against the reference's router (module docstring) and the share of
    (layer, token) pairs on which the two sets ``differ``."""
    f = _forward(program, params, [np.asarray(tokens)], fetch,
                 None if routing is None else [np.asarray(routing)])
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(f["hidden"][0][jnp.asarray(rows)] @ f["top"]["lm_head"])
    return {"logits": logits, "own": f["own"][0], "slack": f["slack"],
            "differ": f["differ"] / f["pairs"]}


def logits_at(program: dict, params: dict, tokens, rows, *, fetch, routing=None) -> np.ndarray:
    """Float32 logits [len(rows), vocab] at the given positions."""
    return routed_pass(program, params, tokens, rows, fetch=fetch, routing=routing)["logits"]


def lm_loss(program: dict, params: dict, tokens, *, fetch, routing=None) -> float:
    """Mean next-token cross-entropy of ``tokens`` [S + 1] or [N, S + 1], plus
    ``moe_aux_coeff`` x the load-balancing term (module docstring). ``routing``
    (per sequence [layers, S, k]) holds the experts fixed, as differentiating
    the loss does."""
    tokens = np.asarray(tokens).reshape(-1, np.shape(tokens)[-1])
    f = _forward(program, params, list(tokens[:, :-1]), fetch, routing)
    losses = []
    with jax.default_matmul_precision("highest"):
        for x, labels in zip(f["hidden"], tokens[:, 1:]):
            logits = x @ f["top"]["lm_head"]  # one sequence's [S, vocab] at a time
            picked = jnp.take_along_axis(logits, jnp.asarray(labels)[:, None], axis=-1)[:, 0]
            losses.append(jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked))
    balance = float(program.get("moe_aux_coeff", 0.01)) * f["balance"]
    return float(jnp.mean(jnp.stack(losses))) + balance


def param_counts(program: dict) -> dict:
    """A layer: four attention projections, the router and E gated experts of
    three matrices; two RMSNorms and the two whole-projection norms beside
    them. A token multiplies through the attention, the router, ``moe_top_k``
    experts and the head; the chip holds all E."""
    d, L, V = program["hidden_size"], program["num_layers"], program["vocab_size"]
    f, E, k = program["intermediate_size"], program["num_experts"], program["moe_top_k"]
    expert = 3 * d * f
    outside = 4 * d * d + d * E  # a layer's matmul parameters outside its experts
    return {
        "matmul_per_layer": outside + k * expert,
        "matmul_per_expert": expert,
        "matmul_outside_experts": L * outside + d * V,
        "routed_layers": L,
        "matmul_on_token_path": L * (outside + k * expert) + d * V,
        "total": L * (outside + E * expert + 4 * d) + 2 * V * d + d,
    }
