"""The plain reference of the ``exaone_moe`` architecture as
LGAI-EXAONE/K-EXAONE-236B-A23B configures it: its forward pass, its
language-model loss and its multi-token-prediction module in straightforward
float32 ``jax.numpy`` — one sequence, one layer and ONE EXPERT at a time, the
causal (or windowed) score matrix taken ``QUERY_BLOCK`` whole rows and one K/V
head's group of query heads at a time, the feed-forwards ``ROW_BLOCK`` rows at a
time, the head ``HEAD_BLOCK`` columns at a time: no cache, no ring, no kernel, no
sort, no grouped matmul, no scan over layers — and its parameter counts. The
protocol is stated in ``references/__init__.py``; it shares no code with
``deepspeed_tpu/``.

The block (each symbol a key of the published ``config.json``; the attention
sublayer is ``Exaone4Attention`` of ``transformers``, the router and the experts
carry DeepSeek-V3's keys): RMSNorm (scale only) before attention and before the
feed-forward, a final RMSNorm, no biases, sequential residual, untied head.

*Attention, H query heads and Hkv key/value heads of width D.* ``q = h W_q`` ->
[H, D], ``k = h W_k``, ``v = h W_v`` -> [Hkv, D]; ``q = RMSNorm_D(q) g_q``, ``k =
RMSNorm_D(k) g_k`` on every head by itself, one [D] scale each for all heads. A
layer is SLIDING or FULL (``local_attn_layers``: the published ``layer_types``).
Sliding: rotary on q and k at absolute positions, half rotation (dimension i with
i + D / 2), base ``rotary_base``, all D; key j visible to query i iff j <= i and
i - j < ``local_attn_window``. Full: NO rotary at all (``rotary_layers``; "global
NoPE"), every key j <= i visible. Query head i attends K/V head i // (H / Hkv);
scale D^-1/2; the heads' outputs through ``W_o``.

*Feed-forward.* The first ``moe_first_dense`` layers: ``down(silu(gate(h)) *
up(h))`` at ``dense_intermediate_size``. Every later layer: ``s = sigmoid(h
W_r)`` over ALL ``num_experts`` in float32; the ``moe_top_k`` experts with the
largest ``s + b`` (the selection bias: it selects and does not weigh; one group,
no group limit); weights ``s_e / (sum of the chosen s + 1e-20) x
moe_routed_scale``; plus, on every token, one shared gated MLP of
``moe_shared_size``. **The share** (``moe_experts_held`` = (first, count)): the
parameter tree holds the banks of experts first ... first + count - 1 alone,
the choices are made over all ``num_experts`` and the sum runs over the chosen
experts THAT ARE HELD; what the others would add is another chip's. ``None``:
all of them (the uncut layer).

*The multi-token-prediction module* (``mtp_layers`` = 1; DeepSeek-V3 section 2.2,
``mtp_logits_at``): ``h' = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(x_i)] W_eh`` with
x_i the residual stream behind the last layer BEFORE the final norm, one block of
the FULL kind (no window, no rotary) with the routed feed-forward, its own final
norm, the model's embedding and head -> logits for t_{i+2}.

Assumed, where the published ``config.json`` does not settle it (the
configuration file lists each): pre-norm placement; the selection bias present;
the module's join order (the embedding's half first) and its feed-forward's kind.

Beyond the protocol, for the check of a routed model (as ``deepseek_v3.py``):
``routed_passes`` can be given the experts the SYSTEM chose (``routing`` [routed
layers, tokens, k], over ALL the router's experts); each token then goes through
those of them that are held, weighted by the reference's own float32 scores, and
the pass reports ``slack`` (the largest selection score left out minus the
smallest chosen, over the standard deviation of the layer's selection scores)
and on how many (layer, token) pairs the two sets ``differ``.

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
    "local_attn_window": ANY, "local_attn_layers": ANY, "rotary_layers": ANY,
    "num_experts": ANY, "moe_top_k": ANY, "moe_routed_scale": ANY, "moe_shared_size": ANY,
    "moe_first_dense": ANY, "moe_norm_topk_prob": (False, True), "moe_experts_held": ANY,
    "mtp_layers": (0, 1),
    # what makes the block this architecture's, each at the one value this file implements
    "pos_emb": ("rotary",), "tie_embeddings": (False,), "use_bias": (False,),
    "norm_kind": ("rms",), "activation": ("swiglu",), "qk_norm": ("head",),
    "moe_every": (1,), "moe_routing": ("dropless",), "moe_score_fn": ("sigmoid",),
    "moe_select_bias": (True,), "moe_aux_coeff": (0.0,),
    # how the SYSTEM attends in a decode step; nothing of the model, so nothing here reads it
    "decode_attn": ("xla",),
}
QUERY_BLOCK = 512  # queries a score matrix is taken for at a time (each row's softmax whole)
ROW_BLOCK = 2048  # rows a feed-forward is taken for at a time (a 9,000-row dense MLP's
# 18,432-wide float32 activations would not fit beside the served model)
HEAD_BLOCK = 4096  # columns of the head cast to float32 at a time
ATTENTION = ("ln1_scale", "ln2_scale", "wq", "wk", "wv", "wo", "q_norm_scale", "k_norm_scale")


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


@partial(jax.jit, static_argnames=("eps", "base", "window", "rotary"))
def _attend(x, lp, *, eps, base, window, rotary):
    """x [S, d] -> (x after the attention residual, the normalised input of the
    feed-forward). ``window``: 0 for a full layer; ``rotary``: whether q and k
    are rotated."""
    S = x.shape[0]
    h = _rms(x, lp["ln1_scale"], eps)
    q = _rms(jnp.einsum("sd,dhk->shk", h, lp["wq"]), lp["q_norm_scale"], eps)  # [S, H, D]
    k = _rms(jnp.einsum("sd,dhk->shk", h, lp["wk"]), lp["k_norm_scale"], eps)  # [S, Hkv, D]
    v = jnp.einsum("sd,dhk->shk", h, lp["wv"])
    if rotary:
        q, k = _rotary(q, base), _rotary(k, base)
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
    return x, _rms(x, lp["ln2_scale"], eps)


@jax.jit
def _gated_rows(h2, wg, wi, wo, share):
    return share[:, None] * ((jax.nn.silu(h2 @ wg) * (h2 @ wi)) @ wo)


def _gated_mlp(h2, wg, wi, wo, share):
    """One gated MLP on every token of h2 [S, d], ``ROW_BLOCK`` rows at a time,
    weighted by ``share`` [S]: an expert with the token's weight for it (zero
    where it was not routed to it), or the shared expert / a dense layer with
    ones."""
    return jnp.concatenate([_gated_rows(h2[lo:lo + ROW_BLOCK], wg, wi, wo,
                                        share[lo:lo + ROW_BLOCK])
                            for lo in range(0, h2.shape[0], ROW_BLOCK)], axis=0)


def _route(program: dict, logits, bias, chosen):
    """Router logits [S, E], the selection bias [E] (and, or None, the experts
    someone else chose [S, k]) -> what the layer needs and what the check
    reports. ``mix`` [S, E]: every chosen expert's weight, held here or not."""
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


def held(program: dict) -> tuple:
    """(first, count) of the experts the parameter tree holds banks of."""
    first, count = program.get("moe_experts_held") or (0, int(program["num_experts"]))
    return int(first), int(count)


def _routed_ffn(program, moe, r, xs, h2s, routing, out, fetch):
    """Routed layer ``r`` of the stacks ``moe`` on every sequence: the router
    over all experts, the held experts one at a time, the shared expert."""
    first, count = held(program)
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
    for e in range(count):  # bank e is expert first + e
        w = _f32(fetch({k: v[r, e] for k, v in moe["experts"].items()}))
        for j in range(len(xs)):
            xs[j] = xs[j] + _gated_mlp(h2s[j], w["wg"], w["wi"], w["wo"],
                                       routes[j]["mix"][:, first + e])
    w = _f32(fetch({k: v[r] for k, v in moe["shared"].items()}))
    for j in range(len(xs)):
        xs[j] = xs[j] + _gated_mlp(h2s[j], w["wg"], w["wi"], w["wo"],
                                   jnp.ones((h2s[j].shape[0],), jnp.float32))


def _forward(program: dict, params: dict, sequences, fetch, routing=None) -> dict:
    """Every sequence (a list of [S] token arrays of any lengths) through the
    layers, each layer's leaves fetched once: its attention leaves together, then
    its feed-forward one MLP at a time. ``routing``: per sequence, the experts to
    use [routed layers, S, k], or None for the reference's own. ``last`` is the
    residual stream behind the last layer, ``hidden`` its final norm."""
    eps, base = float(program["layernorm_epsilon"]), float(program["rotary_base"])
    L, lead = int(program["num_layers"]), int(program["moe_first_dense"])
    window = int(program.get("local_attn_window") or 0)
    local = program.get("local_attn_layers") or [0] * L
    turned = program.get("rotary_layers") or [1] * L
    layers = params["layers"]
    out = {"own": [[] for _ in sequences], "slack": -np.inf, "differ": 0, "pairs": 0}
    with jax.default_matmul_precision("highest"):
        # the rows looked up, not the table: the embedding is never float32 whole
        xs = [_f32(fetch({"rows": params["wte"][np.asarray(t)]}))["rows"] for t in sequences]
        for i in range(L):
            lp = _f32(fetch({k: layers[k][i] for k in ATTENTION}))
            h2s = []
            for j, x in enumerate(xs):
                xs[j], h2 = _attend(x, lp, eps=eps, base=base, window=window if local[i] else 0,
                                    rotary=bool(turned[i]))
                h2s.append(h2)
            if i < lead:
                w = _f32(fetch({k: v[i] for k, v in params["dense_ffn"].items()}))
                for j in range(len(xs)):
                    xs[j] = xs[j] + _gated_mlp(h2s[j], w["wg"], w["wi"], w["wo_mlp"],
                                               jnp.ones((h2s[j].shape[0],), jnp.float32))
                continue
            _routed_ffn(program, params["moe"], i - lead, xs, h2s, routing, out, fetch)
        top = _f32(fetch({"lnf_scale": params["lnf_scale"]}))
        out["last"] = xs
        out["hidden"] = [_rms(x, top["lnf_scale"], eps) for x in xs]
    out["own"] = [np.stack(o) for o in out["own"]] if L > lead else out["own"]
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
    """Several sequences in ONE pass over the layers (every expert is fetched
    once for all of them): ``sequences`` a list of [S_j] tokens, ``rows`` the
    positions wanted of each, ``routing`` a list of [routed layers, S_j, k] or
    None -> ``logits`` a list of [len(rows_j), vocab], ``own`` a list, ``slack``
    the largest over all of them, ``differ`` the share over all (layer, token)
    pairs."""
    f = _forward(program, params, [np.asarray(t) for t in sequences], fetch,
                 None if routing is None else [np.asarray(r) for r in routing])
    with jax.default_matmul_precision("highest"):
        logits = [np.asarray(_head_logits(params, fetch, x[jnp.asarray(r)]))
                  for x, r in zip(f["hidden"], rows)]
    return {"logits": logits, "own": f["own"], "slack": f["slack"],
            "differ": f["differ"] / max(f["pairs"], 1)}


def routed_pass(program: dict, params: dict, tokens, rows, *, fetch, routing=None) -> dict:
    """One sequence's float32 ``logits`` [len(rows), vocab] at ``rows`` under
    ``routing`` ([routed layers, S, k]; None: the reference routes for itself),
    the reference's ``own`` choices, the ``slack`` of the routing used and the
    share of (layer, token) pairs on which the two sets ``differ``."""
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


def mtp_logits_at(program: dict, params: dict, tokens, rows, *, fetch,
                  routing=None) -> np.ndarray:
    """The multi-token-prediction module's float32 logits [len(rows), vocab]:
    ``tokens`` [S + 1]; row i is the prediction of t_{i+2} from the model's pass
    over t_0 ... t_i and the embedding of t_{i+1}. ``routing`` [routed layers + 1,
    S, k]: the experts to use, the module's block's last (None: the reference's)."""
    tokens = np.asarray(tokens)
    eps = float(program["layernorm_epsilon"])
    f = _forward(program, params, [tokens[:-1]], fetch,
                 None if routing is None else [np.asarray(routing)[:-1]])
    mtp = params["mtp"]
    with jax.default_matmul_precision("highest"):
        top = _f32(fetch({k: mtp[k] for k in ("enorm_scale", "hnorm_scale", "eh_proj",
                                              "lnf_scale")}))
        emb = _f32(fetch({"rows": params["wte"][tokens[1:]]}))["rows"]
        x = jnp.concatenate([_rms(emb, top["enorm_scale"], eps),
                             _rms(f["last"][0], top["hnorm_scale"], eps)], axis=-1) @ top["eh_proj"]
        lp = _f32(fetch({k: mtp["layers"][k][0] for k in ATTENTION}))
        x, h2 = _attend(x, lp, eps=eps, base=1.0, window=0, rotary=False)
        xs, log = [x], {"own": [[]], "slack": -np.inf, "differ": 0, "pairs": 0}
        _routed_ffn(program, mtp["moe"], 0, xs, [h2],
                    None if routing is None else [np.asarray(routing)[-1:]], log, fetch)
        hidden = _rms(xs[0], top["lnf_scale"], eps)
        return np.asarray(_head_logits(params, fetch, hidden[jnp.asarray(rows)]))


def param_counts(program: dict) -> dict:
    """Attention: W_q, W_k, W_v, W_o (+ two head norms). A leading layer adds a
    gated MLP of ``dense_intermediate_size``; a routed one the router, the HELD
    gated experts and the shared expert. A token multiplies through the attention
    of every layer, the dense MLPs, the routers, the shared experts, the head,
    and, of the ``moe_top_k`` experts a routed layer chooses for it, those that
    are held: ``moe_top_k`` x held / ``num_experts`` of them on average (all
    ``moe_top_k`` where every expert is held). The shared expert and the leading
    layers count OUTSIDE the experts: every token reads them."""
    d, L, V = program["hidden_size"], program["num_layers"], program["vocab_size"]
    H, Hkv, D = program["num_heads"], program["num_kv_heads"], program["qk_head_dim"]
    f, E, k = program["intermediate_size"], program["num_experts"], program["moe_top_k"]
    lead, routed = program["moe_first_dense"], L - program["moe_first_dense"]
    first, count = held(program)
    attention = d * H * D + 2 * d * Hkv * D + H * D * d
    expert = 3 * d * f
    dense = 3 * d * program["dense_intermediate_size"]
    shared = 3 * d * program["moe_shared_size"]
    outside = lead * (attention + dense) + routed * (attention + d * E + shared) + d * V
    norms = L * (2 * d + 2 * D) + d
    module = 0
    if program.get("mtp_layers"):  # two joining norms, W_eh, one routed block, its final norm
        module = (2 * d + 2 * d * d + attention + d * E + E + shared + count * expert
                  + 2 * d + 2 * D + d)
    return {
        "matmul_attention_per_layer": attention,
        "matmul_per_expert": expert,
        "matmul_outside_experts": outside,
        "routed_layers": routed,
        "experts_held": count,
        "held_pairs_per_token_per_layer": k * count / E,
        "matmul_on_token_path": outside + routed * k * count * expert // E,
        "total": outside + routed * (count * expert + E) + V * d + norms + module,
    }
