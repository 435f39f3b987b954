"""The plain reference of the ``sdar_moe`` architecture as JetLM/SDAR-30B-A3B-Chat
configures it: its forward pass, its sampler (generation by diffusion over blocks)
and, at block length 1, its language-model loss, in straightforward float32
``jax.numpy`` — one sequence, one layer and ONE EXPERT at a time, the score matrix
taken ``QUERY_BLOCK`` whole rows and one K/V head's group of query heads at a time,
the experts ``ROW_BLOCK`` rows at a time, the head ``HEAD_BLOCK`` columns at a time:
no cache, no kernel, no sort, no grouped matmul, no scan over layers — and its
parameter counts. The protocol is stated in ``references/__init__.py``; it shares no
code with ``deepspeed_tpu/``.

The block (each symbol a key of the published ``config.json``; the block is
Qwen3-MoE's, key for key): ``x = E[tokens]``; ``x += Attn(RMSNorm(x)); x +=
MoE(RMSNorm(x))`` a layer, RMSNorm with a scale only, no bias anywhere, a final
RMSNorm, an untied head.

*Attention, H query heads and Hkv key/value heads of width D.* ``q = h W_q`` -> [H,
D], ``k = h W_k``, ``v = h W_v`` -> [Hkv, D]; ``q = RMSNorm_D(q) g_q``, ``k =
RMSNorm_D(k) g_k`` on every head by itself, one [D] scale each for all heads; rotary on
q and k at absolute positions, half-split pairing (dimension i with i + D / 2),
``inv_freq_i = base^(-2i / D)``, no scaling; query head i attends K/V head i // (H /
Hkv); scale D^-1/2. **Key j is visible to query i iff j // B <= i // B** (B =
``attn_block_length``): causal between blocks of B positions aligned from position 0,
every position of a block seeing the whole block. B = 1 is the causal mask.

*Routed feed-forward, every layer.* ``p = softmax(h W_r)`` over ALL ``num_experts``
in float32; the ``moe_top_k`` largest kept and, with ``moe_norm_topk_prob``,
renormalised to sum 1; ``y = sum_e p_e W_down_e (silu(W_gate_e h) * W_up_e h)``. No
shared expert, no selection bias, dropless.

*Head.* The logits at position i score the token OF position i (no shift by one).

*Generation* (``generate``). Blocks are aligned to multiples of B from position 0. A
prompt of P tokens stands as it is; the block that holds position P opens with its r =
P mod B prompt tokens in place and B - r masked positions (r = 0: B masks). One
DENOISING PASS: the whole sequence as it stands, a masked position holding
``mask_token_id``, goes through the layers; at each masked row i of the open block,
``x0_i`` = argmax (greedy) or a draw, and ``c_i = softmax(logits_i)[x0_i]``; under
``low_confidence_static`` the n = B // T masked rows of largest c take their x0 (T =
``denoising_steps``; all that are left on the block's T-th pass; fewer where fewer are
masked); under ``low_confidence_dynamic`` every masked row with c_i > ``threshold``
does, or the n largest if those are fewer. When no row is masked the block is final
(a system with a cache then runs one more pass over it to write its K/V, the COMMIT;
here a forward pass keeps nothing, so ``generate`` lists the commit as a pass and
computes its logits only where asked) and the next block opens as B masks. A request
ends at ``n`` tokens (the rest of its last block is thrown away) or at ``eos`` in a
finished block. Which rows are masked is the sampler's own state, never ``token ==
mask_token_id``: a prompt may hold that id.

Departures from the published description, each stated in the configuration's file
under ``assumed``: the per-head q/k RMSNorm (``model_type: sdar_moe`` is in no
installed ``transformers``; the key set is Qwen3-MoE's, whose attention has it); no
shift between a position's logits and its token; the block length, the denoising
steps, the mask token's id and the strategies (the catalog's ``not_given``; the
family's published sampler as far as it is known here). The model's own
``modeling_sdar_moe.py`` and ``generate.py`` are not on this machine.

Beyond the protocol, for the check of a routed model (as ``mellum.py``):
``routed_passes`` can be given the experts the SYSTEM chose (``routing`` [layers,
tokens, k]); each token then goes through those, weighted by the reference's own
float32 probabilities, and the pass reports ``slack`` (the largest router logit left
out minus the smallest chosen, over the standard deviation of the layer's logits) and
on how many (layer, token) pairs the two sets ``differ``.

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
    "layernorm_epsilon": ANY, "rotary_base": ANY,
    "num_experts": ANY, "moe_top_k": ANY, "moe_norm_topk_prob": (False, True),
    # generation by diffusion over blocks: the mask's block and the id a masked position holds
    "attn_block_length": ANY, "mask_token_id": ANY,
    # what makes the block this architecture's, each at the one value this file implements
    "pos_emb": ("rotary",), "tie_embeddings": (False,), "use_bias": (False,),
    "norm_kind": ("rms",), "activation": ("swiglu",), "qk_norm": ("head",),
    "moe_every": (1,), "moe_routing": ("dropless",), "moe_aux_coeff": (0.0,),
    # how the SYSTEM attends in a step; nothing of the model, so nothing here reads it
    "decode_attn": ("xla",),
}
QUERY_BLOCK = 512  # queries a score matrix is taken for at a time (each row's softmax whole)
ROW_BLOCK = 4096  # rows an expert is taken for at a time
HEAD_BLOCK = 8192  # columns of the head cast to float32 at a time
ATTENTION = ("ln1_scale", "ln2_scale", "wq", "wk", "wv", "wo", "q_norm_scale", "k_norm_scale")
STRATEGIES = ("low_confidence_static", "low_confidence_dynamic")


def block_length(program: dict) -> int:
    return int(program.get("attn_block_length", 1))


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotary(x, base):
    """x [S, heads, D]: dimension i rotated with i + D / 2 by position x base^(-2i / D)."""
    S, D = x.shape[0], x.shape[-1]
    freq = base ** (-2.0 * jnp.arange(0, D // 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None, :]  # [S, D / 2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@partial(jax.jit, static_argnames=("eps", "base", "block"))
def _attend(x, lp, gate, *, eps, base, block):
    """x [S, d] -> (x after the attention residual, the normalised input of the
    feed-forward, the router's logits), under the mask of blocks of ``block``."""
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
        last = min(-(-hi // block) * block, S)  # the last query's block ends here
        rows, cols = jnp.arange(lo, hi)[:, None], jnp.arange(0, last)[None, :]
        seen = cols // block <= rows // block

        def group(qkv):  # one K/V head and its query heads: [q, g, D], [s, D], [s, D]
            qg, kg, vg = qkv
            scores = jnp.einsum("qgk,sk->gqs", qg, kg) / math.sqrt(D)
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("gqs,sk->qgk", probs, vg)

        out = jax.lax.map(group, (q[lo:hi].transpose(1, 0, 2, 3),
                                  k[:last].transpose(1, 0, 2), v[:last].transpose(1, 0, 2)))
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
    """Every sequence (a list of [S] token arrays of any lengths) through the layers,
    each layer's leaves fetched once: its attention leaves and router together, then
    one expert at a time (an expert is cast to float32 once for all the sequences).
    ``routing``: per sequence, the experts to use [layers, S, k], or None for the
    reference's own."""
    eps, base = float(program["layernorm_epsilon"]), float(program["rotary_base"])
    L, E, B = int(program["num_layers"]), int(program["num_experts"]), block_length(program)
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
                xs[j], h2, logits = _attend(x, lp, gate, eps=eps, base=base, block=B)
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
    for all of them): ``sequences`` a list of [S_j] tokens AS THEY STAND at a pass (the
    mask token's id where a position is masked), ``rows`` the positions wanted of each,
    ``routing`` a list of [layers, S_j, k] or None -> ``logits`` a list of
    [len(rows_j), vocab], ``own`` a list, ``slack`` the largest over all of them,
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
    """Float32 logits [len(rows), vocab] at the given positions of the sequence as it
    stands (the logits at position i score the token OF position i where the block
    length is over 1; at 1 the backbone is the causal one and they score what a
    next-token loss takes them for)."""
    return routed_pass(program, params, tokens, rows, fetch=fetch, routing=routing)["logits"]


def lm_loss(program: dict, params: dict, tokens, *, fetch, routing=None) -> float:
    """Mean next-token cross-entropy of ``tokens`` [S + 1] or [N, S + 1], at block
    length 1 alone (the program's ``moe_aux_coeff`` is 0: the loss has no other term).
    Under blocks a position sees its own block, its label among it, and the model's
    objective (denoising masked blocks under a noise schedule) is not in its
    configuration: refused by name."""
    if block_length(program) > 1:
        raise NotImplementedError(
            f"lm_loss at attn_block_length={block_length(program)}: the diffusion objective "
            "needs the noise schedule, which the configuration does not give")
    tokens = np.asarray(tokens).reshape(-1, np.shape(tokens)[-1])
    f = _forward(program, params, list(tokens[:, :-1]), fetch, routing)
    losses = []
    with jax.default_matmul_precision("highest"):
        for x, labels in zip(f["hidden"], tokens[:, 1:]):
            logits = _head_logits(params, fetch, x)  # one sequence's [S, vocab] at a time
            picked = jnp.take_along_axis(logits, jnp.asarray(labels)[:, None], axis=-1)[:, 0]
            losses.append(jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked))
    return float(jnp.mean(jnp.stack(losses)))


def reveal_count(block: int, steps: int, pass_index: int, masked: int) -> int:
    """How many masked rows ``low_confidence_static`` reveals on pass ``pass_index``
    (from 0) of a block: ``block // steps``, all that are left on the last of the
    ``steps`` passes, never more than are ``masked``."""
    return masked if pass_index >= steps - 1 else min(block // steps, masked)


def _filtered(logits, temperature, top_k, top_p):
    """One row's logits over the temperature, under its top-k and nucleus limits."""
    scaled = np.asarray(logits, np.float64) / max(temperature, 1e-6)
    order = np.argsort(-scaled, kind="stable")
    if 0 < top_k < len(scaled):
        scaled[order[top_k:]] = -np.inf
    if top_p < 1.0:
        p = np.exp(scaled[order] - scaled[order[0]])
        p = p / p.sum()
        keep = (np.cumsum(p) - p) < top_p
        keep[0] = True
        scaled[order[~keep]] = -np.inf
    return scaled


def generate(program: dict, params: dict, prompt, n: int, *, fetch, denoising_steps: int,
             strategy: str = "low_confidence_static", threshold: float = 0.9,
             temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0, eos=None, seed: int = 0,
             reveals=None, commit_logits: bool = False) -> dict:
    """The sampler of the module docstring by whole forward passes -> ``tokens`` (the
    ``n`` generated, fewer behind an ``eos``) and ``passes``, one record a pass in order:
    ``start`` (the open block's first position), ``sequence`` (as it stood at the pass),
    ``masked`` (the block's positions masked at entry), ``logits`` [B, vocab] of the
    block's rows (None for a commit unless ``commit_logits``), ``x0`` and ``confidence``
    of every block row (``-inf`` where not masked), ``revealed`` (positions, in the order
    of their confidence) and ``commit``.

    ``reveals``: someone else's choices, one ``{position: token}`` a DENOISING pass in
    order; the pass then reveals exactly those (teacher forcing: the records still hold
    the reference's own logits, x0 and confidences, for the caller to compare)."""
    if strategy not in STRATEGIES:
        raise ValueError(f"strategy is one of {STRATEGIES}, not {strategy!r}")
    B, T, mask_id = block_length(program), int(denoising_steps), int(program["mask_token_id"])
    if not 1 <= T <= B:
        raise ValueError(f"denoising_steps is 1 .. the block length {B}, got {T}")
    rng = np.random.default_rng([int(seed), 0x5DA2])
    seq = [int(t) for t in np.asarray(prompt).reshape(-1)]
    P = len(seq)
    start = P - P % B
    masked = list(range(P, start + B))
    seq += [mask_id] * len(masked)
    passes, out, forced = [], [], iter(reveals or ())
    done = False
    while not done:
        for p in range(T + 1):
            rows = np.arange(start, start + B)
            commit = not masked
            record = {"start": start, "sequence": np.asarray(seq, np.int32),
                      "masked": list(masked), "commit": commit, "logits": None,
                      "x0": None, "confidence": None, "revealed": []}
            passes.append(record)
            if commit and not commit_logits:
                break
            logits = logits_at(program, params, record["sequence"], rows, fetch=fetch)
            record["logits"] = logits
            if commit:
                break
            x0 = np.argmax(logits, axis=-1)
            if temperature > 0:
                for i in range(B):
                    scaled = _filtered(logits[i], temperature, top_k, top_p)
                    pr = np.exp(scaled - scaled.max())
                    x0[i] = rng.choice(len(pr), p=pr / pr.sum())
            z = logits.astype(np.float64)
            probs = np.exp(z - z.max(axis=-1, keepdims=True))
            probs /= probs.sum(axis=-1, keepdims=True)
            given = next(forced, None) if reveals is not None else None
            if given is not None:  # the caller's tokens stand in for the draws
                for pos, tok in given.items():
                    x0[pos - start] = tok
            conf = np.full((B,), -np.inf)
            for pos in masked:
                conf[pos - start] = probs[pos - start, x0[pos - start]]
            record["x0"], record["confidence"] = x0.astype(np.int32), conf
            if given is not None:
                chosen = sorted(given, key=lambda pos: -conf[pos - start])
            else:
                by_conf = sorted(masked, key=lambda pos: (-conf[pos - start], pos))
                chosen = by_conf[:reveal_count(B, T, p, len(masked))]
                if strategy == "low_confidence_dynamic":
                    over = [pos for pos in by_conf if conf[pos - start] > threshold]
                    if len(over) > len(chosen):
                        chosen = over
            for pos in chosen:
                seq[pos] = int(x0[pos - start])
                masked.remove(pos)
            record["revealed"] = list(chosen)
        # the block is final: its generated positions to the output
        for pos in range(max(start, P), start + B):
            if len(out) < n:
                out.append(seq[pos])
                if eos is not None and seq[pos] == eos:
                    done = True
                    break
        done = done or len(out) >= n
        start += B
        masked = list(range(start, start + B))
        seq += [mask_id] * B
    return {"tokens": np.asarray(out, np.int32), "passes": passes}


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
