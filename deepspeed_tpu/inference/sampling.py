"""Token sampling for generative inference — temperature, top-k, top-p
(nucleus), repetition penalty.

The reference's inference stack leans on greedy/HF-side sampling; a real p50
serving path needs the sampler inside the compiled decode loop, so these are
pure jnp transforms on [B, V] logits usable under jit/scan.

Repetition penalty is CTRL-style (as in HF generation): logits of tokens seen
in the history are divided by the penalty when positive, multiplied when
negative. The "seen" set is carried as a [B, V] bool mask updated per step —
O(V) memory but branch-free under XLA.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


class SamplerConfig(NamedTuple):
    temperature: jnp.ndarray | float = 1.0
    top_k: int = 0  # 0 = disabled
    top_p: float = 1.0  # 1.0 = disabled
    repetition_penalty: float = 1.0  # 1.0 = disabled


def update_seen(seen: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """seen [B, V] bool | tokens [B, T] -> seen with those tokens marked."""
    B, V = seen.shape
    onehot = jax.nn.one_hot(tokens, V, dtype=jnp.bool_)  # [B, T, V]
    return seen | jnp.any(onehot, axis=1)


def apply_repetition_penalty(logits, seen, penalty: float):
    if penalty == 1.0:
        return logits
    penalized = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(seen, penalized, logits)


def apply_top_k(logits, k: int):
    if k <= 0 or k >= logits.shape[-1]:
        return logits
    vals, _ = jax.lax.top_k(logits, k)
    thresh = vals[..., -1:]
    return jnp.where(logits < thresh, NEG_INF, logits)


def apply_top_p(logits, p: float):
    if p >= 1.0:
        return logits
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # keep tokens while the cumulative mass BEFORE them is < p; the first
    # token is forced kept (p <= 0 would otherwise mask EVERY logit and
    # categorical would degenerate to token 0)
    keep_sorted = ((cum - probs) < p).at[..., 0].set(True)
    # threshold = smallest kept logit
    thresh = jnp.min(
        jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(logits < thresh, NEG_INF, logits)


def apply_top_k_vector(logits, k):
    """Per-row top-k: logits [B, V], k [B] int32 (<= 0 disables that row).

    The threshold is data (the k-th largest logit per row), so distinct
    per-request k values NEVER change the compiled program — the property the
    continuous-batching decode step needs to compile exactly once."""
    V = logits.shape[-1]
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    idx = jnp.clip(k - 1, 0, V - 1)
    thresh = jnp.take_along_axis(sorted_desc, idx[:, None], axis=-1)  # [B, 1]
    enabled = (k > 0) & (k < V)
    return jnp.where(enabled[:, None] & (logits < thresh), NEG_INF, logits)


def apply_top_p_vector(logits, p):
    """Per-row nucleus sampling: logits [B, V], p [B] fp32 (>= 1 disables;
    p <= 0 degenerates to top-1)."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = ((cum - probs) < p[:, None]).at[..., 0].set(True)
    thresh = jnp.min(
        jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    enabled = p < 1.0
    return jnp.where(enabled[:, None] & (logits < thresh), NEG_INF, logits)


def _filter_logits_vector(logits, t, k, p):
    """The shared per-row filter core: scale fp32 ``logits`` [B, V] by
    temperature ``t`` [B], then mask below the top-k and nucleus thresholds
    (k/p [B] arrays; <= 0 / >= 1 disable per row). Returns the filtered
    SCALED logits — the distribution both the decode sampler and the
    speculative verifier draw from, factored out so the verify programs
    score drafts against EXACTLY the distribution decode samples from.

    ONE [B, V] sort serves both filters (this runs every decode step; the
    O(V log V) sort dominates sampling cost at real vocabs): top-k masks a
    suffix of the descending sort to NEG_INF, which keeps it sorted, so the
    nucleus pass reuses it — identical semantics to applying
    ``apply_top_k_vector`` then ``apply_top_p_vector`` in sequence."""
    scaled = logits / jnp.maximum(t, 1e-6)[:, None]
    V = scaled.shape[-1]

    sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
    kth = jnp.take_along_axis(sorted_desc, jnp.clip(k - 1, 0, V - 1)[:, None], axis=-1)
    k_on = ((k > 0) & (k < V))[:, None]
    scaled = jnp.where(k_on & (scaled < kth), NEG_INF, scaled)
    sorted_desc = jnp.where(k_on & (sorted_desc < kth), NEG_INF, sorted_desc)

    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # first token forced kept: p <= 0 must degenerate to top-1, not to an
    # all-masked row that categorical resolves as token 0
    keep_sorted = ((cum - probs) < p[:, None]).at[..., 0].set(True)
    pth = jnp.min(jnp.where(keep_sorted, sorted_desc, jnp.inf), axis=-1, keepdims=True)
    return jnp.where((p < 1.0)[:, None] & (scaled < pth), NEG_INF, scaled)


SAMPLER_FORMS = ("argmax", "draw", "filter")


def sampler_form(temperature, top_k, top_p, vocab: int):
    """Which of ``SAMPLER_FORMS`` the rows ``temperature`` / ``top_k`` /
    ``top_p`` [B] ask ``sample_logits_vector`` for, as its index: 0 no row
    samples, 1 some row samples and none of those filters, 2 a sampling row
    sets a top-k or a nucleus. Written once in what numpy arrays and traced
    ones both do, so the compiled programs branch on it and ``SlotWorker``
    names it on their spans from the arrays it hands them (a greedy row's
    filters count for nothing: its token is the arg-max either way)."""
    samples = temperature > 0.0
    filters = samples & (((top_k > 0) & (top_k < vocab)) | (top_p < 1.0))
    return samples.any().astype("int32") + filters.any().astype("int32")


def sample_logits_vector(logits, rng, temperature, top_k, top_p):
    """Per-slot sampling: logits [B, V] with PER-ROW sampler state as arrays
    (temperature/top_k/top_p all [B]) -> token ids [B] int32.

    Rows with temperature <= 0 take the greedy argmax. Every sampler knob is
    an array operand, so admitting a request with new sampling params reuses
    the already-compiled decode step (the ServingEngine contract).

    The program does the work its operands ask for (``sampler_form``, one
    conditional): the sort over the vocabulary runs only when a sampling row
    filters, the divide and the draw only when a row samples. The three forms
    are one function with its dead work left out: ``_filter_logits_vector``
    hands back ``scaled`` untouched for a row with no filter, and a row's
    draw reads its own logits and noise alone, so the tokens are the same
    bits whichever form runs."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.asarray(temperature, jnp.float32)
    k = jnp.asarray(top_k, jnp.int32)
    p = jnp.asarray(top_p, jnp.float32)

    def drawn_from(scaled):
        drawn = jax.random.categorical(rng, scaled, axis=-1)
        return jnp.where(t <= 0.0, greedy, drawn).astype(jnp.int32)

    return jax.lax.switch(
        sampler_form(t, k, p, logits.shape[-1]),
        (lambda: greedy,
         lambda: drawn_from(logits / jnp.maximum(t, 1e-6)[:, None]),
         lambda: drawn_from(_filter_logits_vector(logits, t, k, p))))


def sample_with_confidence(logits, rng, temperature, top_k, top_p):
    """``sample_logits_vector`` and, per row, the probability of the token it chose
    under the softmax over the row's whole vocabulary (the raw logits: no temperature,
    no filter): logits [R, V] -> (token ids [R] int32, confidence [R] float32). What a
    pass of generation by diffusion over blocks ranks a block's masked rows by."""
    tokens = sample_logits_vector(logits, rng, temperature, top_k, top_p)
    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return tokens, jnp.exp(picked - jax.nn.logsumexp(logits, axis=-1))


def reveal_rows(confidence, masked, count, threshold):
    """Which of a block's masked rows a denoising pass reveals: confidence [n, B]
    float32 (read where ``masked``), masked [n, B] bool, count [n] int32, threshold [n]
    float32 -> [n, B] bool. The ``count`` masked rows of largest confidence a slot (the
    earlier position at a tie; all of them where fewer are masked), and every masked
    row whose confidence is over ``threshold`` (``inf``: none). Every operand is an
    array: a slot's pass, its schedule and its strategy are data, never the program."""
    c = jnp.where(masked, confidence, -jnp.inf)
    at = jnp.arange(c.shape[1])
    ahead = (c[:, None, :] > c[:, :, None]) | (
        (c[:, None, :] == c[:, :, None]) & (at[None, None, :] < at[None, :, None]))
    rank = jnp.sum(ahead, axis=-1)  # [n, B]: how many rows of the slot go before this one
    return masked & ((rank < count[:, None]) | (c > threshold[:, None]))


def verify_logits_vector(logits, draft, rng, temperature, top_k, top_p):
    """Speculative verify over a whole draft block: logits [B, D+1, V]
    (position j's logits predict the token AFTER draft token j), draft
    [B, D] int32 proposals, per-row sampler state [B] arrays ->

      accept   [B, D]   bool  — per-position accept verdicts
      resample [B, D+1] int32 — the token to emit AT a rejection: drawn
                                from the residual distribution (the
                                filtered distribution with the rejected
                                draft token masked out); the final column
                                (no draft to reject) falls back to clean
      clean    [B, D+1] int32 — an unconditional sample per position, used
                                for the bonus token when the draft was
                                exhausted rather than rejected (sampling
                                from the residual there would bias toward
                                not-the-pad-token)

    Greedy rows (temperature <= 0) accept exactly when the draft token IS
    the argmax, and both resample and clean ARE the argmax — so the emitted
    stream is bitwise what one-token-at-a-time decode produces. Sampled
    rows use the standard speculative acceptance rule (Leviathan et al.
    2023) against a DETERMINISTIC drafter (q(d)=1): accept with probability
    p(d) under the filtered distribution, else emit the residual sample —
    the output marginal stays exactly the filtered distribution.

    The host applies the PREFIX rule (stop at the first rejection) and
    clamps to each row's true draft length; rows drafted shorter than D —
    or not at all — ride along with pad tokens and emit ``clean`` at their
    first free position, which is exactly the decode-step sample."""
    logits = logits.astype(jnp.float32)
    B, D1, V = logits.shape
    D = D1 - 1
    t = jnp.asarray(temperature, jnp.float32)
    k = jnp.asarray(top_k, jnp.int32)
    p = jnp.asarray(top_p, jnp.float32)
    rep = lambda a, dt: jnp.broadcast_to(
        jnp.asarray(a, dt)[:, None], (B, D1)).reshape(B * D1)
    filt = _filter_logits_vector(
        logits.reshape(B * D1, V), rep(t, jnp.float32),
        rep(k, jnp.int32), rep(p, jnp.float32)).reshape(B, D1, V)
    greedy = jnp.argmax(logits, axis=-1)  # [B, D1]
    sampled = (t > 0.0)[:, None]

    probs = jax.nn.softmax(filt, axis=-1)
    p_draft = jnp.take_along_axis(
        probs[:, :D], draft[..., None], axis=-1)[..., 0]  # [B, D]
    r_accept, r_res, r_clean = jax.random.split(rng, 3)
    u = jax.random.uniform(r_accept, (B, D))
    accept = jnp.where(sampled, u < p_draft, draft == greedy[:, :D])

    clean_drawn = jax.random.categorical(r_clean, filt, axis=-1)  # [B, D1]
    clean = jnp.where(sampled, clean_drawn, greedy).astype(jnp.int32)
    # residual for a deterministic drafter: p with the draft token removed,
    # renormalized — i.e. the filtered logits with that token masked out
    masked = jnp.where(jax.nn.one_hot(draft, V, dtype=jnp.bool_),
                       NEG_INF, filt[:, :D])
    res_drawn = jax.random.categorical(r_res, masked, axis=-1)  # [B, D]
    res = jnp.where(sampled, res_drawn, greedy[:, :D])
    resample = jnp.concatenate([res, clean[:, D:]], axis=1).astype(jnp.int32)
    return accept, resample, clean


def sample_logits(logits, rng, cfg: SamplerConfig, seen=None):
    """logits [B, V] -> sampled token ids [B] int32.

    temperature <= 0 selects greedy argmax (after repetition penalty)."""
    logits = logits.astype(jnp.float32)
    if seen is not None:
        logits = apply_repetition_penalty(logits, seen, cfg.repetition_penalty)
    greedy = jnp.argmax(logits, axis=-1)
    t = jnp.asarray(cfg.temperature, jnp.float32)
    scaled = logits / jnp.maximum(t, 1e-6)
    scaled = apply_top_k(scaled, cfg.top_k)
    scaled = apply_top_p(scaled, cfg.top_p)
    drawn = jax.random.categorical(rng, scaled, axis=-1)
    return jnp.where(t <= 0.0, greedy, drawn).astype(jnp.int32)
