"""Driver for a serving cell whose model keeps RECURRENT state per sequence
beside its K/V (a state-space mixer: Falcon-H1): ``serve.py`` with another
``_check`` and nothing else. The build, the warm-up, the measured loop, the
instrumentation and the ``ctx`` are ``serve.py``'s own (``run`` below calls
``serve.run`` with this file's ``_check`` in the place of ``serve._check``, as
``serve_latent.py`` does).

Why another check. ``serve.py``'s probe copies the cache leaves ``"k"`` and
``"v"`` by name from the prefill's local cache into the slot cache: the
recurrent state, which is neither, is dropped on the way and the decode steps
would start from nothing. And it pads a prompt to its bucket with no mask:
attention hides the padding behind causality, a recurrence would run over it.
The probe here is built from the program's own helpers on whatever leaves the
cache has (``init_cache`` / ``update_cache_slot`` / ``apply_with_cache``), and
hands the prefill the live-row mask ``SlotWorker._build_prefill`` hands it.

What is judged, and of which program, is ``serve.py``'s: the check prompts are
served by the engine that is TIMED (greedy, 9 tokens, four rows live together);
(a) the probe's logits (bucket-padded prefill into a slot cache, then
``DECODE_STEPS`` decode steps through it, fed the engine's tokens) agree with
the plain float32 reference's full forward pass within ``serve.LOGIT_TOL``; (b)
every token the engine emitted lies within ``LOGIT_TOL`` of the reference's top
logit at its step. And (c) ``reference_logit_std`` lies in ``LOGIT_STD``: the
model's multipliers make a carelessly drawn model's logits and attention scores
hundreds of times smaller than the tolerance (any error would pass); the seeded
draw compensates them (``transformer.init``), and this is the check that it did.

The check prompts are 40, 97, 200 and 900 tokens: the 64, 128, 256 and 1024
prefill buckets, four of the five programs the cell's traffic is timed on (one
shorter than the scan's chunk, one that is not a multiple of it, one of several
chunks, one of the longest bucket), each followed by decode steps whose state
came from that prefill. At a rehearsal's budget they are cut to it.
"""

from __future__ import annotations

from unittest import mock

import numpy as np

from ..references import load_reference, program_of
from . import serve
from .serve import DECODE_STEPS, LOGIT_TOL, WARM_UID

CHECK_PROMPT_LENS = (40, 97, 200, 900)
LOGIT_STD = (0.5, 2.0)  # where the reference's logits' standard deviation must lie


def probe_logits(cfg, params, prompts, buckets, forced):
    """Logits [n, 1 + DECODE_STEPS, V] of the serving path's own computation, on
    any cache tree: each prompt padded to its bucket and prefilled into a local
    cache the bucket long with the live-row mask (what
    ``SlotWorker._build_prefill`` does), written into its row of a slot cache by
    ``update_cache_slot``, then ``DECODE_STEPS`` decode steps at per-row
    positions (``_build_decode``), fed ``forced`` [n, DECODE_STEPS]."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from deepspeed_tpu.models import transformer as tfm

    lens = np.asarray([len(p) for p in prompts], np.int32)
    padded = []
    for p, b in zip(prompts, buckets):
        row = np.zeros((1, b), np.int32)
        row[0, :len(p)] = p
        padded.append(row)
    smax = -(-(max(buckets) + DECODE_STEPS) // 128) * 128

    def probe(params, padded, lens, forced):
        cache = tfm.init_cache(cfg, len(padded), smax, dtype=cfg.dtype)
        first = []
        for j, p in enumerate(padded):
            local = tfm.init_cache(cfg, 1, p.shape[1], dtype=cfg.dtype)
            logits, local = tfm.apply_with_cache(
                cfg, params, p, local, 0, last_index=lens[j] - 1,
                live=jnp.arange(p.shape[1])[None, :] < lens[j])
            first.append(logits[0, 0])
            cache = tfm.update_cache_slot(cache, local, j)

        def decode(carry, toks):
            cache, pos = carry
            logits, cache = tfm.apply_with_cache(cfg, params, toks[:, None], cache, pos,
                                                 write_pos=pos)
            return (cache, pos + 1), logits[:, 0]

        _, steps = lax.scan(decode, (cache, lens), forced.T)
        return jnp.concatenate([jnp.stack(first)[:, None], steps.transpose(1, 0, 2)], axis=1)

    out = jax.jit(probe)(params, padded, lens, np.asarray(forced, np.int32))
    return np.asarray(out, np.float32)


def judge(reference, program, params, prompts, got, probe) -> dict:
    """The comparison of the module docstring. ``got``: the tokens the engine
    emitted per prompt; ``probe``: ``probe_logits``'s. The reference takes all
    the prompts in one pass over its layers."""
    seqs = [np.concatenate([p, g[:DECODE_STEPS]]) for p, g in zip(prompts, got)]
    rows = [np.arange(len(p) - 1, len(p) + DECODE_STEPS) for p in prompts]
    refs = reference.logits_of(program, params, seqs, rows, fetch=lambda leaves: leaves)
    errs = [float(np.max(np.abs(x - ref))) for x, ref in zip(probe, refs)]
    gaps = [float(np.max(ref.max(axis=-1) - ref[np.arange(len(g)), g]))
            for ref, g in zip(refs, got)]
    spread = float(np.std(refs[-1]))
    finite = bool(np.isfinite(probe).all())
    return {"ok": (finite and max(errs) <= LOGIT_TOL and max(gaps) <= LOGIT_TOL
                   and LOGIT_STD[0] <= spread <= LOGIT_STD[1]),
            "logit_max_abs_err": max(errs), "token_gap_to_reference_top": max(gaps),
            "reference_logit_std": spread, "logit_tol": LOGIT_TOL, "logit_std": LOGIT_STD,
            # not judged: the same, prompt by prompt
            "logit_err_by_prompt": errs, "token_gap_by_prompt": gaps}


def _check(run, srv, Request) -> dict:
    """The check prompts through the engine that is timed; then the probe and
    ``judge``."""
    reference = load_reference(run.program)
    rng = np.random.default_rng([run.seed, 0xC4EC])
    vocab = run.program["vocab_size"]
    budget = run.sized("deployment")["max_seq_len"]
    lens = [min(n, budget - DECODE_STEPS - 2) for n in CHECK_PROMPT_LENS]
    prompts = [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]
    reqs = [Request(uid=WARM_UID + i, prompt=p, max_new_tokens=DECODE_STEPS + 1)
            for i, p in enumerate(prompts)]
    results = srv.serve(reqs)
    got = [np.asarray(results[r.uid].tokens, np.int32) for r in reqs]
    if any(results[r.uid].status != "ok" or len(g) != DECODE_STEPS + 1
           for r, g in zip(reqs, got)):
        return {"ok": False, "why": "a check request did not complete"}
    params = srv.engine.params
    buckets = [serve._bucket(srv, len(p)) for p in prompts]
    probe = probe_logits(srv.engine.cfg, params, prompts, buckets,
                         np.stack([g[:DECODE_STEPS] for g in got]))
    out = judge(reference, run.program, params, prompts, got, probe)
    return {**out, "check_buckets": buckets}


def run(run) -> dict:
    """``serve.run`` — build, check, warm-up, measured loop, ``ctx`` — with the
    check above where it calls ``_check``; the ``ctx`` gains the engine's
    ``worker``, whose account of its cache a reader wants. A rehearsal runs the
    configuration's ``rehearse_recurrent_program``, the tiny twin WITH the mixer
    (``rehearse_program`` is the one ``parity.py``'s cache case can take, which
    has none: the configuration's notes say why), so that ``--rehearse`` drives
    the state path and the readers of it."""
    if run.rehearse:
        run.program = program_of(run.config, "rehearse_recurrent_program")
        load_reference(run.program)  # a key the reference does not cover: refused by name
    seen = {}

    def check(run, srv, Request):
        seen["worker"] = srv.worker
        return _check(run, srv, Request)

    with mock.patch.object(serve, "_check", check):
        ctx = serve.run(run)
    return {**ctx, **seen}
