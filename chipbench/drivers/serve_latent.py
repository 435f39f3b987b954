"""Driver for a serving cell whose model routes AND caches something other than
per-head keys and values (latent attention): ``drivers/serve_routed.py``'s
two-part comparison, held to the engine that is TIMED, with a probe that moves
whatever leaves the cache has and two more check prompts. The build, the
warm-up, the measured loop, the instrumentation and the ``ctx`` are
``serve.py``'s own (``run`` below calls ``serve.run`` with this file's
``_check`` in the place of ``serve._check``).

What is judged, and of which program. The check prompts are served by the
engine that is timed, through its own compiled programs, and BOTH things taken
from it are judged:

* **its tokens, under its own routing**: a routed model's serving programs
  return the experts they chose (``SlotWorker.routing_log``; the programs are
  the same with the log on or off), the reference is given those choices
  (``references/<name>.py::routed_passes``), and every token the engine emitted
  lies within ``LOGIT_TOL`` of that reference's top logit;
* **its routing, against the reference's router**: in that same pass the
  ``slack`` of the engine's choices is <= ``ROUTING_TOL``.

The logits are judged on a probe, as in ``serve.py`` and ``serve_routed.py``
(the engine returns tokens, not logits): the same bucket-padded prefills into
a slot cache and the same decode steps through it, by the program's own
helpers on whatever leaves the cache has, fed the engine's tokens. max |probe -
reference UNDER THE PROBE'S OWN CHOICES| <= ``LOGIT_TOL``, and the probe's
slack <= ``ROUTING_TOL``.

Why two sets of choices where ``serve_routed.judge`` takes one. That check
holds the engine's TOKENS to a reference given the PROBE's choices. The engine
and the probe are two programs, and they may break a near tie in a router
differently. For OLMoE nothing shows: its raw top-8 probabilities sum to about
0.3 and one expert exchanged moves a logit by 0.1. This router's weights are
normalised and scaled (they sum to 2.448), so one expert exchanged moves a
logit by 1 to 2.5 (``logit_max_abs_err_free_routing`` on the "setup" line): on
the chip the engine's token was 0.2 to 0.5 under the top of a reference that had
been given the probe's choices in some runs, and ``correct`` changed with the
seed for a system that computes what it should (PERF.md section 6, PR 31). Each
program is held to the reference under the choices IT made. Where the two sets
are the same, one reference pass serves both; the reference takes all prompts
in one pass over its layers (an expert is cast to float32 once, not once a
prompt).

The check prompts are ``serve.py``'s two (200 and 97 tokens: the 256 and 128
buckets, whose prefill attends in the dense expanded form), ONE of
``FLASH_PROMPT_LEN`` tokens, whose bucket (1024) is the shortest that takes
the flash kernel at 32 heads (``cache_attention_form``: 4 x 32 x 1024^2 = 128
MiB of scores), and ONE of ``LONG_PROMPT_LEN`` tokens, which goes through the
4096-row prefill program that the cell's traffic is timed on: the decided
``correct`` sees the kernel with unequal q/k and value head sizes at two
lengths, the dense expanded form, and behind each ``DECODE_STEPS`` decode steps
in the absorbed form over the latent cache, four rows live of the cell's 24. At
a rehearsal's 256-token budget the long prompts are cut to the budget like the
others.
"""

from __future__ import annotations

from unittest import mock

import numpy as np

from ..references import load_reference
from . import serve
from .serve import CHECK_PROMPT_LENS, DECODE_STEPS, WARM_UID

FLASH_PROMPT_LEN = 900
LONG_PROMPT_LEN = 2500

# Both limits are set from two readings on the chip at the cell's own size
# (PR 31; PERF.md section 6 has every run): the largest a sound system read over
# all its seeds, and the smallest a planted fault read, the engine SERVING the
# wrong leaves while the reference keeps the right ones.
#
# ``LOGIT_TOL``, this driver's own and not ``serve.py``'s 0.09: max |probe -
# reference under the probe's choices| read 0.0678 to 0.0802 over 33 runs with
# three check prompts and 0.0728 to 0.0884 over 9 with the 2,500-token one (this
# model's routed sum is weighted 2.448 where OLMoE's raw top-8 probabilities sum
# to about 0.3, so the experts' bfloat16 error reaches the residual stream whole;
# OLMoE reads 0.03 to 0.04). The head alone through float8 reads 0.180,
# every matrix outside the experts through float8 1.07. 0.125 is their geometric
# middle: 1.41 x the largest sound reading, 0.69 x the smallest fault.
LOGIT_TOL = 0.125
# ``ROUTING_TOL``: the largest routing slack either program may show, in standard
# deviations of a layer's selection scores (sigmoid score + selection bias: the
# quantity the top-6 is taken of; ``references/deepseek_v3.py``). Sound: 0.0254
# to 0.0466 over 33 runs on three prompts, 0.0316 to 0.0659 over 18 readings
# (engine and probe, 9 runs) on four, which hold three times the (layer, token)
# pairs. The tolerance is 1.5 x the largest. Faults: a float8 router 0.134,
# the selection bias dropped 0.54. On the CPU at the rehearsal size
# (tests/test_kanana.py): bfloat16 compute reads 0.02 to 0.05, float8 router
# weights over 0.1, one expert replaced at random or the bias dropped over 0.2.
ROUTING_TOL = 0.10


def probe_logits(cfg, params, prompts, buckets, forced):
    """``serve_routed.probe_logits`` for any number of prompts and any cache
    tree: each prompt padded to its bucket and prefilled into a local cache the
    bucket long (what ``SlotWorker._build_prefill`` does, so the same attention
    form), written into its row of a slot cache by ``update_cache_slot``, then
    ``DECODE_STEPS`` decode steps at per-row positions fed ``forced`` [n,
    DECODE_STEPS] -> (logits [n, 1 + DECODE_STEPS, V] float32, per prompt the
    experts chosen int32 [routed layers, len(prompt) + DECODE_STEPS, k])."""
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
        first, prefill_chosen = [], []
        for j, p in enumerate(padded):
            local = tfm.init_cache(cfg, 1, p.shape[1], dtype=cfg.dtype)
            logits, local, chosen = tfm.apply_with_cache(
                cfg, params, p, local, 0, last_index=lens[j] - 1, return_routing=True)
            first.append(logits[0, 0])
            prefill_chosen.append(chosen[:, 0])  # [layers, bucket, k]
            cache = tfm.update_cache_slot(cache, local, j)

        def decode(carry, toks):
            cache, pos = carry
            logits, cache, chosen = tfm.apply_with_cache(
                cfg, params, toks[:, None], cache, pos, write_pos=pos, return_routing=True)
            return (cache, pos + 1), (logits[:, 0], chosen[:, :, 0])  # [n, V], [layers, n, k]

        _, (steps, step_chosen) = lax.scan(decode, (cache, lens), forced.T)
        logits = jnp.concatenate([jnp.stack(first)[:, None], steps.transpose(1, 0, 2)], axis=1)
        return logits, prefill_chosen, step_chosen.transpose(2, 1, 0, 3)  # [n, layers, steps, k]

    logits, prefill_chosen, step_chosen = jax.jit(probe)(
        params, padded, lens, np.asarray(forced, np.int32))
    chosen = [np.concatenate([np.asarray(pc)[:, :n], np.asarray(sc)], axis=1)
              for pc, sc, n in zip(prefill_chosen, step_chosen, lens)]
    return np.asarray(logits, np.float32), chosen


def served_choices(log: list, uids: list, lens: list) -> list:
    """The experts the ENGINE chose for each check request, out of its worker's
    ``routing_log``: per request int32 [routed layers, len(prompt) +
    DECODE_STEPS, k], the prompt's own rows of its prefill (not the bucket's
    padding), then the row of each decode step."""
    rows = [[None] * (1 + DECODE_STEPS) for _ in uids]
    slot_of = {}
    for rec in log:
        if rec["span"] == "prefill" and rec["uid"] in uids:
            j = uids.index(rec["uid"])
            slot_of[rec["slot"]] = j
            rows[j][0] = rec["chosen"][:, 0, :lens[j]]
        elif rec["span"] == "decode":
            for slot, j in slot_of.items():
                i = int(rec["pos"][slot]) - lens[j]  # the step that reads position len + i
                if rec["active"][slot] and 0 <= i < DECODE_STEPS:
                    rows[j][1 + i] = rec["chosen"][:, slot]  # [layers, 1, k]
    if any(r is None for per in rows for r in per):
        raise RuntimeError("the engine's routing log lacks a call of a check request")
    return [np.concatenate(per, axis=1) for per in rows]


def judge(reference, program, params, prompts, got, probe, probe_chosen, engine_chosen) -> dict:
    """The comparison of the module docstring. ``got``: the tokens the engine
    emitted per prompt, ``engine_chosen`` the experts it chose on the way;
    ``probe`` / ``probe_chosen``: ``probe_logits``'s."""
    whole = lambda leaves: leaves  # all on the one chip
    seqs = [np.concatenate([p, g[:DECODE_STEPS]]) for p, g in zip(prompts, got)]
    rows = [np.arange(len(p) - 1, len(p) + DECODE_STEPS) for p in prompts]
    passed = lambda routing: reference.routed_passes(program, params, seqs, rows, fetch=whole,
                                                     routing=routing)
    same = all(np.array_equal(a, b) for a, b in zip(probe_chosen, engine_chosen))
    of_probe, free = passed(probe_chosen), passed(None)
    of_engine = of_probe if same else passed(engine_chosen)
    err = max(float(np.max(np.abs(x - ref))) for x, ref in zip(probe, of_probe["logits"]))
    free_err = max(float(np.max(np.abs(x - ref))) for x, ref in zip(probe, free["logits"]))
    tie_gap = max(float(np.max(ref.max(axis=-1) - ref[np.arange(len(g)), g]))
                  for ref, g in zip(of_engine["logits"], got))
    slack = max(of_engine["slack"], of_probe["slack"])
    finite = bool(np.isfinite(probe).all())
    return {"ok": (finite and err <= LOGIT_TOL and tie_gap <= LOGIT_TOL
                   and slack <= ROUTING_TOL),
            "logit_max_abs_err": err, "token_gap_to_reference_top": tie_gap,
            "routing_slack": of_engine["slack"], "probe_routing_slack": of_probe["slack"],
            "reference_logit_std": float(np.std(of_engine["logits"][-1])),
            "logit_tol": LOGIT_TOL, "routing_tol": ROUTING_TOL,
            # not judged
            "routing_differs_share": of_engine["differ"],
            "logit_max_abs_err_free_routing": free_err,
            "engine_and_probe_chose_alike": same}


def _check(run, srv, Request) -> dict:
    """The check prompts through the engine that is timed, its routing log on;
    then the probe and ``judge``."""
    reference = load_reference(run.program)
    rng = np.random.default_rng([run.seed, 0xC4EC])
    vocab = run.program["vocab_size"]
    budget = run.sized("deployment")["max_seq_len"]
    lens = [min(n, budget - DECODE_STEPS - 2)
            for n in (*CHECK_PROMPT_LENS, FLASH_PROMPT_LEN, LONG_PROMPT_LEN)]
    prompts = [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]
    reqs = [Request(uid=WARM_UID + i, prompt=p, max_new_tokens=DECODE_STEPS + 1)
            for i, p in enumerate(prompts)]
    srv.worker.routing_log = log = []
    try:
        results = srv.serve(reqs)
    finally:
        srv.worker.routing_log = None
    got = [np.asarray(results[r.uid].tokens, np.int32) for r in reqs]
    if any(results[r.uid].status != "ok" or len(g) != DECODE_STEPS + 1
           for r, g in zip(reqs, got)):
        return {"ok": False, "why": "a check request did not complete"}
    engine_chosen = served_choices(log, [r.uid for r in reqs], lens)
    del log[:]
    params = srv.engine.params
    buckets = [serve._bucket(srv, len(p)) for p in prompts]
    probe, probe_chosen = probe_logits(srv.engine.cfg, params, prompts, buckets,
                                       np.stack([g[:DECODE_STEPS] for g in got]))
    out = judge(reference, run.program, params, prompts, got, probe, probe_chosen, engine_chosen)
    return {**out, "check_buckets": buckets}


def run(run) -> dict:
    """``serve.run`` — build, check, warm-up, measured loop, ``ctx`` — with the
    check above where it calls ``_check``; the ``ctx`` gains the engine's
    ``worker``, whose account of its cache a reader wants."""
    seen = {}

    def check(run, srv, Request):
        seen["worker"] = srv.worker
        return _check(run, srv, Request)

    with mock.patch.object(serve, "_check", check):
        ctx = serve.run(run)
    return {**ctx, **seen}
