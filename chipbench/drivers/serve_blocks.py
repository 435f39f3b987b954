"""Driver for a serving cell whose model GENERATES BY DIFFUSION OVER BLOCKS
(``attn_block_length`` B > 1: SDAR): ``drivers/serve.py`` with this file's check, the
block-step program in the place of the decode program wherever ``serve.py`` reaches for
one, and nothing else. The build, the warm-up, the measured loop and the ``ctx`` are
``serve.py``'s own (``run`` below calls ``serve.run`` under this file's patches, as
``serve_routed.py`` and ``serve_chunked_kinds.py`` do).

What the engine runs. A device step is ``SlotWorker.block_step``: every slot's open block
of B positions through the layers, under the mask that is causal between blocks, written
into the slot cache before it attends. A block takes T denoising passes (each reveals B /
T of its masked positions, the most confident) and one more, the COMMIT, over its final
tokens, which leaves its final K/V; a request receives its tokens a block at a time. So a
step yields B / (T + 1) tokens a slot, and every serving metric that assumed one is read
by this cell's own readers (``layer_metrics/block_*``, ``tokens_per_block_step``).

The check, in set-up, at the timed sizes, of BOTH programs that touch a block:

* **the timed engine's tokens and reveals.** Two prompts (``CHECK_PROMPT_LENS``: P mod B
  = 0 and 3, so one opens its first block with B masks and one with three prompt tokens in
  place) are served by the engine that is timed, greedy, with its block log and its
  routing log on (the programs are the same with the logs on or off). From the logs every
  pass is replayed: the sequence as it stood (the mask token's id where masked), which
  positions the pass revealed, to which tokens, and the experts the program chose for
  every row. The reference (``references/sdar_moe.py``, float32, whole forward passes,
  ``routed_passes`` given the ENGINE's choices) is run on each pass's sequence, all in one
  pass over its layers. Judged: every revealed token lies within ``LOGIT_TOL`` of the
  reference's top logit at its row; the revealed rows' reference confidences (log
  softmax of the token) lie within ``REVEAL_TOL`` of the best masked row's (the engine may
  break a near tie in the ORDER the other way and nothing more); the routing slack <=
  ``ROUTING_TOL``.
* **the timed program's own arithmetic.** The block-step program hands back every row's
  confidence, the probability of its top token under the softmax over the vocabulary, as
  an output that is always there (it stays on the device unless the block log is on). Over
  every block row of every pass: |log confidence(engine) - (top logit - logsumexp)
  (reference)| <= ``CONF_TOL``. This is the number a lower precision IN the timed step
  moves: it is read from ``SlotWorker._block_prog``'s own output, not from a probe.
* **the logits, on a probe at the timed shapes.** The engine returns tokens, not logits.
  The probe is the block step's own computation (``serving._forward`` over [n_slots, B]
  rows at per-row ``pos`` / ``write_pos``) ON THE ENGINE'S OWN CACHE, the prompts'
  whole blocks prefilled into two free slots by the engine's OWN prefill programs, then
  every pass of the first two generated blocks and the first of the third, teacher-forced
  with the engine's reveals, the commit passes among them; it returns the two slots'
  logits. max |probe - reference under the PROBE's choices| over every block row of
  every pass <= ``LOGIT_TOL``; the probe's slack <= ``ROUTING_TOL``.

A commit that is skipped, a block written one position off, the causal mask inside a
block, the masked rows ranked the wrong way round and float8 expert matrices each fail it,
planted in the TIMED engine (``experiments/block_chip.py`` plants them on the chip,
``tests/sdar_cases.py`` on the CPU; the readings are beside the limits below).
"""

from __future__ import annotations

from unittest import mock

import numpy as np

from ..references import load_reference, program_of
from . import serve
from .serve import WARM_UID

CHECK_PROMPT_LENS = (200, 99)  # P mod 4 = 0 and 3; the 256 and 128 buckets
CHECK_NEW_TOKENS = 9  # two blocks and the first token of a third behind a whole prompt
CHECK_BLOCKS = 2  # every pass of this many generated blocks, and the first pass of one more

# This cell's own limits, each set between two readings on the chip at the cell's own
# size, 64 slots x 3,072, bf16, published widths (my chip runs, PR 63; PERF.md section 6 and
# README_blocks.md have every reading): the largest a sound system read over its seeds, and
# the smallest a planted fault read. First round: ``experiments/block_chip.py`` on one engine
# over six seeds, call 224, and nine runs of the cell, calls 224 and 225 (fifteen seeds).
# Review round: calls 229 and 230, six sound seeds, every fault on two seeds, each fault of
# the engine planted in the TIMED engine; call 231, four more runs of the cell.
#
# ``LOGIT_TOL``: max |probe - reference under the probe's choices| over the 76 block rows of
# the 19 passes (logits of standard deviation 1.00) read 0.0364 to 0.0434; the engine's
# revealed tokens lay 0.000 to 0.0144 under the reference's top. Faults: the TIMED engine on
# float8 (e4m3) expert matrices (the probe runs on the engine's weights) 0.149 and 0.159;
# the reference on float8 matrices in the probe's place 0.153 and 0.173; in the probe's
# steps the commit skipped 0.330 and 0.390, a block's K/V one position off 0.354 and 0.378,
# the causal mask inside a block 0.351 and 0.404: ``ok`` false each. 0.085: 2.0 x the
# largest sound reading, 0.57 x the smallest fault.
LOGIT_TOL = 0.085
# ``CONF_TOL``: the TIMED block-step program's own number, max |log confidence(engine) -
# (top logit - logsumexp)(reference)| over the same 76 rows. A row's top logit is one
# element where ``LOGIT_TOL`` bounds the worst of 11.5 million, so it reads lower. Sound,
# ten seeds (six on one engine, four runs of the cell, call 231): 0.0142 to 0.0241. Faults,
# all IN the timed engine: float8 (e4m3) expert matrices 0.0653 and 0.0787 (the precision
# below the configuration's: caught by this limit and by the probe's), the commit skipped
# 0.0871 and 0.113, the K/V one position off 0.0880 and 0.0977, the causal mask in its own
# block step 0.0945 and 0.107. 0.040 is the geometric middle of 0.0241 and 0.0653: 1.7 x the
# largest sound reading, 0.61 x the smallest fault. (The four runs of call 231 were judged
# at 0.037, the middle of the first six seeds' 0.0213; they read 0.0163 to 0.0241.)
CONF_TOL = 0.040
# ``ROUTING_TOL``: the largest routing slack either program may show, in standard deviations
# of a layer's router logits, over 7 layers x the passes' sequences. Sound: 0.0189 to 0.0448
# (engine and probe alike). Faults: the probe's 0.364 and 0.384 (commit skipped), 0.482 and
# 0.492 (causal mask inside a block), 0.497 and 0.576 (K/V one off); in the TIMED engine
# 0.594 and 0.635 (its commits skipped), 0.576 and 0.609 (its K/V one off), 0.492 and 0.631
# (the causal mask in its own block step, traced again inside the plant). 0.13 is the
# geometric middle of 0.0448 and 0.364: 2.9 x the largest sound reading, 0.36 x the smallest
# fault. (Float8 expert matrices in the timed engine read 0.110 here: under it, and caught
# by the two limits above.)
ROUTING_TOL = 0.13
# ``REVEAL_TOL``: the log-confidence a revealed row may lie under the best masked row of its
# pass. Sound: 0.000 to 0.0163 (a near tie broken the other way). What it is for: the TIMED
# engine's block step ranking a block's masked rows the wrong way round (traced again inside
# the plant) reads 0.270 and 0.426 at the cell's size, with every other number sound (each
# token it reveals is still its row's arg-max). The other faults of the engine read 0.0098
# to 0.071 here and are caught by the limits above. 0.06 lies between 0.0163 and 0.270: 3.7 x
# the largest sound reading, 0.22 x the smallest fault (their geometric middle is 0.066).
REVEAL_TOL = 0.06
# A rehearsal's own limits (the CPU, bfloat16, the twin's widths: 24-wide heads on a hidden
# state of 64). A rehearsal prints no result; what its ``correct`` guards is the control
# flow. The faults the check must catch are planted in float32 (``tests/test_sdar_engine.py``).
REHEARSAL_TOL = {"LOGIT_TOL": 0.5, "CONF_TOL": 0.5, "ROUTING_TOL": 0.5, "REVEAL_TOL": 1.0}


def replay(block_log: list, routing_log: list, uids: list, prompts: list, B: int,
           mask_id: int) -> list:
    """The engine's logs -> per request ``{"prefill": chosen [layers, whole, k] or None,
    "passes": [...]}``; a pass: ``start``, ``sequence`` (as it stood: the mask id where
    masked), ``masked`` (positions at entry), ``revealed`` ({position: token}), ``commit``
    and ``chosen`` [layers, B, k]. A block step's two log entries are appended at the same
    fetch, so the two logs pair off in order."""
    steps = [r for r in routing_log if r["span"] == "block_step"]
    if len(steps) != len(block_log):
        raise RuntimeError("the engine's routing log and block log do not pair off")
    out = []
    for uid, prompt in zip(uids, prompts):
        P = len(prompt)
        whole = P - P % B
        pre = [r for r in routing_log if r["span"] == "prefill" and r.get("uid") == uid]
        if whole and not pre:
            raise RuntimeError("the engine's routing log lacks a check request's prefill")
        slot = int(pre[-1]["slot"]) if pre else None
        seq = [int(t) for t in prompt[:whole]]
        passes, mask_before = [], None
        for rec, routed in zip(block_log, steps):
            if slot is None:  # (a prompt under one block: the slot of its first step)
                opened = np.flatnonzero(rec["opened"] & rec["active"])
                slot = int(opened[0]) if len(opened) == 1 else None
            if slot is None or not rec["active"][slot] or int(rec["pos"][slot]) < whole:
                continue
            start = int(rec["pos"][slot])
            if start != len(seq):
                continue  # another request's stay in the slot
            if rec["opened"][slot]:
                first = start == whole
                block = [int(t) for t in prompt[whole:]] + [mask_id] * (B - P % B) if first \
                    else [mask_id] * B
                mask_before = np.arange(B) >= (P % B if first else 0)
            else:
                block = passes[-1]["after"]
            toks, mask = rec["toks"][slot], rec["mask"][slot]
            revealed = {start + i: int(toks[i]) for i in np.flatnonzero(mask_before & ~mask)}
            passes.append({
                "start": start, "sequence": np.asarray(seq + block, np.int32),
                "masked": [start + int(i) for i in np.flatnonzero(mask_before)],
                "revealed": revealed, "commit": not mask_before.any(),
                "chosen": routed["chosen"][:, slot], "conf": rec["conf"][slot],
                "after": [mask_id if m else int(t) for t, m in zip(toks, mask)]})
            mask_before = mask.copy()
            if passes[-1]["commit"]:
                seq = seq + passes[-1]["after"]
        out.append({"prefill": pre[-1]["chosen"][:, 0, :whole] if pre else None, "slot": slot,
                    "passes": passes})
    return out


def cut(passes: list, blocks: int = CHECK_BLOCKS) -> list:
    """The passes of the first ``blocks`` blocks and the first pass of the next."""
    starts = sorted({p["start"] for p in passes})
    keep = [p for p in passes if p["start"] in starts[:blocks]]
    return keep + [p for p in passes if p["start"] in starts[blocks:blocks + 1]][:1]


def routing_of(request: dict, upto: int, chosen_of) -> np.ndarray:
    """The experts chosen for every position of pass ``upto``'s sequence [layers, S, k]: the
    prompt's whole blocks from the prefill, each finished block from ITS commit pass (the
    pass that wrote the K/V later blocks read), the open block from ``chosen_of(pass)``."""
    passes = request["passes"]
    parts = [] if request["prefill"] is None else [request["prefill"]]
    parts += [chosen_of(p) for p in passes[:upto]
              if p["commit"] and p["start"] < passes[upto]["start"]]
    return np.concatenate(parts + [chosen_of(passes[upto])], axis=1)


def probe_passes(srv, requests: list, prompts: list, *, skip_commit: bool = False,
                 write_off: int = 0) -> None:
    """Every pass of ``requests`` (``replay``'s, cut) through the block step's own
    computation at the timed shapes, on the ENGINE's cache and by the engine's own prefill
    programs, in two free slots: each pass gains ``probe_logits`` [B, V] float32 and
    ``probe_chosen`` [layers, B, k]. ``skip_commit`` / ``write_off``: planted faults (a
    commit pass that does not run; a block written that many positions off)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference import serving

    w = srv.worker
    cfg, n, B = w.cfg, w.n_slots, w.block_len

    def step(params, cache, toks, pos, wpos, active):
        on = jnp.broadcast_to(active[:, None], toks.shape)
        logits, cache, (_, chosen) = serving._forward(cfg, params, toks, cache, pos, on,
                                                      write_pos=wpos)
        return cache, logits[:len(requests)].astype(jnp.float32), chosen[:, :len(requests)]

    step = jax.jit(step, donate_argnums=(1,), out_shardings=(w._cache_shardings, None, None))
    log, w.routing_log = w.routing_log, []
    for slot, (req, prompt) in enumerate(zip(requests, prompts)):
        whole = len(prompt) - len(prompt) % B
        if whole:  # the engine's own prefill program, into slot ``slot`` (free: set-up)
            bucket = serve._bucket(srv, whole)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :whole] = prompt[:whole]
            w.prefill(bucket, padded, slot, whole, 0.0, 0, 1.0, uid=WARM_UID + 50 + slot)
            req["probe_prefill"] = w.routing_log[-1]["chosen"][:, 0, :whole]
        else:
            req["probe_prefill"] = None
        for p in req["passes"]:
            if skip_commit and p["commit"]:
                p["probe_logits"], p["probe_chosen"] = None, p["chosen"]
                continue
            toks = np.zeros((n, B), np.int32)
            toks[slot] = p["sequence"][p["start"]:]
            pos = np.zeros((n,), np.int32)
            pos[slot] = p["start"]
            active = np.arange(n) == slot
            w._cache, logits, chosen = step(w.params, w._cache, toks, pos,
                                            np.where(active, pos + write_off, w.Smax)
                                            .astype(np.int32), active)
            p["probe_logits"] = np.asarray(logits)[slot]
            p["probe_chosen"] = np.asarray(chosen)[:, slot]
    w.routing_log = log


def judge(reference, program, params, requests: list, limits: dict) -> dict:
    """The comparison of the module docstring over ``requests`` (``replay``'s, cut, with the
    probe's logits and choices on each pass)."""
    whole = lambda leaves: leaves  # noqa: E731  (all on the one chip)
    B = int(program["attn_block_length"])
    flat = [(req, i) for req in requests for i in range(len(req["passes"]))]
    seqs = [req["passes"][i]["sequence"] for req, i in flat]
    rows = [np.arange(len(s) - B, len(s)) for s in seqs]
    out = {"logit_max_abs_err": 0.0, "engine_log_conf_err": 0.0,
           "token_gap_to_reference_top": 0.0, "reveal_gap_log_conf": 0.0, "routing_slack": -np.inf, "probe_routing_slack": -np.inf}
    for which in ("engine", "probe"):
        chosen_of = (lambda p: p["chosen"]) if which == "engine" else (lambda p: p["probe_chosen"])
        routing = []
        for req, i in flat:
            own = dict(req, prefill=req["prefill"] if which == "engine" else req["probe_prefill"])
            routing.append(routing_of(own, i, chosen_of))
        ref = reference.routed_passes(program, params, seqs, rows, fetch=whole, routing=routing)
        slack_key = "routing_slack" if which == "engine" else "probe_routing_slack"
        out[slack_key] = max(out[slack_key], ref["slack"])
        out[f"{which}_routing_differs_share"] = ref["differ"]
        for (req, i), logits in zip(flat, ref["logits"]):
            p = req["passes"][i]
            if which == "probe":
                if p["probe_logits"] is not None:
                    out["logit_max_abs_err"] = max(
                        out["logit_max_abs_err"],
                        float(np.max(np.abs(p["probe_logits"] - logits))))
                out["reference_logit_std"] = float(np.std(logits))
                continue
            z = logits.astype(np.float64)
            lse = np.log(np.sum(np.exp(z - z.max(axis=-1, keepdims=True)), axis=-1)) + z.max(axis=-1)
            # the TIMED program's own number, every row of every pass (greedy: a row's
            # confidence is its top token's): log softmax of the top, engine against reference
            out["engine_log_conf_err"] = max(out["engine_log_conf_err"], float(np.max(np.abs(
                np.log(np.maximum(p["conf"].astype(np.float64), 1e-300)) - (z.max(axis=-1) - lse)))))
            if not p["revealed"]:
                continue
            # what each masked row WOULD reveal and how sure the reference is of it
            best = max(float(z[pos - p["start"]].max() - lse[pos - p["start"]])
                       for pos in p["masked"])
            for pos, tok in p["revealed"].items():
                r = pos - p["start"]
                out["token_gap_to_reference_top"] = max(
                    out["token_gap_to_reference_top"], float(z[r].max() - z[r, tok]))
                out["reveal_gap_log_conf"] = max(
                    out["reveal_gap_log_conf"], best - float(z[r, tok] - lse[r]))
    finite = all(p["probe_logits"] is None or np.isfinite(p["probe_logits"]).all()
                 for req in requests for p in req["passes"])
    out["ok"] = bool(
        finite and out["logit_max_abs_err"] <= limits["LOGIT_TOL"]
        and out["engine_log_conf_err"] <= limits["CONF_TOL"]
        and out["token_gap_to_reference_top"] <= limits["LOGIT_TOL"]
        and out["reveal_gap_log_conf"] <= limits["REVEAL_TOL"]
        and max(out["routing_slack"], out["probe_routing_slack"]) <= limits["ROUTING_TOL"])
    return {**out, "logit_tol": limits["LOGIT_TOL"], "conf_tol": limits["CONF_TOL"],
            "routing_tol": limits["ROUTING_TOL"], "reveal_tol": limits["REVEAL_TOL"]}


def served(run, srv, Request, prompts: list) -> list:
    """The check prompts through the TIMED engine, logs on -> ``replay``'s requests, or
    None where a request did not complete."""
    w = srv.worker
    reqs = [Request(uid=WARM_UID + i, prompt=p, max_new_tokens=CHECK_NEW_TOKENS)
            for i, p in enumerate(prompts)]
    w.routing_log, w.block_log = [], []
    try:
        results = srv.serve(reqs)
        routing_log, block_log = w.routing_log, w.block_log
    finally:
        w.routing_log = w.block_log = None
    if any(results[r.uid].status != "ok" or len(results[r.uid].tokens) != CHECK_NEW_TOKENS
           for r in reqs):
        return None
    return replay(block_log, routing_log, [r.uid for r in reqs], prompts, w.block_len,
                  int(run.program["mask_token_id"]))


def check_prompts(run) -> list:
    rng = np.random.default_rng([run.seed, 0xC4EC])
    budget = run.sized("deployment")["max_seq_len"]
    B = int(run.program["attn_block_length"])
    lens = [min(n, (budget - CHECK_NEW_TOKENS - B) // B * B + n % B) for n in CHECK_PROMPT_LENS]
    return [rng.integers(0, run.program["vocab_size"], size=n).astype(np.int32) for n in lens]


def limits_of(run) -> dict:
    return REHEARSAL_TOL if run.rehearse else {
        "LOGIT_TOL": LOGIT_TOL, "CONF_TOL": CONF_TOL, "ROUTING_TOL": ROUTING_TOL,
        "REVEAL_TOL": REVEAL_TOL}


def _check(run, srv, Request) -> dict:
    reference = load_reference(run.program)
    prompts = check_prompts(run)
    requests = served(run, srv, Request, prompts)
    if requests is None:
        return {"ok": False, "why": "a check request did not complete"}
    for req in requests:
        req["passes"] = cut(req["passes"])
    probe_passes(srv, requests, prompts)
    out = judge(reference, run.program, srv.engine.params, requests, limits_of(run))
    out["passes_checked"] = sum(len(req["passes"]) for req in requests)
    return out


def _instrument(run, worker, method: str, samples: list) -> None:
    """``serve._instrument`` with the block step where ``serve.run`` names the decode step
    (the span and the samples keep ``serve.run``'s word for the loop's device step)."""
    import time

    name = "block_step" if method == "decode" else method
    fn = getattr(worker, name)

    def timed(*args, **kwargs):
        t0 = time.perf_counter()
        with run.span(f"serve.{method}"):
            out = fn(*args, **kwargs)
        samples.append((t0, time.perf_counter()))
        return out

    setattr(worker, name, timed)


def _memory_analysis(run, srv):
    """The compiler's account of the block-step program, lowered again at its own operand
    shapes (a cache hit). Reads the worker's private handles: a rename fails here, loudly."""
    import jax
    import jax.numpy as jnp

    w = srv.worker
    n, B = w.n_slots, w.block_len
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)  # noqa: E731
    vec = lambda d: jax.ShapeDtypeStruct((n,), d)  # noqa: E731
    blk = lambda d: jax.ShapeDtypeStruct((n, B), d)  # noqa: E731
    return run.memory_dict(w._block_prog().lower(
        jax.tree.map(sds, w.params), jax.tree.map(sds, w._cache), sds(w._btoks), sds(w._bmask),
        vec(jnp.bool_), blk(jnp.int32), blk(jnp.bool_), vec(jnp.int32), vec(jnp.int32),
        vec(jnp.bool_), vec(jnp.int32), vec(jnp.float32), sds(w._rng), vec(jnp.float32),
        vec(jnp.int32), vec(jnp.float32)).compile())


def run(run) -> dict:
    """``serve.run`` with the above. A rehearsal runs the configuration's
    ``rehearse_blocks_program``, the tiny twin WITH the block mask (``rehearse_program`` is
    the causal backbone that ``parity.py`` takes)."""
    if run.rehearse:
        run.program = program_of(run.config, "rehearse_blocks_program")
        load_reference(run.program)  # a key the reference does not cover: refused by name
        over = run.cell["rehearse"].get("serving", {})
        run.cell = {**run.cell, "serving": {**run.cell["serving"], **over}}
    with mock.patch.object(serve, "_check", _check), \
            mock.patch.object(serve, "_instrument", _instrument), \
            mock.patch.object(serve, "_memory_analysis", _memory_analysis):
        return serve.run(run)
