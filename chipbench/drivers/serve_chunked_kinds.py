"""Driver for a serving cell whose model has layers of several KINDS (sliding window
and whole context, each with a rotary of its own: Mellum2) and whose prompts enter
the slot cache IN CHUNKS (``chunked_prefill`` on): ``serve_latent.py`` with the
cell's own check prompts, a probe that takes each prompt through the chunks the
engine cuts it into, the engine's choices read from its ``chunk`` calls, the cell's
own limits, and nothing else. The build, the warm-up, the measured loop, the
instrumentation and the ``ctx`` are ``serve.py``'s own; the check (the engine that is
TIMED serves the prompts with its routing log on; its tokens are held to the
reference under ITS choices, the probe's logits under the probe's, both routings to
the reference's router) and ``judge`` are ``serve_latent.py``'s, imported and not
copied: ``run`` below calls ``serve_latent.run`` with this file's prompts, probe,
``served_choices`` and limits in the place of that module's, as ``serve_kinds.py``
does with its own.

Why other prompts. About 1,500, 5,000 and 12,000 tokens at chunks of 2,048: a lone
padded tail (one 2,048-row chunk at position 0, whose last 1,024 LIVE rows wrap the
ring); two whole chunks and a 1,024-row tail entering at 4,096; five whole chunks
and a 2,048-row tail entering at 10,240, past position 8,192 where YaRN's blended
frequencies have parted from plain ones by whole turns. Every chunk behind the
first enters a window layer's ring past position 0 and reads its whole-context
layers' cache over the key blocks before it. Each prompt is followed by
``DECODE_STEPS`` decode steps through the rings. At a rehearsal's budget the prompts
are cut to it, the chunk is the rehearsal's own (``rehearse.serving``) and the
window of the rehearsal's twin is 16.

Why another probe. ``serve_kinds.probe_logits`` prefills a prompt WHOLE into a local
cache its bucket long: a 16,384-row program of its own, and not what the timed
engine runs. This one is ``SlotWorker._build_chunk``'s computation by the program's
own helpers: the slot's window sliced out of a slot cache AS LONG AS THE ENGINE'S
(``max_seq_len``: the walk's grid, the cache's rows and the decode step's scores
scale with it, so the programs whose logits are held to ``LOGIT_TOL`` have the
shapes of the programs that are timed; one slot a prompt), extended through
``apply_with_cache`` at the chunk's offset under the mask of its live rows, the
chunk's region and the rings written back; then the decode steps, all rows at their
own positions. ``buckets`` (what ``serve._bucket`` gives: here the width of a
prompt's LAST chunk, the program that differs between prompts) is not what cuts a
prompt; the engine's own ``_segments`` is, handed over by ``run``.

Why ``serve._bucket`` is replaced for the length of the run. ``serve._warm`` sends
one request a prefill bucket the traffic reaches, so that nothing compiles in the
window. With chunking on, a prompt's programs are the whole chunk's and its tail's:
the "bucket" that tells two prompts apart is the tail's width, and ``_warm`` then
sends the longest prompt of each tail width (every one of them goes through the
whole chunk's program too).
"""

from __future__ import annotations

from unittest import mock

import numpy as np

from ..references import load_reference, program_of
from . import serve, serve_latent
from .serve import DECODE_STEPS

CHECK_PROMPT_LENS = (1500, 5000, 12000)

# This cell's own limits, each set between two readings on the chip at the cell's own size
# (PR 59; PERF.md section 6 and README_chunked_kinds.md have every run): the largest a sound system read
# over its seeds, and the smallest a wrong program read (``experiments/chunk_chip.py``: the
# probe with one line of the program wrong, and the float32 reference on float8 matrices in
# the probe's place, each through ``judge``).
#
# ``LOGIT_TOL``: max |probe - reference under the probe's choices| over the 27 rows of the
# three prompts (logits of standard deviation 1.00) read 0.0421 to 0.0552 on seventeen seeds with
# the probe's slots as long as the engine's (0.0422 to 0.0516 on twelve before, in slots of
# 12,800), the token's gap to the reference's top 0.000 to 0.027. The wrong programs, read
# twice (the first session's seed; the review's, at the engine's slot length): the window off by
# one 0.155 / 0.279 (one key in 1,024 a query a window layer: the nearest),
# ``attention_factor`` dropped 0.621 / 0.594, the reference on float8 (e4m3) matrices 0.964 /
# 0.842, plain rotary on the full layers 1.319 / 1.208, a ring that a chunk overwrote before its
# queries read it 5.23 / 4.86: ``ok`` false each. 0.09 is the geometric middle of 0.0552 and
# 0.155 to two places: 1.63 x the largest sound reading, 0.58 x the smallest fault (and
# ``serve.py``'s own number: OLMoE's softmax router, whose top-8 weights this model
# renormalises, reads 0.03 to 0.04 under it).
LOGIT_TOL = 0.09
# ``ROUTING_TOL``: the largest routing slack either program may show, in standard deviations
# of a layer's router logits (``references/mellum.py``), over 8 layers x 18,524 tokens. Sound:
# 0.0426 to 0.0634 (engine and probe, twenty-nine seeds). The wrong programs in the probe's
# place: the window off by one 0.609 / 0.445, ``attention_factor`` dropped 0.668 / 0.620,
# float8 0.979 / 0.897, plain rotary 1.61 / 1.36, the overwritten ring 6.78 / 6.59; the
# overwritten ring in the ENGINE's own chunk programs 8.32 (its tokens 3.54 under the
# reference's top). 0.15 is 2.4 x the largest sound reading and a third of the smallest fault.
ROUTING_TOL = 0.15

# A rehearsal's own limit (the CPU, bfloat16, the twin's widths: 24-wide heads on a
# hidden state of 64). A rehearsal prints no result; what its ``correct`` guards is the
# control flow. The faults the check must catch are planted in float32
# (``tests/test_mellum2_cache.py``).
REHEARSAL_LOGIT_TOL = 0.5
REHEARSAL_ROUTING_TOL = 0.5

_SEGMENTS = []  # the engine's own cut of a prompt into chunks (``probe_as``)
_SLOT_LEN = []  # the engine's ``max_seq_len`` (``probe_as``): the probe's slots are as long


def probe_as(srv) -> None:
    """Hand the probe the engine's own cut of a prompt (a private method, so a rename
    fails here, loudly) and the length of its slots."""
    _SEGMENTS[:] = [lambda n: srv._segments(0, int(n))]
    _SLOT_LEN[:] = [srv.worker.Smax]


def tail_width(srv, n: int) -> int:
    """The width of the LAST chunk the engine cuts a prompt of ``n`` tokens into (its
    own rule: a private method, so a rename fails here, loudly)."""
    return int(srv._segments(0, int(n))[-1][1])


def served_choices(log: list, uids: list, lens: list) -> list:
    """``serve_latent.served_choices`` for an engine that admits in chunks: per
    request int32 [routed layers, len(prompt) + DECODE_STEPS, k], the LIVE rows of
    each of its ``chunk`` calls in the order of their ``start`` (an intermediate
    chunk's choices are logged though nothing else of it is fetched), then the row of
    each decode step."""
    chunks = [[] for _ in uids]
    steps = [[None] * DECODE_STEPS for _ in uids]
    slot_of = {}
    for rec in log:
        if rec["span"] == "chunk" and rec["uid"] in uids:
            j = uids.index(rec["uid"])
            slot_of[rec["slot"]] = j
            chunks[j].append((int(rec["start"]), rec["chosen"][:, 0, :int(rec["live"])]))
        elif rec["span"] == "decode":
            for slot, j in slot_of.items():
                i = int(rec["pos"][slot]) - lens[j]  # the step that reads position len + i
                if rec["active"][slot] and 0 <= i < DECODE_STEPS:
                    steps[j][i] = rec["chosen"][:, slot]  # [layers, 1, k]
    out = []
    for j, n in enumerate(lens):
        rows = [c for _, c in sorted(chunks[j], key=lambda sc: sc[0])]
        if sum(c.shape[1] for c in rows) != n or any(s is None for s in steps[j]):
            raise RuntimeError("the engine's routing log lacks a call of a check request")
        out.append(np.concatenate(rows + steps[j], axis=1))
    return out


def probe_logits(cfg, params, prompts, buckets, forced):
    """The serving path's own computation, chunk by chunk: each prompt cut as the
    engine cuts it (``_SEGMENTS``), every chunk padded to its width and taken through
    ``apply_with_cache`` at its offset in the slot's window under its live rows
    (``SlotWorker._build_chunk``), then ``DECODE_STEPS`` decode steps at per-row
    positions fed ``forced`` [n, DECODE_STEPS] -> (logits [n, 1 + DECODE_STEPS, V]
    float32, per prompt the experts chosen int32 [routed layers, len(prompt) +
    DECODE_STEPS, k])."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from deepspeed_tpu.models import transformer as tfm

    cut = _SEGMENTS[0]
    lens = np.asarray([len(p) for p in prompts], np.int32)
    smax = int(_SLOT_LEN[0])

    def chunk(params, cache, toks, slot, start, live):
        width = toks.shape[1]
        local = tfm.slice_cache_slot(cache, slot, smax)
        logits, local, chosen = tfm.apply_with_cache(
            cfg, params, toks, local, jnp.reshape(start, (1,)), last_index=live - 1,
            return_routing=True, live=jnp.arange(width)[None, :] < live)
        new = tfm.slice_cache_slot(local, 0, width, start=start)
        return tfm.update_cache_slot(cache, new, slot, start=start), logits[0, 0], chosen[:, 0]

    def decode(params, cache, lens, forced):
        def step(carry, toks):
            cache, pos = carry
            logits, cache, chosen = tfm.apply_with_cache(
                cfg, params, toks[:, None], cache, pos, write_pos=pos, return_routing=True)
            return (cache, pos + 1), (logits[:, 0], chosen[:, :, 0])  # [n, V], [layers, n, k]

        _, (steps, chosen) = lax.scan(step, (cache, lens), forced.T)
        return steps.transpose(1, 0, 2), chosen.transpose(2, 1, 0, 3)  # [n, layers, steps, k]

    chunk = jax.jit(chunk, donate_argnums=(1,))  # one program a width, as the engine's
    cache = jax.jit(lambda: tfm.init_cache(cfg, len(prompts), smax, dtype=cfg.dtype))()
    first, prefill_chosen = [], []
    for j, p in enumerate(prompts):
        rows = []
        for start, width, live in cut(len(p)):
            toks = np.zeros((1, width), np.int32)
            toks[0, :live] = p[start:start + live]
            cache, logits, chosen = chunk(params, cache, toks, np.int32(j), np.int32(start),
                                          np.int32(live))
            rows.append(np.asarray(chosen)[:, :live])
        first.append(np.asarray(logits, np.float32))  # the last chunk's: the prompt's last row
        prefill_chosen.append(np.concatenate(rows, axis=1))
    steps, step_chosen = jax.jit(decode)(params, cache, lens, np.asarray(forced, np.int32))
    logits = np.concatenate([np.stack(first)[:, None], np.asarray(steps, np.float32)], axis=1)
    chosen = [np.concatenate([pc, np.asarray(sc)], axis=1)
              for pc, sc in zip(prefill_chosen, step_chosen)]
    return logits, chosen


def as_this_cell(rehearse: bool = False, **limits):
    """``serve_latent`` with the prompts, the probe, the reading of the engine's log
    and the limits above (or those handed in) in the place of its own, for as long as
    the context is open."""
    short, mid, long = CHECK_PROMPT_LENS
    own = {"LOGIT_TOL": REHEARSAL_LOGIT_TOL if rehearse else LOGIT_TOL,
           "ROUTING_TOL": REHEARSAL_ROUTING_TOL if rehearse else ROUTING_TOL, **limits}
    return mock.patch.multiple(
        serve_latent, CHECK_PROMPT_LENS=(short,), FLASH_PROMPT_LEN=mid, LONG_PROMPT_LEN=long,
        probe_logits=probe_logits, served_choices=served_choices, **own)


def run(run) -> dict:
    """``serve_latent.run`` with the above. A rehearsal runs the configuration's
    ``rehearse_kinds_program``, the tiny twin WITH window layers and both rotaries
    (``rehearse_program`` is the one ``parity.py``'s cache case can take, which has no
    window layer: the configuration's notes say why), under the cell's
    ``rehearse.serving`` (a chunk the twin's prompts are several of), so that
    ``--rehearse`` drives chunks into rings past position 0 and the readers of them."""
    if run.rehearse:
        run.program = program_of(run.config, "rehearse_kinds_program")
        load_reference(run.program)  # a key the reference does not cover: refused by name
        over = run.cell["rehearse"].get("serving", {})
        run.cell = {**run.cell, "serving": {**run.cell["serving"], **over}}
    build = serve._build

    def built(run):
        srv, dep = build(run)
        probe_as(srv)
        return srv, dep

    with as_this_cell(run.rehearse), mock.patch.object(serve, "_bucket", tail_width), \
            mock.patch.object(serve, "_build", built):
        return serve_latent.run(run)
