"""Driver for a serving cell whose model has layers of two OPERATORS (attention,
or a gated delta rule in its place: Qwen3-Next), a routed feed-forward of which the
program holds a share and a slot cache with a float32 matrix a sequence a delta
layer: ``serve_shortconv.py``'s twin, with the cell's own check prompts, limits and
twin key, and nothing else. The build, the warm-up, the measured loop, the
instrumentation and the ``ctx`` are ``serve.py``'s own; the check (the engine that
is TIMED serves the prompts with its routing log on; its tokens are held to the
reference under ITS choices, the probe's logits under the probe's, both routings to
the reference's router), ``judge`` and ``served_choices`` are ``serve_latent.py``'s;
the probe (the one that hands a padded prefill its live rows, on whatever leaves the
cache has) is ``serve_kinds.py``'s: all imported, none copied. ``run`` below calls
``serve_latent.run`` with this file's prompts, limits and that probe in the place of
that module's.

Why other prompts. The cell's traffic is 3,072 to 7,680 tokens: the 4,096 and 8,192
prefill buckets. Here: 100 and 300 tokens (the 128 and 512 buckets: dense attention,
two and five chunks of the rule's block form, a tail that is mostly the old one),
about 3,500 and about 7,000 (both of the buckets the traffic is timed on, both
padded: the flash kernel at a 256-wide head in two layers, 55 and 110 chunks in
six), each followed by ``DECODE_STEPS`` steps of the recurrence from the state the
block form left. Every prompt is padded, so the state a delta layer hands the
decode steps must be that of the LIVE rows: a probe without the live-row mask is
refused by the program by name. At a rehearsal's budget the prompts are cut to it.
"""

from __future__ import annotations

from unittest import mock

from ..references import load_reference, program_of
from . import serve_latent
from .serve_kinds import probe_logits

CHECK_PROMPT_LENS = (100, 300, 3500, 7000)
TWIN = "rehearse_delta_program"

# This cell's own limits, in ``serve_latent``'s rule (its ``judge``, its quantities), each set
# between two readings on the chip at the cell's own size (PR 52; PERF.md section 6 has every
# run). ISSUE 52 asked for ``serve.py``'s 0.09; honest bfloat16 compute reads twice that here:
# a delta layer passes a relative error of its input on about twice over (its q . k, k . k and
# beta v are products of rounded projections, then a norm divides by the output's own size:
# measured in float32 on the CPU, a 2^-9 relative perturbation of one layer's input comes out
# 1.9 x as large at 128-wide heads) where an attention layer averages it down, and six of the
# eight layers are such. ``logit_max_abs_err``: sound 0.179 to 0.232 over fourteen seeds (the
# token's gap to the reference's top 0 to 0.105), the reference itself through float8 (e4m3)
# matrices 2.48 (gap 0.81): the limit is 1.7 x the largest sound reading and a sixth of the
# fault's. ``routing_slack`` (in standard deviations of a layer's scores, here a softmax over
# 512 experts whose tenth and eleventh lie a few hundredths of one apart): sound 0.71 to 1.10,
# float8 19.6: the limit is 1.8 x and a tenth.
LOGIT_TOL = 0.40
ROUTING_TOL = 2.0


# A rehearsal's own limits (the CPU, bfloat16, the twin's widths: 16-wide heads on a hidden
# state of 64). A delta layer passes a relative error of its input on about twice over (q . k,
# k . k and beta v are products of rounded projections, and the gated norm divides by the
# output's own size) where an attention layer averages it down, and the twin's six are narrow:
# bfloat16 compute reads 0.58 to 0.88 and a slack of 1.8 to 1.9 there, float8 matrices in the
# reference 4.3 and 9.0 (``experiments/delta_chip.py --tiny --float8``). A rehearsal prints no
# result; what its ``correct`` guards is the control flow. The faults the check must catch are
# planted in float32 against the cell's limits above (``tests/test_qwen3_next_engine.py``).
REHEARSAL_LOGIT_TOL = 1.8
REHEARSAL_ROUTING_TOL = 4.0


def as_this_cell(logit_tol=None, routing_tol=None):
    """``serve_latent`` with the prompts, the limits above (or those handed in) and
    ``serve_kinds``' probe in the place of its own, for as long as the context is open."""
    short, flash, long = CHECK_PROMPT_LENS[:2], CHECK_PROMPT_LENS[2], CHECK_PROMPT_LENS[3]
    return mock.patch.multiple(serve_latent, CHECK_PROMPT_LENS=short, FLASH_PROMPT_LEN=flash,
                               LONG_PROMPT_LEN=long, probe_logits=probe_logits,
                               LOGIT_TOL=logit_tol or LOGIT_TOL,
                               ROUTING_TOL=routing_tol or ROUTING_TOL)


def run(run) -> dict:
    """``serve_latent.run`` with the prompts above and ``serve_kinds``' probe. A
    rehearsal runs the configuration's ``rehearse_delta_program``, the tiny twin WITH
    delta layers (``rehearse_program`` is the one ``parity.py``'s cache case can take,
    which has none: the configuration's notes say why), so that ``--rehearse`` drives
    the state and the readers of it."""
    limits = {}
    if run.rehearse:
        run.program = program_of(run.config, TWIN)
        load_reference(run.program)  # a key the reference does not cover: refused by name
        limits = dict(logit_tol=REHEARSAL_LOGIT_TOL, routing_tol=REHEARSAL_ROUTING_TOL)
    with as_this_cell(**limits):
        return serve_latent.run(run)
