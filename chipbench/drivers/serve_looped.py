"""Driver for a serving cell whose model runs its layer stack several times over the
SAME weights (``layer_passes``: Ouro) and keeps K/V per (pass, layer):
``serve.py`` with the cell's own check prompts and limits, and nothing else. The
build, the warm-up, the measured loop, the instrumentation and the ``ctx`` are
``serve.py``'s own; the check (the engine that is TIMED serves the prompts; a probe
of the serving path's own computation on any cache tree; ``judge``) is
``serve_recurrent.py``'s, imported and not copied: ``run`` below calls ``serve.run``
with that module's ``_check`` under this file's prompts and limits in the place of
``serve._check``, and the ``ctx`` gains the engine's ``worker``, whose account of its
cache ``loop_cache_bytes_per_token`` reads (``serve.py``'s ``ctx`` has none).

Why other prompts. ``serve.py``'s are two (200 and 97 tokens: two buckets) and its
probe takes exactly two. This cell's traffic is timed on THREE prefill programs (the
128, 256 and 512 buckets), so the prompts are about 100, 200 and 400 tokens: all
three buckets, all padded, each followed by ``DECODE_STEPS`` decode steps through
the cache. The decode steps are where a pass that reads another pass's K/V shows: a
prefill attends to its own block in every pass.

What is judged is ``serve.py``'s: (a) the probe's logits (bucket-padded prefill into
a slot cache 48 cache layers deep, then decode steps through it, fed the engine's
tokens) agree with the plain float32 reference's full forward pass of four passes
within ``LOGIT_TOL``; (b) every token the engine emitted lies within ``LOGIT_TOL`` of
the reference's top logit at its step; (c) the reference's logits have a standard
deviation inside ``LOGIT_STD`` (a draw whose logits were tiny would pass anything).
"""

from __future__ import annotations

from unittest import mock

from . import serve, serve_recurrent

CHECK_PROMPT_LENS = (100, 200, 400)

# This cell's own limit, set between two readings on the chip at the cell's own size
# (PR 56; PERF.md section 6 has every run). ISSUE 56 asked for ``serve.py``'s 0.09 unless
# the cell's readings stood astride it; honest bfloat16 compute reads twice that here:
# a token's stream goes through 48 (pass, layer) applications, 96 branches each normed to
# unit size before it is added (so every branch's rounding counts at full weight), and the
# stream itself is normed and rounded again between the passes. ``logit_max_abs_err``
# (logits of standard deviation 1.0): sound 0.173 to 0.233 over twelve seeds (the token's gap
# to the reference's top 0.011 to 0.103); the reference itself on float8 (e4m3) matrices,
# put in the probe's place and held to the float32 reference by ``judge`` under these limits:
# 3.06, ``ok`` false, and by this limit alone (the tokens are the sound engine's: gap 0.046);
# the probe with a pass too few 4.46 to 4.72, with a decode step that reads the pass before's
# K/V 4.52 to 4.69, the norm between the passes dropped 6.07 to 6.33, a branch norm dropped
# 4.75 to 4.87: ``ok`` false each. The limit is 1.7 x the largest sound reading, between a
# seventh and an eighth of float8's and an eleventh of the nearest fault's. It does NOT tell
# the norm between the passes computed in bfloat16 from float32 (0.220 against 0.203 and 0.197
# against 0.222 on the same prompts): that is inside the seeds' own spread, one rounding among
# a hundred. That arithmetic is held where it stands alone, on the CPU, bit for bit
# (``tests/test_ouro.py``: the norm between passes is float32 arithmetic under bfloat16 compute).
LOGIT_TOL = 0.40
LOGIT_STD = (0.5, 2.0)

# A rehearsal's own limit (the CPU, bfloat16, the twin's widths: 16-wide heads on a hidden
# state of 64, three passes of two layers). A rehearsal prints no result; what its ``correct``
# guards is the control flow. The faults the check must catch are planted in float32
# (``tests/test_ouro_cache.py``).
REHEARSAL_LOGIT_TOL = 0.5  # bfloat16 on the CPU reads 0.07 to 0.10 at the twin's widths


def as_this_cell(logit_tol=None):
    """``serve_recurrent`` with the prompts and the limits above (or the one handed
    in) in the place of its own, for as long as the context is open."""
    return mock.patch.multiple(serve_recurrent, CHECK_PROMPT_LENS=CHECK_PROMPT_LENS,
                               LOGIT_TOL=logit_tol or LOGIT_TOL, LOGIT_STD=LOGIT_STD)


def run(run) -> dict:
    """``serve.run`` with ``serve_recurrent._check`` under this cell's prompts and
    limits where it calls ``_check``; the ``ctx`` gains the engine's ``worker``."""
    seen = {}

    def check(run, srv, Request):
        seen["worker"] = srv.worker
        with as_this_cell(REHEARSAL_LOGIT_TOL if run.rehearse else None):
            return serve_recurrent._check(run, srv, Request)

    with mock.patch.object(serve, "_check", check):
        ctx = serve.run(run)
    return {**ctx, **seen}
