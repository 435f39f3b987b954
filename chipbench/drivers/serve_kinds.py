"""Driver for a serving cell whose model has layers of several KINDS (sliding
window and whole context in one stack: K-EXAONE) and holds a share of its
experts: ``serve_latent.py`` with the cell's own check prompts and a probe that
hands the prefill its live rows, and nothing else. The build, the warm-up, the
measured loop, the instrumentation and the ``ctx`` are ``serve.py``'s own; the
check (the engine that is TIMED serves the prompts with its routing log on; its
tokens are held to the reference under ITS choices, the probe's logits under the
probe's, both routings to the reference's router), ``judge``, ``served_choices``
and both tolerances are ``serve_latent.py``'s, imported and not copied: ``run``
below calls ``serve_latent.run`` with this file's prompts and probe in the place
of that module's.

Why other prompts. ``serve_latent``'s are constants (200, 97, 900, 2,500 tokens):
two buckets this cell's traffic never uses and none over 4,096, and a check that
never wraps a window layer's ring at a long position checks little. Here: 400,
1,500, 3,000 and 9,000 tokens, the 512, 2,048, 4,096 and 16,384 prefill buckets
(four of the six programs the traffic is timed on). At 64 heads the 512 bucket
attends densely under the window's bias and the others through the flash kernel
with its runtime window (``cache_attention_form``: 4 x 64 x 512^2 = 64 MiB of
scores, 4 x 64 x 2048^2 = 1 GiB); the rings (128 positions) wrap 3, 11, 23 and 70
times before the first decode step; the whole-context layer attends 9,000
positions with no rotary; every prompt is padded (the ring must hold the last 128
LIVE rows, not the bucket's last). At a rehearsal's budget they are cut to it, and
the window of the rehearsal's twin is 16.

Why another probe. ``serve_latent.probe_logits`` hands ``apply_with_cache`` a
padded block with ``last_index`` and no ``live``: a window layer's ring would
keep the bucket's padding, and the program refuses that by name. This one is
that probe with the live-row mask ``SlotWorker._build_prefill`` hands its
prefill, on whatever leaves the cache has (``init_cache`` / ``update_cache_slot``
/ ``apply_with_cache``).
"""

from __future__ import annotations

from unittest import mock

import numpy as np

from ..references import load_reference, program_of
from . import serve_latent
from .serve import DECODE_STEPS

CHECK_PROMPT_LENS = (400, 1500, 3000, 9000)


def probe_logits(cfg, params, prompts, buckets, forced):
    """``serve_latent.probe_logits`` with the live rows: each prompt padded to its
    bucket and prefilled into a local cache the bucket long under the mask of its
    own rows, written into its row of a slot cache by ``update_cache_slot`` (the
    rings whole), then ``DECODE_STEPS`` decode steps at per-row positions fed
    ``forced`` [n, DECODE_STEPS] -> (logits [n, 1 + DECODE_STEPS, V] float32, per
    prompt the experts chosen int32 [routed layers, len(prompt) + DECODE_STEPS, k])."""
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
                cfg, params, p, local, 0, last_index=lens[j] - 1, return_routing=True,
                live=jnp.arange(p.shape[1])[None, :] < lens[j])
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


def run(run) -> dict:
    """``serve_latent.run`` with the prompts and the probe above. A rehearsal runs
    the configuration's ``rehearse_kinds_program``, the tiny twin WITH window
    layers (``rehearse_program`` is the one ``parity.py``'s cache case can take,
    which has none: the configuration's notes say why), so that ``--rehearse``
    drives the rings and the readers of them."""
    if run.rehearse:
        run.program = program_of(run.config, "rehearse_kinds_program")
        load_reference(run.program)  # a key the reference does not cover: refused by name
    short, flash, long = CHECK_PROMPT_LENS[:2], CHECK_PROMPT_LENS[2], CHECK_PROMPT_LENS[3]
    with mock.patch.multiple(serve_latent, CHECK_PROMPT_LENS=short, FLASH_PROMPT_LEN=flash,
                             LONG_PROMPT_LEN=long, probe_logits=probe_logits):
        return serve_latent.run(run)
