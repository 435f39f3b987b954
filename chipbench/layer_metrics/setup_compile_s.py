"""Seconds XLA really compiled in set-up: the ``xla/compile`` spans whose
``cache`` says ``written`` or ``not_kept`` (a hit is ``setup_cache_load_s``).

Also writes the ``setup_anatomy`` note (``setup_spans.note``): process start to
the first span, the build's phases, the calls that compiled, trace / lower /
compile / load seconds by whose program it was (the build's, a first call's, the
harness's own), what the cache did not keep, the ten programs with most seconds,
what no kept span accounts for, and the kept list's length and drops."""
from . import setup_spans as S

NAME, UNIT, LAYER = "setup_compile_s", "s", "start-up"


def read(ctx):
    S.note(ctx)
    return S.seconds(S.xla(ctx, "compile", cache=("written", "not_kept")))
