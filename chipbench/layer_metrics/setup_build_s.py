"""Seconds of the program's own build in set-up: the ``startup/build`` spans
(``build_serving_engine`` / ``deepspeed_tpu.initialize``: mesh, weights, caches,
state), the compiles inside them included. A phase, not a share: it contains
some of what ``setup_trace_lower_s`` / ``setup_compile_s`` / ``setup_cache_load_s``
count."""
from . import setup_spans as S

NAME, UNIT, LAYER = "setup_build_s", "s", "start-up"


def read(ctx):
    return S.seconds([sp for sp in S.in_setup(ctx) if sp.path == S.BUILD]) or None
