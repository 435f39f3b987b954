"""Seconds of set-up spent loading programs from the persistent compile cache:
the ``xla/compile`` spans whose ``cache`` says ``hit`` (retrieval, deserialising,
loading onto the device)."""
from . import setup_spans as S

NAME, UNIT, LAYER = "setup_cache_load_s", "s", "start-up"


def read(ctx):
    return S.seconds(S.xla(ctx, "compile", cache=("hit",)))
