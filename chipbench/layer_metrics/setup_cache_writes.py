"""Programs the persistent compile cache did not hold and now keeps: set-up's
``xla/compile`` spans whose ``cache`` says ``written``. 0 in a run after a run of
the same tree."""
from . import setup_spans as S

NAME, UNIT, LAYER = "setup_cache_writes", "count", "start-up"


def read(ctx):
    written = S.xla(ctx, "compile", cache=("written",))
    return None if written is None else len(written)
