"""The part of ``collective_share_pct`` during which no compute operation ran
on that device: communication the step waited for."""
NAME, UNIT, LAYER = "collective_exposed_pct", "%", "sharding / collectives"


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["devices"] < 2:
        return None
    return 100.0 * tr["collective_exposed_s_mean"] / tr["window_s"]
