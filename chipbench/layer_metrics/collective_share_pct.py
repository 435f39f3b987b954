"""Share of the traced window during which a collective (all-gather,
reduce-scatter, all-reduce, all-to-all, collective-permute) was in flight on
a device, mean over devices. Trace only; absent on one chip."""
NAME, UNIT, LAYER = "collective_share_pct", "%", "sharding / collectives"


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["devices"] < 2:
        return None
    return 100.0 * tr["collective_s_mean"] / tr["window_s"]
