"""1 - (union of the intervals in which an operation ran on the device) over
the traced window, on the device that was idle most."""
NAME, UNIT, LAYER = "device_idle_pct", "%", "device"


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s_worst"] / tr["window_s"])
