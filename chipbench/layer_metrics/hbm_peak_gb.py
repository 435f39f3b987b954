"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, in GB. The
compiler's ``memory_analysis()`` of the main program is printed beside it on
the run's "setup" line."""
NAME, UNIT, LAYER = "hbm_peak_gb", "GB", "device"


def read(ctx):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in ctx["devices"]]
    peaks = [p for p in peaks if p]
    return max(peaks) / 1e9 if peaks else None
