"""Median duration of the ``train/train_batch`` span over the counted steps:
the host's whole share of a training step (resilience gates, batch placement
and enqueue, telemetry). The call is asynchronous, so this is NOT the step
time: only the caller's ``block_until_ready`` closes a device-true step
(``train_step_ms_p50``)."""
from . import span_ring as R

NAME, UNIT, LAYER = "train_host_ms_p50", "ms", "training engine"


def read(ctx):
    spans = R.started_in(R.train_window(ctx))
    return R.median_ms([sp for sp in spans if R.is_a(sp, "train/train_batch")])
