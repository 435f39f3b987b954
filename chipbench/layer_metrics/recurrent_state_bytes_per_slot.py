"""Bytes of recurrent state the slot cache holds a SEQUENCE, all layers: the
worker's own account of its cache (``SlotWorker.hbm_pools()["slot_state"]``,
from array metadata) over its slots. 16,900,096 for four Falcon-H1-34B layers
(4 x (a float32 [32, 128, 256] state + a 3 x 5120 bf16 convolution tail)),
whatever the sequence's length: the number the architecture exists for, against
2,048 B a TOKEN a layer of K/V. A program whose worker has no such pool (one
without the mixer) gives nothing."""
NAME, UNIT, LAYER = "recurrent_state_bytes_per_slot", "bytes", "model"


def read(ctx):
    worker = ctx.get("worker")
    pools = worker.hbm_pools() if worker is not None else {}
    if "slot_state" not in pools:
        return None
    return pools["slot_state"] / worker.n_slots
