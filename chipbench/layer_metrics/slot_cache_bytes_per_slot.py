"""Bytes of attention cache the slot cache holds a SLOT, all layers, for a model
whose window layers keep a ring: the worker's own account of its cache
(``SlotWorker.hbm_pools()``: ``slot_kv_cache``, the whole-context layers' ``Smax``
positions, + ``slot_kv_ring``, the window layers' rings; from array metadata) over
its slots. 69,206,016 for K-EXAONE's five layers S S S G S at 16,384 positions
(``kinds_cost.slot_cache_bytes``), where one length for every layer is
335,544,320: the number the architecture exists for. A program whose worker has
no ring pool (one without window layers) gives nothing."""
NAME, UNIT, LAYER = "slot_cache_bytes_per_slot", "bytes", "model"


def read(ctx):
    worker = ctx.get("worker")
    pools = worker.hbm_pools() if worker is not None else {}
    if "slot_kv_ring" not in pools:
        return None
    return (pools["slot_kv_cache"] + pools["slot_kv_ring"]) / worker.n_slots
