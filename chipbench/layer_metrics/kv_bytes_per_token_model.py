"""Bytes of K/V the slot cache holds a TOKEN, all layers: the worker's own account
of its cache (``SlotWorker.hbm_pools()["slot_kv_cache"]``, from array metadata)
over slots x cache length. 4,096 for nine LFM2-24B-A2B layers of which two attend
(2 x 8 K/V heads x 64 x 2 values x 2 B): the number a gated short convolution in
the other seven exists for; K/V in every layer would read 18,432. A program whose
layers all attend (no ``layer_operators``) gives nothing: its readers divide by
the layers."""
NAME, UNIT, LAYER = "kv_bytes_per_token_model", "bytes", "model"


def read(ctx):
    worker = ctx.get("worker")
    if worker is None or "layer_operators" not in ctx["program"]:
        return None
    return worker.hbm_pools()["slot_kv_cache"] / (worker.n_slots * worker.Smax)
