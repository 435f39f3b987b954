"""Bytes of K/V the slot cache holds a TOKEN, all cache layers, for a model whose
layer stack runs several times over the same weights: the worker's own account of
its cache (``SlotWorker.hbm_pools()["slot_kv_cache"]``, from array metadata) over
slots x cache length. 393,216 for twelve Ouro layers run four times (4 x 12 x 2 x
16 heads x 128 x 2 B: a (pass, layer) keeps K/V of its own); one pass's K/V would
read 98,304, which is what sharing K/V between passes would buy. A program without
``layer_passes`` gives nothing."""
NAME, UNIT, LAYER = "loop_cache_bytes_per_token", "bytes", "model"


def read(ctx):
    worker = ctx.get("worker")
    if worker is None or "layer_passes" not in ctx["program"]:
        return None
    return worker.hbm_pools()["slot_kv_cache"] / (worker.n_slots * worker.Smax)
