"""Bytes the slot cache holds a token a layer: the worker's own account of its
cache (``SlotWorker.hbm_pools()["slot_kv_cache"]``, from array metadata) over
slots x cache length x layers. 1,152 for a 512 + 64 latent in bf16: the number
latent attention exists for; a program that cached the expanded keys and values
of 32 heads of 192 + 128 would read 20,480."""
NAME, UNIT, LAYER = "latent_cache_bytes_per_token", "bytes", "model"


def read(ctx):
    worker = ctx.get("worker")
    if worker is None or "kv_lora_rank" not in ctx["program"]:
        return None
    tokens = worker.n_slots * worker.Smax * ctx["program"]["num_layers"]
    return worker.hbm_pools()["slot_kv_cache"] / tokens
