"""Do two trees trace the same serving and training programs? (PR 52)

    PYTHONPATH=<tree> python3 experiments/jaxpr_text.py <tree> > <tree>.json

prints, for the twins WITH their operators of the four state / kind / latent
configurations (falcon-h1, lfm2, K-EXAONE, kanana) and, since PR 56, of the four
others (bloom, OLMoE, qwen3-next, pythia), a hash and the length of the jaxpr
TEXT of a decode step at per-row positions under a live-row mask, of two padded prefill
buckets and of the gradient of the loss; since PR 60 of the two newest configurations' twins
too (Ouro, Mellum2: ten in all), and for the nine that are served of the bodies of
``SlotWorker``'s OWN programs as well (``worker.decode``, ``worker.prefill64`` / ``256``,
``worker.chunk64``: the sampler, the sentinel and the key's split around the model's pass,
traced from the functions the worker jits, on a worker that holds nothing but the
configuration). Two trees that print the same lines trace the same programs, jaxpr text for
jaxpr text. Nothing is compiled or run."""
import hashlib
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.models import transformer as tfm  # noqa: E402

TWINS = {"falcon-h1-34b-L4": "rehearse_recurrent_program", "lfm2-24b-a2b-L9": "rehearse_conv_program",
         "k-exaone-236b-a23b-L5": "rehearse_kinds_program", "kanana-2-30b-a3b-L7": "rehearse_program",
         # PR 56: the four others, so that every configuration older than the tree is here
         "bloom-1b7": "rehearse_program", "olmoe-1b-7b-L4": "rehearse_program",
         "qwen3-next-80b-a3b-L8": "rehearse_delta_program", "pythia-1.4b": "rehearse_program",
         # PR 60: the two newest
         "ouro-2.6b-L12": "rehearse_program", "mellum2-12b-a2.5b-L8": "rehearse_kinds_program"}
TRAINED_ONLY = {"pythia-1.4b"}  # no serving cell runs it
SLOTS, SMAX, BUCKETS = 4, 256, (64, 256)


def _hash(fn, *args) -> tuple:
    text = str(jax.make_jaxpr(fn)(*args))
    return hashlib.sha256(text.encode()).hexdigest()[:16], len(text)


def worker_programs(cfg, held) -> dict:
    """The bodies of the programs ``SlotWorker`` jits for ``cfg``, by the names it builds them
    under: the worker here holds the configuration and a sharding to type its key by, nothing
    else (no weights, no cache: a body is traced from shapes)."""
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.inference.serving import SlotWorker

    w = object.__new__(SlotWorker)
    w.cfg, w.Smax = cfg, SMAX
    w._cache_shardings = SingleDeviceSharding(jax.devices()[0])
    sds = jax.ShapeDtypeStruct
    cache = jax.eval_shape(lambda: tfm.init_cache(cfg, SLOTS, SMAX))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    row = lambda n, dtype: sds((n,), dtype)  # noqa: E731
    scalar = sds((), jnp.int32)
    sampler = lambda n: (row(n, jnp.float32), row(n, jnp.int32), row(n, jnp.float32))  # noqa: E731
    out = {"worker.decode": _hash(
        w._build_decode().__wrapped__, held, cache, row(SLOTS, jnp.int32), row(SLOTS, jnp.int32),
        row(SLOTS, jnp.int32), row(SLOTS, jnp.bool_), key, *sampler(SLOTS))}
    for bucket in BUCKETS:
        out[f"worker.prefill{bucket}"] = _hash(
            w._build_prefill(bucket).__wrapped__, held, cache, sds((1, bucket), jnp.int32), scalar,
            scalar, key, *sampler(1))
    out["worker.chunk64"] = _hash(
        w._build_chunk(64).__wrapped__, held, cache, sds((1, 64), jnp.int32), scalar, scalar,
        scalar, key, *sampler(1))
    return out


def programs(root: str) -> dict:
    out = {}
    sds = jax.ShapeDtypeStruct
    for name, key in TWINS.items():
        with open(os.path.join(root, "chipbench", "configs", f"{name}.json")) as f:
            cfg = tfm.TransformerConfig(dtype=jnp.bfloat16, **json.load(f)[key])
        tfm._ACTIVE_MESH[0] = None
        masters = jax.eval_shape(lambda: tfm.init(cfg, jax.random.PRNGKey(0)))
        held = jax.eval_shape(lambda p: tfm.hold_for_compute(cfg, p), masters)
        routed = cfg.moe_routing == "dropless"
        out[f"{name}.step"] = _hash(
            lambda p, t, c, pos, live: tfm.apply_with_cache(
                cfg, p, t, c, pos, write_pos=pos, live=live[:, None], return_routing=routed),
            held, sds((SLOTS, 1), jnp.int32), jax.eval_shape(lambda: tfm.init_cache(cfg, SLOTS, SMAX)),
            sds((SLOTS,), jnp.int32), sds((SLOTS,), jnp.bool_))
        for bucket in BUCKETS:
            out[f"{name}.prefill{bucket}"] = _hash(
                lambda p, t, c, n: tfm.apply_with_cache(
                    cfg, p, t, c, 0, last_index=n - 1, live=jnp.arange(t.shape[1])[None, :] < n),
                held, sds((1, bucket), jnp.int32),
                jax.eval_shape(lambda: tfm.init_cache(cfg, 1, bucket)), sds((), jnp.int32))
        if name not in TRAINED_ONLY:
            out.update({f"{name}.{k}": v for k, v in worker_programs(cfg, held).items()})
        trained = cfg.replace(dtype=jnp.float32)
        out[f"{name}.grad"] = _hash(
            jax.grad(lambda p, t: tfm.causal_lm_loss(trained, p, {"tokens": t})),
            masters, sds((2, 65), jnp.int32))
    return out


if __name__ == "__main__":
    print(json.dumps(programs(sys.argv[1] if len(sys.argv) > 1 else "."), indent=0))
