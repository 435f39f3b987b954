#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the tree still runs on the chip.

One process drives the two main paths through the normal entry points at the
full width and depth of GPT-2 125M (12L, 768 hidden, 12 heads, vocab 50304,
seq 1024, bf16), weights random from a seed:

    python chip_smoke.py             # one TPU chip: device, kernels, train, serve
    python chip_smoke.py --chips 4   # four chips: ZeRO-3 over fsdp=4 against a
                                     # one-device run, and nothing else
    python chip_smoke.py --rehearse [--chips 4]
                                     # the same control flow at a tiny size on
                                     # whatever platform jax finds (CPU: Pallas
                                     # in interpret mode); never says ok

Phases, each printing one JSON line as it finishes; the first failure ends the
run with a non-zero exit:

  device   jax.devices(); anything but platform "tpu" is a failure (no retry,
           no CPU branch).
  kernels  flash attention fwd+bwd and the decode-attention kernel, compiled
           (interpret=False, ``tpu_custom_call`` in the lowered text) at the
           shapes the next two phases use, against ``xla_attention`` in
           float32.
  train    ``deepspeed_tpu.initialize`` with a GPT-2 125M configuration,
           a few ``engine.train_batch`` calls on one seeded batch: finite
           falling loss, no overflow, one compilation of the step.
  serve    ``launcher.serving_worker.build_serving_engine`` + ``ServingEngine
           .serve`` on ragged greedy requests: every request ``ok``, prefill
           logits against a float32 ``apply``, token streams against
           ``InferenceEngine.generate`` (or a near-tie of the top logits where
           two bf16 streams part), one decode program under the watchdog in
           raise mode, the Pallas decode kernel in that program.
  fsdp     (--chips 4 only) per-step losses of the sharded and the one-device
           run agree, state lives on four devices, per-device memory is well
           under the one-device figure.

The last line of a passing chip run, and of nothing else, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Step and token times printed on the way are smoke readings, not metrics.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

# normalised max error (max|a-b| / max|ref|) of a bf16 kernel against the
# float32 XLA reference on the same bf16 inputs
KERNEL_FWD_TOL = 2e-2
KERNEL_BWD_TOL = 2e-2
# max |logit| difference between the bf16 serving path and a float32 apply;
# also the width of a "near tie" where two bf16 greedy streams may part
LOGIT_TOL = 8e-2
# per-step |loss| difference between the fsdp=4 run and the one-device run
FSDP_LOSS_TOL = 2e-2

REAL = dict(L=12, H=12, D=768, V=50304, S=1024, B=64, micro=16, chunk=256,
            flash_block=1024, train_steps=6, n_slots=8,
            prompt_lens=(5, 23, 97, 180, 410, 700),
            new_tokens=(32, 48, 64, 40, 56, 64), fsdp_steps=6)
TINY = dict(L=2, H=4, D=128, V=512, S=128, B=8, micro=2, chunk=32,
            flash_block=128, train_steps=5, n_slots=8,
            prompt_lens=(5, 9, 17, 30, 51, 70),
            new_tokens=(6, 8, 10, 7, 9, 10), fsdp_steps=3)


class SmokeFailure(Exception):
    """A phase's check did not hold."""


# persistent compile cache traffic so far, counted from jax's own monitoring
# events; every phase line carries the running totals
_CACHE = {"hits": 0, "writes": 0}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "writes"}


def _count_cache_event(event: str, **_) -> None:
    if event in _CACHE_EVENTS:
        _CACHE[_CACHE_EVENTS[event]] += 1


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields, "compile_cache": dict(_CACHE)}),
          flush=True)


def finish(phase: str, problems: list, **fields) -> None:
    """Print the phase's line — with what did not hold, if anything — and
    end the run there if anything did not."""
    problems = [p for p in problems if p]
    emit(phase, status="fail" if problems else "pass", **fields,
         **({"problems": problems} if problems else {}))
    if problems:
        raise SmokeFailure(f"{phase}: " + "; ".join(problems))


def _rel_err(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))), 1e-6))


def _bytes_in_use(dev):
    stats = dev.memory_stats() or {}
    return stats.get("bytes_in_use")


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def phase_device(args) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    want = 4 if args.chips == 4 else 1
    finish("device", [
        not (args.rehearse or info["platform"] == "tpu")
        and f"need platform 'tpu', found {info['platform']!r}",
        len(devs) < want and f"need {want} device(s), found {len(devs)}",
    ], **info, rehearsal=args.rehearse, jax=jax.__version__)
    return info


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def phase_kernels(args, sz) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.transformer import xla_attention
    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    # the chip run forces compilation; the rehearsal takes the engines'
    # default (interpreted on the CPU platform)
    interpret = None if args.rehearse else False
    Dh = sz["D"] // sz["H"]
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 8)
    t0 = time.perf_counter()

    # flash fwd+bwd at the train step's micro-batch shape
    shape = (sz["micro"], sz["S"], sz["H"], Dh)
    q, k, v, g = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in keys[:4])
    blk = sz["flash_block"]

    # g (the cotangent) is an operand, not a closed-over constant: a 25 MB
    # constant baked into the program costs ~80 MB of compile cache
    def flash_loss(q, k, v, g):
        out = flash_attention(q, k, v, causal=True, block_q=blk, block_k=blk,
                              interpret=interpret)
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32)), out

    def ref_loss(q, k, v, g):
        out = xla_attention(*(x.astype(jnp.float32) for x in (q, k, v)))
        return jnp.sum(out * g.astype(jnp.float32)), out

    flash_fb = jax.jit(jax.value_and_grad(flash_loss, argnums=(0, 1, 2), has_aux=True))
    flash_text = flash_fb.lower(q, k, v, g).as_text()
    (_, out), grads = flash_fb(q, k, v, g)
    (_, ref_out), ref_grads = jax.jit(jax.value_and_grad(
        ref_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v, g)
    flash_err = {"out": _rel_err(out, ref_out)}
    for name, a, b in zip(("dq", "dk", "dv"), grads, ref_grads):
        flash_err[name] = _rel_err(a, b)
    del out, ref_out, grads, ref_grads

    # decode kernel at the serve phase's slot-cache shape, ragged positions
    n, Smax = sz["n_slots"], sz["S"]
    dq = jax.random.normal(keys[4], (n, sz["H"], Dh), jnp.bfloat16)
    kc = jax.random.normal(keys[5], (n, Smax, sz["H"], Dh), jnp.bfloat16)
    vc = jax.random.normal(keys[6], (n, Smax, sz["H"], Dh), jnp.bfloat16)
    pos = jnp.asarray(np.linspace(0, Smax - 1, n).astype(np.int32))
    dec = jax.jit(lambda q, k, v, p: decode_attention(q, k, v, p, interpret=interpret))
    dec_text = dec.lower(dq, kc, vc, pos).as_text()
    dec_out = dec(dq, kc, vc, pos)
    dec_ref = jax.jit(lambda q, k, v, p: xla_attention(
        q[:, None].astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), causal_offset=p)[:, 0])(dq, kc, vc, pos)
    dec_err = _rel_err(dec_out, dec_ref)

    mosaic = {"flash": "tpu_custom_call" in flash_text,
              "decode": "tpu_custom_call" in dec_text}
    finish("kernels", [
        not np.isfinite(np.asarray(dec_out, np.float32)).all() and "non-finite decode output",
        flash_err["out"] > KERNEL_FWD_TOL and "flash forward beyond tolerance",
        max(flash_err[k] for k in ("dq", "dk", "dv")) > KERNEL_BWD_TOL
        and "flash backward beyond tolerance",
        dec_err > KERNEL_FWD_TOL and "decode kernel beyond tolerance",
        not (args.rehearse or all(mosaic.values())) and "a kernel did not lower to tpu_custom_call",
    ], flash_shape=list(shape), flash_block=blk, flash_rel_err=flash_err,
        decode_cache_shape=[n, Smax, sz["H"], Dh], decode_rel_err=dec_err,
        tol={"fwd": KERNEL_FWD_TOL, "bwd": KERNEL_BWD_TOL},
        tpu_custom_call=mosaic, seconds=round(time.perf_counter() - t0, 2))


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _train_model(sz):
    """The GPT-2 125M configuration, at ``sz``."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import Model, TransformerConfig

    return Model(TransformerConfig(
        vocab_size=sz["V"], max_seq_len=sz["S"], num_layers=sz["L"],
        num_heads=sz["H"], hidden_size=sz["D"], pos_emb="learned",
        dtype=jnp.bfloat16, remat=True, remat_policy="dots_and_flash",
        attn_impl="flash", flash_block_q=sz["flash_block"],
        flash_block_k=sz["flash_block"], loss_chunk_size=sz["chunk"]))


def _ds_config(sz, *, zero_stage: int, micro: int, gas: int, mesh: dict) -> dict:
    return {
        "train_batch_size": sz["B"],
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": {"type": "AdamW", "params": {"lr": 6e-4, "weight_decay": 0.1}},
        "zero_optimization": {"stage": zero_stage},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 1000000,
        "mesh": mesh,
    }


def _tokens(args, sz):
    import numpy as np

    return np.random.default_rng(args.seed).integers(
        0, sz["V"], size=(sz["B"], sz["S"] + 1)).astype(np.int32)


def _run_steps(engine, batch, steps: int) -> dict:
    """``steps`` synchronised train_batch calls on one batch: per-step loss,
    overflow flags, first-call (compile) seconds and the later step times."""
    import jax
    import numpy as np

    losses, overflow, times = [], [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        m = engine.train_batch(batch)
        jax.block_until_ready(m["loss"])
        times.append(time.perf_counter() - t0)
        losses.append(float(np.asarray(jax.device_get(m["loss"]))))
        overflow.append(bool(np.asarray(jax.device_get(m["overflow"]))))
    compiles = [ev for ev in engine.telemetry.watchdog.events
                if ev["type"] == "compile" and ev["name"].startswith("train/train_step")]
    return {"losses": losses, "overflow": overflow, "first_call_s": times[0],
            "step_s": float(np.median(times[1:])), "compiles": len(compiles),
            "jit_cache_size": int(engine._train_step._cache_size())}


def _step_problems(r: dict, what: str) -> list:
    import math

    return [
        not all(math.isfinite(x) for x in r["losses"]) and f"{what}: non-finite loss",
        not r["losses"][-1] < r["losses"][0] and f"{what}: loss did not fall",
        any(r["overflow"]) and f"{what}: overflow flag set",
        (r["compiles"], r["jit_cache_size"]) != (1, 1)
        and f"{what}: the step compiled {r['compiles']} times "
            f"(jit cache {r['jit_cache_size']}), expected exactly once",
    ]


def phase_train(args, sz) -> None:
    import jax

    import deepspeed_tpu

    dev = jax.devices()[0]
    ds_cfg = _ds_config(sz, zero_stage=1, micro=sz["micro"],
                        gas=sz["B"] // sz["micro"], mesh={"data": -1})
    t0 = time.perf_counter()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=_train_model(sz), config=ds_cfg, rng=jax.random.PRNGKey(args.seed))
    jax.block_until_ready(engine.state)
    build_s = time.perf_counter() - t0
    r = _run_steps(engine, {"tokens": _tokens(args, sz)}, sz["train_steps"])
    stats = dev.memory_stats() or {}
    finish("train", _step_problems(r, "train"),
           model=f"{sz['L']}Lx{sz['D']}hx{sz['V']}v seq {sz['S']} bf16",
           batch=sz["B"], micro=sz["micro"], steps=sz["train_steps"], seed=args.seed,
           losses=[round(x, 4) for x in r["losses"]], overflow=any(r["overflow"]),
           step_compiles=r["compiles"], build_s=round(build_s, 2),
           compile_s=round(r["first_call_s"], 2), smoke_step_s=round(r["step_s"], 4),
           peak_bytes_in_use=stats.get("peak_bytes_in_use"))


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def phase_serve(args, sz) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.serving import Request
    from deepspeed_tpu.launcher.serving_worker import build_serving_engine
    from deepspeed_tpu.models import transformer as tfm

    S, V = sz["S"], sz["V"]
    spec = {
        "model": {"vocab_size": V, "max_seq_len": S, "num_layers": sz["L"],
                  "num_heads": sz["H"], "hidden_size": sz["D"],
                  "pos_emb": "learned", "dtype": "bfloat16"},
        "engine_dtype": "bf16",
        "serving": {"n_slots": sz["n_slots"], "max_seq_len": S,
                    "watchdog_mode": "raise", "seed": args.seed},
    }
    t0 = time.perf_counter()
    srv = build_serving_engine(spec)
    eng, cfg, params = srv.engine, srv.engine.cfg, srv.engine.params
    build_s = time.perf_counter() - t0

    rng = np.random.default_rng(args.seed + 1)
    prompts = [rng.integers(0, V, size=(n,)).astype(np.int32) for n in sz["prompt_lens"]]
    requests = [Request(uid=i, prompt=p, max_new_tokens=n, temperature=0.0)
                for i, (p, n) in enumerate(zip(prompts, sz["new_tokens"]))]
    t0 = time.perf_counter()
    results = srv.serve(requests)
    serve_s = time.perf_counter() - t0
    statuses = {u: r.status for u, r in results.items()}
    n_tokens = sum(len(r.tokens) for r in results.values())

    # float32 reference on the same weights: one program at the full
    # sequence length — under the causal mask the padding after ``n - 1``
    # cannot reach row ``n - 1``
    ref_cfg = cfg.replace(dtype=jnp.float32, attn_impl="xla")
    ref_row = jax.jit(lambda p, t, i: tfm.apply(ref_cfg, p, t)[0, i])

    def reference_logits(tokens: np.ndarray) -> np.ndarray:
        padded = np.zeros((1, S), np.int32)
        padded[0, :len(tokens)] = tokens
        return np.asarray(ref_row(params, padded, len(tokens) - 1), np.float32)

    # the serving prefill's own computation (SlotWorker._build_prefill): the
    # bucket-padded prompt through apply_with_cache, logits at the live row
    def prefill_logits(prompt: np.ndarray) -> np.ndarray:
        bucket = srv._bucket_len(len(prompt))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(prompt)] = prompt

        def fn(p, t, n):
            local = tfm.init_cache(cfg, 1, bucket, dtype=cfg.dtype)
            logits, _ = tfm.apply_with_cache(cfg, p, t, local, 0, last_index=n - 1)
            return logits[0, 0]

        return np.asarray(jax.jit(fn)(params, padded, np.int32(len(prompt))), np.float32)

    logit_err, parted, mismatched = [], [], []
    for req in requests:
        got = np.asarray(results[req.uid].tokens)
        ref0 = reference_logits(req.prompt)
        logit_err.append(float(np.max(np.abs(prefill_logits(req.prompt) - ref0))))
        want = eng.generate(req.prompt[None], max_new_tokens=req.max_new_tokens)[0]
        if len(got) != len(want):
            mismatched.append({"uid": req.uid, "len": [len(got), len(want)]})
            continue
        diff = np.nonzero(got != want)[0]
        if len(diff) == 0:
            continue
        # the two bf16 streams part at step i: fine only if both picked a
        # token within LOGIT_TOL of the float32 top logit at that step
        i = int(diff[0])
        ref = ref0 if i == 0 else reference_logits(np.concatenate([req.prompt, got[:i]]))
        gaps = [float(ref.max() - ref[int(t)]) for t in (got[i], want[i])]
        parted.append({"uid": req.uid, "step": i, "gap_to_top": [round(g, 4) for g in gaps]})
        if max(gaps) > LOGIT_TOL:
            mismatched.append(parted[-1])

    counts = srv.compile_counts()
    # the decode program the engine compiled, lowered again at its own
    # operand shapes: the Pallas kernel must be in it
    w = srv.worker
    n = w.n_slots
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    i32, f32 = (lambda d: jax.ShapeDtypeStruct((n,), d)), jnp.float32
    decode_text = w._decode.lower(
        jax.tree.map(sds, w.params), jax.tree.map(sds, w._cache),
        i32(jnp.int32), i32(jnp.int32), i32(jnp.int32), i32(jnp.bool_),
        jax.random.PRNGKey(0), i32(f32), i32(jnp.int32), i32(f32)).as_text()
    kernel_in_decode = "tpu_custom_call" in decode_text

    finish("serve", [
        set(statuses.values()) != {"ok"} and f"not every request ok: {statuses}",
        len(statuses) != len(requests) and "a request has no result",
        max(logit_err) > LOGIT_TOL and "prefill logits beyond tolerance of float32 apply",
        mismatched and "a token stream parts from generate() away from a near tie",
        counts["decode"] != 1 and f"{counts['decode']} decode programs, expected one",
        set(counts["prefill"].values()) != {1} and "a prefill bucket compiled more than once",
        cfg.decode_attn != "kernel" and f"decode_attn is {cfg.decode_attn!r}",
        not (args.rehearse or kernel_in_decode) and "no tpu_custom_call in the decode program",
    ], n_slots=n, Smax=int(w.Smax), requests=len(requests),
        prompt_lens=list(sz["prompt_lens"]), new_tokens=list(sz["new_tokens"]),
        statuses=sorted(set(statuses.values())), tokens_out=n_tokens,
        prefill_logit_max_abs_err=[round(e, 4) for e in logit_err],
        logit_tol=LOGIT_TOL, streams_equal=len(requests) - len(parted),
        streams_parted_at_near_tie=parted, mismatched=mismatched,
        decode_programs=counts["decode"], decode_steps=counts["decode_steps"],
        prefill_programs={str(b): c for b, c in counts["prefill"].items()},
        watchdog=srv.telemetry.watchdog.mode, decode_attn=cfg.decode_attn,
        tpu_custom_call_in_decode=kernel_in_decode,
        build_s=round(build_s, 2), smoke_serve_wall_s=round(serve_s, 2),
        smoke_decode_step_s=_decode_step_p50(srv))


def _decode_step_p50(srv):
    """Median steady decode-step seconds from the engine's own histogram
    (compiling calls excluded there) — a smoke reading, not a metric."""
    h = srv.telemetry.histogram("serving/decode_step_sec").summary()
    return round(float(h["p50"]), 5) if h["count"] else None


# ---------------------------------------------------------------------------
# fsdp (--chips 4)
# ---------------------------------------------------------------------------

def _leaf_device_report(tree) -> dict:
    """How a pytree of arrays is spread: devices holding each leaf, and the
    bytes each device holds of the whole tree."""
    import jax

    per_dev: dict = {}
    min_devices, total = None, 0
    for leaf in jax.tree.leaves(tree):
        if not hasattr(leaf, "sharding") or leaf.ndim == 0:
            continue
        n_dev = len(leaf.sharding.device_set)
        min_devices = n_dev if min_devices is None else min(min_devices, n_dev)
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) + sh.data.nbytes
    return {"min_devices_per_leaf": min_devices, "total_bytes": total,
            "bytes_by_device": dict(sorted(per_dev.items()))}


def phase_fsdp(args, sz) -> None:
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh

    devs = jax.devices()[:4]
    batch = {"tokens": _tokens(args, sz)}
    steps = sz["fsdp_steps"]
    rng = jax.random.PRNGKey(args.seed)

    # what it is compared with: the same model, seed and global batch on ONE
    # device (gradient accumulation makes up the batch)
    one_cfg = _ds_config(sz, zero_stage=3, micro=sz["micro"],
                         gas=sz["B"] // sz["micro"], mesh={"data": 1})
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=_train_model(sz), config=one_cfg, rng=rng,
        mesh=build_mesh(MeshConfig(data=1), devices=devs[:1]))
    one = _run_steps(engine, batch, steps)
    one_bytes = _bytes_in_use(devs[0])
    one_state = _leaf_device_report((engine.state["params"], engine.state["opt"]))
    del engine
    gc.collect()
    residual = _bytes_in_use(devs[0])

    # ZeRO-3 over fsdp=4: one micro-batch of B/4 per device, no accumulation
    per_dev = sz["B"] // 4
    micro = min(sz["micro"], per_dev)
    four_cfg = _ds_config(sz, zero_stage=3, micro=micro, gas=per_dev // micro,
                          mesh={"data": 1, "fsdp": 4})
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=_train_model(sz), config=four_cfg, rng=rng,
        mesh=build_mesh(MeshConfig(data=1, fsdp=4), devices=devs))
    four = _run_steps(engine, batch, steps)
    four_bytes = [_bytes_in_use(d) for d in devs]
    state = _leaf_device_report((engine.state["params"], engine.state["opt"]))

    loss_gap = max(abs(a - b) for a, b in zip(one["losses"], four["losses"]))
    shard_bytes = list(state["bytes_by_device"].values())
    finish("fsdp", _step_problems(one, "one-device") + _step_problems(four, "fsdp=4") + [
        loss_gap > FSDP_LOSS_TOL and "per-step losses beyond tolerance of the one-device run",
        state["min_devices_per_leaf"] != 4 and "a state leaf is not on four devices",
        (len(shard_bytes) != 4 or max(shard_bytes) > 0.5 * one_state["total_bytes"])
        and "the state is not spread over four devices",
        # bytes_in_use is None where the backend keeps no allocator statistics
        # (the CPU rehearsal); the sharding-derived bytes above still hold there
        one_bytes is not None and max(four_bytes) > 0.5 * one_bytes
        and "a device's bytes_in_use is not well under the one-device figure",
    ], mesh={"fsdp": 4}, zero_stage=3, batch=sz["B"], steps=steps, seed=args.seed,
        losses_one_device=[round(x, 4) for x in one["losses"]],
        losses_fsdp4=[round(x, 4) for x in four["losses"]],
        max_loss_gap=round(loss_gap, 5), loss_tol=FSDP_LOSS_TOL,
        min_devices_per_state_leaf=state["min_devices_per_leaf"],
        state_bytes_one_device=one_state["total_bytes"],
        state_bytes_by_device=state["bytes_by_device"],
        bytes_in_use_one_device=one_bytes, bytes_in_use_after_free=residual,
        bytes_in_use_fsdp4=four_bytes,
        compile_s={"one_device": round(one["first_call_s"], 2),
                   "fsdp4": round(four["first_call_s"], 2)},
        smoke_step_s={"one_device": round(one["step_s"], 4),
                      "fsdp4": round(four["step_s"], 4)})


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the fsdp=4 phase and its one-device comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on whatever platform jax finds; never prints ok")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sz = TINY if args.rehearse else REAL

    import jax.monitoring

    from deepspeed_tpu.utils.jax_env import use_compile_cache

    cache_dir = use_compile_cache()
    jax.monitoring.register_event_listener(_count_cache_event)
    emit("setup", cache_dir=cache_dir, cache_entries_at_start=(
        len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0))
    t0 = time.perf_counter()
    try:
        device = phase_device(args)
        if args.chips == 4:
            phase_fsdp(args, sz)
        else:
            phase_kernels(args, sz)
            phase_train(args, sz)
            gc.collect()
            phase_serve(args, sz)
    except SmokeFailure as e:
        emit("failed", error=str(e), seconds=round(time.perf_counter() - t0, 1))
        return 1
    emit("done", seconds=round(time.perf_counter() - t0, 1))
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": device}), flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
