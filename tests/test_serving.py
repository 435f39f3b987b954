"""Continuous-batching serving engine (inference/serving.py).

The contract under test: a slot-based KV cache with per-row positions gives
TOKENWISE the same greedy output as the one-shot ``InferenceEngine.generate``
path, regardless of what else shares the batch — staggered admission, slot
reuse, ragged sampling params — and the single compiled ``decode_step`` never
retraces when the workload mix changes (the property that makes admission
free on TPU).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngine, Request, ServingEngine
from deepspeed_tpu.inference.sampling import (
    apply_top_k,
    apply_top_k_vector,
    apply_top_p,
    apply_top_p_vector,
)
from deepspeed_tpu.models.transformer import Model, TransformerConfig


@pytest.fixture(scope="module")
def engine(tiny_serving_engine):
    # the shared session-scoped tiny model (tests/conftest.py) — every
    # serving test module decodes the same params through the same cached
    # XLA programs
    return tiny_serving_engine


def _prompts(sizes, seed=0, vocab=97):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=s).astype(np.int32) for s in sizes]


def test_greedy_parity_with_generate(engine):
    """Continuous-batch greedy output per request is tokenwise identical to
    single-request one-shot generate."""
    srv = ServingEngine(engine, n_slots=4, max_seq_len=128)
    prompts = _prompts([5, 11, 23])
    reqs = [Request(uid=i, prompt=p, max_new_tokens=8) for i, p in enumerate(prompts)]
    res = srv.serve(reqs)
    for i, p in enumerate(prompts):
        ref = engine.generate(p[None], max_new_tokens=8)[0]
        np.testing.assert_array_equal(res[i].tokens, ref)
        assert res[i].prompt_len == len(p)
        assert res[i].ttft >= 0 and res[i].finish_time >= res[i].first_token_time


def test_staggered_admission_preserves_in_flight_output(engine):
    """Admitting B while A is mid-decode must not perturb A's tokens (per-row
    positions: the rows never interact)."""
    srv = ServingEngine(engine, n_slots=2, max_seq_len=128)
    pa, pb = _prompts([7, 13], seed=1)
    srv.submit(Request(uid=0, prompt=pa, max_new_tokens=10))
    for _ in range(4):
        srv.step(now=float("inf"))
    srv.submit(Request(uid=1, prompt=pb, max_new_tokens=6))
    res = srv.drain()
    np.testing.assert_array_equal(res[0].tokens, engine.generate(pa[None], 10)[0])
    np.testing.assert_array_equal(res[1].tokens, engine.generate(pb[None], 6)[0])


def test_slot_reuse_after_eviction(engine):
    """More requests than slots: evicted slots are reused and later
    occupants still match the solo reference (stale KV is masked/overwritten)."""
    srv = ServingEngine(engine, n_slots=2, max_seq_len=128)
    prompts = _prompts([5, 9, 17, 6, 12], seed=2)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=4 + i) for i, p in enumerate(prompts)]
    for r in reqs:
        srv.submit(r)
    res = srv.drain()
    assert len(res) == 5  # 5 requests through 2 slots => reuse happened
    slots_used = {res[i].slot for i in range(5)}
    assert slots_used == {0, 1}
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            res[i].tokens, engine.generate(p[None], 4 + i)[0])


def test_eos_evicts_early(engine):
    """A request whose eos appears mid-stream frees its slot immediately."""
    srv = ServingEngine(engine, n_slots=2, max_seq_len=128)
    (p,) = _prompts([8], seed=3)
    ref = engine.generate(p[None], max_new_tokens=8)[0]
    # greedy is deterministic: pick a mid-stream token whose FIRST occurrence
    # is its position, and declare it the stop token
    stop_at = next(i for i in range(1, 8) if ref[i] not in ref[:i])
    srv.submit(Request(uid=0, prompt=p, max_new_tokens=8, eos_token=int(ref[stop_at])))
    res = srv.drain()
    np.testing.assert_array_equal(res[0].tokens, ref[: stop_at + 1])  # includes eos
    assert srv.n_active == 0 and len(srv._free) == 2


def test_decode_compiles_once_across_mixed_workload(engine):
    """Acceptance: ONE decode_step compile across >= 8 requests with distinct
    prompt lengths, sampling params, and arrival times."""
    srv = ServingEngine(engine, n_slots=4, max_seq_len=128)
    rng = np.random.default_rng(4)
    reqs = [
        Request(
            uid=i,
            prompt=rng.integers(0, 97, size=4 + 3 * i).astype(np.int32),
            max_new_tokens=3 + i,
            temperature=float(i % 3) * 0.7,
            top_k=int(i % 4) * 5,
            top_p=1.0 - 0.05 * (i % 2),
            arrival_time=0.01 * i,
        )
        for i in range(8)
    ]
    res = srv.serve(reqs)
    assert len(res) == 8
    counts = srv.compile_counts()
    assert counts["decode"] == 1, counts
    # bucketed prefill: one compile per power-of-two bucket, not per length
    assert all(v == 1 for v in counts["prefill"].values()), counts
    assert len(counts["prefill"]) < 8


def test_admission_not_blocked_by_future_head(engine):
    """A queue head whose arrival_time is still in the future must not block
    admission of later-submitted requests that have already arrived — the
    scheduler scans for the earliest ARRIVED request, not queue[0]."""
    srv = ServingEngine(engine, n_slots=2, max_seq_len=128)
    pa, pb = _prompts([6, 9], seed=11)
    srv.submit(Request(uid=0, prompt=pa, max_new_tokens=4, arrival_time=1e6))
    srv.submit(Request(uid=1, prompt=pb, max_new_tokens=4, arrival_time=0.0))
    srv.step(now=1.0)
    assert srv.n_active == 1  # uid 1 admitted past the future-dated head
    assert [r.uid for r in srv._queue] == [0]
    res = srv.drain()  # drain ignores arrival times: uid 0 completes too
    np.testing.assert_array_equal(res[1].tokens, engine.generate(pb[None], 4)[0])
    assert len(res[0].tokens) == 4  # the future-dated head still completed


def test_greedy_rows_immune_to_neighbour_sampling(engine):
    """A greedy request sharing the batch with high-temperature neighbours
    still matches its solo greedy output (per-slot sampler arrays)."""
    srv = ServingEngine(engine, n_slots=3, max_seq_len=128)
    pg, p1, p2 = _prompts([9, 6, 14], seed=5)
    srv.submit(Request(uid=0, prompt=pg, max_new_tokens=8))  # greedy
    srv.submit(Request(uid=1, prompt=p1, max_new_tokens=8, temperature=1.3, top_k=7))
    srv.submit(Request(uid=2, prompt=p2, max_new_tokens=8, temperature=0.9, top_p=0.8))
    res = srv.drain()
    np.testing.assert_array_equal(res[0].tokens, engine.generate(pg[None], 8)[0])
    assert all(len(res[i].tokens) == 8 for i in range(3))
    assert all(0 <= t < 97 for i in range(3) for t in res[i].tokens)


def test_budget_rejection(engine):
    srv = ServingEngine(engine, n_slots=1, max_seq_len=128)
    (p,) = _prompts([100], seed=6)
    with pytest.raises(ValueError, match="exceeds the slot budget"):
        srv.submit(Request(uid=0, prompt=p, max_new_tokens=64))
    with pytest.raises(ValueError, match="max_new_tokens must be >= 1"):
        srv.submit(Request(uid=1, prompt=p[:10], max_new_tokens=0))
    with pytest.raises(ValueError, match="exceeds the engine's sequence budget"):
        # admission budget must NOT inherit the cache's 128-rounding: the
        # learned position table ends at the model's max_seq_len
        ServingEngine(engine, n_slots=1, max_seq_len=129)
    srv.submit(Request(uid=2, prompt=p[:10], max_new_tokens=4))
    with pytest.raises(ValueError, match="must be unique"):
        srv.submit(Request(uid=2, prompt=p[:10], max_new_tokens=4))
    srv.drain()


def test_sampler_fused_filters_match_sequential():
    """sample_logits_vector's shared-sort top-k+top-p must draw only from the
    support that sequential apply_top_k_vector -> apply_top_p_vector leaves."""
    key = jax.random.PRNGKey(3)
    logits = jax.random.normal(key, (4, 33), jnp.float32) * 3.0
    t = jnp.ones((4,), jnp.float32)
    ks = jnp.asarray([0, 3, 5, 12], jnp.int32)
    ps = jnp.asarray([0.7, 0.9, 1.0, 0.5], jnp.float32)
    from deepspeed_tpu.inference.sampling import NEG_INF, sample_logits_vector

    seq = apply_top_p_vector(apply_top_k_vector(logits, ks), ps)
    allowed = np.asarray(seq) > NEG_INF / 2
    assert 0 < allowed.sum() < allowed.size
    for i in range(50):
        toks = np.asarray(sample_logits_vector(
            logits, jax.random.fold_in(key, i), t, ks, ps))
        for b in range(4):
            assert allowed[b, toks[b]], (b, toks[b])


def _sampler_rows(mix, B):
    """One operand mix of ``sample_logits_vector`` at ``B`` rows: (temperature,
    top_k, top_p) and the form the mix asks for. At one row the mixes that
    need a second row fall back to what their first row asks for."""
    t, k, p = np.zeros(B, np.float32), np.zeros(B, np.int32), np.ones(B, np.float32)
    ramp = np.linspace(0.6, 1.4, B).astype(np.float32)
    if mix == "all_greedy":
        form = "argmax"
    elif mix == "one_sampled_row_no_filter":
        t[-1], form = 0.8, "draw"
    elif mix == "temperature_only":
        t[:], form = ramp, "draw"
        k[0], p[-1] = 2 ** 20, 1.5  # a top-k of the whole row and a nucleus over 1 filter nothing
    elif mix == "top_k_only":
        t[:], form = ramp, "filter"
        k[::2] = np.arange(B)[::2] * 7 + 3
    elif mix == "top_p_only":
        t[:], form = ramp, "filter"
        p[::2] = np.linspace(0.0, 0.95, B)[::2]  # 0: the nucleus of one token
    elif mix == "top_k_and_top_p":
        t[:], form = ramp, "filter"
        k[:] = np.arange(B) % 3 * 20
        p[:] = np.linspace(0.3, 1.0, B)
    elif mix == "greedy_row_filters_beside_sampled_rows":
        t[1:], form = ramp[1:], "draw" if B > 1 else "argmax"
        k[0], p[0] = 5, 0.5  # asked of a row whose token is the arg-max either way
    return (t, k, p), form


@pytest.mark.parametrize("B,V", [(8, 1000), (1, 50_304)])
@pytest.mark.parametrize("seed", [0, 11])
@pytest.mark.parametrize("mix", [
    "all_greedy", "one_sampled_row_no_filter", "temperature_only", "top_k_only", "top_p_only",
    "top_k_and_top_p", "greedy_row_filters_beside_sampled_rows"])
def test_sampler_forms_are_the_parents_bits(mix, seed, B, V):
    """``sample_logits_vector`` leaves out the work no row asks for (the sort
    unless a sampling row filters, the divide and the draw unless a row
    samples) and returns, bit for bit, what the one formula it had before
    returns: arg-max, filter, draw, select."""
    from deepspeed_tpu.inference.sampling import (SAMPLER_FORMS, _filter_logits_vector,
                                                  sample_logits_vector, sampler_form)

    def parent(logits, rng, t, k, p):
        logits = logits.astype(jnp.float32)
        greedy = jnp.argmax(logits, axis=-1)
        scaled = _filter_logits_vector(logits, t, k, p)
        drawn = jax.random.categorical(rng, scaled, axis=-1)
        return jnp.where(t <= 0.0, greedy, drawn).astype(jnp.int32)

    rows, form = _sampler_rows(mix, B)
    assert SAMPLER_FORMS[int(sampler_form(*rows, V))] == form
    logits = 4.0 * jax.random.normal(jax.random.PRNGKey(seed), (B, V), jnp.float32)
    key = jax.random.PRNGKey(1000 + seed)
    got = jax.jit(sample_logits_vector)(logits, key, *rows)
    want = jax.jit(parent)(logits, key, *rows)
    assert got.dtype == want.dtype == jnp.int32 and got.shape == (B,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if form != "argmax" and B > 1:  # the mix does draw: not the arg-max in every row
        assert np.any(np.asarray(got) != np.asarray(jnp.argmax(logits, axis=-1)))


def _sorts_and_argmaxes(jaxpr, under_cond=False):
    """(the primitive's name, whether it sits in a branch of a ``cond``) for
    every sort and arg-max of ``jaxpr`` and of the jaxprs nested in it."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("sort", "argmax"):
            found.append((eqn.primitive.name, under_cond))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _sorts_and_argmaxes(sub, under_cond or eqn.primitive.name == "cond")
    return found


@pytest.mark.parametrize("program", ["decode", "prefill", "chunk"])
def test_serving_programs_sort_only_under_the_conditional(engine, program):
    """The three programs that sample: the vocabulary-wide sort is in a branch
    of a ``cond``, never beside it; the arg-max every form starts from is
    outside."""
    srv = ServingEngine(engine, n_slots=2, max_seq_len=128)
    w, n = srv.worker, 2
    rng = jax.random.PRNGKey(0)
    vec = lambda dtype, rows: np.zeros((rows,), dtype)
    sampler = lambda rows: (rng, vec(np.float32, rows), vec(np.int32, rows), vec(np.float32, rows))
    i32 = np.int32(0)
    if program == "decode":
        fn, args = w._build_decode(), (vec(np.int32, n), vec(np.int32, n), vec(np.int32, n),
                                       vec(np.bool_, n), *sampler(n))
    elif program == "prefill":
        fn, args = w._build_prefill(16), (np.zeros((1, 16), np.int32), i32, i32, *sampler(1))
    else:
        fn, args = w._build_chunk(16), (np.zeros((1, 16), np.int32), i32, i32, i32, *sampler(1))
    found = _sorts_and_argmaxes(jax.make_jaxpr(fn)(w.params, w._cache, *args).jaxpr)
    assert ("sort", True) in found and ("sort", False) not in found, found
    assert ("argmax", False) in found


def test_vector_samplers_match_scalar():
    """The per-row array samplers agree with the scalar-config ones row by
    row (the decode step's no-recompile path must not change semantics)."""
    rng = jax.random.PRNGKey(0)
    logits = jax.random.normal(rng, (4, 33), jnp.float32)
    ks = [0, 3, 7, 40]
    ps = [1.0, 0.9, 0.5, 1.0]
    vk = apply_top_k_vector(logits, jnp.asarray(ks, jnp.int32))
    vp = apply_top_p_vector(logits, jnp.asarray(ps, jnp.float32))
    for i, (k, p) in enumerate(zip(ks, ps)):
        np.testing.assert_allclose(
            np.asarray(vk[i]), np.asarray(apply_top_k(logits[i : i + 1], k)[0]))
        np.testing.assert_allclose(
            np.asarray(vp[i]), np.asarray(apply_top_p(logits[i : i + 1], p)[0]))


def test_serving_with_decode_kernel(engine):
    """The Pallas decode kernel path (per-row pos through the kernel's
    masking) produces the same greedy tokens as the dense XLA path."""
    cfg = engine.cfg.replace(decode_attn="kernel")
    eng_k = InferenceEngine(model=Model(cfg), config={"dtype": "fp32"},
                            params=engine.params)
    srv = ServingEngine(eng_k, n_slots=2, max_seq_len=128)
    pa, pb = _prompts([5, 12], seed=7)
    srv.submit(Request(uid=0, prompt=pa, max_new_tokens=5))
    srv.submit(Request(uid=1, prompt=pb, max_new_tokens=5))
    res = srv.drain()
    np.testing.assert_array_equal(res[0].tokens, engine.generate(pa[None], 5)[0])
    np.testing.assert_array_equal(res[1].tokens, engine.generate(pb[None], 5)[0])


# ---------------------------------------------------------------------------
# apply_with_cache keeps the stacked cache in place through the layer loop
# (PR 25): bit-identical to the per-layer-cache semantics it replaced
# ---------------------------------------------------------------------------

def _restacking_apply_with_cache(cfg, params, tokens, cache, pos, write_pos=None):
    """``apply_with_cache`` as it was before the cache became the layer loop's
    carry: every layer gets its own [B, Smax, H, Dh] cache (the scan's xs),
    updates it, and the updated caches are restacked (the scan's ys). Full
    logits, dense or MoE-grouped, built from the model's own layer pieces."""
    from jax import lax

    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.moe.layer import moe_ffn_apply, moe_ffn_dense
    from deepspeed_tpu.ops.pallas.decode_attention import decode_attention

    B, T = tokens.shape
    pos = jnp.asarray(pos, jnp.int32)
    Smax = cache["k"].shape[2]
    steps = jnp.arange(T)
    if pos.ndim:
        positions = pos[:, None] + steps[None, :]
        wp = positions if write_pos is None else jnp.asarray(write_pos, jnp.int32)[:, None] + steps[None, :]
        rows = jnp.arange(B)[:, None]
        write = lambda c, new: c.at[rows, wp].set(new.astype(c.dtype), mode="drop")
    else:
        positions = pos + jnp.broadcast_to(steps[None, :], (B, T))
        write = lambda c, new: lax.dynamic_update_slice(c, new.astype(c.dtype), (0, pos, 0, 0))
    x, _ = tfm.embed(cfg, params, tokens, positions)
    bias = None
    if cfg.pos_emb == "alibi":
        slopes = tfm.alibi_slopes(cfg.num_heads)
        if pos.ndim:
            dist = jnp.arange(Smax)[None, None, :] - positions[:, :, None]
            bias = (slopes[None, :, None, None] * dist[:, None]).astype(jnp.float32)
        else:
            dist = jnp.arange(Smax)[None, :] - (pos + steps[:, None])
            bias = (slopes[:, None, None] * dist[None]).astype(jnp.float32)[None]
    kernel = T == 1 and cfg.decode_attn == "kernel" and cfg.pos_emb != "alibi"

    def one_layer(x, lp, kc, vc, ffn):
        h = tfm.layer_norm(x, lp["ln1_scale"], lp["ln1_bias"], cfg.layernorm_epsilon)
        q, k, v = tfm._qkv_proj(cfg, lp, h, positions)
        kc, vc = write(kc, k), write(vc, v)
        if kernel:
            attn = decode_attention(q[:, 0], kc, vc, pos)[:, None]
        else:
            attn = tfm.cached_attention(q, kc, vc, pos, bias=bias)
        out = tfm._attn_out_proj(cfg, lp, attn)
        if cfg.parallel_residual:
            h2 = tfm.layer_norm(x, lp["ln2_scale"], lp["ln2_bias"], cfg.layernorm_epsilon)
            return x + out + ffn(lp, h2), kc, vc
        x = x + out
        h2 = tfm.layer_norm(x, lp["ln2_scale"], lp["ln2_bias"], cfg.layernorm_epsilon)
        return x + ffn(lp, h2), kc, vc

    def dense(x, xs):
        x, kc, vc = one_layer(x, *xs, lambda lp, h2: tfm._ffn(cfg, lp, h2))
        return x, (kc, vc)

    if cfg.moe_every:
        E, G = cfg.moe_every, cfg.num_layers // cfg.moe_every
        group = lambda a: a.reshape((G, E) + a.shape[1:])
        if T == 1:
            moe = lambda p, h2: moe_ffn_dense(cfg, p, h2)
        else:
            moe = lambda p, h2: moe_ffn_apply(cfg, p, h2, mesh=None)[0]

        def grouped(x, xs):
            lg, moe_p, kc, vc = xs
            head = jax.tree.map(lambda a: a[:E - 1], (lg, kc, vc))
            x, (kc_head, vc_head) = lax.scan(dense, x, head)
            x, kc_last, vc_last = one_layer(x, jax.tree.map(lambda a: a[E - 1], lg), kc[E - 1],
                                            vc[E - 1], lambda lp, h2: moe(moe_p, h2))
            return x, (jnp.concatenate([kc_head, kc_last[None]]),
                       jnp.concatenate([vc_head, vc_last[None]]))

        x, (new_k, new_v) = lax.scan(grouped, x, (jax.tree.map(group, params["layers"]),
                                                  params["moe"], group(cache["k"]), group(cache["v"])))
        new_k, new_v = (a.reshape(cache["k"].shape) for a in (new_k, new_v))
    else:
        x, (new_k, new_v) = lax.scan(dense, x, (params["layers"], cache["k"], cache["v"]))
    if cfg.final_ln:
        x = tfm.layer_norm(x, params["lnf_scale"], params["lnf_bias"], cfg.layernorm_epsilon)
    head = params["lm_head"] if "lm_head" in params else params["wte"].T
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype)).astype(jnp.float32)
    if "lm_head_bias" in params:
        logits = logits + params["lm_head_bias"].astype(jnp.float32)
    return logits, {"k": new_k, "v": new_v}


_SMAX = 32
# (model fields, T, pos, write_pos): scalar pos = lock-step, list = one position a slot
_IN_PLACE_CASES = {
    "prefill_scalar_pos_T16": (dict(), 16, 3, None),
    "prefill_block_fills_the_cache": (dict(), _SMAX, 0, None),
    "alibi_prefill_block_fills_the_cache": (dict(pos_emb="alibi", embed_ln=True), _SMAX, 0, None),
    "decode_T1_one_row_parked_at_Smax": (dict(), 1, [5, 0, 17], [5, _SMAX, 17]),
    "verify_T4": (dict(), 4, [5, 0, 17], [5, _SMAX, 17]),
    "moe_every_2_prefill": (dict(moe_every=2, num_experts=4, moe_top_k=1, moe_capacity_factor=8.0,
                                 num_layers=4), 8, 0, None),
    "moe_every_2_decode": (dict(moe_every=2, num_experts=4, moe_top_k=1, num_layers=4),
                           1, [5, 0, 17], [5, _SMAX, 17]),
    "alibi_decode": (dict(pos_emb="alibi", embed_ln=True), 1, [5, 0, 17], [5, _SMAX, 17]),
    "alibi_prefill": (dict(pos_emb="alibi", embed_ln=True), 16, 0, None),
    "partial_rotary_parallel_residual": (dict(pos_emb="rotary", rotary_pct=0.25, parallel_residual=True,
                                              tie_embeddings=False), 1, [5, 0, 17], [5, _SMAX, 17]),
    "pallas_decode_kernel": (dict(pos_emb="rotary", decode_attn="kernel"), 1, [5, 0, 17], [5, _SMAX, 17]),
    "bf16_decode": (dict(dtype=jnp.bfloat16), 1, [5, 0, 17], [5, _SMAX, 17]),
}


@pytest.mark.parametrize("case", list(_IN_PLACE_CASES))
def test_apply_with_cache_in_place_is_bit_identical_to_restacking(case):
    from deepspeed_tpu.models import transformer as tfm

    fields, T, pos, write_pos = _IN_PLACE_CASES[case]
    cfg = TransformerConfig(**{**dict(
        vocab_size=97, max_seq_len=_SMAX, num_layers=3, num_heads=4, hidden_size=32,
        dtype=jnp.float32, loss_chunk_size=0, decode_attn="xla"), **fields})
    tfm._ACTIVE_MESH[0] = None  # the MoE sharding hook of an earlier test's engine
    params = tfm.init(cfg, jax.random.PRNGKey(0))
    B = 3
    shape = (cfg.num_layers, B, _SMAX, cfg.num_heads, cfg.head_dim)
    kk, kv, kt = jax.random.split(jax.random.PRNGKey(1), 3)
    # a cache that already holds something everywhere: an untouched element
    # must come back as it went in, not as zero
    cache = {"k": jax.random.normal(kk, shape).astype(cfg.dtype),
             "v": jax.random.normal(kv, shape).astype(cfg.dtype)}
    tokens = jax.random.randint(kt, (B, T), 0, cfg.vocab_size)
    kw = {} if write_pos is None else {"write_pos": jnp.asarray(write_pos, jnp.int32)}
    pos = jnp.asarray(pos, jnp.int32)

    got_logits, got = jax.jit(
        lambda p, t, c: tfm.apply_with_cache(cfg, p, t, c, pos, **kw))(params, tokens, cache)
    ref_logits, ref = jax.jit(
        lambda p, t, c: _restacking_apply_with_cache(cfg, p, t, c, pos, **kw))(params, tokens, cache)

    np.testing.assert_array_equal(np.asarray(got_logits), np.asarray(ref_logits))
    for side in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got[side], np.float32), np.asarray(ref[side], np.float32))
        assert got[side].shape == shape and got[side].dtype == cache[side].dtype
    if write_pos is not None:
        # the row parked at write_pos = Smax wrote nothing: mode="drop"
        for side in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(got[side][:, 1], np.float32),
                                          np.asarray(cache[side][:, 1], np.float32))
        assert not np.array_equal(np.asarray(got["k"][:, 0], np.float32),
                                  np.asarray(cache["k"][:, 0], np.float32))


# ---------------------------------------------------------------------------
# One block: training (`apply`), serving (`apply_with_cache`) and the pipeline
# compute the same function of the same `cfg`, whatever its residual form, norm
# and feed-forward (a routed layer honours `parallel_residual` and `norm_style`
# like a dense one; serving honours `norm_style`).
# ---------------------------------------------------------------------------

_AGREE_FFN = {
    "dense": dict(),
    "gshard_every_2nd": dict(moe_every=2, num_experts=4, moe_top_k=1, moe_capacity_factor=8.0),
    "dropless_top2": dict(moe_every=1, num_experts=4, moe_top_k=2, moe_routing="dropless",
                          activation="swiglu"),
}
_AGREE_CASES = {
    f"{res}-{ffn}-{kind}": dict(_AGREE_FFN[ffn], parallel_residual=res == "parallel", norm_kind=kind)
    for res in ("sequential", "parallel") for ffn in _AGREE_FFN for kind in ("layer", "rms")
}
_AGREE_CASES["post_norm-dense-layer"] = dict(norm_style="post")
# two whole periods, then one trailing dense layer (L % moe_every != 0)
_AGREE_CASES["sequential-gshard_every_2nd_of_5-layer"] = dict(_AGREE_FFN["gshard_every_2nd"], num_layers=5)
# latent attention (cache: a 16-wide latent + an 8-wide rotary key, narrower than ONE head) over a
# leading dense gated layer and three routed ones with a sigmoid router, a selection bias, a
# scale and a shared expert: the prefill below is shorter than its cache, so it and the decode
# step attend in the absorbed form, and ``apply`` in the expanded one
_AGREE_CASES["sequential-latent_lead1_sigmoid_shared-rms"] = dict(
    _AGREE_FFN["dropless_top2"], norm_kind="rms", use_bias=False, rotary_interleaved=True,
    qk_head_dim=24, v_head_dim=12, kv_lora_rank=16, qk_rope_head_dim=8, moe_first_dense=1,
    dense_intermediate_size=48, moe_score_fn="sigmoid", moe_select_bias=True,
    moe_norm_topk_prob=True, moe_routed_scale=2.5, moe_shared_size=24)

# the third residual form: a state-space mixer beside grouped-query attention (4 query / 2 K/V
# heads of 12, not hidden / heads) on the same normed input, then a sequential gated
# feed-forward, every multiplier other than 1; the mixer's state rides in the cache tree
_AGREE_CASES["mixer_beside_grouped_attention-gated-rms"] = dict(
    norm_kind="rms", use_bias=False, activation="swiglu", intermediate_size=48, num_kv_heads=2,
    qk_head_dim=12, tie_embeddings=False, ssm_state_size=8, ssm_heads=4, ssm_head_dim=8,
    ssm_groups=2, ssm_chunk_size=4,
    multipliers={"embedding_multiplier": 2.0, "attention_in_multiplier": 0.5,
                 "key_multiplier": 0.25, "attention_out_multiplier": 0.3,
                 "ssm_in_multiplier": 0.5, "ssm_multipliers": [0.35, 0.25, 0.18, 0.5, 0.35],
                 "ssm_out_multiplier": 0.2, "mlp_multipliers": [0.4, 0.1],
                 "lm_head_multiplier": 0.125})

# the third operator: a gated delta rule in three layers of four (2 key / 4 value heads of 8, a
# 4-tap filter, a float32 matrix a value head in the cache tree) under gated attention with
# rotary on half a head, a softmax router and a GATED shared expert
_AGREE_CASES["sequential-delta_in_3_of_4_gated_attention_gated_shared-rms"] = dict(
    _AGREE_FFN["dropless_top2"], norm_kind="rms", use_bias=False, num_kv_heads=2, qk_head_dim=16,
    rotary_pct=0.5, qk_norm="head", attn_output_gate=True, tie_embeddings=False,
    moe_norm_topk_prob=True, moe_shared_size=24, moe_shared_gate=True,
    layer_operators=["delta", "delta", "delta", "attn"], conv_kernel=4, delta_key_heads=2,
    delta_value_heads=4, delta_head_dim=8)

# a second norm on each sublayer's branch (PR 56), alone and with the stack run three times
# over the same weights: the cache keeps K/V per (pass, layer), 12 cache layers for 4 of weights
_AGREE_CASES["sequential-dense-sandwich_rms"] = dict(
    norm_style="sandwich", norm_kind="rms", use_bias=False, activation="swiglu",
    tie_embeddings=False)
_AGREE_CASES["sequential-dense-sandwich_layer_norms_with_biases"] = dict(norm_style="sandwich")
_AGREE_CASES["sequential-dense-sandwich_rms-3_passes-exit_gate"] = dict(
    _AGREE_CASES["sequential-dense-sandwich_rms"], layer_passes=3, exit_gate=True)
_AGREE_CASES["parallel-dense-pre_layer-2_passes"] = dict(parallel_residual=True, layer_passes=2)



def _agree_model(fields):
    from deepspeed_tpu.models import transformer as tfm

    cfg = TransformerConfig(**{**dict(
        vocab_size=97, max_seq_len=_SMAX, num_layers=4, num_heads=4, hidden_size=32,
        pos_emb="rotary", dtype=jnp.float32, loss_chunk_size=0, decode_attn="xla"), **fields})
    tfm._ACTIVE_MESH[0] = None
    params = tfm.init(cfg, jax.random.PRNGKey(0))
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    # noise on every leaf: a norm's scale and bias must count
    noisy = [a + 0.1 * jax.random.normal(k, a.shape, a.dtype) for a, k in zip(leaves, keys)]
    return tfm, cfg, jax.tree.unflatten(tree, noisy)


@pytest.mark.parametrize("case", list(_AGREE_CASES))
def test_training_and_serving_compute_the_same_layers(case):
    tfm, cfg, params = _agree_model(_AGREE_CASES[case])
    prompt = jnp.asarray(np.random.default_rng(1).integers(0, 97, size=(2, 9)), jnp.int32)
    full = tfm.apply(cfg, params, prompt)[:, -1]
    logits, cache = tfm.apply_with_cache(cfg, params, prompt, tfm.init_cache(cfg, 2, _SMAX), 0,
                                         last_only=True)
    np.testing.assert_allclose(np.asarray(logits[:, -1]), np.asarray(full), rtol=0, atol=2e-3)
    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    dec, _ = tfm.apply_with_cache(cfg, params, nxt, cache, jnp.full((2,), 9, jnp.int32))
    ext = tfm.apply(cfg, params, jnp.concatenate([prompt, nxt], 1))[:, -1]
    np.testing.assert_allclose(np.asarray(dec[:, -1]), np.asarray(ext), rtol=0, atol=2e-3)


@pytest.mark.parametrize("case", ["parallel-gshard_every_2nd-layer", "parallel-gshard_every_2nd-rms"])
def test_pipeline_computes_the_same_layers(case):
    from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh
    from deepspeed_tpu.pipe import PipelinedTransformer

    tfm, cfg, params = _agree_model(_AGREE_CASES[case])
    mesh = build_mesh(MeshConfig(pipe=2, data=-1))
    plain = Model(cfg)
    plain.set_mesh(mesh)
    batch = {"tokens": np.random.default_rng(2).integers(0, 97, size=(4, 17)).astype(np.int32)}
    want = plain.loss(params, batch)
    piped = PipelinedTransformer(cfg, num_stages=2, num_micro_batches=1)
    piped.set_mesh(mesh)
    staged = dict(params, **{name: jax.tree.map(lambda a: a.reshape((2, a.shape[0] // 2) + a.shape[1:]),
                                                params[name]) for name in ("layers", "moe")})
    try:
        np.testing.assert_allclose(np.asarray(piped.loss(staged, batch)), np.asarray(want), rtol=2e-5)
    finally:
        tfm._ACTIVE_MESH[0] = None


# ---------------------------------------------------------------------------
# A lock-step block as long as its cache attends through the flash forward
# kernel once the dense form's score matrix is over DENSE_SCORE_BYTES (PR 30):
# the same function as the dense form, chosen by the shapes alone.
# ---------------------------------------------------------------------------

_FLASH_POS = {"alibi": dict(pos_emb="alibi", embed_ln=True), "rotary": dict(pos_emb="rotary")}
_FLASH_PREFILL_CASES = [(pos, dtype, live) for pos in _FLASH_POS for dtype in ("float32", "bfloat16")
                        for live in ("full_bucket", "padded_bucket")]
_FLASH_ROWS, _FLASH_HEADS = 256, 4


@pytest.fixture
def kernel_from_256_rows(monkeypatch):
    """The measured constant is 100 MiB of scores (16 heads x 1280 rows): the
    tiny model reaches the kernel on the CPU with the constant steered, in the
    test, to one byte under its own 256-row block's scores."""
    from deepspeed_tpu.models import transformer as tfm

    monkeypatch.setattr(tfm, "DENSE_SCORE_BYTES", 4 * _FLASH_HEADS * _FLASH_ROWS ** 2 - 1)
    return tfm


def test_cache_attention_form_reads_the_shapes():
    from deepspeed_tpu.models import transformer as tfm

    form = tfm.cache_attention_form
    # 16 heads (the benchmark's three models): dense up to 1280 rows, the kernel from 1536
    assert [form(16, 1, T, T) for T in (1, 128, 1024, 1280, 1536, 2048)] == \
        ["dense"] * 4 + ["flash"] * 2
    assert form(32, 1, 1024, 1024) == "flash" and form(4, 1, 2048, 2048) == "dense"  # by bytes
    assert form(16, 2, 1024, 1024) == "flash"  # a lock-step batch holds B score matrices
    # a prompt shorter than its cache, a decode step, per-row positions: dense whatever the size
    assert form(16, 1, 2048, 4096) == form(16, 8, 1, 2048) == "dense"
    assert form(16, 1, 2048, 2048, lock_step=False) == "dense"


@pytest.mark.parametrize("pos_emb,dtype,live", _FLASH_PREFILL_CASES,
                         ids=["-".join(c) for c in _FLASH_PREFILL_CASES])
def test_prefill_block_that_fills_its_cache_attends_through_the_flash_kernel(
        pos_emb, dtype, live, kernel_from_256_rows):
    """``apply_with_cache`` with ``T == Smax`` at the smallest block over the
    constant (the kernel in interpreter mode) against the hand-written layer
    above, which goes down ``cached_attention`` whatever the shapes: the
    logits of the live rows to round-off of the compute dtype, the sampled
    token equal, the cache written bit for bit in layer 0 (its rows have
    passed through no attention) and to round-off below it. Half as long, or
    shorter than its cache, the same call traces no kernel."""
    tfm = kernel_from_256_rows
    T = _FLASH_ROWS
    true_len = T if live == "full_bucket" else T - 37
    cfg = TransformerConfig(**{**dict(
        vocab_size=97, max_seq_len=T, num_layers=3, num_heads=_FLASH_HEADS, hidden_size=32,
        dtype=jnp.dtype(dtype), loss_chunk_size=0, decode_attn="xla"), **_FLASH_POS[pos_emb]})
    tfm._ACTIVE_MESH[0] = None  # an earlier test's engine
    params = tfm.hold_for_compute(cfg, tfm.init(cfg, jax.random.PRNGKey(0)))
    tokens = np.zeros((1, T), np.int32)  # a bucket: the prompt, then padding
    tokens[0, :true_len] = np.random.default_rng(5).integers(0, cfg.vocab_size, size=true_len)
    cache = tfm.init_cache(cfg, 1, T, dtype=cfg.dtype)

    flash = lambda p, t, c: tfm.apply_with_cache(cfg, p, t, c, 0)
    assert "flash_fwd" in str(jax.make_jaxpr(flash)(params, tokens, cache))
    got_logits, got = jax.jit(flash)(params, tokens, cache)
    ref_logits, ref = jax.jit(
        lambda p, t, c: _restacking_apply_with_cache(cfg, p, t, c, 0))(params, tokens, cache)

    got_logits, ref_logits = (np.asarray(x[0, :true_len], np.float32) for x in (got_logits, ref_logits))
    step = float(jnp.finfo(cfg.dtype).eps)  # float32: 1.2e-7, bf16: 7.8e-3, of values of order 1
    np.testing.assert_allclose(got_logits, ref_logits, rtol=0, atol=4 * step * np.abs(ref_logits).max())
    assert np.argmax(got_logits[-1]) == np.argmax(ref_logits[-1])  # greedy at last_index
    for side in ("k", "v"):
        g, r = (np.asarray(x[side][:, 0, :true_len], np.float32) for x in (got, ref))
        np.testing.assert_array_equal(g[0], r[0])
        np.testing.assert_allclose(g[1:], r[1:], rtol=0, atol=4 * step * np.abs(r).max())
        assert got[side].shape == cache[side].shape and got[side].dtype == cache[side].dtype

    short = T // 2
    for t_new, s_max in ((short, short), (short, T)):
        jaxpr = jax.make_jaxpr(lambda p, t, c: tfm.apply_with_cache(cfg, p, t, c, 0))(
            params, tokens[:, :t_new], tfm.init_cache(cfg, 1, s_max, dtype=cfg.dtype))
        assert "pallas_call" not in str(jaxpr)


def test_prefill_span_says_which_attention_its_program_runs(kernel_from_256_rows):
    """The ``…/prefill`` span's ``attn`` comes from the rule the bucket's
    program was traced with: a long prompt's bucket reaches the kernel, a
    short one's does not, and both serve the model's own greedy tokens."""
    import time

    from deepspeed_tpu.ops.pallas.flash_attention import causal_tiles_pct
    from deepspeed_tpu.telemetry import tracing

    tfm, rows = kernel_from_256_rows, _FLASH_ROWS
    cfg = TransformerConfig(vocab_size=97, max_seq_len=2 * rows, num_layers=2, num_heads=_FLASH_HEADS,
                            hidden_size=32, dtype=jnp.float32, loss_chunk_size=0,
                            decode_attn="xla", pos_emb="alibi", embed_ln=True)
    eng = InferenceEngine(model=Model(cfg), config={"dtype": "fp32"})
    srv = ServingEngine(eng, n_slots=2, max_seq_len=2 * rows)
    long_p, short_p = _prompts([rows - 30, 9], seed=3)
    t0 = time.perf_counter()
    res = srv.serve([Request(uid=0, prompt=long_p, max_new_tokens=3),
                     Request(uid=1, prompt=short_p, max_new_tokens=3)])
    spans = {sp.attrs["uid"]: sp.attrs for sp in tracing.spans(t0) if sp.name == "prefill"}
    assert (spans[0]["bucket"], spans[0]["attn"]) == (rows, "flash")
    assert spans[1]["bucket"] < rows and spans[1]["attn"] == "dense"
    # and what the kernel's causal schedule computes of the triangle at the bucket's rows
    assert spans[0]["causal_tiles_pct"] == round(causal_tiles_pct(rows, cfg.head_dim, 4), 2) >= 100
    assert "causal_tiles_pct" not in spans[1]
    for uid, prompt in ((0, long_p), (1, short_p)):
        assert res[uid].status == "ok"
        logits = tfm.apply(cfg, eng.params, prompt[None])  # training's forward: xla attention
        assert int(jnp.argmax(logits[0, -1])) == int(res[uid].tokens[0])
