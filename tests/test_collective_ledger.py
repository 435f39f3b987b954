"""Collective X-ray: HLO collective parsing, mesh-axis mapping, the ICI
comm-time model, step-anatomy math, and comm reconcile.

Contracts under test:

  * the HLO parser extracts op kind / payload bytes / replica groups (both
    the brace and iota spellings) / channel ids, folds async ``-start``/
    ``-done`` pairs into one logical op, and judges overlap from the
    instructions scheduled between them;
  * replica groups map back to mesh AXIS NAMES on a known mesh (single
    axes, combined axes, permute rings via source_target_pairs), with an
    attributable fallback label when nothing matches;
  * hand-computed anatomy fixtures: exact bytes/flops/peaks -> exact
    compute/hbm/comm times and exposed-comm estimates, and an ``unrated``
    platform yields NO comm roofline (labeled nulls), never fabricated
    numbers;
  * a REAL shard_map psum program round-trips through the ProgramLedger's
    lazy resolution with bit-exact compile-count equality pre/post
    snapshot under watchdog raise — the X-ray adds zero XLA programs;
  * ``CommsLogger.summary()`` per-axis totals and ``reconcile()`` verdicts
    (ok / unlogged-in-host / unseen-in-hlo).

Speed: everything here is host-side string/dict work except ONE tiny
shard_map psum program (first run compiles it into tests/.xla_cache;
warm runs load it).
"""

import textwrap

import numpy as np
import pytest

from deepspeed_tpu.telemetry import Telemetry
from deepspeed_tpu.telemetry.collective_ledger import (
    infer_axes, parse_hlo_collectives, pipeline_bubble_fraction,
    step_anatomy, summarize_collectives)


# ---------------------------------------------------------------------------
# HLO parsing (synthetic modules — pure host)
# ---------------------------------------------------------------------------

SYNC_HLO = textwrap.dedent("""\
    HloModule sync
    ENTRY %main (p0: f32[8,16]) -> f32[8,16] {
      %p0 = f32[8,16]{1,0} parameter(0)
      %ar = f32[8,16]{1,0} all-reduce(f32[8,16]{1,0} %p0), channel_id=1, replica_groups={{0,1},{2,3}}, use_global_device_ids=true, to_apply=%region_0.4
      ROOT %ag = bf16[16,16]{1,0} all-gather(bf16[8,16]{1,0} %ar2), channel_id=2, replica_groups=[2,2]<=[4], dimensions={0}
    }
""")

# the same module as the installed XLA prints it: operands by name only, so
# each is sized from the instruction (or tuple-typed start) that defined it
SYNC_HLO_NAMED = textwrap.dedent("""\
    HloModule sync
    ENTRY %main (p0: f32[8,16]) -> f32[8,16] {
      %p0 = f32[8,16]{1,0} parameter(0)
      %ar = f32[8,16]{1,0} all-reduce(%p0), channel_id=1, replica_groups={{0,1},{2,3}}, use_global_device_ids=true, to_apply=%region_0.4
      %ar2 = bf16[8,16]{1,0:T(8,128)(2,1)} convert(%ar)
      ROOT %ag = bf16[16,16]{1,0} all-gather(%ar2), channel_id=2, replica_groups=[2,2]<=[4], dimensions={0}
    }
""")

ASYNC_OVERLAPPED_HLO = textwrap.dedent("""\
    HloModule ovl
    ENTRY %main (p0: f32[128]) -> f32[128] {
      %p0 = f32[128]{0} parameter(0)
      %ars = (f32[128]{0}, f32[128]{0}) all-reduce-start(f32[128]{0} %p0), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_0.4
      %fus = f32[128]{0} fusion(f32[128]{0} %p0), kind=kLoop, calls=%fused_computation
      %ard = f32[128]{0} all-reduce-done((f32[128]{0}, f32[128]{0}) %ars)
      ROOT %add = f32[128]{0} add(f32[128]{0} %ard, f32[128]{0} %fus)
    }
""")

ASYNC_SERIAL_HLO = ASYNC_OVERLAPPED_HLO.replace(
    "  %fus = f32[128]{0} fusion(f32[128]{0} %p0), kind=kLoop, calls=%fused_computation\n",
    "")

PERMUTE_HLO = textwrap.dedent("""\
    HloModule perm
    ENTRY %main (p0: u8[64]) -> u8[64] {
      %p0 = u8[64]{0} parameter(0)
      ROOT %cp = u8[64]{0} collective-permute(u8[64]{0} %p0), channel_id=1, source_target_pairs={{0,1},{1,0},{2,3},{3,2}}
    }
""")

MESH22 = {"data": 2, "model": 2}


@pytest.mark.parametrize("hlo", [SYNC_HLO, SYNC_HLO_NAMED],
                         ids=["typed-operands", "named-operands"])
def test_parse_sync_collectives_bytes_groups_and_channels(hlo):
    ops = parse_hlo_collectives(hlo)
    ar, ag = ops
    assert ar["op"] == "all-reduce" and not ar["async"]
    assert ar["payload_bytes"] == 8 * 16 * 4  # f32 operand
    assert ar["groups"] == [[0, 1], [2, 3]]
    assert ar["channel_id"] == 1
    assert ag["op"] == "all-gather"
    assert ag["payload_bytes"] == 8 * 16 * 2  # bf16 SHARD operand
    assert ag["groups"] == [[0, 1], [2, 3]]  # iota [2,2]<=[4] decoded


def test_parse_async_pair_overlap_verdicts():
    (start,) = parse_hlo_collectives(ASYNC_OVERLAPPED_HLO)
    assert start["async"] and start["overlapped"]
    (serial,) = parse_hlo_collectives(ASYNC_SERIAL_HLO)
    assert serial["async"] and not serial["overlapped"]
    # the pair folds to ONE logical op — bytes never double-counted
    assert start["payload_bytes"] == 128 * 4


def test_tuple_result_compute_counts_for_overlap():
    """Post-opt HLO routinely emits multi-output fusions / while loops with
    TUPLE result shapes between an async pair — they are real compute and
    must flip the verdict to overlapped (regression: single-token shape
    regex read them as non-compute)."""
    hlo = ASYNC_OVERLAPPED_HLO.replace(
        "%fus = f32[128]{0} fusion(f32[128]{0} %p0), kind=kLoop, calls=%fused_computation",
        "%fus = (f32[128]{0}, f32[128]{0}) fusion(f32[128]{0} %p0), kind=kLoop, calls=%fc")
    (start,) = parse_hlo_collectives(hlo)
    assert start["overlapped"]
    # nested tuple results (a while's carry) count too
    hlo2 = ASYNC_OVERLAPPED_HLO.replace(
        "%fus = f32[128]{0} fusion(f32[128]{0} %p0), kind=kLoop, calls=%fused_computation",
        "%w = ((f32[8,8]{1,0}, s32[]), f32[]) while(((f32[8,8]{1,0}, s32[]), f32[]) %t), condition=%c, body=%b")
    (start2,) = parse_hlo_collectives(hlo2)
    assert start2["overlapped"]


def test_suffixed_async_names_pair_exactly():
    """'%all-reduce-start' vs '%all-reduce-start.1' must pair by EXACT
    identifier (substring matching judged the wrong start over the wrong
    line span and left the other pair verdict-less)."""
    hlo = textwrap.dedent("""\
        HloModule two
        ENTRY %main (p0: f32[128]) -> f32[128] {
          %p0 = f32[128]{0} parameter(0)
          %all-reduce-start = (f32[128]{0}, f32[128]{0}) all-reduce-start(f32[128]{0} %p0), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%r
          %all-reduce-done = f32[128]{0} all-reduce-done((f32[128]{0}, f32[128]{0}) %all-reduce-start)
          %all-reduce-start.1 = (f32[128]{0}, f32[128]{0}) all-reduce-start(f32[128]{0} %p0), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%r
          %fus = f32[128]{0} fusion(f32[128]{0} %p0), kind=kLoop, calls=%fc
          %all-reduce-done.1 = f32[128]{0} all-reduce-done((f32[128]{0}, f32[128]{0}) %all-reduce-start.1)
        }
    """)
    first, second = parse_hlo_collectives(hlo)
    # nothing between the FIRST pair; the fusion sits inside the SECOND
    assert not first["overlapped"]
    assert second["overlapped"]
    s = summarize_collectives(hlo, {"data": 4})
    assert s["async_pairs"] == 2 and s["overlapped_pairs"] == 1
    assert s["overlap_verdict"] == "partial-overlap"


def test_infer_axes_on_known_mesh():
    # row-major enumeration over {data:2, model:2}: device = 2*d + m
    assert infer_axes([[0, 1], [2, 3]], MESH22) == "model"
    assert infer_axes([[0, 2], [1, 3]], MESH22) == "data"
    assert infer_axes([[0, 1, 2, 3]], MESH22) == "data+model"
    assert infer_axes([[0, 3], [1, 2]], MESH22).startswith("unmapped[2x2]")
    assert infer_axes([[0, 1]], None).startswith("unmapped")
    assert infer_axes([], MESH22) == "world"


def test_permute_pairs_map_through_components():
    s = summarize_collectives(PERMUTE_HLO, MESH22)
    # pairs {0,1},{2,3} component exactly into the model-axis partition
    assert s["bytes_by_axis"] == {"model": 64}
    assert s["counts_by_op"] == {"collective-permute": 1}
    assert s["overlap_verdict"] == "serialized"


def test_summarize_wire_factors_and_verdict():
    s = summarize_collectives(SYNC_HLO, MESH22)
    # all-reduce over 2 ranks: 2*(n-1)/n = 1.0x payload; all-gather: n-1 = 1x
    assert s["wire_bytes_by_axis"]["model"] == pytest.approx(
        8 * 16 * 4 * 1.0 + 8 * 16 * 2 * 1.0)
    assert s["by_op_axis"]["all-reduce@model"] == {
        "count": 1, "bytes": 8 * 16 * 4}
    assert s["overlap_verdict"] == "serialized"
    assert summarize_collectives("HloModule empty", MESH22)[
        "overlap_verdict"] == "none"
    ovl = summarize_collectives(ASYNC_OVERLAPPED_HLO, {"data": 4})
    assert ovl["overlap_verdict"] == "overlapped"
    assert ovl["async_pairs"] == 1 and ovl["overlapped_pairs"] == 1


# ---------------------------------------------------------------------------
# step anatomy against hand-computed fixtures
# ---------------------------------------------------------------------------

RATED = {"platform": "tpu", "device_kind": "fixture", "label": "fixture",
         "peak_tflops": 4.0, "peak_hbm_gbps": 1000.0, "peak_ici_gbps": 100.0}
UNRATED = {"platform": "cpu", "device_kind": "cpu", "label": "cpu (unrated)",
           "peak_tflops": None, "peak_hbm_gbps": None, "peak_ici_gbps": None}


def _coll(wire_bytes_by_axis, payload=None, verdict="serialized"):
    return {
        "bytes_by_axis": payload or {k: int(v)
                                     for k, v in wire_bytes_by_axis.items()},
        "wire_bytes_by_axis": wire_bytes_by_axis,
        "counts_by_op": {"all-reduce": 1},
        "by_op_axis": {},
        "async_pairs": 0, "overlapped_pairs": 0,
        "overlap_verdict": verdict,
    }


def test_anatomy_exact_times_on_rated_platform():
    # compute = 2e12 / 4e12 = 0.5s; hbm = 1e12 / 1e12 = 1.0s;
    # comm = 50e9 wire bytes / 100e9 B/s = 0.5s;
    # exposed = wall 1.6 - max(device 1.0, comm 0.5) = 0.6s
    row = {"name": "prog", "flops": 2e12, "bytes_accessed": 1e12}
    wall = {"count": 3, "p50": 1.6}
    a = step_anatomy(row, wall, RATED, _coll({"data": 50e9}))
    assert a["compute_time_s"] == pytest.approx(0.5)
    assert a["hbm_time_s"] == pytest.approx(1.0)
    assert a["comm_time_by_axis"] == {"data": pytest.approx(0.5)}
    assert a["comm_time_s"] == pytest.approx(0.5)
    assert a["exposed_comm_estimate_s"] == pytest.approx(0.6)
    assert a["overlap_verdict"] == "serialized"
    assert a["comm_rated"] is True


def test_anatomy_comm_dominated_and_hidden_cases():
    row = {"name": "prog", "flops": 2e12, "bytes_accessed": 1e12}
    # comm roof (2.0s) above device roof (1.0s): exposed = wall - comm
    a = step_anatomy(row, {"count": 1, "p50": 2.5}, RATED,
                     _coll({"data": 200e9}))
    assert a["comm_time_s"] == pytest.approx(2.0)
    assert a["exposed_comm_estimate_s"] == pytest.approx(0.5)
    # perfectly hidden: wall at the device roof -> exposed 0 (clamped)
    b = step_anatomy(row, {"count": 1, "p50": 0.9}, RATED,
                     _coll({"data": 50e9}))
    assert b["exposed_comm_estimate_s"] == 0.0


def test_anatomy_unrated_platform_has_no_comm_roofline():
    """Acceptance: an unrated platform keeps the static facts (bytes per
    axis, overlap verdict) but carries LABELED nulls — no comm roofline,
    no exposed-comm, never fabricated numbers."""
    row = {"name": "prog", "flops": 2e12, "bytes_accessed": 1e12}
    a = step_anatomy(row, {"count": 3, "p50": 1.6}, UNRATED,
                     _coll({"data": 50e9}, verdict="overlapped"))
    assert a["compute_time_s"] is None and a["hbm_time_s"] is None
    assert a["comm_time_by_axis"] is None and a["comm_time_s"] is None
    assert a["exposed_comm_estimate_s"] is None
    assert a["comm_rated"] is False
    # static HLO facts survive unrated
    assert a["comm_bytes_by_axis"] == {"data": int(50e9)}
    assert a["overlap_verdict"] == "overlapped"


def test_anatomy_ici_override_rates_an_unrated_comm_side():
    # explicit telemetry.ledger.collectives.ici_gbps rates the comm model
    # even when the peak table has no entry — but compute/hbm stay null
    row = {"name": "prog", "flops": 2e12, "bytes_accessed": 1e12}
    a = step_anatomy(row, {"count": 1, "p50": 1.0}, UNRATED,
                     _coll({"data": 50e9}), ici_gbps=50.0)
    assert a["comm_time_s"] == pytest.approx(1.0)
    assert a["compute_time_s"] is None
    assert a["exposed_comm_estimate_s"] is None  # device side unrated


def test_anatomy_no_collectives_is_labeled_none():
    row = {"name": "prog", "flops": 2e12, "bytes_accessed": 1e12}
    a = step_anatomy(row, {"count": 1, "p50": 1.0}, RATED, None)
    assert a["overlap_verdict"] == "none"
    assert a["comm_bytes_by_axis"] == {} and a["comm_rated"] is False
    assert a["comm_time_s"] is None


def test_pipeline_bubble_fraction():
    assert pipeline_bubble_fraction(4, 8) == pytest.approx(3 / 11)
    assert pipeline_bubble_fraction(1, 8) == 0.0
    assert pipeline_bubble_fraction(2, 2) == pytest.approx(1 / 3)


def test_peak_table_carries_ici_with_unrated_nulls():
    from deepspeed_tpu.telemetry.program_ledger import PEAKS

    for key, entry in PEAKS.items():
        assert "peak_ici_gbps" in entry, key
        if entry["peak_tflops"] is None:
            assert entry["peak_ici_gbps"] is None, key  # unrated stays null
        else:
            assert entry["peak_ici_gbps"] > 0, key


# ---------------------------------------------------------------------------
# a REAL compiled collective program: zero new XLA programs
# ---------------------------------------------------------------------------

def test_real_psum_program_xray_zero_new_programs(mesh8):
    """A shard_map psum program captured by the watchdog resolves through
    the SAME lower().compile() path as the cost model: the jit cache is
    bit-identical before/after the snapshot (watchdog raise armed), and
    the HLO-derived summary attributes the reduce to the mesh axis."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    axis = next(a for a, s in mesh8.shape.items() if s > 1)  # "data" (8)
    fn = jax.jit(shard_map(
        lambda x: lax.psum(x, axis), mesh=mesh8,
        in_specs=P(axis), out_specs=P()))
    tm = Telemetry(watchdog_mode="raise")
    tm.ledger.set_mesh_shape(dict(mesh8.shape))
    watched = tm.watch(fn, "test/psum", stable=True)
    x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)
    watched(x)
    watched(x)  # second call must not compile (raise-armed)
    before = fn._cache_size()
    snap = tm.snapshot()
    assert fn._cache_size() == before  # resolution added NO program
    snap2 = tm.snapshot()  # memoized: second snapshot identical counts
    assert fn._cache_size() == before

    coll = tm.ledger.collectives.get("test/psum")
    assert coll is not None and coll["n_collectives"] >= 1
    assert set(coll["bytes_by_axis"]) == {axis}
    assert coll["bytes_by_axis"][axis] > 0
    rows = {r["name"]: r for r in snap["step_anatomy"]}
    assert rows["test/psum"]["comm_time_s"] is None  # cpu stays unrated
    assert rows["test/psum"]["comm_bytes_by_axis"][axis] > 0
    assert snap2["step_anatomy"]


# ---------------------------------------------------------------------------
# comm logger: per-axis totals + reconcile
# ---------------------------------------------------------------------------

@pytest.fixture
def clean_comms_logger():
    from deepspeed_tpu.comm.logger import comms_logger

    was_enabled = comms_logger.enabled
    comms_logger.reset()
    comms_logger.configure(enabled=True)
    yield comms_logger
    comms_logger.reset()
    comms_logger.configure(enabled=was_enabled)


def test_summary_gains_per_axis_totals(clean_comms_logger):
    log = clean_comms_logger
    a = np.zeros((4, 8), np.float32)  # 128B
    log.record("all_reduce[sum]", "data", a)
    log.record("all_gather", "data", a)
    log.record("ppermute", ("data", "fsdp"), a)  # tuple axis -> one label
    s = log.summary()
    assert s["all_reduce[sum]@data"] == {"count": 1, "bytes": 128}
    assert "ppermute@data+fsdp" in s  # canonical tuple spelling
    assert s["by_axis"]["data"] == {"count": 2, "bytes": 256}
    assert s["by_axis"]["data+fsdp"] == {"count": 1, "bytes": 128}


def test_nbytes_handles_pytrees(clean_comms_logger):
    log = clean_comms_logger
    tree = {"a": np.zeros((2, 2), np.float32), "b": np.zeros(4, np.float32)}
    log.record("all_reduce[mean]", "data", tree)  # a whole-grad reduce
    assert log.summary()["all_reduce[mean]@data"]["bytes"] == 32


def test_reconcile_verdicts(clean_comms_logger):
    log = clean_comms_logger
    log.record("all_reduce[sum]", "data", np.zeros(32, np.float32))
    rows = {r["axis"]: r for r in log.reconcile({
        "data": {"count": 2, "bytes": 256},
        "model": {"count": 1, "bytes": 64},
    })}
    # both sides saw 'data' (counts need not match — scan bodies log per
    # trace but appear once in HLO): ok
    assert rows["data"]["verdict"] == "ok"
    assert rows["data"]["host_bytes"] == 128
    assert rows["data"]["hlo_bytes"] == 256
    # 'model' compiled collectives the host never logged: the unlogged-
    # collective lint rule's runtime twin, surfaced as a labeled warning
    assert rows["model"]["verdict"] == "unlogged-in-host"
    # host-only axis (ledger never resolved that program): unseen-in-hlo
    log.record("all_gather", "fsdp", np.zeros(4, np.float32))
    rows = {r["axis"]: r for r in log.reconcile({})}
    assert rows["fsdp"]["verdict"] == "unseen-in-hlo"


def test_reconcile_canonicalizes_trivial_axes(clean_comms_logger):
    """The engine logs its dp reduce over ('data','fsdp'); on a
    {data:8, fsdp:1} mesh the HLO groups are indistinguishable from plain
    'data' — reconcile must NOT emit a false warning pair (regression:
    unlogged-in-host 'data' + unseen-in-hlo 'data+fsdp' on every healthy
    snapshot)."""
    log = clean_comms_logger
    log.record("all_reduce[mean]", ("data", "fsdp"), np.zeros(8, np.float32))
    mesh = {"data": 8, "fsdp": 1}
    rows = {r["axis"]: r for r in log.reconcile(
        {"data": {"count": 1, "bytes": 32}}, mesh_shape=mesh)}
    assert set(rows) == {"data"}
    assert rows["data"]["verdict"] == "ok"
    assert rows["data"]["host_bytes"] == 32
    # a collective over a FULLY trivial axis is identity — nothing in HLO
    # to reconcile against, so it is skipped, not flagged
    log.record("all_gather", "fsdp", np.zeros(4, np.float32))
    rows = {r["axis"]: r for r in log.reconcile(
        {"data": {"count": 1, "bytes": 32}}, mesh_shape=mesh)}
    assert "fsdp" not in rows and set(rows) == {"data"}
    # caller-order tuples re-canonicalize to MESH order: ('fsdp','data')
    # on a non-trivial mesh is the same collective as 'data+fsdp'
    log.reset()
    log.record("all_reduce[sum]", ("fsdp", "data"), np.zeros(8, np.float32))
    rows = {r["axis"]: r for r in log.reconcile(
        {"data+fsdp": {"count": 1, "bytes": 32}},
        mesh_shape={"data": 2, "fsdp": 4})}
    assert set(rows) == {"data+fsdp"}
    assert rows["data+fsdp"]["verdict"] == "ok"


def test_reconcile_warning_renders_in_report(clean_comms_logger):
    from deepspeed_tpu.telemetry.report import summarize

    snap_ev = {"type": "snapshot",
               "comm_reconcile": [
                   {"axis": "data", "host_count": 0, "host_bytes": 0,
                    "hlo_count": 3, "hlo_bytes": 4096,
                    "verdict": "unlogged-in-host"}],
               "metrics": {"counters": {}, "gauges": {}, "histograms": {}}}
    out = summarize([snap_ev])
    assert "comm reconcile WARNINGS" in out
    assert "unlogged-in-host" in out and "data" in out


# ---------------------------------------------------------------------------
# ledger config plumbing
# ---------------------------------------------------------------------------

def test_collectives_config_block_schema():
    from deepspeed_tpu.runtime.config import (CollectiveLedgerConfig,
                                              DeepSpeedConfigError,
                                              LedgerConfig)

    lc = LedgerConfig(collectives={"enabled": False, "ici_gbps": 42.0})
    assert isinstance(lc.collectives, CollectiveLedgerConfig)
    assert lc.collectives.enabled is False
    assert lc.collectives.ici_gbps == 42.0
    assert LedgerConfig().collectives.enabled is True  # default on
    with pytest.raises(DeepSpeedConfigError):
        CollectiveLedgerConfig(ici_gbps=-1.0)


def test_disabled_collectives_skip_hlo_capture(mesh8):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    axis = next(a for a, s in mesh8.shape.items() if s > 1)
    fn = jax.jit(shard_map(
        lambda x: lax.psum(x, axis), mesh=mesh8,
        in_specs=P(axis), out_specs=P()))
    tm = Telemetry(watchdog_mode="off", ledger_collectives=False)
    watched = tm.watch(fn, "test/psum-off")
    watched(jnp.ones((8, 16), jnp.float32))
    snap = tm.snapshot()
    assert tm.ledger.collectives.programs == {}
    rows = {r["name"]: r for r in snap["step_anatomy"]}
    assert rows["test/psum-off"]["overlap_verdict"] == "none"
