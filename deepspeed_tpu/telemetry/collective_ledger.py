"""Collective X-ray: per-collective comm ledger + ICI roofline + step anatomy.

The ROADMAP's perf push names its headline tactic — overlap the dp grad
allreduce with backward — but until now nothing in the repo could *see*
collective time: the ProgramLedger rates whole programs against compute/HBM
roofs only, and ``comm/logger.py`` counts host-side bytes with no time model
and no view of what XLA actually scheduled. This module closes that gap by
reading the COMPILED program, not the python that traced it:

  * ``parse_hlo_collectives`` walks the post-optimization HLO text of a
    ``lower().compile()`` artifact (the ProgramLedger's lazily-resolved
    executables — same zero-new-XLA-programs discipline as the cost model)
    and extracts every collective op: ``all-reduce``, ``all-gather``,
    ``reduce-scatter``, ``all-to-all``, ``collective-permute`` and their
    async ``-start``/``-done`` pairs, with per-op payload bytes from the
    operand shapes and the replica/partition groups XLA assigned;
  * replica groups are mapped back to MESH AXIS NAMES (``infer_axes``):
    the row-major device enumeration over the mesh axes makes each axis
    subset's group partition computable, so ``{{0,2},{1,3}}`` on a
    ``{data:2, model:2}`` mesh reads as ``data``, not as opaque id lists;
  * the overlap verdict is STATIC, read from the schedule XLA emitted: an
    async ``-start``/``-done`` pair with real compute (fusion / dot /
    convolution / custom-call / while) between the two instructions is
    overlapped — this answers "did the dp allreduce hide behind backward?"
    from the executable itself, before and after any async-collective work;
  * ``step_anatomy`` joins the per-program collective summary with the
    platform peak table (now carrying per-generation ICI bandwidth) and the
    measured wall-time histograms into where-every-millisecond-goes rows:
    ``{compute_time_s, hbm_time_s, comm_time_by_axis,
    exposed_comm_estimate_s = wall_p50 - max(device_time, comm_time),
    overlap_verdict}``. CPU/unknown platforms keep the static facts (bytes,
    verdict) but carry LABELED null times — an unrated platform never gets
    a fabricated comm roofline.

Known limits (by design): the byte model is per-compiled-program — a
collective inside a ``while``/scan body is counted once, not per trip
(the *measured* wall time in the anatomy absorbs the repetition); the
wire-time model is the standard ring-algorithm factor per op, an
estimate, not a measurement. Methodology, ICI peak provenance and the
metric catalog live in docs/observability.md ("Collective X-ray").
"""

from __future__ import annotations

import itertools
import re
from typing import Optional

# ---------------------------------------------------------------------------
# HLO text parsing (pure string work — no jax import needed)
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(
    r"\b(" + "|".join(sorted(_DTYPE_BYTES, key=len, reverse=True))
    + r")\[([0-9,]*)\]")

# `%name = <shape> <op>(` — shape may be a tuple for async starts
_OP_LINE_RE = re.compile(
    r"=\s*(?:\([^=()]*(?:\([^()]*\)[^=()]*)*\)|\S+)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<kind>-start|-done)?\(")

_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=")
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
_GROUPS_V1_RE = re.compile(r"replica_groups=\{(\{[0-9,\s]*\}(?:,\s*\{[0-9,\s]*\})*)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{(\{[0-9,\s]*\}(?:,\s*\{[0-9,\s]*\})*)\}")
# instruction lines whose op counts as real compute for the overlap verdict
# (result shape may be a tuple — multi-output fusions, while loops — with
# one nesting level, same alternative as _OP_LINE_RE)
_COMPUTE_RE = re.compile(
    r"=\s*(?:\([^=()]*(?:\([^()]*\)[^=()]*)*\)|\S+)\s+"
    r"(?:fusion|dot|convolution|custom-call|while)\(")
_ID_RE = re.compile(r"%([\w.\-]+)")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        d = d.strip()
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _parse_brace_groups(body: str) -> list[list[int]]:
    """``{0,1},{2,3}`` -> [[0,1],[2,3]]."""
    out = []
    for grp in re.findall(r"\{([0-9,\s]*)\}", body):
        ids = [int(x) for x in grp.replace(" ", "").split(",") if x]
        if ids:
            out.append(ids)
    return out


def _parse_iota_groups(g: int, s: int, dims: str,
                       perm: Optional[str]) -> list[list[int]]:
    """V2 ``[G,S]<=[d0,d1,...]T(p...)`` iota tile assignment -> id lists."""
    shape = [int(x) for x in dims.split(",") if x.strip()]
    n = 1
    for d in shape:
        n *= d
    ids = list(range(n))
    if perm:
        order = [int(x) for x in perm.split(",") if x.strip()]
        # reshape to `shape`, transpose by `order`, flatten — index math only
        strides = [0] * len(shape)
        acc = 1
        for i in range(len(shape) - 1, -1, -1):
            strides[i] = acc
            acc *= shape[i]
        tshape = [shape[o] for o in order]
        tstrides = [strides[o] for o in order]
        ids = []
        for coords in itertools.product(*[range(d) for d in tshape]):
            ids.append(sum(c * st for c, st in zip(coords, tstrides)))
    return [ids[i * s:(i + 1) * s] for i in range(g)]


def _pairs_components(pairs: list[list[int]], n_devices: int) -> list[list[int]]:
    """source_target_pairs -> connected components (the permutation's device
    partition; a ring/shift over one mesh axis components exactly into that
    axis's groups). Devices outside every pair are singleton components."""
    parent = list(range(n_devices)) if n_devices else []
    seen = max((max(p) for p in pairs), default=-1)
    if seen >= len(parent):
        parent = list(range(seen + 1))

    def find(a):
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    for src, dst in pairs:
        ra, rb = find(src), find(dst)
        if ra != rb:
            parent[ra] = rb
    comps: dict[int, list[int]] = {}
    for i in range(len(parent)):
        comps.setdefault(find(i), []).append(i)
    return sorted(comps.values())


def _balanced_operands(text: str, open_idx: int) -> str:
    """The operand text between ``(`` at ``open_idx`` and its match."""
    depth = 0
    for i in range(open_idx, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return text[open_idx + 1:i]
    return text[open_idx + 1:]


def _result_bytes(line: str, eq_end: int) -> int:
    """Bytes of an instruction's RESULT type — the text right after the
    ``=`` at ``eq_end``: one shape token, or a balanced tuple of them."""
    rest = line[eq_end:].lstrip()
    if rest.startswith("("):
        rest = _balanced_operands(rest, 0)
    else:
        rest = rest.split(None, 1)[0] if rest else ""
    return sum(_shape_bytes(dt, dims) for dt, dims in _SHAPE_RE.findall(rest))


def parse_hlo_collectives(hlo_text: str) -> list[dict]:
    """Every collective instruction in an HLO module, in textual (schedule)
    order: ``{op, async, name, line, payload_bytes, groups, channel_id,
    overlapped}``. ``-done`` halves of async pairs are folded into their
    ``-start`` (one logical op, bytes counted once, ``overlapped`` judged
    from the instructions scheduled between the two).

    HLO text names an operand with or without its type
    (``all-reduce(f32[8]{0} %p)`` / ``all-reduce(%p)``): inline shapes are
    used where present, otherwise each operand identifier is sized from the
    result type of the instruction that defined it."""
    lines = hlo_text.splitlines()
    ops: list[dict] = []
    starts: dict[str, dict] = {}  # %name of a -start -> its op record
    defined: dict[str, int] = {}  # %name -> result bytes of its definition
    for ln, line in enumerate(lines):
        nm = _NAME_RE.match(line)
        if nm:
            defined[nm.group("name")] = _result_bytes(line, nm.end())
        m = _OP_LINE_RE.search(line)
        if m is None:
            continue
        kind = m.group("kind") or ""
        name = nm.group("name") if nm else f"line{ln}"
        # m.end() - 1 is exactly the op's own open paren (the regex ends on
        # it) — `line.index("(")` would grab a tuple RESULT shape's paren
        if kind == "-done":
            # pair with the -start this done consumes: EXACT identifier
            # match on the operand tokens (substring matching mispairs
            # '%all-reduce-start' with '%all-reduce-start.1'), and pop the
            # start so a later done can never re-pair an already-judged one
            operand = _balanced_operands(line, m.end() - 1)
            start = None
            for ident in _ID_RE.findall(operand):
                start = starts.pop(ident, None)
                if start is not None:
                    break
            if start is not None:
                between = lines[start["line"] + 1:ln]
                start["overlapped"] = any(
                    _COMPUTE_RE.search(x) for x in between)
                start["done_line"] = ln
            continue
        operand = _balanced_operands(line, m.end() - 1)
        payload = sum(_shape_bytes(dt, dims)
                      for dt, dims in _SHAPE_RE.findall(operand))
        if not payload:
            payload = sum(defined.get(ident, 0)
                          for ident in _ID_RE.findall(operand))
        groups: list[list[int]] = []
        g1 = _GROUPS_V1_RE.search(line)
        if g1:
            groups = _parse_brace_groups(g1.group(1))
        else:
            gi = _GROUPS_IOTA_RE.search(line)
            if gi:
                groups = _parse_iota_groups(int(gi.group(1)), int(gi.group(2)),
                                            gi.group(3), gi.group(4))
        pairs_m = _PAIRS_RE.search(line)
        pairs = _parse_brace_groups(pairs_m.group(1)) if pairs_m else []
        ch = _CHANNEL_RE.search(line)
        rec = {
            "op": m.group("op"),
            "async": kind == "-start",
            "name": name,
            "line": ln,
            "payload_bytes": payload,
            "groups": groups,
            "pairs": pairs,
            "channel_id": int(ch.group(1)) if ch else None,
            "overlapped": False,  # sync ops are by definition not overlapped
        }
        ops.append(rec)
        if kind == "-start":
            starts[name] = rec
    return ops


# ---------------------------------------------------------------------------
# replica-group -> mesh-axis mapping
# ---------------------------------------------------------------------------

def _axis_partition(mesh_shape: dict[str, int],
                    axes: tuple[str, ...]) -> frozenset:
    """Canonical device partition when collecting over ``axes`` of a mesh
    whose devices enumerate row-major over ``mesh_shape``'s axis order (the
    jit/shard_map partition-id convention for a mesh built over
    ``jax.devices()``)."""
    names = list(mesh_shape)
    sizes = [int(mesh_shape[n]) for n in names]
    groups: dict[tuple, list[int]] = {}
    for idx, coords in enumerate(itertools.product(*[range(s) for s in sizes])):
        key = tuple(c for n, c in zip(names, coords) if n not in axes)
        groups.setdefault(key, []).append(idx)
    return frozenset(frozenset(g) for g in groups.values())


def infer_axes(groups: list[list[int]],
               mesh_shape: Optional[dict[str, int]]) -> str:
    """Label a replica-group partition with the mesh axis name(s) it reduces
    over (``"data"``, ``"data+fsdp"``), or a size-shaped fallback label when
    no axis subset matches — attributable, never silently wrong."""
    if not groups:
        return "world"
    fallback = f"unmapped[{len(groups)}x{len(groups[0])}]"
    if not mesh_shape:
        return fallback
    want = frozenset(frozenset(g) for g in groups)
    names = [n for n in mesh_shape if int(mesh_shape[n]) > 1]
    # smallest subsets first: a single-axis label beats axis+trivial combos
    for r in range(1, len(names) + 1):
        for combo in itertools.combinations(names, r):
            if _axis_partition(mesh_shape, combo) == want:
                return "+".join(combo)
    return fallback


# wire-time algorithm factors (ring algorithms; docs/observability.md
# "Collective X-ray"): payload_bytes * factor / ici_bw models the per-chip
# link time
def _wire_factor(op: str, group_size: int) -> float:
    n = max(2, group_size)
    if op == "all-reduce":
        return 2.0 * (n - 1) / n
    if op in ("reduce-scatter", "all-to-all"):
        return (n - 1) / n
    if op == "all-gather":
        # operand is the local shard; a ring moves it to n-1 peers
        return float(n - 1)
    return 1.0  # collective-permute: one hop


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------

_MAX_DETAIL_OPS = 32


def summarize_collectives(hlo_text: str,
                          mesh_shape: Optional[dict[str, int]]) -> dict:
    """One program's collective summary: per-axis payload/wire bytes, per
    ``op@axis`` counts (the ``comm/logger.py`` reconcile view), async/overlap
    tallies and the static overlap verdict."""
    ops = parse_hlo_collectives(hlo_text)
    bytes_by_axis: dict[str, int] = {}
    wire_by_axis: dict[str, dict] = {}  # axis -> {bytes: wire, time needs n}
    by_op_axis: dict[str, dict] = {}
    counts_by_op: dict[str, int] = {}
    detail = []
    async_pairs = overlapped = 0
    for op in ops:
        groups = op["groups"]
        if not groups and op["pairs"]:
            n_dev = 1
            for s in (mesh_shape or {}).values():
                n_dev *= int(s)
            groups = _pairs_components(op["pairs"], n_dev)
            # singleton components are devices the permute does not touch —
            # drop them so a ring over one axis maps to that axis cleanly
            groups = [g for g in groups if len(g) > 1] or groups
        axis = infer_axes(groups, mesh_shape)
        gsize = len(groups[0]) if groups else 1
        payload = op["payload_bytes"]
        wire = payload * _wire_factor(op["op"], gsize)
        bytes_by_axis[axis] = bytes_by_axis.get(axis, 0) + payload
        w = wire_by_axis.setdefault(axis, {"wire_bytes": 0.0})
        w["wire_bytes"] += wire
        key = f"{op['op']}@{axis}"
        ent = by_op_axis.setdefault(key, {"count": 0, "bytes": 0})
        ent["count"] += 1
        ent["bytes"] += payload
        counts_by_op[op["op"]] = counts_by_op.get(op["op"], 0) + 1
        if op["async"]:
            async_pairs += 1
            if op["overlapped"]:
                overlapped += 1
        if len(detail) < _MAX_DETAIL_OPS:
            detail.append({"op": op["op"], "async": op["async"],
                           "bytes": payload, "axis": axis,
                           "group_size": gsize,
                           "overlapped": op["overlapped"]})
    if not ops:
        verdict = "none"
    elif overlapped and overlapped == async_pairs:
        verdict = "overlapped"
    elif overlapped:
        verdict = "partial-overlap"
    else:
        verdict = "serialized"
    return {
        "n_collectives": len(ops),
        "counts_by_op": counts_by_op,
        "bytes_by_axis": bytes_by_axis,
        "wire_bytes_by_axis": {k: v["wire_bytes"]
                               for k, v in wire_by_axis.items()},
        "by_op_axis": by_op_axis,
        "async_pairs": async_pairs,
        "overlapped_pairs": overlapped,
        "overlap_verdict": verdict,
        "ops": detail,
        "ops_truncated": max(0, len(ops) - len(detail)),
    }


class CollectiveLedger:
    """Per-program collective summaries, populated by the ProgramLedger's
    lazy resolution pass (the HLO text comes from the SAME memoized
    ``lower().compile()`` the cost model reads — zero new XLA programs).

    ``set_mesh_shape`` must be called with the engine's mesh axis sizes (in
    mesh axis order) for replica-group -> axis-name mapping; without it,
    groups keep size-shaped fallback labels."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.mesh_shape: Optional[dict[str, int]] = None
        self.programs: dict[str, dict] = {}  # program name -> summary

    def set_mesh_shape(self, mesh_shape: dict[str, int]) -> None:
        self.mesh_shape = {k: int(v) for k, v in mesh_shape.items()}

    def record(self, name: str, hlo_text: str) -> None:
        if not self.enabled or not hlo_text:
            return
        self.programs[name] = summarize_collectives(hlo_text, self.mesh_shape)

    def get(self, name: str) -> Optional[dict]:
        return self.programs.get(name)

    def bytes_by_axis(self) -> dict[str, dict]:
        """Aggregate per-axis counts/bytes across every recorded program —
        the HLO-derived side of ``CommsLogger.reconcile``."""
        out: dict[str, dict] = {}
        for summ in self.programs.values():
            for key, ent in summ["by_op_axis"].items():
                axis = key.split("@", 1)[1]
                agg = out.setdefault(axis, {"count": 0, "bytes": 0})
                agg["count"] += ent["count"]
                agg["bytes"] += ent["bytes"]
        return out


# ---------------------------------------------------------------------------
# step anatomy
# ---------------------------------------------------------------------------

def step_anatomy(row: dict, wall: Optional[dict], peaks: dict,
                 coll: Optional[dict],
                 ici_gbps: Optional[float] = None) -> dict:
    """Join one program's cost-model row, measured wall summary, platform
    peaks and collective summary into the where-does-the-time-go record.

    Rated platforms get modeled times; CPU/unknown keep the static facts
    (bytes per axis, overlap verdict) with LABELED null times — no peak, no
    fabricated comm roofline (`comm_rated: false`)."""
    peak_tf = peaks.get("peak_tflops")
    peak_bw = peaks.get("peak_hbm_gbps")
    ici = ici_gbps if ici_gbps else peaks.get("peak_ici_gbps")
    flops = row.get("flops")
    by = row.get("bytes_accessed")
    rated = peak_tf is not None and peak_bw is not None
    out: dict = {
        "name": row.get("name"),
        "platform": peaks.get("platform", "unknown"),
        "compute_time_s": (flops / (peak_tf * 1e12)
                           if rated and flops else None),
        "hbm_time_s": by / (peak_bw * 1e9) if rated and by else None,
    }
    if coll:
        out["comm_bytes_by_axis"] = dict(coll["bytes_by_axis"])
        out["comm_ops"] = dict(coll["counts_by_op"])
        out["overlap_verdict"] = coll["overlap_verdict"]
        out["async_pairs"] = coll["async_pairs"]
        out["overlapped_pairs"] = coll["overlapped_pairs"]
    else:
        out["comm_bytes_by_axis"] = {}
        out["comm_ops"] = {}
        out["overlap_verdict"] = "none"
    out["comm_bytes_total"] = sum(out["comm_bytes_by_axis"].values())
    out["comm_rated"] = bool(ici) and coll is not None
    if out["comm_rated"]:
        ctba = {axis: wb / (ici * 1e9)
                for axis, wb in coll["wire_bytes_by_axis"].items()}
        out["comm_time_by_axis"] = ctba
        out["comm_time_s"] = sum(ctba.values())
    else:
        # labeled nulls: an unrated platform (CPU fallback, unknown TPU
        # generation) must never carry a fabricated comm time
        out["comm_time_by_axis"] = None
        out["comm_time_s"] = None
    wall_p50 = wall.get("p50") if wall and wall.get("count") else None
    if wall_p50:
        out["wall_p50_s"] = wall_p50
    if (wall_p50 and rated
            and (out["compute_time_s"] or out["hbm_time_s"])):
        device_t = max(out["compute_time_s"] or 0.0, out["hbm_time_s"] or 0.0)
        comm_t = out["comm_time_s"] or 0.0
        # wall beyond the slower of (device roof, comm roof) is time the
        # schedule failed to hide — 0 for a perfectly overlapped step
        out["exposed_comm_estimate_s"] = max(
            0.0, wall_p50 - max(device_t, comm_t))
    else:
        out["exposed_comm_estimate_s"] = None
    return out


def pipeline_bubble_fraction(num_stages: int, micro_batches: int) -> float:
    """Fill/drain fraction of the clocked pipeline schedule: ticks =
    M + S - 1, of which S - 1 are bubble (pipe/engine.py docstring; same
    fraction for the executed 1F1B and the autodiff GPipe profile)."""
    s, m = int(num_stages), int(micro_batches)
    if s <= 1 or m < 1:
        return 0.0
    return (s - 1) / (m + s - 1)


__all__ = ["CollectiveLedger", "parse_hlo_collectives",
           "summarize_collectives", "infer_axes", "step_anatomy",
           "pipeline_bubble_fraction"]
