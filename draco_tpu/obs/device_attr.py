"""Device-time attribution: fold a jax.profiler capture into per-phase and
per-collective ledgers (ISSUE 9 — the device-side half of the telemetry
spine).

Every op of a step program sits under one ``jax.named_scope`` phase
(``draco_comp`` / ``draco_pack`` / ``draco_input`` / ``draco_attack`` /
``draco_health`` / ``draco_encode`` / ``draco_decode`` / ``draco_update`` —
training/step.py, parallel/common.py) and ``--profile-dir`` captures a
jax.profiler trace of a window of steps. This module folds such a capture
into the ledgers. Apart from reading an ``.xplane.pb`` (which takes
``jax.profiler.ProfileData``, imported inside the reader) it **imports no
jax** — it is pure artifact folding, importable from the jax-free tools
(tools/device_profile.py, tools/trace_report.py).

Capture shapes handled
----------------------

jax.profiler writes ``profile_dir/plugins/profile/<ts>/<host>.xplane.pb``
(and, on the CPU backend, a ``.trace.json.gz`` Chrome dump beside it).
:func:`load_trace` turns either into one list of Chrome-style event dicts,
``args.hlo_op`` = the *optimized*-HLO instruction name and
``args.hlo_module`` = the program:

* **TPU (.xplane.pb, looked at by hand in PERF.md §6):** each chip is a
  plane ``/device:TPU:<i>``; its line ``XLA Ops`` holds one event per
  executed op, whose NAME is the instruction's whole HLO text
  (``%fusion.16 = (bf16[8,32,...``) — the instruction name is the part
  before ``" = "``. The line ``XLA Modules`` holds one event per program
  execution; an op belongs to the module event that contains it. A TPU
  event carries **no** named-scope path.
* **XLA:CPU (.xplane.pb or .trace.json.gz):** the executor threads are
  lines of ``/host:CPU``; an op event carries ``hlo_module`` and ``hlo_op``
  as stats / args (e.g. ``jit_many_body``, ``fusion.17``).
* **Host annotations:** while a profiler window is open the span tracer's
  spans are ``TraceAnnotation`` events on ``/host:CPU`` too (obs/tracer.py),
  and the window's ``draco_anchor`` annotation marks the instant
  ``host_anchor.json`` stamps (obs/profiling.py). They come back with
  ``cat="host"``: the program's own spans on the profiler's clock.

In every shape the named-scope path is NOT in the event — it lives in the
compiled executable's HLO metadata
(``metadata={op_name="jit(f)/.../draco_decode/dot_general"}``). Attribution
therefore needs a **scope map**: optimized-instruction name → draco phase,
parsed from ``compiled.as_text()`` by :func:`scope_map_from_hlo`. The
profiler window writes it beside the capture (``device_scope_map.json``)
for every program the loop dispatched in the window, from that call's own
arguments (obs/profiling.ProfilerWindow). Compilation is deterministic for
a fixed program, so the text's names match the executed trace's — and a
drift would be loud, not silent: unmatched ops land in the ``unattributed``
row, never in a phase. A fusion carries its root's scope; an instruction
the compiler made itself (a concatenate rewritten into update-slices, an
async copy) has no metadata at all and lands in ``other``.

Accounting rule (the "provably sums" contract)
----------------------------------------------

Device op events NEST (a ``call`` computation event wraps its body's op
events on the same thread) and run CONCURRENTLY across executor threads, so
naive duration sums double-count. Attribution uses per-thread **self time**:
each event's duration minus the durations of events nested inside it on the
same thread. Per program, the ledger rows

  draco_comp + draco_pack + draco_input + draco_attack + draco_health
  + draco_encode + draco_decode + draco_update
  + other (mapped op, no draco scope) + unattributed (op not in the map)

sum EXACTLY to the program's total device self-time in the profiled window —
the residual is carried explicitly (``other`` / ``unattributed``), never
absorbed into a phase. ``wall_us`` (envelope of the module's events) is
reported separately; on a multi-threaded executor total self-time > wall is
normal (it is core-time, the chip analogue of busy lanes).

Collective cross-check
----------------------

The PR 3 linter pins each program's *explicit* collective counts
(shard_map psum/ppermute rings) in its ``Manifest``; GSPMD-inserted
collectives materialize only inside the SPMD partitioner and are exempt
(analysis/registry.py docstring). In the compiled HLO the two are separable
by metadata: an explicit collective's ``op_name`` path ends in the jax
primitive that lowered it (``.../psum``, ``.../ppermute``), a GSPMD-inserted
one carries the compute op it was inserted for (``.../dot_general``,
``.../reduce_sum``). The runtime cross-check — :func:`cross_check` — demands
that the distinct explicit collective instructions OBSERVED EXECUTING in the
trace equal the manifest counts per kind; any mismatch is a hard
:class:`CollectiveMismatchError` (the static audit and the runtime trace
must agree). GSPMD collectives are folded into their own ledger row for
observability, never counted against the manifest.
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re
import warnings
from typing import Optional

# the named-scope phases every step body carries (training/step.py +
# parallel/common.py) — ledger row order: the gradient, its packing into
# the stack, the step's rng-derived inputs, the simulated adversary, the
# health columns, then the code and the update
PHASES = ("draco_comp", "draco_pack", "draco_input", "draco_attack",
          "draco_health", "draco_encode", "draco_decode", "draco_update")
SCOPE_MAP_FILE = "device_scope_map.json"
# obs/profiling.ANCHOR_EVENT (not imported: this module pulls in no sibling)
ANCHOR_EVENT = "draco_anchor"
# the TPU planes' lines (PERF.md §6)
_TPU_OPS_LINE, _TPU_MODULES_LINE = "XLA Ops", "XLA Modules"
# residual rows: "other" = op mapped by the scope map but under no draco
# scope (optimizer glue, schedule slicing, metric folds), "unattributed" =
# op absent from the scope map entirely (post-scheduling copies, or a
# scope-map drift)
RESIDUAL_ROWS = ("other", "unattributed")

# optimized-HLO opcode -> manifest collective kind (analysis/registry.py
# COLLECTIVE_KINDS spelling)
HLO_COLLECTIVES = {
    "all-reduce": "all_reduce",
    "all-gather": "all_gather",
    "all-to-all": "all_to_all",
    "collective-permute": "collective_permute",
    "reduce-scatter": "reduce_scatter",
    # async pairs (TPU lowers collectives to start/done) — counted on start
    "all-reduce-start": "all_reduce",
    "all-gather-start": "all_gather",
    "collective-permute-start": "collective_permute",
}
COLLECTIVE_KINDS = ("all_reduce", "all_gather", "all_to_all",
                    "collective_permute", "reduce_scatter")

# jax primitive (the last op_name path segment of an EXPLICIT collective)
# -> manifest kind; a collective whose metadata ends elsewhere is
# GSPMD-inserted
PRIM_COLLECTIVES = {
    "psum": "all_reduce",
    "ppermute": "collective_permute",
    "all_gather": "all_gather",
    "all_to_all": "all_to_all",
    "psum_scatter": "reduce_scatter",
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_SCOPE_RE = re.compile(r"draco_\w+")
_HLO_LINE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*.*?\s([\w\-]+)\(")
_META_RE = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


class CollectiveMismatchError(RuntimeError):
    """The runtime trace's explicit-collective structure disagrees with the
    program's linted Manifest — the hard-error contract of ISSUE 9."""


# --------------------------------------------------------------------------
# scope map: optimized-HLO text -> {op: phase}, collective classification
# --------------------------------------------------------------------------

def _shape_bytes(type_text: str) -> int:
    """Byte size of an HLO result type (sums tuple elements); 0 when no
    sized array appears (token/opaque)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def phase_of(op_name: Optional[str]) -> str:
    """First ``draco_*`` segment of a metadata op_name path ('' if none)."""
    if not op_name:
        return ""
    m = _SCOPE_RE.search(op_name)
    return m.group(0) if m else ""


def scope_map_from_hlo(hlo_text: str) -> dict:
    """Parse ``compiled.as_text()`` into the attribution scope map.

    Returns ``{"module", "ops": {instr: phase|""}, "collectives":
    {instr: {kind, bytes, explicit, phase}}}``. Pure text parsing — callable
    without jax (the profiled runner dumps the text; tests feed fixtures).
    """
    m = re.match(r"HloModule\s+([\w.\-]+)", hlo_text)
    module = m.group(1).rstrip(",") if m else ""
    ops: dict = {}
    collectives: dict = {}
    for line in hlo_text.splitlines():
        hm = _HLO_LINE_RE.match(line)
        if not hm:
            continue
        instr, opcode = hm.group(1), hm.group(2)
        meta = _META_RE.search(line)
        op_name = meta.group(1) if meta else ""
        ops[instr] = phase_of(op_name)
        kind = HLO_COLLECTIVES.get(opcode)
        if kind is not None:
            tail = op_name.rsplit("/", 1)[-1] if op_name else ""
            explicit = PRIM_COLLECTIVES.get(tail) == kind
            # result type text sits between '=' and the opcode
            type_text = line.split("=", 1)[1].split(opcode + "(", 1)[0]
            collectives[instr] = {
                "kind": kind,
                "bytes": _shape_bytes(type_text),
                "explicit": bool(explicit),
                "phase": ops[instr],
            }
    return {"module": module, "ops": ops, "collectives": collectives}


# --------------------------------------------------------------------------
# capture loading
# --------------------------------------------------------------------------

def find_capture(profile_dir: str) -> Optional[str]:
    """Newest capture under the jax profiler layout
    ``profile_dir/plugins/profile/<ts>/``: the ``*.xplane.pb`` (what a chip
    run leaves) where there is one, else a ``*.trace.json(.gz)``; None when
    the directory holds no capture (tolerated, like a missing
    metrics.jsonl)."""
    for ext in ("*.xplane.pb", "*.trace.json.gz", "*.trace.json"):
        hits = glob.glob(os.path.join(profile_dir, "plugins", "profile",
                                      "*", ext))
        if hits:
            return max(hits, key=os.path.getmtime)
    return None


def instruction_of(event_name: str) -> str:
    """``%fusion.16 = (bf16[...`` -> ``fusion.16``: a TPU op event is
    named by its instruction's whole HLO text."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def load_xplane(path: str, host: bool = True) -> list:
    """An ``.xplane.pb`` as Chrome-style complete events
    (:func:`events_from_planes`)."""
    from jax.profiler import ProfileData  # the one jax import, kept lazy

    with warnings.catch_warnings():
        # jaxlib's stats iterator type warns as it is first made
        warnings.simplefilter("ignore", DeprecationWarning)
        return events_from_planes(ProfileData.from_file(path).planes, host)


def events_from_planes(planes, host: bool = True) -> list:
    """Profiler planes (``.name``, ``.lines`` of ``.name`` / ``.events`` of
    ``.name`` / ``.start_ns`` / ``.duration_ns`` / ``.stats``) as
    Chrome-style complete events (µs): device ops with ``args.hlo_op`` /
    ``args.hlo_module`` (module docstring, both shapes), and the host
    plane's other events with ``cat="host"`` (left out with ``host=False``:
    a chip capture's runtime threads hold hundreds of thousands, and the
    ledgers read none). One ``pid`` per plane, one ``tid`` per line, so
    self times stay per line."""
    events: list = []
    for pid, plane in enumerate(planes):
        tpu = plane.name.startswith("/device:TPU:")
        if not tpu and plane.name != "/host:CPU":
            continue
        lines = list(plane.lines)
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": plane.name}})
        modules = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns,
             re.match(r"[\w.\-]*", ev.name).group(0))
            for ln in lines if tpu and ln.name == _TPU_MODULES_LINE
            for ev in ln.events)
        for tid, ln in enumerate(lines):
            if tpu and ln.name != _TPU_OPS_LINE:
                continue
            at = 0  # ops and modules both come in time order
            for ev in ln.events:
                out = {"name": ev.name, "ph": "X", "pid": pid, "tid": tid,
                       "ts": ev.start_ns / 1e3, "dur": ev.duration_ns / 1e3}
                if tpu:
                    while at < len(modules) and modules[at][1] <= ev.start_ns:
                        at += 1
                    inside = (at < len(modules)
                              and modules[at][0] <= ev.start_ns)
                    out["name"] = instruction_of(ev.name)
                    out["args"] = {
                        "hlo_op": out["name"],
                        "hlo_module": modules[at][2] if inside else None}
                else:
                    stats = dict(ev.stats)
                    if "hlo_op" in stats:
                        out["args"] = {"hlo_op": stats["hlo_op"],
                                       "hlo_module": stats.get("hlo_module")}
                    elif host:
                        out["cat"] = "host"
                    else:
                        continue
                events.append(out)
    return events


def load_trace(path: str, host: bool = True) -> "tuple[list, dict]":
    """(events, top-level payload) of a capture: an ``.xplane.pb``
    (:func:`load_xplane`) or a Chrome-trace JSON (.gz or plain; tolerates
    the bare event-array form)."""
    if path.endswith(".xplane.pb"):
        return load_xplane(path, host), {}
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        payload = json.load(fh)
    if isinstance(payload, list):
        return payload, {}
    return payload.get("traceEvents", []) or [], payload


def load_json(path: str) -> Optional[dict]:
    try:
        with open(path) as fh:
            out = json.load(fh)
        return out if isinstance(out, dict) else None
    except (OSError, ValueError):
        return None


def load_scope_map(profile_dir: str) -> Optional[dict]:
    """The window-written ``device_scope_map.json`` (None when absent — a
    capture taken outside a profiler window has none; attribution then
    degrades to module totals with everything unattributed)."""
    return load_json(os.path.join(profile_dir, SCOPE_MAP_FILE))


def load_anchor(profile_dir: str) -> Optional[dict]:
    """``host_anchor.json`` stamped by obs.profiling.profiler_window at
    start/stop — the shared-clock anchor the merged timeline needs."""
    return load_json(os.path.join(profile_dir, "host_anchor.json"))


def _module_of(ev: dict) -> Optional[str]:
    args = ev.get("args")
    return args.get("hlo_module") if isinstance(args, dict) else None


def _op_of(ev: dict) -> str:
    args = ev.get("args") or {}
    return args.get("hlo_op") or ev.get("name", "")


# --------------------------------------------------------------------------
# per-thread self-time (the anti-double-count accounting)
# --------------------------------------------------------------------------

def self_times(events: list) -> "list[tuple[dict, float]]":
    """[(event, self_dur_us)] — each complete event's duration minus the
    durations of events nested inside it on the SAME thread (a ``call``
    computation event wraps its body ops; summing both would double-count).
    Partial overlaps (distinct executor work items) stay independent."""
    out = []
    by_tid: dict = collections.defaultdict(list)
    for ev in events:
        if ev.get("ph") != "X":
            continue
        by_tid[ev.get("tid", 0)].append(ev)
    for evs in by_tid.values():
        evs.sort(key=lambda e: (float(e.get("ts", 0.0)),
                                -float(e.get("dur", 0.0))))
        stack: list = []  # [ev, end_ts, child_dur]
        for ev in evs:
            ts = float(ev.get("ts", 0.0))
            dur = float(ev.get("dur", 0.0))
            while stack and stack[-1][1] <= ts + 1e-9:
                top = stack.pop()
                out.append((top[0], max(float(top[0].get("dur", 0.0))
                                        - top[2], 0.0)))
            if stack and ts + dur <= stack[-1][1] + 1e-6:
                stack[-1][2] += dur  # nested: parent pays the child's time
            stack.append([ev, ts + dur, 0.0])
        while stack:
            top = stack.pop()
            out.append((top[0], max(float(top[0].get("dur", 0.0))
                                    - top[2], 0.0)))
    return out


# --------------------------------------------------------------------------
# per-phase ledger
# --------------------------------------------------------------------------

def _module_events(events: list, module: str) -> list:
    """One selection rule for both ledgers: complete events tagged
    ``args.hlo_module == module``."""
    return [ev for ev in events
            if ev.get("ph") == "X" and _module_of(ev) == module]


def _phase_rows(pairs: list, scope: dict) -> dict:
    """Per-phase ledger rows from precomputed (event, self_us) pairs —
    each pair lands in exactly one row (phase / other / unattributed), so
    the rows sum to the total device self-time by construction."""
    ops = scope.get("ops", {})
    rows = {k: {"time_us": 0.0, "events": 0}
            for k in PHASES + RESIDUAL_ROWS}
    t_lo, t_hi = float("inf"), float("-inf")
    for ev, self_us in pairs:
        ph = ops.get(_op_of(ev))
        key = "unattributed" if ph is None else (ph or "other")
        if key not in rows:
            # a draco_* scope this ledger predates: residual, loud
            key = "unattributed"
        rows[key]["time_us"] += self_us
        rows[key]["events"] += 1
        ts = float(ev.get("ts", 0.0))
        t_lo = min(t_lo, ts)
        t_hi = max(t_hi, ts + float(ev.get("dur", 0.0)))
    total = sum(r["time_us"] for r in rows.values())
    for r in rows.values():
        r["frac"] = (r["time_us"] / total) if total else 0.0
    return {
        "module": scope.get("module", ""),
        "phases": rows,
        "total_device_us": total,
        "wall_us": (t_hi - t_lo) if t_hi > t_lo else 0.0,
        "matched_events": len(pairs),
    }


def attribute_phases(events: list, scope: dict) -> dict:
    """Fold one program's device events into the per-phase ledger.

    ``scope``: a :func:`scope_map_from_hlo` dict. Events are selected by
    :func:`_module_events`; each selected event's SELF time lands in
    exactly one row (phase / other / unattributed), so the rows sum to
    ``total_device_us`` by construction.
    """
    pairs = self_times(_module_events(events, scope.get("module", "")))
    return _phase_rows(pairs, scope)


# --------------------------------------------------------------------------
# collective comms ledger + manifest cross-check
# --------------------------------------------------------------------------

def collective_ledger(events: list, scope: dict) -> dict:
    """Per-kind count/bytes/time ledger of the program's collectives.

    ``explicit`` rows carry ``instructions`` (DISTINCT collective
    instructions observed executing — the static quantity the Manifest
    pins), ``events`` (executions: instructions × devices × scan trips ×
    profiled dispatches), ``bytes`` (result bytes × executions) and device
    self-time. GSPMD-inserted collectives fold into one ``gspmd`` row per
    kind — real traffic worth seeing, but exempt from the manifest
    (analysis/registry.py: a manifest pins the *explicit* ICI structure)."""
    pairs = self_times(_module_events(events, scope.get("module", "")))
    return _collective_rows(pairs, scope)


def _collective_rows(pairs: list, scope: dict) -> dict:
    """Collective ledger from precomputed (event, self_us) pairs."""
    coll = scope.get("collectives", {})
    explicit = {k: {"instructions": 0, "events": 0, "bytes": 0,
                    "time_us": 0.0} for k in COLLECTIVE_KINDS}
    gspmd = {k: {"instructions": 0, "events": 0, "bytes": 0, "time_us": 0.0}
             for k in COLLECTIVE_KINDS}
    seen: dict = collections.defaultdict(set)
    for ev, self_us in pairs:
        op = _op_of(ev)
        info = coll.get(op)
        if info is None:
            continue
        side = explicit if info["explicit"] else gspmd
        row = side[info["kind"]]
        row["events"] += 1
        row["bytes"] += int(info.get("bytes", 0))
        row["time_us"] += self_us
        bucket = ("explicit", info["kind"]) if info["explicit"] \
            else ("gspmd", info["kind"])
        if op not in seen[bucket]:
            seen[bucket].add(op)
            row["instructions"] += 1
    return {"explicit": explicit, "gspmd": gspmd}


def cross_check(ledger: dict, manifest_counts: Optional[dict],
                program: str) -> dict:
    """The hard-error reconciliation: distinct explicit collective
    instructions observed in the runtime trace must equal the program's
    linted Manifest counts per kind (missing kinds default to 0). Returns
    ``{"ok": True, "expected": ..., "observed": ...}`` or raises
    :class:`CollectiveMismatchError` naming every drifted kind. A program
    whose manifest skips the rule (``None``) cross-checks nothing."""
    observed = {k: ledger["explicit"][k]["instructions"]
                for k in COLLECTIVE_KINDS}
    if manifest_counts is None:
        return {"ok": True, "skipped": True, "observed": observed}
    expected = {k: int(manifest_counts.get(k, 0)) for k in COLLECTIVE_KINDS}
    if observed != expected:
        diff = {k: {"manifest": expected[k], "trace": observed[k]}
                for k in COLLECTIVE_KINDS if expected[k] != observed[k]}
        raise CollectiveMismatchError(
            f"{program}: runtime trace's explicit collective structure "
            f"disagrees with the linted Manifest — {diff}. The static audit "
            f"and the runtime trace must agree: either the program changed "
            f"without relinting (run tools/program_lint.py) or the scope "
            f"map drifted from the executed program (PERF_HISTORY.md §12)")
    return {"ok": True, "expected": expected, "observed": observed}


# --------------------------------------------------------------------------
# roofline join (PR 5 cost_analysis columns from program_lint.json)
# --------------------------------------------------------------------------

def roofline(total_device_us: float, steps_profiled: int, lint_row: dict,
             peak_flops: Optional[float] = None,
             peak_bytes_per_s: Optional[float] = None) -> dict:
    """Join measured device time with the program's analytic cost columns
    (``rules.memory_budget``: cost_analysis flops + memory byte columns;
    PERF_HISTORY.md §8). ``flops`` of a K-fused row counts the scan body ONCE
    (rules._cost_flops), so it is the per-step figure either way. Fractions
    are reported only when a peak is supplied (on the XLA:CPU fallback there
    is no honest hardware peak — PERF_HISTORY.md §8c; chip runs pass the chip
    numbers)."""
    mb = (lint_row.get("rules") or {}).get("memory_budget") or {}
    flops = mb.get("flops")
    mem = mb.get("memory") or {}
    # bytes the program touches per execution: argument + output + temp —
    # the working-set proxy, not a DMA count
    touched = sum(int(mem.get(k, 0)) for k in
                  ("argument_bytes", "output_bytes", "temp_bytes"))
    out: dict = {"flops_per_step": flops, "touched_bytes_per_step": touched}
    secs = total_device_us / 1e6
    if flops and secs > 0 and steps_profiled:
        out["achieved_flops_per_s"] = flops * steps_profiled / secs
        if peak_flops:
            out["achieved_flops_frac"] = out["achieved_flops_per_s"] / peak_flops
            out["peak_flops"] = peak_flops
    if touched and secs > 0 and steps_profiled:
        out["achieved_bytes_per_s"] = touched * steps_profiled / secs
        if peak_bytes_per_s:
            out["achieved_bw_frac"] = (out["achieved_bytes_per_s"]
                                       / peak_bytes_per_s)
            out["peak_bytes_per_s"] = peak_bytes_per_s
    return out


# --------------------------------------------------------------------------
# merged host+device timeline
# --------------------------------------------------------------------------

# pid offset for re-emitted device lanes (host tracer uses the real pid)
DEVICE_PID_BASE = 1 << 20

_START_TRACE_RE = re.compile(r"start_trace")


def _start_trace_end(events: list) -> Optional[float]:
    """Device-trace timestamp (µs) of the moment ``start_trace`` RETURNED.
    jax's python tracer emits a ``$profiler.py:<line> start_trace`` event
    whose END is exactly that moment; None when the capture has no such
    event (the quiet capture — obs/profiling._quiet_start_trace disables
    the python tracer — or the TPU shape)."""
    best = None
    for ev in events:
        if ev.get("ph") == "X" and _START_TRACE_RE.search(ev.get("name", "")):
            end = float(ev.get("ts", 0.0)) + float(ev.get("dur", 0.0))
            best = end if best is None else min(best, end)
    return best


def _event_span(events: list) -> "tuple[Optional[float], Optional[float]]":
    """(earliest start, latest end) of the capture's complete events."""
    lo, hi = None, None
    for ev in events:
        if ev.get("ph") != "X":
            continue
        ts = float(ev.get("ts", 0.0))
        end = ts + float(ev.get("dur", 0.0))
        lo = ts if lo is None else min(lo, ts)
        hi = end if hi is None else max(hi, end)
    return lo, hi


def device_time_origin(events: list) -> float:
    """The device-trace timestamp (µs) of the profiler's start-time anchor:
    the ``start_trace`` frame END when the python tracer recorded one, else
    the earliest event (which over-shifts by at most the capture lead-in)."""
    best = _start_trace_end(events)
    if best is not None:
        return best
    lo, _ = _event_span(events)
    return lo if lo is not None else 0.0


def merge_timeline(host_events: list, device_events: list,
                   scope: Optional[dict] = None,
                   anchor: Optional[dict] = None,
                   max_device_events: int = 0) -> dict:
    """One Perfetto-loadable payload: the PR 4 host tracer lanes plus the
    capture's device lanes on a shared clock.

    The device timebase is shifted onto the host tracer clock through the
    best anchor pair available (obs/profiling.py stamps both ends):

    * the capture's own ``draco_anchor`` host annotation paired with
      ``anchor["tracer_ts_us"]``, stamped inside it — the same instant on
      both clocks, no estimate (an ``.xplane.pb`` taken by a profiler
      window);
    * the capture's ``start_trace`` frame END paired with
      ``anchor["tracer_ts_us"]`` (python-tracer captures — exact);
    * else the capture's LAST event END paired with
      ``anchor["drained_tracer_ts_us"]`` — the quiet capture has no start
      event, but the devices were provably idle at the drain stamp, so the
      final device event ends at that host instant (the drain-stamp anchor
      profiling.stop() exists to provide);
    * else the earliest event paired with ``tracer_ts_us``, over-shifting
      the device lanes EARLY by at most the start-to-first-dispatch
      lead-in.

    The capture's host-plane events (``cat="host"``: the tracer's spans as
    annotations, and the runtime's own) duplicate ``host_events`` and are
    left out unless ``host_events`` is empty — then they ARE the host lanes,
    already on the device's clock. Device events are
    re-emitted under ``pid += DEVICE_PID_BASE`` with their draco phase (from
    the scope map) in ``args.phase`` and ``cat="device"`` — so one trace
    answers "is the gap host prefetch or chip decode". Without an anchor
    (no host tracer was running) the device lanes keep their own origin at
    ts 0.

    ``max_device_events`` > 0 bounds the device lanes to the LONGEST that
    many complete events (XLA:CPU conv thunks emit hundreds of thousands of
    sub-ms slices — an unbounded merge is a viewer-killing multi-100MB
    file); the drop count is carried explicitly in ``mergedTimeline`` —
    never a silent cap. Metadata/counter events always survive."""
    tracer_ts = (anchor or {}).get("tracer_ts_us")
    drained_ts = (anchor or {}).get("drained_tracer_ts_us")
    marks = [ev for ev in device_events if ev.get("cat") == "host"
             and ev.get("name") == ANCHOR_EVENT]
    if host_events:
        device_events = [ev for ev in device_events
                         if ev.get("cat") != "host"]
    start_end = _start_trace_end(device_events)
    span_lo, span_hi = _event_span(device_events)
    if tracer_ts is not None and marks:
        anchor_kind = "annotation"
        offset = tracer_ts - (float(marks[0]["ts"])
                              + float(marks[0].get("dur", 0.0)))
    elif tracer_ts is not None and start_end is not None:
        anchor_kind = "start_trace"
        offset = tracer_ts - start_end
    elif drained_ts is not None and span_hi is not None:
        anchor_kind = "drain"
        offset = drained_ts - span_hi
    elif tracer_ts is not None:
        anchor_kind = "start_stamp"
        offset = tracer_ts - (span_lo if span_lo is not None else 0.0)
    else:
        anchor_kind = None
        offset = -(span_lo if span_lo is not None else 0.0)
    ops = (scope or {}).get("ops", {})
    merged = list(host_events)
    seen_pids = set()
    dropped = 0
    if max_device_events > 0:
        xs = [ev for ev in device_events
              if ev.get("ph") == "X" and ev.get("cat") != "host"]
        if len(xs) > max_device_events:
            xs.sort(key=lambda e: -float(e.get("dur", 0.0)))
            keep = set(map(id, xs[:max_device_events]))
            dropped = len(xs) - max_device_events
            device_events = [ev for ev in device_events
                             if ev.get("ph") != "X" or id(ev) in keep
                             or ev.get("cat") == "host"]
    for ev in device_events:
        ph = ev.get("ph")
        if ph not in ("X", "M", "C", "i"):
            continue
        out = dict(ev)
        pid = int(ev.get("pid", 0)) + DEVICE_PID_BASE
        out["pid"] = pid
        if ph != "M":
            out["ts"] = round(float(ev.get("ts", 0.0)) + offset, 3)
            out["cat"] = "host" if ev.get("cat") == "host" else "device"
            phase = ops.get(_op_of(ev))
            if phase:
                out.setdefault("args", {})
                out["args"] = dict(out["args"], phase=phase)
        elif ev.get("name") == "process_name":
            out["args"] = {"name": "device: "
                           + str((ev.get("args") or {}).get("name", ""))}
        merged.append(out)
        seen_pids.add(pid)
    for pid in sorted(seen_pids):
        merged.append({"name": "process_sort_index", "ph": "M", "pid": pid,
                       "args": {"sort_index": pid}})
    return {"traceEvents": merged, "displayTimeUnit": "ms",
            "mergedTimeline": {"device_offset_us": round(offset, 3),
                               "anchored": anchor_kind is not None,
                               "anchor_kind": anchor_kind,
                               "droppedDeviceEvents": dropped}}


# --------------------------------------------------------------------------
# one-call fold (tools/trace_report.py + tools/device_profile.py entry)
# --------------------------------------------------------------------------

def fold_capture(profile_dir: str, strict: bool = False) -> Optional[dict]:
    """Fold a profile dir (capture + the window's scope map) into the
    device report: per-program phase ledger + collective ledger. None when
    no capture exists; a capture without a scope map folds with every op
    unattributed (still honest — the residual carries it). A capture that
    cannot be read (a run killed mid-flush) returns None too unless
    ``strict`` — the same partial-artifact tolerance metrics.jsonl
    consumers follow; heartbeat.observe_device folds strictly and records
    the cause."""
    trace_path = find_capture(profile_dir)
    if trace_path is None:
        return None
    try:
        events, payload = load_trace(trace_path, host=False)
    except Exception:
        if strict:
            raise
        return None
    sm = load_scope_map(profile_dir)
    meta = {k: sm[k] for k in ("cell", "steps_profiled", "steps_per_call")
            if sm and k in sm}
    programs = (sm or {}).get("programs")
    if not programs:
        # no scope map: fold the busiest module so the report still shows
        # device time, all of it unattributed
        mods = collections.Counter(m for m in map(_module_of, events) if m)
        programs = [{"module": m, "ops": {}, "collectives": {}}
                    for m, _ in mods.most_common(1)]
    out_programs = []
    for scope in programs:
        # one selection + self-time pass feeds both ledgers (captures run
        # to ~1M events and this fold also runs inline at window close via
        # heartbeat.observe_device — don't pay the O(n log n) pass twice)
        pairs = self_times(_module_events(events, scope.get("module", "")))
        row = _phase_rows(pairs, scope)
        row["collectives"] = _collective_rows(pairs, scope)
        for k in ("lint_row", "flops_per_step"):
            if isinstance(scope, dict) and k in scope:
                row[k] = scope[k]
        out_programs.append(row)
    out = {"trace": trace_path, "programs": out_programs,
           "anchor": load_anchor(profile_dir), **meta}
    if sm and sm.get("errors"):
        out["scope_map_errors"] = sm["errors"]
    return out


def device_status_block(fold: dict) -> Optional[dict]:
    """The heartbeat's ``device`` status.json block from a folded capture
    (obs/heartbeat.RunHeartbeat.observe_device): the last profiled window's
    phase fractions, decode share, attribution coverage, and — when the
    scope map carries the program's analytic flops (stamped by
    tools/device_profile.py) — the achieved-FLOPs rate. On the XLA:CPU
    fallback there is no honest hardware peak (PERF_HISTORY.md §8c), so
    ``achieved_flops_frac`` stays None unless a peak was supplied."""
    programs = (fold or {}).get("programs") or []
    if not programs:
        return None
    totals = {k: 0.0 for k in PHASES + RESIDUAL_ROWS}
    total_us = 0.0
    flops = 0.0
    for row in programs:
        for k, r in row.get("phases", {}).items():
            totals[k] = totals.get(k, 0.0) + float(r.get("time_us", 0.0))
        total_us += float(row.get("total_device_us", 0.0))
        if isinstance(row.get("flops_per_step"), (int, float)):
            flops += float(row["flops_per_step"])
    anchor = fold.get("anchor") or {}
    steps = anchor.get("steps_profiled")
    block = {
        "profiled_steps": steps,
        "total_device_us": round(total_us, 1),
        "phase_fracs": {k: (round(v / total_us, 4) if total_us else 0.0)
                        for k, v in totals.items()},
        "decode_share": (round(totals["draco_decode"] / total_us, 4)
                         if total_us else 0.0),
        # share of device time whose instruction the scope map knows: near
        # 1 when the map is of the programs that ran (the window writes it,
        # obs/profiling.py); 0.0 for a capture with no scope map
        "attributed_frac": (round(1.0 - totals["unattributed"] / total_us, 4)
                            if total_us else 0.0),
        "achieved_flops_per_s": None,
        "achieved_flops_frac": None,
    }
    if flops and steps and total_us > 0:
        block["achieved_flops_per_s"] = flops * steps / (total_us / 1e6)
    return block
