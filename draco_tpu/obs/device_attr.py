"""Device-time attribution: fold a jax.profiler capture into per-phase and
per-collective ledgers (ISSUE 9 — the device-side half of the telemetry
spine).

PR 4 planted ``jax.named_scope`` phases (``draco_comp`` / ``draco_encode`` /
``draco_decode`` / ``draco_update``) in every step body and ``--profile-dir``
captures jax.profiler traces, but nothing parsed them: all attribution was
host-side spans around opaque jitted dispatches. This module closes the gap
**without importing jax** — it is pure artifact folding, importable from the
jax-free tools (tools/device_profile.py, tools/trace_report.py) and usable on
a laptop against capture dirs scp'd from a chip job.

Capture shapes handled
----------------------

jax.profiler writes ``profile_dir/plugins/profile/<ts>/*.trace.json.gz`` — a
Chrome-trace-event dump. Two event shapes exist:

* **XLA:CPU fallback (this container, PERF_HISTORY.md §8c):** each executed HLO op
  is one complete event whose ``args`` carry only ``hlo_module`` (e.g.
  ``jit_many_body``) and ``hlo_op`` (the *optimized*-HLO instruction name,
  e.g. ``dot.2`` / ``fusion.17``). The named-scope path is NOT in the event —
  it lives in the compiled executable's HLO metadata
  (``metadata={op_name="jit(f)/.../draco_decode/dot_general"}``). Attribution
  therefore needs a **scope map**: optimized-instruction name → draco phase,
  parsed from ``compiled.as_text()`` by :func:`scope_map_from_hlo` and dumped
  next to the capture (``device_scope_map.json``) by the profiled run
  (tools/device_profile.py ``--run-cell``). Because XLA:CPU compilation is
  deterministic for a fixed program, the re-compiled text names match the
  executed trace's names — and a drift would be loud, not silent: unmatched
  ops land in the ``unattributed`` row, never in a phase.
* **TPU (XProf) traces** carry the full scope path in the event itself; ops
  whose name/args embed a ``draco_*`` segment attribute directly, scope map
  optional.

Accounting rule (the "provably sums" contract)
----------------------------------------------

Device op events NEST (a ``call`` computation event wraps its body's op
events on the same thread) and run CONCURRENTLY across executor threads, so
naive duration sums double-count. Attribution uses per-thread **self time**:
each event's duration minus the durations of events nested inside it on the
same thread. Per program, the ledger rows

  draco_comp + draco_encode + draco_decode + draco_update
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
from typing import Optional

# the named-scope phases every step body carries (PR 4; training/step.py +
# parallel/common.py) — ledger row order
PHASES = ("draco_comp", "draco_encode", "draco_decode", "draco_update")
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
    """Newest ``*.trace.json.gz`` (or ``.trace.json``) under the jax
    profiler layout ``profile_dir/plugins/profile/<ts>/``; None when the
    directory holds no capture (tolerated, like a missing metrics.jsonl)."""
    pats = (os.path.join(profile_dir, "plugins", "profile", "*",
                         "*.trace.json.gz"),
            os.path.join(profile_dir, "plugins", "profile", "*",
                         "*.trace.json"))
    hits = [p for pat in pats for p in glob.glob(pat)]
    return max(hits, key=os.path.getmtime) if hits else None


def load_trace(path: str) -> "tuple[list, dict]":
    """(events, top-level payload) from a Chrome-trace JSON (.gz or plain;
    tolerates the bare event-array form)."""
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
    """The runner-dumped ``device_scope_map.json`` (None when absent — a
    plain ``--profile-dir`` run never dumps one; attribution then degrades
    to module totals with everything unattributed)."""
    return load_json(os.path.join(profile_dir, "device_scope_map.json"))


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
    ``args.hlo_module == module``, plus untagged events carrying a
    ``draco_*`` segment in their name/op path (the TPU scope-in-name
    shape)."""
    out = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        evm = _module_of(ev)
        if evm is not None:
            if evm == module:
                out.append(ev)
        elif (_SCOPE_RE.search(_op_of(ev))
              or _SCOPE_RE.search(ev.get("name", ""))):
            # scope-in-name (TPU) shape — _op_of prefers args.hlo_op, so
            # also search the event name the scope path actually rides in
            out.append(ev)
    return out


def _phase_rows(pairs: list, scope: dict) -> dict:
    """Per-phase ledger rows from precomputed (event, self_us) pairs —
    each pair lands in exactly one row (phase / other / unattributed), so
    the rows sum to the total device self-time by construction."""
    ops = scope.get("ops", {})
    rows = {k: {"time_us": 0.0, "events": 0}
            for k in PHASES + RESIDUAL_ROWS}
    t_lo, t_hi = float("inf"), float("-inf")
    for ev, self_us in pairs:
        op = _op_of(ev)
        ph = ops.get(op)
        if ph is None:
            ph = phase_of(op)  # TPU shape: the path is the event name
            key = ph if ph else "unattributed"
        else:
            key = ph if ph else "other"
        if key not in rows:
            # a draco_* token outside the ledger rows — e.g. "draco_tpu"
            # matched from a repo file path in a python-tracer frame name,
            # or a future named scope this ledger predates: residual, loud
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
    ``total_device_us`` by construction. Ops with no module tag but a
    ``draco_*`` segment in their name/op path (TPU trace shape) attribute
    directly.
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

    Device events are
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
    start_end = _start_trace_end(device_events)
    span_lo, span_hi = _event_span(device_events)
    if tracer_ts is not None and start_end is not None:
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
        xs = [ev for ev in device_events if ev.get("ph") == "X"]
        if len(xs) > max_device_events:
            xs.sort(key=lambda e: -float(e.get("dur", 0.0)))
            keep = set(map(id, xs[:max_device_events]))
            dropped = len(xs) - max_device_events
            device_events = [ev for ev in device_events
                             if ev.get("ph") != "X" or id(ev) in keep]
    for ev in device_events:
        ph = ev.get("ph")
        if ph not in ("X", "M", "C", "i"):
            continue
        out = dict(ev)
        pid = int(ev.get("pid", 0)) + DEVICE_PID_BASE
        out["pid"] = pid
        if ph != "M":
            out["ts"] = round(float(ev.get("ts", 0.0)) + offset, 3)
            out["cat"] = "device"
            phase = ops.get(_op_of(ev)) or phase_of(_op_of(ev))
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
    """Fold a profile dir (capture + runner-dumped scope map) into the
    device report: per-program phase ledger + collective ledger. None when
    no capture exists; a capture without a scope map folds with every op
    unattributed (still honest — the residual carries it). A torn/corrupt
    capture (a run killed mid-flush) returns None too unless ``strict`` —
    the same partial-artifact tolerance metrics.jsonl consumers follow."""
    trace_path = find_capture(profile_dir)
    if trace_path is None:
        return None
    try:
        events, payload = load_trace(trace_path)
    except (OSError, ValueError, EOFError):
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
    return {"trace": trace_path, "programs": out_programs,
            "anchor": load_anchor(profile_dir), **meta}


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
        # share of device time the scope map could attribute at all — a
        # plain --profile-dir run has no scope map and reads 0.0 here
        # (everything in the unattributed row), which is the honest state
        "attributed_frac": (round(1.0 - totals["unattributed"] / total_us, 4)
                            if total_us else 0.0),
        "achieved_flops_per_s": None,
        "achieved_flops_frac": None,
    }
    if flops and steps and total_us > 0:
        block["achieved_flops_per_s"] = flops * steps / (total_us / 1e6)
    return block
