"""From a jax.profiler capture to numbers: the reduction every PR's traced
run goes through. Reads the ``.xplane.pb`` with jax's own ProfileData.

What is read (TPU v5e, jax 0.9.0; looked at by hand in PR 23, PERF.md §6):
each chip is a plane ``/device:TPU:<i>``; its line ``XLA Ops`` holds one
event per executed HLO op, which may nest (a ``while`` wraps its body), so
times are SELF times: an event's duration less the events nested in it on
the same line. Busy time is the union of the ops' intervals. An event's name
is the instruction's whole HLO text and carries NO named-scope path, so the
``draco_*`` scope of an op comes from a scope map: instruction name ->
scope, parsed from the compiled step program's text, whose ``metadata``
holds the path. The host's threads are lines of ``/host:CPU``; the
benchmark's own ``TraceAnnotation`` there ties the trace's clock to
``perf_counter``.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import time

ANCHOR = "bench_anchor"
SCOPE_RE = re.compile(r"draco_[a-z]+")
COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter")
OPS_LINE = "XLA Ops"


def self_times(events: list) -> list:
    """[(event, self_ns)] for events (name, start_ns, dur_ns, scope) of ONE
    line: duration less the durations of events nested inside."""
    out = []
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    stack: list = []  # [event, end, child_ns]
    for ev in evs:
        start, dur = ev[1], ev[2]
        while stack and stack[-1][1] <= start:
            top = stack.pop()
            out.append((top[0], max(top[0][2] - top[2], 0.0)))
        if stack and start + dur <= stack[-1][1]:
            stack[-1][2] += dur
        stack.append([ev, start + dur, 0.0])
    while stack:
        top = stack.pop()
        out.append((top[0], max(top[0][2] - top[2], 0.0)))
    return out


def union_ns(intervals: list) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def gaps(intervals: list, lo: float, hi: float) -> list:
    """Idle stretches [(start, end)] of [lo, hi] not covered."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if e <= cur:
            continue
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


_HLO_LINE_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_META_RE = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def scope_map_from_hlo(hlo_text: str) -> dict:
    """{instruction name: first ``draco_*`` segment of its metadata op_name,
    or ''} for every instruction of a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE_RE.match(line)
        if not m:
            continue
        meta = _META_RE.search(line)
        scope = SCOPE_RE.search(meta.group(1)) if meta else None
        out[m.group(1)] = scope.group(0) if scope else ""
    return out


def instruction_of(event_name: str) -> str:
    """``%fusion.16 = (bf16[...`` -> ``fusion.16``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def label_of(event_name: str) -> str:
    """A short name for a breakdown row: instruction and first result
    shape."""
    head, _, rest = event_name.partition(" = ")
    shape = rest.lstrip("(").split("{", 1)[0].split(" ", 1)[0]
    return (head.strip().lstrip("%") + " " + shape)[:80].strip()


class Trace:
    """One capture, reduced. ``raw``: what :func:`read_planes` returns;
    events become (instruction, start_ns, dur_ns, scope, label)."""

    def __init__(self, raw: dict, scope_map: dict, anchor_clock: float,
                 window: tuple, steps: int):
        self.devices = {
            plane: [(instruction_of(n), s, d,
                     scope_map.get(instruction_of(n)), label_of(n))
                    for n, s, d in evs]
            for plane, evs in raw["devices"].items()}
        self.anchor_ns = raw["anchor_ns"]
        self.anchor_clock = anchor_clock
        self.window = window  # perf_counter (t0, t1) of the traced call
        self.steps = steps
        self.window_s = window[1] - window[0]
        per = [union_ns([(e[1], e[1] + e[2]) for e in evs]) * 1e-9
               for evs in self.devices.values()]
        self.busy_s = sum(per) / len(per) if per else 0.0
        self._self = self_times(self.first())  # first chip, computed once

    def first(self) -> list:
        return self.devices[sorted(self.devices)[0]] if self.devices else []

    def mapped_share(self) -> float:
        """Share of the first chip's op time whose instruction the scope map
        knows: near 1 when the map is of the program that ran."""
        total = sum(ns for _, ns in self._self)
        known = sum(ns for ev, ns in self._self if ev[3] is not None)
        return known / total if total else 0.0

    def scope_seconds(self, scopes):
        """Self time under ``scopes`` on the first chip; None where the
        scope map does not cover the ops that ran."""
        if self.mapped_share() < 0.9:
            return None
        return sum(ns for ev, ns in self._self if ev[3] in scopes) * 1e-9

    def to_clock(self, ns: float):
        if self.anchor_ns is None:
            return None
        return self.anchor_clock + (ns - self.anchor_ns) * 1e-9

    def breakdown(self, spans: list) -> dict:
        """Top device ops by self time, and the idle gaps of the first chip
        summed by the host span that covers the middle of each."""
        by_op: dict = {}
        for ev, ns in self._self:
            key = f"{ev[4]} {ev[3]}".strip() if ev[3] else ev[4]
            by_op[key] = by_op.get(key, 0.0) + ns * 1e-9
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
        evs = self.first()
        out = {"device_ops": [[k, v] for k, v in ops], "idle_gaps": []}
        if not evs or self.anchor_ns is None:
            return out
        lo = min(e[1] for e in evs)
        hi = max(e[1] + e[2] for e in evs)
        main = sorted(spans, key=lambda s: s[2] - s[1])
        by_span: dict = {}
        for s, e in gaps([(x[1], x[1] + x[2]) for x in evs], lo, hi):
            mid = self.to_clock((s + e) / 2)
            name = next((n for n, a, b in main if a <= mid <= b),
                        "between_spans")
            by_span[name] = by_span.get(name, 0.0) + (e - s) * 1e-9
        idle = sorted(by_span.items(), key=lambda kv: -kv[1])[:10]
        out["idle_gaps"] = [[k, v] for k, v in idle]
        return out


def read_planes(profile_dir: str) -> dict:
    """{"devices": {plane: [(name, start_ns, dur_ns)]}, "anchor_ns"} of the
    newest capture under ``profile_dir``."""
    from jax.profiler import ProfileData

    hits = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    if not hits:
        raise RuntimeError(f"no capture under {profile_dir}")
    data = ProfileData.from_file(max(hits, key=os.path.getmtime))
    devices: dict = {}
    anchor_ns = None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (ev.name, float(ev.start_ns), float(ev.duration_ns))
                        for ev in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR and anchor_ns is None:
                        anchor_ns = float(ev.start_ns)
    return {"devices": devices, "anchor_ns": anchor_ns}


def capture(profile_dir: str, drive, scope_map: dict) -> Trace:
    """Trace ``drive()`` (which returns (records, t0, t1)) and reduce it.
    The profiler's Python tracer is off and its host tracer at level 1: at
    their defaults the host loop of a traced step ran 60 ms behind an
    untraced one (PR 23)."""
    import jax

    shutil.rmtree(profile_dir, ignore_errors=True)
    os.makedirs(profile_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(profile_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(ANCHOR):
            anchor_clock = time.perf_counter()
        records, t0, t1 = drive()
    finally:
        jax.profiler.stop_trace()
    return Trace(read_planes(profile_dir), scope_map, anchor_clock,
                 (t0, t1), len(records))
