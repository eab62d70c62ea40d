"""The one profiler-window implementation both production loops share.

PR 4 wired ``--profile-dir`` into four loop bodies (Trainer eager/chunked,
token_loop eager/chunked) as four copy-pasted ``jax.profiler.start_trace`` /
``stop_trace`` blocks — and the drain-before-stop fix (stop during async
dispatch truncates the still-executing profiled steps) was re-implemented
per site, incompletely (the CNN eager loop never drained). ISSUE 9
deduplicates them into :func:`profiler_window`, which also stamps the
**wall-clock anchor** (``profile_dir/host_anchor.json``) that the merged
host+device timeline needs: the host tracer's relative timestamp at the
moment ``start_trace`` returned, taken inside a ``draco_anchor``
``TraceAnnotation`` so that the capture's host plane holds the same instant
on the profiler's clock (obs/device_attr.merge_timeline) — and, on stop,
writes the **scope map** (``profile_dir/device_scope_map.json``) of every
program the loop dispatched in the window: instruction name -> ``draco_*``
scope, parsed from ``lower(*the call's own arguments).compile().as_text()``.
A device event names its instruction and nothing else, so this file is what
turns a capture into the per-phase ledger (obs/device_attr.py).

Window semantics (unchanged from the per-site logic):

* ``maybe_start(step_end)`` before a work unit whose last step is
  ``step_end`` — starts the capture at the first unit reaching
  ``profile_steps[0]`` (chunk-snapped under K>1), at most once per run.
* ``note_program(label, fn, args, key)`` right before a dispatch — kept
  (as shapes) for the first call of each program inside the window.
* ``maybe_stop(step_end, drain)`` after the unit — stops once
  ``step_end >= profile_steps[1] - 1``, draining ``drain`` (the state
  carry) through ``jax.block_until_ready`` first so the capture contains
  the full device execution, not the dispatch tail.
* ``holds(step_end)`` before a loop dispatches the NEXT unit ahead of this
  one's retirement — True at the window's two edges, where it must not.
* ``stop(drain)`` in the loop's exit path — the safety stop when the run
  ends inside the window.

The disabled path is a shared no-op singleton (``NULL_PROFILER_WINDOW``):
loops hold a window unconditionally and never branch on enablement, the
same contract as the tracer (obs/tracer.py). jax is imported lazily inside
start/stop so the obs package stays importable without jax (the jax-free
tools import sibling modules).
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional

from draco_tpu.obs.tracer import NULL_TRACER

ANCHOR_FILE = "host_anchor.json"
# the annotation the window opens as it stamps ``tracer_ts_us``: the same
# instant on the capture's host plane (device_attr.merge_timeline)
ANCHOR_EVENT = "draco_anchor"


def _quiet_start_trace(log_dir: str) -> None:
    """``jax.profiler.start_trace`` with the python tracer OFF and the host
    tracer at level 1.

    The default capture interleaves a python-callstack event per host frame
    — ~1M events for a CI-sized 8-step window, flooding the bounded trace
    buffer and truncating the device stream this module exists to capture —
    and at the default host level a traced step's host side ran 60 ms
    behind an untraced one on the chip (PERF.md §6). Level 1 keeps
    ``TraceAnnotation`` events, which is how the span tracer's spans
    (obs/tracer.py) and the anchor below reach the capture's host plane."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=options)


def abstract_args(args):
    """The call's arguments as shapes (with their shardings): what
    ``lower`` needs, and safe to keep after a donating call."""
    import jax

    return jax.tree.map(
        lambda a: (jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=a.sharding)
                   if isinstance(a, jax.Array) else a), args)


def program_text(fn, args) -> str:
    """Optimized-HLO text of the executable ``fn(*args)`` dispatches: the
    same lowering, so with the persistent compile cache on it is a cache
    load, and its instruction names are the ones a capture's device events
    carry (obs/device_attr.scope_map_from_hlo)."""
    return fn.lower(*args).compile().as_text()


class NullProfilerWindow:
    """Disabled window: every call is a no-op (no clock read, no branch
    beyond the method call)."""

    __slots__ = ()
    active = False
    profiled = False

    def maybe_start(self, step_end: int, first_step=None) -> None:
        pass

    def note_program(self, label: str, fn, args, key=None) -> None:
        pass

    def maybe_stop(self, step_end: int, drain=None) -> None:
        pass

    def holds(self, step_end: int) -> bool:
        return False

    def stop(self, drain=None) -> None:
        pass


NULL_PROFILER_WINDOW = NullProfilerWindow()


class ProfilerWindow:
    """One jax.profiler capture window over steps
    [profile_steps[0], profile_steps[1]) — snapped outward to whole work
    units by the caller's ``step_end`` granularity (a chunk profiles whole
    or not at all, exactly the PR 4 per-site behavior)."""

    def __init__(self, profile_dir: str, profile_steps: tuple = (3, 8),
                 tracer=NULL_TRACER, on_stop=None):
        self.dir = profile_dir
        self.steps = tuple(profile_steps)
        self.tracer = tracer
        self.active = False
        self.profiled = False
        self._anchor: dict = {}
        self._first: Optional[int] = None
        self._last_end: Optional[int] = None
        # (label, key) -> (jitted callable, the call's arguments as shapes):
        # the programs dispatched while the window was open
        self._programs: dict = {}
        # called with the profile dir after a successful stop — the loops
        # pass heartbeat.observe_device so status.json grows the ``device``
        # block from the capture that just landed
        self._on_stop = on_stop

    def maybe_start(self, step_end: int, first_step=None) -> None:
        """``first_step``: the unit's FIRST step (chunk start) — under K>1
        the capture snaps outward to the whole chunk, so the profiled step
        count is [first_step, last stop step], not [profile_steps)."""
        if self.active or self.profiled or step_end < self.steps[0]:
            return
        import jax

        os.makedirs(self.dir, exist_ok=True)
        _quiet_start_trace(self.dir)
        self._first = int(first_step if first_step is not None else step_end)
        # stamped AFTER start_trace returns, inside an annotation of its
        # own: the capture's host plane then holds the very instant the
        # stamps name, on the profiler's clock (device_attr.merge_timeline
        # anchors there; the DRAIN stamp below is the fallback)
        with jax.profiler.TraceAnnotation(ANCHOR_EVENT):
            self._anchor = {
                "schema": 1,
                "profile_steps": list(self.steps),
                "first_step": self._first,
                "started_unix": time.time(),
                "started_perf": time.perf_counter(),
                # host-tracer-relative µs of the same instant (None when
                # the run has no tracer — the timeline then keeps separate
                # origins)
                "tracer_ts_us": getattr(self.tracer, "now_us",
                                        lambda: None)(),
            }
        self.active = True

    def note_program(self, label: str, fn, args, key=None) -> None:
        """The loop is about to dispatch ``fn(*args)`` under compile-watch
        label ``label`` (``key``: the chunk length of a K-fused program).
        Kept, as shapes, for the first call of each program inside the
        window; :meth:`stop` turns them into ``device_scope_map.json``."""
        if self.active and (label, key) not in self._programs:
            self._programs[(label, key)] = (fn, abstract_args(args))

    def maybe_stop(self, step_end: int, drain=None) -> None:
        if not self.active:
            return
        self._last_end = int(step_end)  # newest unit fully inside the window
        if step_end >= self.steps[1] - 1:
            self.stop(drain)

    def holds(self, step_end: int) -> bool:
        """True at the window's two edges: the unit before the one
        :meth:`maybe_start` starts the capture at, and the unit
        :meth:`maybe_stop` stops it after. A loop that dispatches ahead asks
        before it sends the unit after ``step_end``, and sends it only once
        this one is retired — so the capture holds whole units."""
        if self.profiled:
            return False
        if self.active:
            return step_end >= self.steps[1] - 1
        return step_end + 1 >= self.steps[0]

    def stop(self, drain=None) -> None:
        """Stop the capture (drain first — the PR 4 fix, now unconditional:
        stopping mid-async-dispatch truncates the profiled steps) and write
        the anchor file."""
        if not self.active:
            return
        import jax

        if drain is not None:
            try:
                jax.block_until_ready(drain)
            except Exception:
                # a poisoned carry (fault injection, device error) raises on
                # await — the loops call stop() from their finally blocks,
                # so propagating here would MASK the original exception and
                # leak the profiler session; a truncated capture is the
                # honest outcome of a run that died mid-window
                pass
        # the DRAIN stamp: the devices just went idle, so the capture's last
        # device-event END corresponds to this host instant — the merge
        # anchor that survives the python tracer being off
        self._anchor.update(
            drained_unix=time.time(),
            drained_perf=time.perf_counter(),
            drained_tracer_ts_us=getattr(self.tracer, "now_us",
                                         lambda: None)(),
        )
        jax.profiler.stop_trace()
        self.active = False
        self.profiled = True
        self._anchor.update(
            stopped_unix=time.time(),
            stopped_perf=time.perf_counter(),
            last_step=self._last_end,
        )
        if self._last_end is not None and self._first is not None:
            self._anchor["steps_profiled"] = self._last_end - self._first + 1
        tmp = os.path.join(self.dir, ANCHOR_FILE + ".tmp")
        try:
            with open(tmp, "w") as fh:
                json.dump(self._anchor, fh)
            os.replace(tmp, os.path.join(self.dir, ANCHOR_FILE))
        except OSError:
            pass  # anchor is best-effort; the capture itself already landed
        self._write_scope_map()
        if self._on_stop is not None:
            try:
                self._on_stop(self.dir)
            except Exception:
                pass  # observation must never take the run down

    def _write_scope_map(self) -> None:
        """``device_scope_map.json`` beside the capture: instruction ->
        ``draco_*`` scope for every program dispatched in the window, from
        the program's own text (a device event names the instruction and
        nothing else). Keeps what a tool stamped there before the run
        (``cell``, a program's ``lint_row`` / ``flops_per_step``). A program
        whose text cannot be had leaves its one-line cause instead."""
        if not self._programs:
            return
        from draco_tpu.obs import device_attr

        path = os.path.join(self.dir, device_attr.SCOPE_MAP_FILE)
        payload = device_attr.load_json(path) or {}
        stamped = {p.get("module"): p for p in payload.get("programs", [])}
        programs, errors = [], []
        for (label, key), (fn, args) in self._programs.items():
            name = label if key is None else f"{label}[{key}]"
            try:
                scope = device_attr.scope_map_from_hlo(program_text(fn, args))
            except Exception as e:
                errors.append(f"{name}: {type(e).__name__}: {e}"[:300])
                continue
            same = next((p for p in programs
                         if p["module"] == scope["module"]), None)
            if same is not None:
                # two programs of one name (a main and a remainder chunk of
                # one scan body): an event names its module, not which of
                # the two ran, so they fold as one — the first's entry
                # stands where an instruction name is in both
                for k in ("ops", "collectives"):
                    same[k] = {**scope[k], **same[k]}
                same["label"] += "+" + name
                continue
            old = stamped.get(scope["module"], {})
            scope.update({k: old[k] for k in ("lint_row", "flops_per_step")
                          if k in old})
            scope["label"] = name
            programs.append(scope)
        payload.update(schema=1, programs=programs,
                       steps_profiled=self._anchor.get("steps_profiled"))
        if errors:
            payload["errors"] = errors
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as fh:
                json.dump(payload, fh)
            os.replace(tmp, path)
        except OSError:
            pass


def profiler_window(profile_dir: Optional[str], profile_steps: tuple = (3, 8),
                    enabled: bool = True, tracer=NULL_TRACER, on_stop=None):
    """The one construction rule all four loop sites share: a real window
    iff a profile_dir is configured on the metrics-emitting process, else
    the shared no-op singleton (callers never branch)."""
    if profile_dir and enabled:
        return ProfilerWindow(profile_dir, profile_steps, tracer, on_stop)
    return NULL_PROFILER_WINDOW
