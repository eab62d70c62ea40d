"""Host-side span tracer emitting Chrome trace events (Perfetto-loadable).

The chunked training regimes (trainer._run_chunked / token_loop._run_chunked)
deliberately removed every per-step host sync, so the per-step Segments
timers see nothing: all host wall-clock now happens in a handful of
per-chunk phases — gather, upload, dispatch, sync, flush, eval, ckpt — plus
the prefetcher worker threads racing the device. This tracer makes those
phases a loadable artifact: ``trace_dir/trace.json`` in the Chrome trace
event format (the same format ``chrome://tracing`` and https://ui.perfetto.dev
open directly), with one lane per thread and counter tracks for prefetch
queue depth.

Design constraints (the PR 1–2 invariant):

* **No device fetches.** Spans time host phases with ``time.perf_counter``
  only; nothing here ever touches a jax array. Device-side phase attribution
  is jax.profiler's job (``--profile-dir``) — the step programs carry
  ``jax.named_scope`` annotations so both views share Draco's phase names.
* **Zero overhead when disabled.** The disabled path is ``NULL_TRACER``, a
  module singleton whose ``span()`` returns one shared no-op context
  manager — no allocation, no clock read, no branch beyond the method call.
  Loops hold a tracer unconditionally and never test ``enabled``.
* **Thread-safe.** Prefetcher worker threads emit spans from their own
  threads; events append under a lock and carry the emitting thread's id,
  so each worker gets its own lane (``name_thread`` labels it).
* **One clock with the profiler.** While a span is open on an enabled
  tracer it also holds a ``jax.profiler.TraceAnnotation`` of the same name
  (where jax is importable; outside a capture an annotation costs a flag
  test), so a jax.profiler capture's ``/host:CPU`` plane carries the
  program's own spans on the profiler's clock, next to the device's ops —
  obs/device_attr.py reads them from there and estimates no offset.
  ``NULL_TRACER`` takes no annotation.

Event kinds used (Chrome trace event format spec):

  ph="X"  complete event — one span with ``ts``/``dur`` (microseconds)
  ph="C"  counter event — e.g. prefetch queue depth over time
  ph="M"  metadata — process/thread names for the lane headers
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional


class _NullSpan:
    """The shared no-op context manager the disabled tracer hands out."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled tracer: every call is a no-op, ``span`` returns one shared
    context manager (no allocation, no clock read)."""

    __slots__ = ()
    enabled = False
    last_span = None  # no spans recorded, ever

    def span(self, name: str, **args) -> _NullSpan:
        return _NULL_SPAN

    def complete(self, name: str, dur_s: float, cat: str = "host",
                 **args) -> None:
        pass

    def counter(self, name: str, value) -> None:
        pass

    def instant(self, name: str, **args) -> None:
        pass

    def name_thread(self, label: str) -> None:
        pass

    def now_us(self):
        """No tracer clock — anchor consumers treat None as "no shared
        timebase" (obs/profiling.profiler_window)."""
        return None

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


NULL_TRACER = NullTracer()


class _Span:
    """One live span: records ts on __enter__, appends the complete event
    on __exit__ (so nesting falls out of wall-clock containment)."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_note")

    def __init__(self, tracer: "SpanTracer", name: str, args: Optional[dict]):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self):
        annotate = self._tracer._annotate
        self._note = None if annotate is None else annotate(self._name)
        if self._note is not None:
            self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        tr = self._tracer
        t1 = time.perf_counter()
        if self._note is not None:
            self._note.__exit__(*exc)
        ev = {
            "name": self._name,
            "ph": "X",
            "ts": round((self._t0 - tr._t0) * 1e6, 3),
            "dur": round((t1 - self._t0) * 1e6, 3),
            "pid": tr._pid,
            "tid": threading.get_ident(),
            "cat": "host",
        }
        if self._args:
            ev["args"] = self._args
        tr._append(ev)
        return False


class SpanTracer:
    """Collects Chrome trace events in memory; ``flush()`` rewrites the
    JSON file atomically (a crash keeps the last flushed window),
    ``close()`` flushes and disarms.

    The buffer is BOUNDED: past ``max_events`` the oldest non-metadata
    events are dropped (metadata lane labels are kept, and the written
    payload carries a top-level ``droppedEvents`` count), so an
    arbitrarily long chip job holds a sliding window of its newest spans
    at O(max_events) memory and O(max_events) bytes per flush — "where is
    the wall-clock going NOW", never an unbounded rewrite."""

    enabled = True

    def __init__(self, path: str, process_name: str = "draco_tpu host",
                 max_events: int = 100_000):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self.path = path
        self._pid = os.getpid()
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._max_events = max(int(max_events), 16)
        self._dropped = 0
        # the profiler's own annotation type, so an open span also shows on
        # a capture's host plane (module docstring); None without jax
        try:
            from jax.profiler import TraceAnnotation
            self._annotate = TraceAnnotation
        except ImportError:
            self._annotate = None
        self._events: list = [
            {"name": "process_name", "ph": "M", "pid": self._pid, "tid": 0,
             "args": {"name": process_name}},
        ]
        self.name_thread("main")

    def _append(self, ev: dict) -> None:
        with self._lock:
            self._events.append(ev)
            if len(self._events) > self._max_events:
                # drop the oldest half of the non-metadata events; lane
                # labels (ph=M) survive so the remaining window renders
                meta = [e for e in self._events if e.get("ph") == "M"]
                rest = [e for e in self._events if e.get("ph") != "M"]
                keep = len(rest) // 2
                self._dropped += len(rest) - keep
                self._events = meta + rest[-keep:]

    # ---- emission --------------------------------------------------------
    def span(self, name: str, **args) -> _Span:
        """Context manager timing one host phase on the calling thread."""
        return _Span(self, name, args or None)

    def complete(self, name: str, dur_s: float, cat: str = "host",
                 **args) -> None:
        """Append an already-measured span ending now (duration in seconds)
        on the calling thread's lane — how externally-timed phases (e.g. XLA
        compiles observed via jax.monitoring, obs/compile_watch.py) land in
        the trace without a context manager around them."""
        t1 = time.perf_counter()
        ev = {
            "name": name,
            "ph": "X",
            "ts": round((t1 - self._t0 - dur_s) * 1e6, 3),
            "dur": round(dur_s * 1e6, 3),
            "pid": self._pid,
            "tid": threading.get_ident(),
            "cat": cat,
        }
        if args:
            ev["args"] = args
        self._append(ev)

    def counter(self, name: str, value) -> None:
        """One sample of a counter track (e.g. prefetch queue depth)."""
        ev = {"name": name, "ph": "C",
              "ts": round((time.perf_counter() - self._t0) * 1e6, 3),
              "pid": self._pid, "args": {name: value}}
        self._append(ev)

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker on the calling thread's lane."""
        ev = {"name": name, "ph": "i", "s": "t",
              "ts": round((time.perf_counter() - self._t0) * 1e6, 3),
              "pid": self._pid, "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        self._append(ev)

    def name_thread(self, label: str) -> None:
        """Label the calling thread's lane (prefetcher workers call this
        once so their spans render under a named track)."""
        ev = {"name": "thread_name", "ph": "M", "pid": self._pid,
              "tid": threading.get_ident(), "args": {"name": label}}
        self._append(ev)

    def now_us(self) -> float:
        """Current tracer-relative timestamp (µs) — the shared clock the
        profiler window's anchor stamps so device captures can be shifted
        onto the host lanes (obs/profiling.py + obs/device_attr.py)."""
        return round((time.perf_counter() - self._t0) * 1e6, 3)

    @property
    def last_span(self) -> Optional[str]:
        """Name of the newest completed span — the 'what was happening
        last' breadcrumb error paths attach (e.g. PrefetchStallError)."""
        with self._lock:
            for ev in reversed(self._events):
                if ev.get("ph") == "X":
                    return ev.get("name")
        return None

    # ---- persistence -----------------------------------------------------
    def flush(self) -> None:
        """Rewrite ``path`` with everything collected so far (atomic:
        tmp + rename, so a monitor never reads a torn file)."""
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
        payload = {"traceEvents": events, "displayTimeUnit": "ms"}
        if dropped:
            payload["droppedEvents"] = dropped
        tmp = self.path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(payload, fh)
        os.replace(tmp, self.path)

    def close(self) -> None:
        self.flush()


def make_tracer(trace_dir: Optional[str], is_main: bool = True):
    """The one construction rule both production loops share: a real tracer
    iff a trace_dir is configured on the metrics-emitting process, else the
    shared no-op singleton (callers never branch)."""
    if trace_dir and is_main:
        return SpanTracer(os.path.join(trace_dir, "trace.json"))
    return NULL_TRACER
