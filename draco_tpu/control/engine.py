"""The ONE scan-chunked host loop — ``ChunkedEngine`` (ROADMAP item 5).

Until this module the coded-DP CNN Trainer (training/trainer.py) and the
shared LM token loop (parallel/token_loop.py) each carried a private copy
of the same host machinery around their K-fused ``lax.scan`` dispatch:
double-buffered chunk assembly, deferred (K, m) metric blocks, the
eval/checkpoint chunk-boundary snapping, the host span tracer, the
compile/retrace sentinel, the heartbeat beat, the graceful-stop poll, and
the profiler capture window. PR 10's ``metric_family_names`` proved the
seam by unifying the column declarations; this engine unifies the loop
itself. Each loop now contributes only a thin *client* — what a chunk's
payload IS (stacked image batches vs token blocks vs a step-index vector),
how to dispatch it, and what happens at an eval/checkpoint boundary — and
the engine owns everything that must behave identically: the flush
cadence, the t_fetch/t_comp accounting (CNN loop), the stop/snap
discipline, and the chunk-boundary **autopilot hook**
(draco_tpu/control/autopilot.py) that this refactor exists to unlock.

Client protocol (duck-typed; both implementations live next to their
loops):

  label           compile-watch program label for the CURRENT regime
                  ("train_many" / "train_token_many"; regime swaps append
                  a suffix so each regime warms its own window)
  metric_names    column order of the current regime's metric block
                  (re-read per chunk — a family swap changes it)
  assemble(i, ranges)         build + upload chunk i's payload (client
                              does its own gather/upload tracer spans and
                              double-buffering)
  program(state, payload)     the chunk program and its call's arguments
                              -> (jitted callable, args); the engine calls
                              it -> (state, block), and hands the pair to
                              the profiler window, which writes the scope
                              map of what ran (obs/profiling.py)
  defer_extras(payload, fetch_s, k)  extra per-chunk record fields
                              (t_fetch, present counts) or None
  should_log(step)            the loop's metrics.jsonl cadence
  beat_extras()               heartbeat extras (prefetch depth/restarts)
  boundary(end, state)        eval + checkpoint at an eval_freq boundary
  stop_requested(end)         graceful-stop poll (fires pending fault-plan
                              sigterm events through the real handler)
  snap_stop(end, state, already_saved)  resumable checkpoint + bookkeeping
  cleanup()                   always runs on exit (close prefetchers)

Equivalence contract: with the autopilot off this engine reproduces the
two historical loops' observable behavior exactly — same trace span names
and nesting, same compile-watch labels, same flush cadence, same record
schema — pinned by the committed K ∈ {1, 4} bitwise suites running
unchanged on it (``compile_guard="raise"``, 0 steady retraces).
"""

from __future__ import annotations

import time
from typing import Optional

from draco_tpu.obs import profiler_window
from draco_tpu.utils.metrics import DeferredMetricWriter


class ChunkedEngine:
    """Run the chunked regime over ``ranges`` with ``client`` supplying the
    loop-specific pieces. ``timed=True`` adds the CNN loop's t_fetch/t_comp
    wall accounting (a ``sync`` span + per-flush ``t_comp`` record field);
    the LM loop runs untimed (its flush IS the sync, PERF_HISTORY.md §0).

    ``autopilot`` (control/autopilot.py, or None) acts at every flush
    boundary — AFTER the heartbeat beat, so the incident engine has folded
    every record and beat signal up to that step. The engine exposes
    ``state`` / ``last_end`` live so an escalated stop
    (resilience.supervisor.ImmediateStopError) can checkpoint the newest
    dispatched state without waiting for the next boundary.
    """

    def __init__(self, client, *, eval_freq: int, total_end: int,
                 tracer, heartbeat, compile_watch, writer,
                 autopilot=None, timed: bool = False,
                 profile_dir: Optional[str] = None,
                 profile_steps: tuple = (3, 8), is_main: bool = True):
        self.client = client
        self.eval_freq = eval_freq
        self.total_end = total_end
        self.tracer = tracer
        self.heartbeat = heartbeat
        self.compile_watch = compile_watch
        self.autopilot = autopilot
        self.timed = timed
        self.profile_dir = profile_dir
        self.profile_steps = profile_steps
        self.is_main = is_main
        self.deferred = DeferredMetricWriter(writer,
                                             observer=heartbeat.observe)
        if autopilot is not None:
            # regime/quarantine state outlives loop objects: re-point the
            # fresh client at the autopilot's current regime
            autopilot.attach(client)
        # newest dispatched state + its chunk-end step — the escalation
        # path's checkpoint source (supervisor.ImmediateStopError)
        self.state = None
        self.last_end: Optional[int] = None

    def run(self, state, ranges):
        """Drive chunks over ``ranges``; returns (state, last record)."""
        client, deferred = self.client, self.deferred
        tracer, heartbeat = self.tracer, self.heartbeat
        watch = self.compile_watch
        self.state = state
        if not ranges:
            return state, {}
        win = profiler_window(self.profile_dir, self.profile_steps,
                              self.is_main, tracer,
                              on_stop=heartbeat.observe_device)
        # t_fetch = the chunk's host assemble + upload wall; t_comp = the
        # flush window's remaining wall (device execution + drain)
        # amortized over its steps — same record keys as the eager loops
        window_t0 = time.perf_counter()
        window_fetch = 0.0
        window_steps = 0

        def upload(i):
            nonlocal window_fetch
            t0 = time.perf_counter()
            payload = client.assemble(i, ranges)
            dt = time.perf_counter() - t0
            window_fetch += dt
            return payload, dt

        try:
            chunk, fetch_s = upload(0)
            for i, (start, k) in enumerate(ranges):
                end = start + k - 1
                # capture snaps to whole chunks; the chunk start rides
                # along so the anchor's steps_profiled reflects the window
                win.maybe_start(end, first_step=start)
                # segmented wire (ISSUE 16): tag dispatch spans with the
                # live segment count ONLY when the regime actually splits
                # the wire — S=1 trace records stay byte-identical to the
                # pre-segmentation suites (the bitwise rail)
                span_kw = {"chunk_start": start, "k": k}
                seg = int(getattr(client, "wire_segments", 1) or 1)
                if seg > 1:
                    span_kw["segments"] = seg
                fn, args = client.program(state, chunk)
                win.note_program(client.label, fn, args, key=k)
                with tracer.span("dispatch", **span_kw), \
                        watch.expect(client.label, key=k):
                    state, block = fn(*args)
                del args  # the donated carry must not outlive its call
                self.state, self.last_end = state, end
                deferred.defer(range(start, end + 1), client.metric_names,
                               block, client.defer_extras(chunk, fetch_s, k))
                window_steps += k
                if i + 1 < len(ranges):  # overlap: assemble i+1 during i
                    chunk, fetch_s = upload(i + 1)
                boundary = bool(self.eval_freq) \
                    and end % self.eval_freq == 0
                if boundary or i + 1 == len(ranges) or deferred.depth >= 4:
                    common = None
                    if self.timed:
                        # drain the window's chunks BEFORE reading the
                        # clock so device execution lands in t_comp (a
                        # device→host fetch, NOT block_until_ready — the
                        # latter only awaits dispatch on remote backends,
                        # PERF_HISTORY.md §0); this is the boundary's one true sync
                        self._sync(end)
                        t_comp = max(time.perf_counter() - window_t0
                                     - window_fetch, 0.0)
                        common = {"t_comp": round(t_comp / window_steps, 6)}
                    with tracer.span("flush", at_step=end):
                        deferred.flush(client.should_log, common)
                        heartbeat.beat(end, self.total_end,
                                       extra={**client.beat_extras(),
                                              **watch.snapshot()})
                        tracer.flush()
                    window_t0 = time.perf_counter()
                    window_fetch = 0.0
                    window_steps = 0
                    if self.autopilot is not None:
                        # every record + beat up to ``end`` has been folded
                        # into the incident engine: decide remediations now,
                        # effective from the NEXT assembled chunk
                        self.autopilot.act(end, self)
                win.maybe_stop(end, state.params)
                if boundary:
                    client.boundary(end, state)
                    # eval/checkpoint wall must not leak into the next
                    # window's t_comp (the eager loops' Segments exclude
                    # them too)
                    window_t0 = time.perf_counter()
                if client.stop_requested(end):
                    # a chunk boundary is a legal stop point mid-window:
                    # drain the pending metric blocks first, then snap the
                    # resumable checkpoint exactly here
                    if self.timed:
                        self._sync(end)
                    with tracer.span("flush", at_step=end):
                        deferred.flush(client.should_log)
                    client.snap_stop(end, state, bool(boundary))
                    break
        finally:
            try:
                win.stop(state.params)  # loop may end inside the window
            finally:
                client.cleanup()
        return state, deferred.last

    def _sync(self, end: int) -> None:
        """The boundary's one true sync, in the eager loop's two parts:
        ``device_wait`` (the newest chunk's outputs ready) then ``drain``
        (the device->host fetch that proves execution on a remote backend;
        the flush span that follows materialises the blocks)."""
        tracer, deferred = self.tracer, self.deferred
        with tracer.span("sync", at_step=end):
            with tracer.span("device_wait", at_step=end):
                deferred.wait()
            with tracer.span("drain", at_step=end, columns=deferred.depth):
                deferred.sync()


class SegmentPipeline:
    """Decode-on-arrival driver over a segmented wire (ISSUE 16).

    The production chunked regime decodes segments IN-GRAPH
    (coding/cyclic.decode_segments / coding/approx.decode_segments — one
    jitted program, zero host seams), so nothing here sits on the training
    path. This driver is the measurement harness over the seam the wire
    actually crosses in a multi-host deployment: the per-segment
    host→device transfer of narrow codeword buffers. In ``pipelined``
    mode each loop turn async-dispatches segment ``j``'s decode, pushes
    segment ``j+1``'s transfer WHILE that decode executes, and only then
    drains ``j`` — so the transfer wall hides under the decode wall. The
    serial rail (``pipelined=False``) drains before the next transfer,
    forbidding overlap; the delta between the rails is the pipeline win
    tools/segment_study.py commits behind perf_watch (PERF_HISTORY.md §18).

    Hooks (duck-typed, like the engine's client protocol):

      put(j, host_segment) -> device buffer      (the wire transfer)
      decode(j, device buffer) -> result          (async dispatch — must
                                                  NOT block)
      drain(result) -> None                       (block until the decode
                                                  actually finished)

    Every hook call is wrapped in a tracer span (``segment_xfer`` /
    ``segment_decode`` / ``segment_drain``, each tagged ``segment=j``) and
    mirrored into ``self.events`` with host perf_counter stamps, so the
    study can both compute the overlap fraction in-process and merge the
    spans against a device-profiler capture (obs/device_attr
    .merge_timeline)."""

    def __init__(self, tracer, put, decode, drain=None, *,
                 pipelined: bool = True):
        self.tracer = tracer
        self.put = put
        self.decode = decode
        self.drain = drain
        self.pipelined = pipelined
        self.events = []  # [{name, segment, t0_s, t1_s}] host wall stamps

    def _timed(self, name, j, fn):
        t0 = time.perf_counter()
        with self.tracer.span(name, segment=j):
            out = fn()
        self.events.append({"name": name, "segment": j,
                            "t0_s": t0, "t1_s": time.perf_counter()})
        return out

    def run(self, host_segments):
        """Drive all segments; returns the per-segment decode results
        (drained when a ``drain`` hook was given)."""
        n = len(host_segments)
        results = []
        if n == 0:
            return results
        dev = self._timed("segment_xfer", 0,
                          lambda: self.put(0, host_segments[0]))
        for j in range(n):
            out = self._timed("segment_decode", j,
                              lambda j=j, dev=dev: self.decode(j, dev))
            if self.pipelined:
                # transfer j+1 rides under decode j's async execution;
                # the drain AFTER it is what exposes the overlap
                if j + 1 < n:
                    dev = self._timed(
                        "segment_xfer", j + 1,
                        lambda j=j: self.put(j + 1, host_segments[j + 1]))
                if self.drain is not None:
                    self._timed("segment_drain", j,
                                lambda out=out: self.drain(out))
            else:
                # serial rail: drain FIRST, so the next transfer cannot
                # overlap — the no-pipeline control
                if self.drain is not None:
                    self._timed("segment_drain", j,
                                lambda out=out: self.drain(out))
                if j + 1 < n:
                    dev = self._timed(
                        "segment_xfer", j + 1,
                        lambda j=j: self.put(j + 1, host_segments[j + 1]))
            results.append(out)
        return results

    def overlap_us(self):
        """(overlapped transfer µs, decode in-flight µs): each pipelined
        turn's in-flight window runs from decode ``j``'s dispatch end to
        its drain end; transfer ``j+1`` wall inside that window is wire
        time the pipeline hid. Serial runs report 0 overlap by
        construction (the drain precedes the transfer)."""
        by_seg = {}
        for ev in self.events:
            by_seg.setdefault(ev["segment"], {})[ev["name"]] = ev
        total_inflight = 0.0
        overlapped = 0.0
        for j, evs in sorted(by_seg.items()):
            dec, drn = evs.get("segment_decode"), evs.get("segment_drain")
            if dec is None or drn is None:
                continue
            lo, hi = dec["t1_s"], drn["t1_s"]
            total_inflight += max(hi - lo, 0.0)
            nxt = by_seg.get(j + 1, {}).get("segment_xfer")
            if nxt is not None:
                overlapped += max(min(nxt["t1_s"], hi)
                                  - max(nxt["t0_s"], lo), 0.0)
        return overlapped * 1e6, total_inflight * 1e6
